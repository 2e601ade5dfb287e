"""The step FLOP function agrees with the trip-count-aware count of the
compiled program (``launch/hlo_cost.py``) at smoke widths.

On the CPU attention takes the reference path, which computes the whole
score matrix and masks it, so the compiled program is compared with the
count of full attention (``causal=False``).  Tolerance 1%: the HLO count
takes every dot and skips elementwise work, which is not a FLOP of the
yardstick; a wrong term (a missing projection, a forward-only count, a
missed backward dot) moves the ratio by 4% or more at these widths.
"""
import dataclasses
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import flops as F  # noqa: E402
from chipbench import harness as H  # noqa: E402
from chipbench import testing as T  # noqa: E402


@pytest.mark.parametrize("config", ["starcoder2-3b-2L", "phi3-mini-2L"])
def test_step_flops_match_the_compiled_step(config):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.launch.hlo_cost import analyse_hlo
    from repro.optim.hparams import hparams_from_config
    from repro.train.train_step import init_train_state, make_hparam_train_step

    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = dict(json.load(f), n_heads=4, **T.SMOKE)
    cfg["n_kv_heads"] = 4 if config.startswith("phi3") else 2
    seq = 64
    model = dataclasses.replace(get_smoke_config(cfg["preset"]),
                                **H.model_overrides(cfg, control=False))
    tc = TrainConfig(model=model, parallel=ParallelConfig(remat="none"))
    state = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), tc))
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32),
             "targets": jax.ShapeDtypeStruct((1, seq), jnp.int32),
             "mask": jax.ShapeDtypeStruct((1, seq), jnp.float32)}
    hp = hparams_from_config(tc)
    text = jax.jit(make_hparam_train_step(tc)).lower(state, batch, hp).compile().as_text()
    counted = analyse_hlo(text).flops
    want = F.train_step_flops(cfg, seq, 1, causal=False)
    assert abs(counted - want) / want < 0.01, (counted, want)
    assert F.train_step_flops(cfg, seq, 1) < want


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert F.roofline_seconds({"flops": 1e12, "bytes": 1e6}, peak) == {
        "seconds": 1.0, "bound": "compute"}
    assert F.roofline_seconds({"flops": 1e6, "bytes": 2e9}, peak)["bound"] == "memory"
