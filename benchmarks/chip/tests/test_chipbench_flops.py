"""The step FLOP function agrees with the trip-count-aware count of the
compiled program (``launch/hlo_cost.py``) at smoke widths.

On the CPU attention takes the reference path, which computes the whole
score matrix and masks it, so the compiled program is compared with the
count of full attention (``causal=False``).  Tolerance 1%: the HLO count
takes every dot and skips elementwise work, which is not a FLOP of the
yardstick; a wrong term (a missing projection, a forward-only count, a
missed backward dot) moves the ratio by 4% or more at these widths.  The
MLA and expert fixture (``deepseek-v2-lite-smoke.json``, the program's smoke
shapes of deepseek-v2-lite-16b) sets ``capacity_factor`` 1.0, so that its
experts' buffers hold exactly the routed tokens (E x C = T x k) and capacity
padding, which the yardstick does not count, adds no dot.
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
from chipbench import modelfile as M  # noqa: E402
from chipbench import testing as T  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))
MLA_MOE = "deepseek-v2-lite-smoke"


def _config(name):
    where = TESTS if name == MLA_MOE else os.path.join(BENCH, "configs")
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config", ["starcoder2-3b-2L", "phi3-mini-2L", MLA_MOE])
def test_step_flops_match_the_compiled_step(config):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.launch.hlo_cost import analyse_hlo
    from repro.optim.hparams import hparams_from_config
    from repro.train.train_step import init_train_state, make_hparam_train_step

    cfg = _config(config)
    if config != MLA_MOE:
        cfg = dict(cfg, n_heads=4, **T.SMOKE)
        cfg["n_kv_heads"] = 4 if config.startswith("phi3") else 2
    seq = 64
    model = dataclasses.replace(get_smoke_config(cfg["preset"]),
                                **M.model_overrides(cfg, control=False))
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


# today's two files at the cells' sizes (seq 1024, batch 1; K=2 lanes): the
# numbers the dense count gave before it walked the layer pattern
GOLDEN = {
    "starcoder2-3b-2L": (2145373913088.0, {"flops": 12897484800.0,
                                           "bytes": 54722560}),
    "phi3-mini-2L": (2035449593856.0, {"flops": 12897484800.0,
                                       "bytes": 100925440}),
}


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_dense_files_count_what_they_counted(config):
    cfg = _config(config)
    step, call = GOLDEN[config]
    assert F.train_step_flops(cfg, 1024, 1) == pytest.approx(step, rel=1e-12)
    got = F.flash_attention_call(cfg, 1024, 2)
    assert got == pytest.approx(call, rel=1e-12)


def test_holding_an_eighth_of_the_experts_scales_only_the_routed_term():
    whole = dict(_config(MLA_MOE), n_experts=64, moe_top_k=6)
    share = dict(whole, n_experts=8, reduced={"n_experts": [64, 8]})
    d, ff = whole["d_model"], whole["moe_d_ff"]
    routed = 6 * 3 * d * ff            # top-6 experts of 3 swiglu matrices
    moe_layers = whole["n_layers"] - 1
    assert F.matmul_params(whole) - F.matmul_params(share) == (
        moe_layers * routed * (1 - 1 / 8))
    # the router keeps its 64 outputs; attention is untouched
    assert F.attention_forward_flops(share, 64) == F.attention_forward_flops(whole, 64)
    # the catalog's own name for the expert count states the same cut
    alias = dict(share, reduced={"n_routed_experts": [64, 8]})
    assert F.matmul_params(alias) == F.matmul_params(share)


def test_mla_call_prices_its_two_widths():
    cfg = _config(MLA_MOE)
    h, hd, rr = cfg["n_heads"], cfg["head_dim"], cfg["rope_head_dim"]
    got = F.flash_attention_call(cfg, 64, 2)
    assert got["flops"] == 2 * 2 * h * ((hd + rr) + hd) * 64 * 65 / 2
    # q and k at hd + rr, v and out at hd, as many k/v heads as query heads
    assert got["bytes"] == 2 * 64 * (2 * h * (hd + rr) + 2 * h * hd) * 4 + 2 * 64 * h * 4


@pytest.mark.parametrize("layer", [("mamba", "none"), ("attn_local", "dense"),
                                   ("attn", "none")])
def test_a_kind_without_terms_raises(layer):
    cfg = dict(_config("starcoder2-3b-2L"), pattern=[list(layer)])
    with pytest.raises(ValueError):
        F.train_step_flops(cfg, 64)


def test_q_lora_raises():
    cfg = dict(_config(MLA_MOE), q_lora_rank=16)
    with pytest.raises(ValueError, match="q_lora_rank"):
        F.matmul_params(cfg)
