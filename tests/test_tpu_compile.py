"""Compiles for a described TPU v5e chip: the Pallas kernels of the main
path at published widths, and one vmapped population train step at the
``chip_smoke.py`` phase-A geometry.  Nothing runs — the TPU compiler
refuses illegal tiles, unlowerable primitives, non-differentiable kernel
routes and programs that do not fit the chip, all without a chip.

The topology is described inside a fixture (never at import): only one
process may load the TPU compiler library at a time, and every test worker
imports this file.  Keep every described-chip compile in this one file.
"""
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HBM_BYTES = 16_909_336_064  # the bytes_limit one v5e chip reports to JAX


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the kernel routes a TPU backend takes (compiled, not
    interpreted), and keep these compiles out of the persistent cache: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(ops, "_use_pallas", lambda *a, **k: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_forward_compiles(one_chip, on_tpu):
    q = _spec(one_chip, (1, 1024, 24, 128))
    kv = _spec(one_chip, (1, 1024, 2, 128))
    c = jax.jit(lambda q, k, v: ops.attention(q, k, v, causal=True)).lower(
        q, kv, kv).compile()
    assert _has_kernel(c)


def test_rmsnorm_forward_and_grad_compile(one_chip, on_tpu):
    x, g = _spec(one_chip, (1, 1024, 3072)), _spec(one_chip, (3072,))
    fwd = jax.jit(lambda x, g: ops.rmsnorm(x, g)).lower(x, g).compile()
    grad = jax.jit(jax.grad(lambda x, g: jnp.sum(ops.rmsnorm(x, g) ** 2),
                            argnums=(0, 1))).lower(x, g).compile()
    assert _has_kernel(fwd) and _has_kernel(grad)


def test_ssm_scan_compiles(one_chip, on_tpu):
    B, L, D, N = 1, 1024, 8192, 16
    args = [_spec(one_chip, s) for s in
            ((B, L, D), (B, L, D), (D, N), (B, L, N), (B, L, N), (D,))]
    c = jax.jit(lambda *a: ops.ssm_scan(*a, chunk=128)).lower(*args).compile()
    assert _has_kernel(c)


def _chip_smoke():
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_population_step_fits_one_chip_at_published_widths(one_chip, on_tpu):
    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.optim.hparams import hparams_from_config
    from repro.train.population import (_population_state_shapes,
                                        get_compiled_population_step)

    geo = _chip_smoke().published_geometry(lanes=2)
    cfg = dataclasses.replace(get_smoke_config("starcoder2-3b"),
                              **geo.overrides)
    tc = TrainConfig(model=cfg, parallel=ParallelConfig(remat="none"))
    k = geo.lanes
    place = lambda x: _spec(one_chip, x.shape, x.dtype)
    state = jax.tree.map(place, _population_state_shapes(tc, k))
    batch = {name: _spec(one_chip, (k, geo.batch, geo.seq), dt)
             for name, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                              ("mask", jnp.float32))}
    hp = jax.tree.map(lambda x: _spec(one_chip, (k,), jnp.asarray(x).dtype),
                      hparams_from_config(tc))
    step = get_compiled_population_step(tc, k, per_trial_batch=True)
    c = step.lower(state, batch, hp).compile()
    assert _has_kernel(c)
    m = c.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, need
