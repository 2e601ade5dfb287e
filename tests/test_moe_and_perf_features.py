"""Perf-feature correctness: shard_map MoE path, grouped dispatch, ZeRO-1
sharding trees, and the capacity/drop semantics."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_smoke_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.distributed.sharding import build_sharding, make_rules, sharding_context
from repro.models import moe as M
from repro.models import transformer as T
from repro.train.train_step import init_train_state, train_state_specs


def _setup(arch="qwen3-moe-30b-a3b", capacity=100.0):
    cfg = dataclasses.replace(get_smoke_config(arch), capacity_factor=capacity)
    p = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
    return cfg, p, x


def test_shard_map_moe_matches_plain():
    cfg, p, x = _setup()
    y0, a0 = M.moe_apply(p, x, cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    with sharding_context(mesh, make_rules(("data", "model"))):
        y1, a1 = jax.jit(lambda p, x: M.moe_apply(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=3e-5)
    np.testing.assert_allclose(float(a1), float(a0), atol=1e-5)


def test_shard_map_moe_grads_match_plain():
    cfg, p, x = _setup()
    g0 = jax.grad(lambda x: (M.moe_apply(p, x, cfg)[0] ** 2).sum())(x)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    with sharding_context(mesh, make_rules(("data", "model"))):
        g1 = jax.jit(jax.grad(lambda x: (M.moe_apply(p, x, cfg)[0] ** 2).sum()))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=3e-4, rtol=3e-4)


def test_grouped_dispatch_matches_global():
    cfg, p, x = _setup()
    y0, a0 = M.moe_apply(p, x, cfg)
    yG, aG = M.moe_apply(p, x, dataclasses.replace(cfg, moe_groups=4))
    np.testing.assert_allclose(np.asarray(yG), np.asarray(y0), atol=3e-5)
    np.testing.assert_allclose(float(aG), float(a0), atol=1e-5)


def test_capacity_drops_tokens():
    """With a tiny capacity factor, some assignments must actually drop
    (outputs differ from the dropless path) — the Switch semantics."""
    cfg, p, x = _setup(capacity=0.25)
    y_cap, _ = M.moe_apply(p, x, cfg)
    y_free, _ = M.moe_apply(p, x, cfg, dropless=True)
    assert float(jnp.abs(y_cap - y_free).max()) > 1e-4


def test_dropless_ignores_groups_and_ctx():
    """Decode path (dropless) must stay exact regardless of grouping/ctx."""
    cfg, p, x = _setup()
    y0, _ = M.moe_apply(p, x, cfg, dropless=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    with sharding_context(mesh, make_rules(("data", "model"))):
        y1, _ = jax.jit(lambda: M.moe_apply(
            p, x, dataclasses.replace(cfg, moe_groups=4), dropless=True))()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=3e-5)


def test_shard_map_moe_skips_when_experts_unshardable():
    """E=6 doesn't divide a 4-way model axis -> plain path, still correct."""
    cfg, p, x = _setup()
    cfg6 = dataclasses.replace(cfg, n_experts=6, moe_top_k=2)
    p6 = M.moe_init(jax.random.PRNGKey(0), cfg6)
    y0, _ = M.moe_apply(p6, x, cfg6)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

    class FakeMesh:  # pretend the model axis is 4-way for the dispatch check
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 4}

    # the dispatch predicate itself
    assert cfg6.n_experts % FakeMesh.shape["model"] != 0
    with sharding_context(mesh, make_rules(("data", "model"))):
        y1, _ = jax.jit(lambda: M.moe_apply(p6, x, cfg6))()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=3e-5)


def test_zero1_vs_fsdp_sharding_trees():
    """ZeRO-1: params replicated over data axes, optimizer still sharded."""
    cfg = get_smoke_config("starcoder2-3b")
    tc = TrainConfig(model=cfg, parallel=ParallelConfig())
    state_shapes = jax.eval_shape(
        functools.partial(init_train_state, tc=tc), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    specs = train_state_specs(tc)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(("data", "model"))

    fsdp = build_sharding(state_shapes, specs, rules, mesh)
    rules_z1 = dict(rules, embed=())
    z1_params = build_sharding(state_shapes["params"], specs["params"], rules_z1, mesh)
    z1_opt = build_sharding(state_shapes["opt"], specs["opt"], rules, mesh)

    def specs_of(tree):
        return [s.spec for s in jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec"))]

    # on a 1x1 mesh everything is legal; the *intent* differs: zero1 params
    # must never reference the data axis
    for s in specs_of(z1_params):
        assert "data" not in jax.tree.leaves(tuple(s)), s
    # fsdp opt == zero1 opt (both data-sharded)
    assert specs_of(fsdp["opt"]) == specs_of(z1_opt)


def test_moe_arch_smoke_with_sharding_ctx():
    """Full MoE arch train step under a sharding context (shard_map engaged)."""
    from repro.train.train_step import make_train_step

    cfg = get_smoke_config("deepseek-v2-lite-16b")
    tc = TrainConfig(model=cfg, parallel=ParallelConfig(remat="none"))
    state = init_train_state(jax.random.PRNGKey(0), tc)
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "targets": jnp.zeros((2, 16), jnp.int32),
        "mask": jnp.ones((2, 16), jnp.float32),
    }
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(("data", "model"))
    step = make_train_step(tc)

    def fn(state, batch):
        with sharding_context(mesh, rules):
            return step(state, batch)

    state, m = jax.jit(fn)(state, batch)
    assert np.isfinite(float(m["loss"]))
