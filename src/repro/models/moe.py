"""Mixture-of-Experts FFN: token-choice top-k routing, sort-based capacity
dispatch (EP-shardable), optional always-on shared experts (DeepSeek style).

Dispatch avoids the O(T*E*C) one-hot tensor of Switch-style implementations:
assignments are sorted by expert id, scattered into an (E, C, d) buffer
(capacity-dropped with `mode="drop"`), processed with one stacked einsum per
matmul, and gathered back.  Sharding: the E axis maps to the mesh "model"
axis -> expert parallelism; XLA turns the scatter/gather into all-to-alls.

Returns (y, aux_loss) — aux is the Switch load-balancing loss
E * Σ_e f_e·P_e, threaded out of the scanned blocks by the caller.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Params, Specs, dense_init, dtype_of
from .mlp import mlp_apply, mlp_init, mlp_specs


def moe_init(key, cfg: ModelConfig) -> Params:
    pdt = dtype_of(cfg.param_dtype)
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    kr, ki, ko, ks = jax.random.split(key, 4)
    n_in = 2 if cfg.activation == "swiglu" else 1
    p = {
        "router": dense_init(kr, (d, E), jnp.float32, fan_in=d),  # fp32 router
        "wi": dense_init(ki, (E, d, n_in, ff), pdt, fan_in=d),
        "wo": dense_init(ko, (E, ff, d), pdt, fan_in=ff),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks, cfg, d_ff=cfg.n_shared_experts * cfg.moe_d_ff)
    return p


def moe_specs(cfg: ModelConfig) -> Specs:
    s = {
        "router": ("embed", None),
        "wi": ("expert", "embed", None, "moe_ff"),
        "wo": ("expert", "moe_ff", "embed"),
    }
    if cfg.n_shared_experts:
        s["shared"] = {"wi": ("embed", None, "ff"), "wo": ("ff", "embed")}
    return s


def _dispatch_tables(top_e, top_p, T, k, E, C):
    """Sort-based dispatch tables for T local tokens: returns
    (slot, keep, tok_idx, weights_sorted) — all (T*k,)."""
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e)                                  # stable
    sorted_e = flat_e[order]
    idx = jnp.arange(T * k)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_in_e = idx - run_start
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)       # dropped -> OOB
    tok_idx = order // k
    weights = top_p.reshape(-1)[order]
    return slot, keep, tok_idx, weights


def _moe_shard_map(p: Params, x: jax.Array, cfg: ModelConfig, mesh, rules):
    """Explicitly-local MoE under shard_map (the production TP/EP path).

    Activations enter replicated over the model axis (TP layout), so every
    model shard runs the cheap dispatch math redundantly on its data shard's
    tokens, computes ONLY its E/n_model experts, and one psum over the model
    axis recombines — the same collective cost as a dense TP FFN.  This
    avoids XLA's SPMD partitioner turning the dispatch scatter/gather into
    mesh-wide partial-gather + all-reduce (measured 25x worse).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in rules.get("batch", ()) if a in mesh.axis_names)
    # keep only the prefix of data axes that evenly divides the batch dim
    # (shard_map is strict; e.g. a 16-sample microbatch on pod*data = 32)
    keep, prod = [], 1
    for a in dp:
        if x.shape[0] % (prod * mesh.shape[a]) == 0:
            keep.append(a)
            prod *= mesh.shape[a]
    dp = tuple(keep)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if dp else None
    mp = "model"
    n_mp = mesh.shape[mp]
    E, k, d = cfg.n_experts, cfg.moe_top_k, cfg.d_model
    E_l = E // n_mp

    def local_fn(x_loc, router, wi_loc, wo_loc):
        B_l, S, _ = x_loc.shape
        T = B_l * S
        xt = x_loc.reshape(T, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        f = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * k)
        aux = E * jnp.sum(f * probs.mean(axis=0))
        if dp:
            aux = jax.lax.pmean(aux, dp)

        C = max(1, int(math.ceil(cfg.capacity_factor * T * k / E)))
        slot, keep, tok_idx, weights = _dispatch_tables(top_e, top_p, T, k, E, C)

        # ---- local experts only: this shard never materializes the other
        # shards' (E, C, d) buffers — dispatch tables are small ints, the
        # only d-wide traffic is one (E_l*C, d) gather in and one out.
        e0 = jax.lax.axis_index(mp) * E_l
        nloc = E_l * C
        slot_rel = slot - e0 * C
        in_local = (slot_rel >= 0) & (slot_rel < nloc) & keep
        slot_safe = jnp.where(in_local, slot_rel, nloc)          # OOB -> dropped
        entry_of_slot = jnp.zeros((nloc + 1,), jnp.int32).at[slot_safe].set(
            jnp.arange(T * k, dtype=jnp.int32) + 1, mode="drop"
        )[:nloc]
        has_tok = entry_of_slot > 0
        src_tok = tok_idx[jnp.maximum(entry_of_slot - 1, 0)]
        buf_l = jnp.where(has_tok[:, None], xt[src_tok], 0).reshape(E_l, C, d)

        h = jnp.einsum("ecd,ednf->ecnf", buf_l, wi_loc)
        if cfg.activation == "swiglu":
            h = jax.nn.silu(h[:, :, 0]) * h[:, :, 1]
        else:
            h = jax.nn.gelu(h[:, :, 0])
        out_l = jnp.einsum("ecf,efd->ecd", h, wo_loc).astype(jnp.float32)
        out_l = out_l.reshape(nloc, d)

        read_idx = jnp.where(in_local, slot_rel, 0)
        expert_out = jnp.where(in_local[:, None], out_l[read_idx], 0.0)
        y = jnp.zeros((T, d), jnp.float32).at[tok_idx].add(expert_out * weights[:, None])
        y = jax.lax.psum(y, mp)                        # combine expert shards (TP AR)
        return y.astype(x_loc.dtype).reshape(B_l, S, d), aux

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(dp_spec, None, None),
            P(None, None),
            P(mp, None, None, None),
            P(mp, None, None),
        ),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )
    y, aux = fn(x, p["router"], p["wi"], p["wo"])
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x, cfg)
    return y, aux


def moe_apply(
    p: Params, x: jax.Array, cfg: ModelConfig, *, dropless: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Grouped sort-based dispatch.

    ``cfg.moe_groups`` splits tokens into G independent dispatch groups with
    per-group capacity.  G=1 is the global baseline; G = number of data
    shards makes every sort/scatter/gather LOCAL to its shard under SPMD
    (the argsort/scatter of a global dispatch cannot be partitioned and
    replicates catastrophically), while the expert einsums reshard the
    (G, E, C, d) buffer expert-over-model — the GShard/Switch all-to-all
    pattern expressed through sharding constraints.
    """
    from ..distributed.sharding import _CTX, constrain

    ctx = _CTX.get()
    if (
        ctx is not None
        and not dropless
        and "model" in ctx[0].axis_names
        and cfg.n_experts % ctx[0].shape["model"] == 0
    ):
        return _moe_shard_map(p, x, cfg, ctx[0], ctx[1])

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    T = B * S
    G = max(1, cfg.moe_groups) if not dropless else 1
    if T % G or (T // G) < 1:
        G = 1
    Tg = T // G
    xt = x.reshape(G, Tg, d)
    xt = constrain(xt, ("batch", None, "act_embed"))

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (G, Tg, E)
    top_p, top_e = jax.lax.top_k(probs, k)                       # (G, Tg, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- aux load-balance loss (Switch): E * sum_e f_e * P_e -------------------
    f = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * k)
    P = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(f * P)

    # --- per-group sort-based capacity dispatch ---------------------------------
    # dropless (decode / exactness-sensitive paths): every assignment fits.
    C = Tg * k if dropless else max(1, int(math.ceil(cfg.capacity_factor * Tg * k / E)))
    flat_e = top_e.reshape(G, Tg * k)                            # (G, Tg*k)
    order = jnp.argsort(flat_e, axis=1)                          # stable, per group
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    # position within the expert's buffer: index - start of its run
    idx = jnp.broadcast_to(jnp.arange(Tg * k)[None], (G, Tg * k))
    is_start = jnp.concatenate(
        [jnp.ones((G, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1
    )
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    pos_in_e = idx - run_start
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)       # dropped -> OOB
    tok_idx = order // k                                          # (G, Tg*k)

    g_iota = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tg * k))
    vals = jnp.take_along_axis(xt, tok_idx[..., None], axis=1)   # (G, Tg*k, d)
    buf = jnp.zeros((G, E * C, d), xt.dtype).at[g_iota, slot].set(vals, mode="drop")
    buf = buf.reshape(G, E, C, d)
    # tokens move data-sharding -> expert-sharding here (all-to-all under SPMD)
    buf = constrain(buf, ("batch", "expert", None, None))

    # --- expert compute (stacked einsums; E shards over the model axis) ---------
    h = jnp.einsum("gecd,ednf->gecnf", buf, p["wi"])
    if cfg.activation == "swiglu":
        h = jax.nn.silu(h[:, :, :, 0]) * h[:, :, :, 1]
    else:
        h = jax.nn.gelu(h[:, :, :, 0])
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    # second all-to-all: expert-sharding -> data-sharding, so the gather-back
    # below is local to each data shard (gathering from an expert-sharded
    # buffer would all-gather the whole thing everywhere)
    out_buf = constrain(out_buf, ("batch", None, None, None))
    out_buf = out_buf.reshape(G, E * C, d)

    # --- gather back + combine with routing weights -----------------------------
    safe_slot = jnp.where(keep, slot, 0)
    expert_out = jnp.where(keep[..., None], out_buf[g_iota, safe_slot], 0.0)
    weights = jnp.take_along_axis(top_p.reshape(G, Tg * k), order, axis=1)
    contrib = expert_out.astype(jnp.float32) * weights[..., None]   # fp32 combine
    y = jnp.zeros((G, Tg, d), jnp.float32).at[g_iota, tok_idx].add(contrib)
    y = y.astype(xt.dtype)
    y = constrain(y, ("batch", None, "act_embed"))

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x, cfg).reshape(G, Tg, d)
    return y.reshape(B, S, d), aux
