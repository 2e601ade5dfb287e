"""Flash attention Pallas kernel (TPU target, interpret-validated on CPU).

TPU-native adaptation of the flash algorithm:

* grid = (batch, q_heads, num_q_blocks, num_kv_blocks) — on TPU the last grid
  dimension iterates sequentially on-core, so the online-softmax state for one
  (b, h, iq) lives in VMEM scratch across the kv sweep; no HBM round-trips.
* BlockSpec tiling: the kernel runs heads-major, (B, H, S, D), so the last
  two block dims are the q tile (block_q, head_dim) and k/v tiles
  (block_kv, head_dim) that TPU tiling requires, staged HBM->VMEM by
  Pallas; the (block_q, block_kv) score tile exists only in VMEM/VREGs and
  is immediately consumed by the MXU for the P·V partial product — the
  memory win the roofline counts.
* GQA: the q-head grid coordinate maps to kv head h // group via the k/v
  index_maps — kv tiles are fetched once per group on TPU (grid order makes
  consecutive h hit the same kv tile).
* causal / sliding-window masks + gemma2 logit softcap computed from iota
  inside the kernel; fully-masked tiles still run (masked to -inf) — block
  *skipping* is done by the jnp stand-in and is a documented follow-up here
  (splash-style index maps).

Backward: ``flash_attention`` is wrapped in jax.custom_vjp — forward is this
kernel (plus an lse output), backward reuses the validated flash-structured
jnp backward from ``ref`` (blockwise P recompute, no O(S^2) residuals).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,           # (1, 1, block_q/kv, D) VMEM tiles
    o_ref, lse_ref,                # outputs
    m_scr, l_scr, acc_scr,         # VMEM scratch carried across the kv sweep
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_kv: int,
    nk: int,
    sq: int,
    sk: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale                # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                        # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                        # (bk, Dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                           # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = (q_pos < sq) & (k_pos < sk)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    # row statistics stay (bq, 1) columns: the layout the lane-wise
    # reductions produce and the (bq, D) accumulator broadcasts against
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_safe), 0.0)
    m_scr[...] = m_new
    l_scr[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        m = m_scr[...]
        lse = jnp.where(m > NEG_INF / 2, m + jnp.log(l), NEG_INF)
        lse_ref[0, 0] = lse.astype(lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q", "block_kv", "interpret"),
)
def _flash_fwd_pallas(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, Hkv, D)
    v: jax.Array,   # (B, Sk, Hkv, Dv)
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
    block_q: int,
    block_kv: int,
    interpret: bool,
):
    B, Sq, H, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    assert H % Hkv == 0
    g = H // Hkv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Sk + pad_k) // block_kv

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, nk=nk, sq=Sq, sk=Sk,
    )
    # heads-major inside the kernel: Mosaic tiles the last two block dims,
    # which must then be (seq block, head_dim) — a head block of 1 in the
    # second-minor position of (B, S, H, D) is not a legal TPU tile
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    out, lse = _call(kernel, grid, qt, kt, vt, B, Sq, H, D, Dv, pad_q,
                     block_q, block_kv, g, interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2), lse[:, :, :Sq, 0]


def _call(kernel, grid, q, k, v, B, Sq, H, D, Dv, pad_q, block_q, block_kv, g, interpret):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, Dv), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
            # lse as a trailing unit column: its tile is (block_q, 1), whose
            # last dim equals the array's — a legal TPU block
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq + pad_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


@functools.lru_cache(maxsize=None)
def _make_flash(causal, window, softcap, scale, block_q, block_kv, interpret):
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              block_q=block_q, block_kv=block_kv)

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _flash_fwd_pallas(q, k, v, interpret=interpret, **kw)
        return out

    def fwd(q, k, v):
        out, lse = _flash_fwd_pallas(q, k, v, interpret=interpret, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        B, Sq, H, D = q.shape
        Hkv = k.shape[2]
        g = H // Hkv
        # ref's flash backward wants lse as (B, Hkv, g, Sq)
        lse_r = lse.reshape(B, Hkv, g, Sq)
        return ref._blocked_bwd(
            q, k, v, out, lse_r, dout,
            causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=0, block_q=block_q, block_kv=block_kv, causal_skip=True,
        )

    attn.defvjp(fwd, bwd)
    return attn


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention (GQA, sliding window, softcap); flash-vjp grads."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    fn = _make_flash(causal, window, softcap, scale, block_q, block_kv, interpret)
    return fn(q, k, v)
