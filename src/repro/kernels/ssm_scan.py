"""Mamba-1 selective-scan Pallas kernel (TPU target, interpret-validated).

TPU-native adaptation of the Mamba CUDA scan: instead of a warp-level
parallel scan, the sequence is cut into VMEM-sized chunks and the grid's last
dimension sweeps chunks **sequentially on-core**, carrying the (N, D_blk) SSM
state in VMEM scratch — the TPU analogue of keeping the recurrence in
registers/SMEM.  Within a chunk the recurrence runs as a fori_loop of rank-1
state updates, fully vectorized over the channel block on the VPU:

    h[t] = exp(dt[t] * A) * h[t-1] + (dt[t] * x[t]) ⊗ B[t]
    y[t] = h[t] · C[t] + D * x[t]

grid = (batch, D/block_d, L/chunk); block spec tiles (N on the sublanes):
    x, dt  (chunk, block_d)   B, C  (N, chunk)   A (N, block_d)   D (1, block_d)

The channel dim is blocked (block_d) so falcon-mamba's d_inner=8192 chunk
tiles stay ~4 MiB; N=16 keeps the state tiny.  fp32 state throughout.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                y_ref, hout_ref, h_scr, *, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    # state-major layout: h is (N, Dblk), so a token's channel row (1, Dblk)
    # broadcasts down the N sublanes and its (N,) B/C coefficients are
    # (N, 1) columns picked out of the chunk's (N, chunk) tiles by a lane
    # mask — every in-loop access is a ref row or a full-tile op, never a
    # dynamic slice of a loaded value (which Mosaic cannot lower)
    bt = b_ref[0].astype(jnp.float32)          # (N, chunk)
    ct = c_ref[0].astype(jnp.float32)          # (N, chunk)
    at = a_ref[...].astype(jnp.float32)        # (N, Dblk)
    d = d_ref[...].astype(jnp.float32)         # (1, Dblk)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, h):
        xt = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)    # (1, Dblk)
        dtt = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, Dblk)
        pick = lane == t
        b_col = jnp.sum(jnp.where(pick, bt, 0.0), axis=1, keepdims=True)  # (N, 1)
        c_col = jnp.sum(jnp.where(pick, ct, 0.0), axis=1, keepdims=True)  # (N, 1)
        h = h * jnp.exp(dtt * at) + b_col * (dtt * xt)       # (N, Dblk)
        yt = jnp.sum(h * c_col, axis=0, keepdims=True) + d * xt
        y_ref[0, pl.ds(t, 1), :] = yt.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == nc - 1)
    def _finish():
        hout_ref[0] = h


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret")
)
def ssm_scan_pallas(
    x: jax.Array,    # (B, L, D)
    dt: jax.Array,   # (B, L, D)
    A: jax.Array,    # (D, N)
    Bc: jax.Array,   # (B, L, N)
    Cc: jax.Array,   # (B, L, N)
    D: jax.Array,    # (D,)
    h0: Optional[jax.Array] = None,   # (B, D, N)
    *,
    chunk: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,L,D), h_last (B,D,N)); matches ref.ssm_scan."""
    B, L, Dm = x.shape
    N = A.shape[1]
    block_d = min(block_d, Dm)
    assert Dm % block_d == 0, (Dm, block_d)
    pad = (-L) % chunk
    if pad:
        zp = ((0, 0), (0, pad), (0, 0))
        x, dt = jnp.pad(x, zp), jnp.pad(dt, zp)
        Bc, Cc = jnp.pad(Bc, zp), jnp.pad(Cc, zp)
    Lp = L + pad
    nc = Lp // chunk
    nd = Dm // block_d
    if h0 is None:
        h0 = jnp.zeros((B, Dm, N), jnp.float32)

    grid = (B, nd, nc)
    kernel = functools.partial(_ssm_kernel, chunk=chunk, nc=nc)
    # the kernel keeps N on the sublanes: B/C, A, the state and D are handed
    # over transposed (tiny next to x/dt) so every block's last two dims are
    # (8k, 128k)-tileable or whole
    bt, ct = jnp.swapaxes(Bc, 1, 2), jnp.swapaxes(Cc, 1, 2)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((1, chunk, block_d), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((1, N, chunk), lambda b, j, ic: (b, 0, ic)),
            pl.BlockSpec((1, N, chunk), lambda b, j, ic: (b, 0, ic)),
            pl.BlockSpec((N, block_d), lambda b, j, ic: (0, j)),
            pl.BlockSpec((1, block_d), lambda b, j, ic: (0, j)),
            pl.BlockSpec((1, N, block_d), lambda b, j, ic: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((1, N, block_d), lambda b, j, ic: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lp, Dm), x.dtype),
            jax.ShapeDtypeStruct((B, N, Dm), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        interpret=interpret,
    )(x, dt, bt, ct, A.T, D.reshape(1, Dm), jnp.swapaxes(h0, 1, 2))
    return y[:, :L], jnp.swapaxes(h_last, 1, 2)
