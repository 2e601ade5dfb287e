"""Fused RMSNorm Pallas kernel (TPU target, interpret-validated on CPU).

One pass over HBM instead of XLA's normalize-then-scale chain: each grid step
loads a (block_rows, d) tile into VMEM, computes fp32 row statistics on the
VPU, applies the (1 + gamma) scale, and writes the tile back in the input
dtype.  d stays whole per tile (a row's statistic needs the full feature dim)
— all assigned archs have d <= 8192, i.e. <= 32 KiB fp32 per row, far under
VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps) * (1.0 + g_ref[...].astype(jnp.float32))
    o_ref[...] = (x * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(
    x: jax.Array,
    gamma: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """RMSNorm over the last dim; leading dims are flattened into rows."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = ((rows + pad) // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            # gamma as a (1, d) row: its tile stays legal when vmap adds a
            # leading lane axis (a 1-D block would put the squeezed lane
            # dim in the second-minor position)
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, gamma.reshape(1, d))
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
