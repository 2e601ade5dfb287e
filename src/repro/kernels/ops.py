"""Dispatching wrappers for the kernel layer.

``attention`` / ``rmsnorm`` / ``ssm_scan`` choose between the Pallas TPU
kernel and the pure-jnp oracle:

* backend == "tpu" and shapes are tile-aligned  -> pallas kernel
* anything else (CPU container, dry-run, odd shapes) -> ref oracle

``force`` overrides for tests: "ref", "pallas" (with interpret=True on CPU).
The dry-run always takes the ref path so XLA cost analysis sees the real math.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref

_FORCE = os.environ.get("REPRO_KERNELS", "")  # "", "ref", "pallas"
# perf levers (exposed for §Perf baseline/optimized comparisons)
_BLOCKED_MIN_SK = int(os.environ.get("REPRO_ATTN_BLOCKED_MIN_SK", "2048"))
_CAUSAL_SKIP = os.environ.get("REPRO_ATTN_CAUSAL_SKIP", "1") == "1"


def _use_pallas(interpret_ok: bool = False) -> bool:
    if _FORCE == "ref":
        return False
    if _FORCE == "pallas":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- rmsnorm ---------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_rmsnorm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    """Pallas rmsnorm: ``--fused-rmsnorm``, and every aligned norm on TPU.

    The forward pass is the kernel (interpret mode off TPU — it handles
    unaligned feature dims, so the %128 tile gate below does not apply);
    the backward pass is the reference norm's VJP — exact w.r.t. the same
    math, and it keeps the kernel free of a hand-written transpose rule.
    """
    from .rmsnorm import rmsnorm_pallas

    return rmsnorm_pallas(x, gamma, eps=eps, interpret=_interpret())


def _fused_rmsnorm_fwd(x, gamma, eps):
    return _fused_rmsnorm(x, gamma, eps), (x, gamma)


def _fused_rmsnorm_bwd(eps, res, g):
    x, gamma = res
    _, vjp = jax.vjp(lambda xx, gg: ref.rmsnorm(xx, gg, eps), x, gamma)
    return vjp(g)


_fused_rmsnorm.defvjp(_fused_rmsnorm_fwd, _fused_rmsnorm_bwd)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
            fused: bool = False) -> jax.Array:
    # both kernel routes share the custom VJP: pallas_call has no JVP rule,
    # so a bare kernel call would break jax.grad of every train step
    if (fused and _FORCE != "ref") or (_use_pallas() and x.shape[-1] % 128 == 0):
        return _fused_rmsnorm(x, gamma, float(eps))
    with jax.named_scope("kernel_rmsnorm"):
        return ref.rmsnorm(x, gamma, eps)


# -- attention ---------------------------------------------------------------------
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
    fused: bool = False,
) -> jax.Array:
    B, Sq, H, Dq = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    if fused and _FORCE != "ref" and kv_len is None and q_offset == 0:
        # --fused-attention: force the Pallas flash kernel (interpret mode off
        # TPU) on the training hot path regardless of tile alignment — the
        # kernel pads q/k/v internally, so smoke-sized sequences work too.
        # Decode paths (kv_len / q_offset) keep the ref oracle.
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            interpret=_interpret(),
        )
    aligned = Sq % 128 == 0 and q.shape[1] == k.shape[1] and Dq in (64, 128, 192, 256) and Dv in (64, 128, 192, 256)
    if _use_pallas() and aligned and kv_len is None and q_offset == 0:
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            interpret=_interpret(),
        )
    if Sk > _BLOCKED_MIN_SK and isinstance(q_offset, int):
        # flash-style blocked jnp path: O(block^2) memory, static causal/window
        # block skipping — the CPU/dry-run stand-in for the Pallas kernel.
        # named_scope marks the region the TPU Pallas kernel fuses (its
        # internal tensors never touch HBM); the roofline analyzer separates
        # these bytes out (see launch/hlo_cost.py).
        with jax.named_scope("kernel_flash_attn"):
            return ref.attention_blocked(
                q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
                q_offset=q_offset, kv_len=kv_len,
                causal_skip=_CAUSAL_SKIP,
            )
    with jax.named_scope("kernel_attn"):
        return ref.attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset, kv_len=kv_len,
        )


# -- selective scan -------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_ssm(x, dt, A, Bc, Cc, D, chunk: int):
    """Pallas selective scan: ``--fused-ssm``, and every aligned fresh-state
    scan on TPU.

    Forward is the chunked Pallas kernel (interpret mode off TPU; it pads L
    internally and ``block_d`` is snapped to a divisor of the channel dim so
    smoke geometries work); backward is the reference scan's VJP — exact
    w.r.t. the same math.  Fresh-state only (h0=None): the decode/resume
    paths keep the ref oracle.
    """
    from .ssm_scan import ssm_scan_pallas

    return ssm_scan_pallas(
        x, dt, A, Bc, Cc, D, h0=None, chunk=chunk,
        block_d=math.gcd(x.shape[-1], 512), interpret=_interpret())


def _fused_ssm_fwd(x, dt, A, Bc, Cc, D, chunk):
    return _fused_ssm(x, dt, A, Bc, Cc, D, chunk), (x, dt, A, Bc, Cc, D)


def _fused_ssm_bwd(chunk, res, ct):
    _, vjp = jax.vjp(lambda *a: ref.ssm_scan(*a, h0=None, chunk=chunk), *res)
    return vjp(ct)


_fused_ssm.defvjp(_fused_ssm_fwd, _fused_ssm_bwd)


def ssm_scan(x, dt, A, Bc, Cc, D, h0=None, chunk: int = 128, fused: bool = False):
    L = x.shape[1]
    if fused and _FORCE != "ref" and h0 is None:
        return _fused_ssm(x, dt, A, Bc, Cc, D, chunk)
    if _use_pallas() and L % chunk == 0 and x.shape[-1] % 128 == 0:
        if h0 is None:  # the custom VJP keeps this path differentiable
            return _fused_ssm(x, dt, A, Bc, Cc, D, chunk)
        from .ssm_scan import ssm_scan_pallas

        return ssm_scan_pallas(x, dt, A, Bc, Cc, D, h0=h0, chunk=chunk,
                               block_d=math.gcd(x.shape[-1], 512),
                               interpret=_interpret())
    with jax.named_scope("kernel_ssm_scan"):
        return ref.ssm_scan(x, dt, A, Bc, Cc, D, h0=h0, chunk=chunk)


def ssm_decode_step(x, dt, A, Bc, Cc, D, h):
    return ref.ssm_decode_step(x, dt, A, Bc, Cc, D, h)
