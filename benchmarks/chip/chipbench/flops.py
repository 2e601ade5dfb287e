"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of the roofline and utilization metrics: what a
step or a kernel call has to do, not what the program happens to do.  A
program that does more (masked tiles, recomputation) shows it as a lower
share; one that does less cannot pass 100%.

Conventions: a multiply-add is 2 operations; a training step is the forward
pass and a backward pass of twice its matmul work; causal attention needs
half of the full score matrix.  Bytes are float32 unless ``dtype_bytes``
says otherwise.
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    ff, v = cfg["d_ff"], cfg["vocab_size"]
    mats = 2 if cfg["activation"] == "swiglu" else 1
    return d, h, kv, hd, ff, v, mats


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that multiply every token once per forward pass (the input
    embedding is a gather, the tied output head a matmul)."""
    d, h, kv, hd, ff, v, mats = _dims(cfg)
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + (mats + 1) * d * ff
    return cfg["n_layers"] * per_layer + v * d


def attention_forward_flops(cfg: Dict[str, Any], seq: int,
                            causal: bool = True) -> float:
    """Scores and weighted values of one sequence, all layers: the causal
    half of the (query, key) pairs, or all of them."""
    _, h, _, hd, _, _, _ = _dims(cfg)
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    return cfg["n_layers"] * 2 * 2 * h * hd * pairs


def train_step_flops(cfg: Dict[str, Any], seq: int, batch: int = 1,
                     causal: bool = True) -> float:
    """One lane's training step over ``batch`` sequences of ``seq`` tokens:
    forward + backward (3x the forward's matmul work), no recomputation."""
    fwd = 2 * matmul_params(cfg) * seq + attention_forward_flops(cfg, seq, causal)
    return 3.0 * fwd * batch


def flash_attention_call(cfg: Dict[str, Any], seq: int, rows: int,
                         dtype_bytes: int = 4) -> Dict[str, float]:
    """One forward flash-attention call over ``rows`` sequences (lanes x
    batch) of one layer: causal FLOPs, and q, k, v read plus out and the
    log-sum-exp column written."""
    _, h, kv, hd, _, _, _ = _dims(cfg)
    flops = rows * 2 * 2 * h * hd * seq * (seq + 1) / 2
    elems = rows * seq * (2 * h * hd + 2 * kv * hd)
    return {"flops": flops,
            "bytes": elems * dtype_bytes + rows * seq * h * 4}


def rmsnorm_call(cfg: Dict[str, Any], seq: int, rows: int,
                 dtype_bytes: int = 4) -> Dict[str, float]:
    """One forward RMSNorm over ``rows`` sequences: x read, y written, the
    scale read once per row; about 4 operations per element."""
    d = cfg["d_model"]
    elems = rows * seq * d
    return {"flops": 4.0 * elems,
            "bytes": 2.0 * elems * dtype_bytes + rows * d * dtype_bytes}


def roofline_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip needs for ``cost``, and which bound sets it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
