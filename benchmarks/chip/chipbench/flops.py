"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of the roofline and utilization metrics: what a
step or a kernel call has to do, not what the program happens to do.  A
program that does more (masked tiles, recomputation) shows it as a lower
share; one that does less cannot pass 100%.

Conventions: a multiply-add is 2 operations; a training step is the forward
pass and a backward pass of twice its matmul work; causal attention needs
half of the full score matrix.  The count walks the configuration's layer
pattern (``modelfile.layers``), each kind with its own terms; a kind without
terms is an error, not a dense guess.  Bytes are float32 unless ``dtype_bytes``
says otherwise.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from .modelfile import layers, published


def _mats(cfg: Dict[str, Any]) -> int:
    return 2 if cfg["activation"] == "swiglu" else 1


def _attention_widths(cfg: Dict[str, Any], mixer: str) -> Tuple[int, int, int, int]:
    """(query heads, key/value heads, q and k width, v and out width)."""
    h, hd = cfg["n_heads"], cfg["head_dim"]
    if mixer == "attn":
        return h, cfg["n_kv_heads"], hd, hd
    if mixer == "attn_mla":
        # models/attention.py projects q with one matrix: no q LoRA to count
        if cfg.get("q_lora_rank"):
            raise ValueError("attn_mla with q_lora_rank > 0 is not what the "
                             "program runs")
        # keys are decompressed per head: as many k/v heads as query heads
        return h, h, hd + cfg["rope_head_dim"], hd
    raise ValueError(f"no FLOP count for mixer {mixer!r}")


def _mixer_params(cfg: Dict[str, Any], mixer: str) -> float:
    d, h, hd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    if mixer == "attn_mla":
        _, _, qk, _ = _attention_widths(cfg, mixer)
        r, rr = cfg["kv_lora_rank"], cfg["rope_head_dim"]
        # q; the latent and the shared rope key; k_nope and v up; out
        return d * h * qk + d * (r + rr) + r * h * 2 * hd + h * hd * d
    _, kv, _, _ = _attention_widths(cfg, mixer)
    return d * (h + 2 * kv) * hd + h * hd * d


def _ffn_params(cfg: Dict[str, Any], ffn: str) -> float:
    d, up_down = cfg["d_model"], _mats(cfg) + 1
    if ffn == "dense":
        return up_down * d * cfg["d_ff"]
    if ffn == "moe":
        # the router spans every published expert; a token's top-k routed
        # experts lie here in the share of them that this chip holds.
        # Capacity padding is not work of the yardstick.
        routed = published(cfg, "n_experts")
        expert = up_down * d * cfg["moe_d_ff"]
        return (d * routed + cfg["n_shared_experts"] * expert
                + cfg["moe_top_k"] * cfg["n_experts"] / routed * expert)
    raise ValueError(f"no FLOP count for ffn {ffn!r}")


def matmul_params(cfg: Dict[str, Any]) -> float:
    """Weights that multiply every token once per forward pass (the input
    embedding is a gather, the output head a matmul), layer by layer."""
    return (sum(_mixer_params(cfg, m) + _ffn_params(cfg, f)
                for m, f in layers(cfg))
            + cfg["vocab_size"] * cfg["d_model"])


def _pairs(seq: int, causal: bool) -> float:
    return seq * (seq + 1) / 2 if causal else seq * seq


def attention_forward_flops(cfg: Dict[str, Any], seq: int,
                            causal: bool = True) -> float:
    """Scores and weighted values of one sequence, all layers: the causal
    half of the (query, key) pairs, or all of them."""
    total = 0.0
    for mixer, _ in layers(cfg):
        h, _, qk, v = _attention_widths(cfg, mixer)
        total += 2 * h * (qk + v) * _pairs(seq, causal)
    return total


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
    log-sum-exp column written.  The layers' one attention kind sets the
    widths."""
    mixers = {m for m, _ in layers(cfg)}
    if len(mixers) != 1:
        raise ValueError(f"one flash-attention call per kind: {sorted(mixers)}")
    h, kv, qk, v = _attention_widths(cfg, mixers.pop())
    flops = rows * 2 * h * (qk + v) * seq * (seq + 1) / 2
    elems = rows * seq * (h * qk + kv * qk + kv * v + h * v)
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
