"""Plain reference of a dense decoder-only language model under AdamW, and
of the synthetic token stream it trains on.

Written from the published descriptions and the configuration file alone:
it imports nothing of the system under test and takes nothing it made.
Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``; no kernels, no batching of lanes, no caches.  One
trial is replayed at a time from the seed: its weights, its tokens, its
hyperparameters, step by step.

The semantics, as the configuration files state them:

* weights: truncated normal in [-2, 2] scaled by 1/sqrt(fan-in), drawn from
  ``PRNGKey(seed)`` in the order embed / body layers (q, k, v, o, then the
  MLP's in and out) / final norm; the token embedding scaled by
  1/sqrt(d_model); RMSNorm scales stored as (gamma - 1) = 0; layer weights
  stacked along a leading layer axis;
* layer: x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x)); causal grouped-query
  attention with half-rotation RoPE and 1/sqrt(head_dim) scaling; MLP GeLU
  (tanh form) or SwiGLU; final RMSNorm; tied or separate output head;
* loss: mean next-token cross-entropy over every position;
* optimizer: gradients rounded to ``grad_dtype``, clipped by global norm,
  AdamW (b1 0.9, eps 1e-8, bias-corrected) with decoupled weight decay on
  every stored leaf of rank >= 2 (the stacked per-layer norm scales are rank
  2), learning rate warm-up then cosine to a tenth, read at the step count
  before the update;
* tokens: a counter-hashed second-order Markov stream (murmur3-style 32-bit
  mixing of seed, stream, step, row and position): each token follows a
  fixed bigram rule with probability 0.85, else is uniform noise.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Iterator, List, Sequence

import numpy as np

ORDER_MIX = 0.85
KIND_INIT, KIND_FOLLOW, KIND_NOISE = 0xA11CE, 0xF0110, 0x707E5
U32 = 0xFFFFFFFF


# -- the token stream --------------------------------------------------------------
def _mix(words: Sequence[np.ndarray], shape) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = np.full(shape, 0x9E3779B9, np.uint32)
        for w in words:
            w = np.broadcast_to(np.asarray(w, np.uint64) & U32, shape).astype(np.uint32)
            h ^= w * np.uint32(0xCC9E2D51)
            h = (h << np.uint32(13)) | (h >> np.uint32(19))
            h = h * np.uint32(5) + np.uint32(0xE6546B64)
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def tokens(seed: int, stream: int, steps: Sequence[int], batch: int,
           seq: int, vocab: int) -> np.ndarray:
    """Token rows ``(len(steps), batch, seq + 1)`` of one trial's stream."""
    shape = (len(steps), batch)
    s = int(stream) & 0xFFFFFFFFFFFFFFFF
    step = np.asarray(steps, np.uint64)[:, None]
    row = np.arange(batch, dtype=np.uint64)[None, :]
    coords = [int(seed) & U32, s & U32, s >> 32, step, 0, row]

    def draw(kind, t):
        return _mix([kind] + coords + [t], shape)

    out = np.empty(shape + (seq + 1,), np.int64)
    out[..., 0] = draw(KIND_INIT, 0) % vocab
    out[..., 1] = draw(KIND_INIT, 1) % vocab
    for t in range(2, seq + 1):
        u = (draw(KIND_FOLLOW, t) >> np.uint32(8)).astype(np.float32) \
            * np.float32(2.0 ** -24)
        rule = _mix([out[..., t - 2], out[..., t - 1]], shape) % vocab
        noise = draw(KIND_NOISE, t) % vocab
        out[..., t] = np.where(u < np.float32(ORDER_MIX), rule, noise)
    return out.astype(np.int32)


# -- the model ---------------------------------------------------------------------
def init_params(cfg: Dict[str, Any], seed: int):
    import jax
    import jax.numpy as jnp

    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    ff, v, n = cfg["d_ff"], cfg["vocab_size"], cfg["n_layers"]
    gated = cfg["activation"] == "swiglu"

    def tn(key, shape, fan):
        return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                           jnp.float32) * (1.0 / math.sqrt(fan))

    def layer(key):
        (k_layer,) = jax.random.split(key, 1)
        k_attn, k_mlp = jax.random.split(k_layer)
        kq, kk, kvv, ko = jax.random.split(k_attn, 4)
        k_in, k_out = jax.random.split(k_mlp)
        return {
            "norm1": jnp.zeros((d,), jnp.float32),
            "wq": tn(kq, (d, h, hd), d), "wk": tn(kk, (d, kv, hd), d),
            "wv": tn(kvv, (d, kv, hd), d), "wo": tn(ko, (h, hd, d), h * hd),
            "norm2": jnp.zeros((d,), jnp.float32),
            "wi": tn(k_in, (d, 2 if gated else 1, ff), d),
            "wf": tn(k_out, (ff, d), ff),
        }

    @jax.jit
    def make(key):
        k_emb, _, k_body, _ = jax.random.split(key, 4)
        k_tok, k_head = jax.random.split(k_emb)
        p = {"embed": tn(k_tok, (v, d), d),
             "layers": jax.vmap(layer)(jax.random.split(k_body, n)),
             "final_norm": jnp.zeros((d,), jnp.float32)}
        if not cfg["tie_embeddings"]:
            p["head"] = tn(k_head, (d, v), d)
        return p

    return make(jax.random.PRNGKey(seed))


def _rmsnorm(x, g, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    import jax.numpy as jnp

    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def loss_fn(params, tok, tgt, cfg: Dict[str, Any], precision):
    import jax
    import jax.numpy as jnp

    ein = lambda s, a, b: jnp.einsum(s, a, b, precision=precision)
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    x = params["embed"][tok]                                      # (B, S, d)
    s = tok.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        y = _rmsnorm(x, p["norm1"], eps)
        q = _rope(ein("bsd,dhk->bshk", y, p["wq"]), theta)
        k = _rope(ein("bsd,dhk->bshk", y, p["wk"]), theta)
        v = ein("bsd,dhk->bshk", y, p["wv"])
        rep = h // kv
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = ein("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        a = ein("bhqs,bshk->bqhk", jax.nn.softmax(sc, -1), v)
        x = x + ein("bqhk,hkd->bqd", a, p["wo"])
        y = _rmsnorm(x, p["norm2"], eps)
        u = ein("bsd,dcf->bscf", y, p["wi"])
        if cfg["activation"] == "swiglu":
            m = jax.nn.silu(u[:, :, 0]) * u[:, :, 1]
        else:
            m = jax.nn.gelu(u[:, :, 0], approximate=True)
        return x + ein("bsf,fd->bsd", m, p["wf"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], eps)
    if cfg["tie_embeddings"]:
        logits = ein("bsd,vd->bsv", x, params["embed"])
    else:
        logits = ein("bsd,dv->bsv", x, params["head"])
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def make_step(cfg: Dict[str, Any], precision=None):
    """``(state, tok, tgt, hp) -> (state, loss)``: one AdamW training step."""
    import jax
    import jax.numpy as jnp

    precision = precision or jax.lax.Precision.HIGHEST
    gdt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["grad_dtype"]]
    b1, eps = 0.9, 1e-8

    def step(state, tok, tgt, hp):
        params, mu, nu, count = state
        loss, g = jax.value_and_grad(loss_fn)(params, tok, tgt, cfg, precision)
        g = jax.tree.map(lambda x: x.astype(gdt).astype(jnp.float32), g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-9))
        s = count.astype(jnp.float32)
        w, total, peak = hp["warmup_steps"], hp["total_steps"], hp["learning_rate"]
        prog = jnp.clip((s - w) / jnp.maximum(total - w, 1.0), 0.0, 1.0)
        lr = jnp.where(s < w, peak * s / jnp.maximum(w, 1.0),
                       peak * (0.1 + 0.45 * (1.0 + jnp.cos(jnp.pi * prog))))
        t = s + 1.0
        c1, c2 = 1.0 - b1 ** t, 1.0 - hp["b2"] ** t

        def upd(p, g, m, v):
            g = g * clip
            m = b1 * m + (1.0 - b1) * g
            v = hp["b2"] * v + (1.0 - hp["b2"]) * g * g
            delta = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if p.ndim >= 2:
                delta = delta + hp["weight_decay"] * p
            return p - lr * delta, m, v

        out = jax.tree.map(upd, params, g, mu, nu)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return (pick(0), pick(1), pick(2), count + 1), loss

    return jax.jit(step, donate_argnums=0)


def hparams(config: Dict[str, Any], budget: int) -> Dict[str, float]:
    """A trial's hyperparameters; the schedule spans its whole budget."""
    return {
        "learning_rate": float(config["learning_rate"]),
        "weight_decay": float(config["weight_decay"]),
        "b2": float(config["b2"]),
        "grad_clip": float(config["grad_clip"]),
        "warmup_steps": max(float(config["warmup_frac"]) * budget, 1.0),
        "total_steps": float(budget),
    }


def replay(cfg: Dict[str, Any], seed: int, batch: int, seq: int,
           trials: Iterable[Dict[str, Any]], precision=None) -> Iterator[List[float]]:
    """Each trial's per-step losses, one trial at a time as it is asked for:
    ``trials`` hold ``config``, ``stream``, ``budget`` (the schedule's
    length) and ``steps`` (steps applied)."""
    import jax
    import jax.numpy as jnp

    p0 = init_params(cfg, seed)
    step = make_step(cfg, precision)
    for t in trials:
        toks = tokens(seed, t["stream"], range(t["steps"]), batch, seq,
                      cfg["vocab_size"])
        hp = {k: jnp.float32(v) for k, v in hparams(t["config"], t["budget"]).items()}
        state = (jax.tree.map(jnp.copy, p0), jax.tree.map(jnp.zeros_like, p0),
                 jax.tree.map(jnp.zeros_like, p0), jnp.int32(0))
        losses = []
        for i in range(t["steps"]):
            tk = jnp.asarray(toks[i])
            state, loss = step(state, tk[:, :-1], tk[:, 1:], hp)
            losses.append(loss)
        del state
        yield [float(x) for x in jax.device_get(losses)]
