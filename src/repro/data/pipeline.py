"""Deterministic synthetic data pipeline — one pure generator, host AND device.

Language modeling: a seeded 2nd-order Markov token stream — structured enough
that a model visibly learns (loss drops from ln(V) toward the process
entropy), cheap enough for CPU smoke training, and exactly reproducible from
``(seed, step)`` so a restored checkpoint resumes on the *same* batch sequence
(the data cursor is just the step counter).

**Counter-based synthesis.** Every random draw is a pure function of its
coordinates — ``hash(kind, seed, stream, step, shard, row, position)`` over
32-bit integer arithmetic (xor / rotate / wrapping multiply) that NumPy and
``jax.numpy`` execute bit-for-bit identically.  The same ``synth_batch``
therefore runs on the host (``xp=numpy`` — the classic ``make_batch`` path)
and *inside a compiled program* (``xp=jax.numpy`` — the fused multi-step scan
engine synthesizes its batches on device, ``repro.train.population.
make_population_scan_step``), and the two are bit-identical by construction.
There is no sequential PRNG state: a batch at ``(stream, step)`` never
depends on any other batch having been drawn.

Host sharding: ``make_batch(step, shard, n_shards)`` yields that host's slice
of the global batch; shards draw from disjoint hash streams.

Per-trial streams (population HPO): ``stream`` is two extra hash words so
every trial of a population consumes an *independent* data sequence;
``make_population_batch`` stacks K such batches along a leading population
axis for the vmapped/sharded engines.  Negative streams are reserved
sentinels (idle/padding population lanes): they wrap to the top of the u64
range, far from any real (small, non-negative) trial stream.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Protocol, Sequence, Tuple

import numpy as np

from ..core import tracing

_U64 = 0xFFFFFFFFFFFFFFFF
_U32 = 0xFFFFFFFF

# draw kinds: the leading hash word, so the three per-position draw families
# (initial tokens / follow-the-rule uniforms / noise tokens) never collide
_KIND_INIT = 0xA11CE
_KIND_FOLLOW = 0xF0110
_KIND_NOISE = 0x707E5


def _rotl13(xp, h):
    u = xp.uint32
    return (h << u(13)) | (h >> u(19))


def _hash_u32(xp, shape, words) -> Any:
    """Combine integer ``words`` (scalars or arrays broadcastable against
    ``shape`` — pre-expand trailing dims yourself) into one uint32 hash.

    murmur3-style combine + finalizer over pure uint32 ops (xor, rotate,
    wrapping ``*``/``+``, logical shifts) — every op is specified bit-exactly
    by both NumPy and XLA, which is what makes host and device batches
    bit-identical.
    """
    u = xp.uint32
    h = xp.full(shape, 0x9E3779B9, dtype=xp.uint32)
    for w in words:
        if isinstance(w, (int, np.integer)):
            # mask host ints before the array constructor sees them: a top-half
            # sentinel word (e.g. 0xFFFFFFFF) must not overflow jnp's int32
            # literal inference
            w = np.uint32(int(w) & _U32)
        w = xp.broadcast_to(xp.asarray(w).astype(xp.uint32), shape)
        h = h ^ (w * u(0xCC9E2D51))
        h = _rotl13(xp, h)
        h = h * u(5) + u(0xE6546B64)
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> u(16))
    return h


def _u01(xp, h):
    """uint32 hash -> float32 uniform in [0, 1): the top 24 bits scaled by
    2^-24 — exact in float32, so the comparison against ``order_mix`` lands
    identically on host and device."""
    return (h >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0 ** -24)


def _rule32(xp, a, b, vocab: int):
    """Fixed pseudo-random bigram successor function (the Markov 'language').

    Independent of seed/stream/step — it is the process being learned, not a
    noise source — and pure uint32, so the recurrence replays identically
    wherever it runs.
    """
    h = _hash_u32(xp, a.shape, [a.astype(xp.uint32), b.astype(xp.uint32)])
    return (h % xp.uint32(vocab)).astype(xp.int32)


def split_stream(stream: int) -> Tuple[int, int]:
    """A (possibly negative, possibly 64-bit) stream id as two uint32 hash
    words.  Negative sentinels wrap to the top of the u64 range, far from any
    real (small, non-negative) trial stream."""
    s = int(stream) & _U64
    return s & _U32, (s >> 32) & _U32


def split_streams(streams: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized ``split_stream``: two uint32[K] word arrays for the
    population engines (host-built once per flight, consumed on device)."""
    pairs = [split_stream(s) for s in streams]
    return (np.asarray([p[0] for p in pairs], np.uint32),
            np.asarray([p[1] for p in pairs], np.uint32))


def synth_tokens(xp, spec: "SyntheticLM", rows_shape, step, stream_lo,
                 stream_hi, shard=0):
    """The pure generator: token array of shape ``rows_shape + (seq_len+1,)``.

    ``rows_shape`` is the batch-rows shape (``(b,)`` for one batch,
    ``(K, b)`` for a population); ``step`` / ``stream_lo`` / ``stream_hi`` /
    ``shard`` are integers or arrays broadcastable against ``rows_shape``
    (pass per-lane values shaped ``(K, 1)``).  With ``xp=numpy`` this is the
    host path; with ``xp=jax.numpy`` it traces into a compiled program —
    same bits either way.  ``step`` may be a traced scalar/array under jax.
    """
    vocab = int(spec.vocab_size)
    row = xp.arange(rows_shape[-1], dtype=xp.uint32)
    coords = [spec.seed, stream_lo, stream_hi, step, shard, row]
    if xp is np:
        return _synth_tokens_host(spec, tuple(rows_shape), coords)

    def draw(kind, t):
        return _hash_u32(xp, rows_shape, [kind] + coords + [t])

    def tok(kind, t):
        return (draw(kind, t) % xp.uint32(vocab)).astype(xp.int32)

    t0, t1 = tok(_KIND_INIT, 0), tok(_KIND_INIT, 1)
    mix = xp.float32(spec.order_mix)

    def next_tok(a, b, t):
        follow = _u01(xp, draw(_KIND_FOLLOW, t)) < mix
        return xp.where(follow, _rule32(xp, a, b, vocab), tok(_KIND_NOISE, t))

    import jax

    def body(carry, t):
        a, b = carry
        nxt = next_tok(a, b, t)
        return (b, nxt), nxt

    ts = xp.arange(2, spec.seq_len + 1, dtype=xp.uint32)
    _, rest = jax.lax.scan(body, (t0, t1), ts)
    rest = xp.moveaxis(rest, 0, -1)  # (T-2,) + rows -> rows + (T-2,)
    return xp.concatenate([t0[..., None], t1[..., None], rest], axis=-1)


def _synth_tokens_host(spec: "SyntheticLM", rows_shape, coords) -> np.ndarray:
    """``synth_tokens`` with NumPy, bit-identical to the traced scan.

    Only the rule hash depends on earlier tokens. The INIT, FOLLOW and NOISE
    draws are pure functions of their coordinates, so each is hashed for
    every position at once over a trailing position axis. The loop over
    positions keeps only the rule hash, written out in place with
    ``_hash_u32``'s constants and order: a per-position ``_hash_u32`` call
    spends its time in NumPy's per-call overhead on arrays of a few rows.
    Tokens are kept as uint32 and returned as an int32 view of the same bits
    (``astype(int32)`` and back, as the scan does, is the same wrap).
    """
    vocab = np.uint32(int(spec.vocab_size))
    n = int(spec.seq_len) + 1
    wide = [w if isinstance(w, (int, np.integer)) else np.asarray(w)[..., None]
            for w in coords]

    def draw_all(kind, width):
        t = np.arange(width, dtype=np.uint32)
        return _hash_u32(np, rows_shape + (width,), [kind] + wide + [t])

    toks = draw_all(_KIND_NOISE, n) % vocab
    toks[..., :2] = draw_all(_KIND_INIT, 2) % vocab
    follow = _u01(np, draw_all(_KIND_FOLLOW, n)) < np.float32(spec.order_mix)

    u = np.uint32
    c1, c2, five = u(0xCC9E2D51), u(0xE6546B64), u(5)
    f1, f2, h0 = u(0x85EBCA6B), u(0xC2B2AE35), u(0x9E3779B9)
    h = np.empty(rows_shape, np.uint32)
    tmp = np.empty_like(h)

    def absorb(w):  # h = rotl13(h ^ w * c1) * 5 + c2
        np.multiply(w, c1, out=tmp)
        np.bitwise_xor(h, tmp, out=h)
        np.left_shift(h, u(13), out=tmp)
        np.right_shift(h, u(19), out=h)
        np.bitwise_or(h, tmp, out=h)
        np.multiply(h, five, out=h)
        np.add(h, c2, out=h)

    def fold(shift):  # h ^= h >> shift
        np.right_shift(h, u(shift), out=tmp)
        np.bitwise_xor(h, tmp, out=h)

    for t in range(2, n):
        h.fill(h0)
        absorb(toks[..., t - 2])
        absorb(toks[..., t - 1])
        fold(16)
        np.multiply(h, f1, out=h)
        fold(13)
        np.multiply(h, f2, out=h)
        fold(16)
        np.remainder(h, vocab, out=h)
        np.copyto(toks[..., t], h, where=follow[..., t])
    return toks.view(np.int32)


def tokens_to_batch(xp, spec: "SyntheticLM", toks) -> Dict[str, Any]:
    """``synth_tokens`` output -> the training-batch dict contract
    (``tokens`` int32, ``targets`` int32, ``mask`` float32 ones)."""
    return {
        "tokens": toks[..., :-1],
        "targets": toks[..., 1:].astype(xp.int32),
        "mask": xp.ones(toks.shape[:-1] + (spec.seq_len,), xp.float32),
    }


def synth_batch(spec: "SyntheticLM", stream, step, *, xp=np, shard=0,
                n_shards: int = 1) -> Dict[str, Any]:
    """One training batch as a pure function of ``(stream, step)``.

    The single source of truth for batch synthesis: ``SyntheticLM.make_batch``
    is this with ``xp=numpy``; the fused scan engine calls it with
    ``xp=jax.numpy`` and a traced ``step`` so batches materialize on device,
    bit-identical to the host's.  ``stream`` must be a host int here (it is
    split into hash words); traced per-lane streams go through
    ``synth_population_batch``.
    """
    assert spec.global_batch % n_shards == 0
    b = spec.global_batch // n_shards
    lo, hi = split_stream(stream)
    toks = synth_tokens(xp, spec, (b,), step, lo, hi, shard=shard)
    return tokens_to_batch(xp, spec, toks)


def synth_population_batch(spec: "SyntheticLM", stream_lo, stream_hi, steps,
                           *, xp=np) -> Dict[str, Any]:
    """K per-lane batches with a leading population axis, from per-lane
    stream words (uint32[K], see ``split_streams``) and per-lane step cursors
    (int[K]; traced under jax).  Lane ``i``'s slab is bit-identical to
    ``synth_batch(spec, streams[i], steps[i])`` — the device-side twin of
    ``make_population_batch``.
    """
    k = stream_lo.shape[0]
    b = spec.global_batch
    lo = xp.asarray(stream_lo)[:, None]
    hi = xp.asarray(stream_hi)[:, None]
    st = xp.asarray(steps)[:, None]
    toks = synth_tokens(xp, spec, (k, b), st, lo, hi)
    return tokens_to_batch(xp, spec, toks)


@dataclasses.dataclass
class SyntheticLM:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    order_mix: float = 0.85  # P(follow the markov rule) vs uniform noise

    @property
    def spec_key(self) -> Tuple:
        """Hashable identity of the generator — keys the scan-step compile
        cache (a program bakes the batch synthesis in, so it is specific to
        this exact stream definition)."""
        return (int(self.vocab_size), int(self.seq_len),
                int(self.global_batch), int(self.seed), float(self.order_mix))

    def make_batch(
        self, step: int, shard: int = 0, n_shards: int = 1, stream: int = 0
    ) -> Dict[str, np.ndarray]:
        """Host batch: ``synth_batch`` evaluated with NumPy.  Bit-identical
        to the device synthesis at the same coordinates — the fused scan
        engine's equivalence contract."""
        return synth_batch(self, stream, int(step), xp=np, shard=int(shard),
                           n_shards=int(n_shards))

    def make_population_batch(
        self, step, streams: Sequence[int]
    ) -> Dict[str, np.ndarray]:
        """K independent per-trial batches stacked on a leading population axis.

        Trial ``i`` of the population consumes the stream ``streams[i]``
        sequence — leaf shapes become ``(K, batch, ...)`` for the population
        engines' ``per_trial_batch`` mode.  ``step`` may be a single int (all
        lanes at the same cursor — the batch-synchronous engines) or one int
        per lane: a *refilled* lane joined the flight late, so it replays its
        own stream from its own local step 0 while older lanes are further in.
        """
        steps = [int(step)] * len(streams) if np.isscalar(step) \
            else [int(s) for s in step]
        assert len(steps) == len(streams)
        with tracing.span("data.host_batch", lanes=len(streams)):
            lo, hi = split_streams(streams)
            return synth_population_batch(
                self, lo, hi, np.asarray(steps, np.int64), xp=np)


class HostDataset(Protocol):
    """What the prefetch ring needs from a host data source.

    One method: a *lane block* — per-lane token rows for K population lanes,
    each lane at its own step cursor, shaped ``(K, batch, seq_len + 1)`` int32
    (the raw ``synth_tokens`` layout; ``tokens_to_batch`` splits it into
    tokens/targets/mask on device).  Implementations must be pure functions
    of ``(streams, steps)`` so a crash-restored flight replays the same
    bytes — the ring's resume contract is exactly the data-cursor contract
    the synthetic stream already has.
    """

    seq_len: int
    global_batch: int

    def lane_block(self, streams: Sequence[int], steps) -> np.ndarray:
        """Token rows ``(K, global_batch, seq_len + 1)`` int32 for lane ``i``
        reading ``streams[i]`` at step ``steps[i]``."""
        ...

    # Implementations may additionally provide
    #     lane_window(streams, steps, n) -> (n, K, global_batch, seq_len + 1)
    # — ``n`` consecutive lane blocks built in one vectorized call,
    # bit-identical to stacking ``lane_block`` per step.  The ring's fill
    # thread prefers it: one call per prefetch window instead of one per
    # step keeps the host fill cheap enough to hide behind device compute.


@dataclasses.dataclass
class SynthHostDataset:
    """``HostDataset`` over the counter-based synthetic stream — the ring's
    bit-equality oracle.  ``lane_block`` evaluates the SAME ``synth_tokens``
    the fused scan traces on device (``xp=numpy`` here, ``xp=jax.numpy``
    there), so a ring filled from this adapter reproduces the in-scan synth
    engine's batches bit-for-bit: the cross-engine matrix can assert ring-fed
    scores equal in-scan-synth scores exactly."""

    spec: SyntheticLM

    @property
    def seq_len(self) -> int:
        return int(self.spec.seq_len)

    @property
    def global_batch(self) -> int:
        return int(self.spec.global_batch)

    def lane_block(self, streams: Sequence[int], steps) -> np.ndarray:
        lo, hi = split_streams(streams)
        st = np.asarray([int(s) for s in steps], np.int64)
        return synth_tokens(np, self.spec, (len(streams), self.global_batch),
                            st[:, None], lo[:, None], hi[:, None])

    def lane_window(self, streams: Sequence[int], steps, n: int) -> np.ndarray:
        """``n`` consecutive ``lane_block`` slabs — steps ``steps[i] + t`` for
        ``t in [0, n)`` — built in ONE vectorized synthesis call, shape
        ``(n, K, global_batch, seq_len + 1)``.  Bit-identical to stacking
        ``lane_block`` per step; one call amortizes the hash-round overhead
        over the whole prefetch window instead of paying it per step, which
        is what keeps the ring's fill thread cheap enough to hide."""
        lo, hi = split_streams(streams)
        st = np.asarray([int(s) for s in steps], np.int64)
        step = (st[None, :, None]
                + np.arange(int(n), dtype=np.int64)[:, None, None])
        return synth_tokens(
            np, self.spec, (int(n), len(streams), self.global_batch),
            step, lo[None, :, None], hi[None, :, None])


@dataclasses.dataclass
class ArrayHostDataset:
    """``HostDataset`` over a real token corpus held in host memory: a
    ``(n_rows, seq_len + 1)`` int32 array (e.g. a memory-mapped tokenized
    shard).  Lane ``i`` at step ``s`` reads ``global_batch`` consecutive rows
    starting at ``(streams[i] * stream_stride + s * global_batch) % n_rows``
    — per-trial streams start at disjoint offsets and the cursor is just the
    step counter, so resume replays identically."""

    tokens: np.ndarray
    global_batch: int
    stream_stride: int = 997  # co-prime-ish lane offset into the corpus

    def __post_init__(self):
        self.tokens = np.asarray(self.tokens, np.int32)
        assert self.tokens.ndim == 2 and len(self.tokens) > 0

    @property
    def seq_len(self) -> int:
        return int(self.tokens.shape[1]) - 1

    def lane_block(self, streams: Sequence[int], steps) -> np.ndarray:
        n = len(self.tokens)
        b = int(self.global_batch)
        out = np.empty((len(streams), b, self.tokens.shape[1]), np.int32)
        for i, (stream, step) in enumerate(zip(streams, steps)):
            start = (int(stream) * self.stream_stride + int(step) * b) % n
            idx = (start + np.arange(b)) % n
            out[i] = self.tokens[idx]
        return out

    def lane_window(self, streams: Sequence[int], steps, n: int) -> np.ndarray:
        """``n`` consecutive ``lane_block`` slabs in one gather, shape
        ``(n, K, global_batch, seq_len + 1)`` — same rows as stacking
        ``lane_block`` per step."""
        nrows = len(self.tokens)
        b = int(self.global_batch)
        sid = np.asarray([int(s) for s in streams], np.int64)
        st = np.asarray([int(s) for s in steps], np.int64)
        step = st[None, :] + np.arange(int(n), dtype=np.int64)[:, None]
        start = sid[None, :] * self.stream_stride + step * b
        idx = (start[..., None] + np.arange(b)) % nrows
        return self.tokens[idx]


class HostPrefetcher:
    """Prefetch-ahead feed for the SERIAL drivers: build batch ``s+1`` and
    dispatch its ``jax.device_put`` while the (asynchronously dispatched)
    step ``s`` program is still running, BEFORE the driver blocks on step
    ``s``'s loss.  A plain generator cannot do this — the consumer blocks on
    ``float(metrics["loss"])`` before it would ever pull the next item — so
    the serial loops call ``pop(s)`` / ``prefetch(s + 1)`` explicitly around
    the blocking read.  Batches are byte-identical to the direct
    ``make_batch`` path (same builder, same coordinates); only the timing of
    the host work moves.
    """

    def __init__(self, build):
        self._build = build  # step -> host batch (dict of numpy arrays)
        self._next: Any = None  # (step, device batch) or None

    def _put(self, step: int):
        import jax

        return jax.device_put(self._build(step))

    def prefetch(self, step: int) -> None:
        """Stage batch ``step`` on device ahead of time (async dispatch)."""
        self._next = (step, self._put(step))

    def pop(self, step: int):
        """The batch for ``step``: the staged one if it matches, else built
        on the spot (first step, or a driver that skipped around)."""
        if self._next is not None and self._next[0] == step:
            batch = self._next[1]
            self._next = None
            return batch
        self._next = None
        return self._put(step)


@dataclasses.dataclass
class SyntheticClassification:
    """Template-plus-noise image classification (paper §IV's MNIST stand-in).

    Each class has a fixed random template; samples are template + Gaussian
    noise.  Accuracy responds smoothly to model capacity / lr / dropout, so
    HPO curves (paper Fig. 5) are meaningful.
    """

    n_classes: int = 10
    image_size: int = 16
    noise: float = 5.0
    seed: int = 7

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # spatially smooth class templates (low-frequency random fields):
        # conv + pooling layers can pick these up; white-noise templates would
        # make the task adversarial to exactly the architectures being tuned
        coarse = rng.standard_normal((self.n_classes, 4, 4)).astype(np.float32)
        up = self.image_size // 4
        smooth = np.kron(coarse, np.ones((1, up, up), np.float32))
        # light blur across pixels to avoid blocky edges
        smooth = (smooth + np.roll(smooth, 1, 1) + np.roll(smooth, 1, 2)
                  + np.roll(smooth, -1, 1) + np.roll(smooth, -1, 2)) / 5.0
        self.templates = (2.0 * smooth[..., None]).astype(np.float32)

    def make_split(self, n: int, seed: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(seed)
        labels = rng.integers(self.n_classes, size=n)
        x = self.templates[labels] + self.noise * rng.standard_normal(
            (n, self.image_size, self.image_size, 1)
        ).astype(np.float32)
        return {"x": x.astype(np.float32), "y": labels.astype(np.int32)}
