"""The NumPy branch of ``synth_tokens`` against the per-position loop it replaced.

The host path hashes the INIT, FOLLOW and NOISE draws for every position at
once and keeps only the rule hash in its loop over positions. These cases
pin it bit for bit to the old loop, kept below as an oracle, over the shapes
its callers use (one batch, a population, the prefetch ring's window), over
sentinel and 64-bit streams, both ends of ``order_mix`` and the shortest
sequences; and to the ``jax.numpy`` branch the fused scans trace. No
hypothesis dependency, so every case always runs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.pipeline import (
    _KIND_FOLLOW,
    _KIND_INIT,
    _KIND_NOISE,
    SyntheticLM,
    SynthHostDataset,
    _hash_u32,
    _rule32,
    _u01,
    split_stream,
    split_streams,
    synth_population_batch,
    synth_tokens,
)


def oracle_tokens(spec, rows_shape, step, stream_lo, stream_hi, shard=0):
    """The host path as one ``_hash_u32`` call per draw per position."""
    xp = np
    vocab = int(spec.vocab_size)
    row = xp.arange(rows_shape[-1], dtype=xp.uint32)
    coords = [spec.seed, stream_lo, stream_hi, step, shard, row]

    def draw(kind, t):
        return _hash_u32(xp, rows_shape, [kind] + coords + [t])

    def tok(kind, t):
        return (draw(kind, t) % xp.uint32(vocab)).astype(xp.int32)

    t0, t1 = tok(_KIND_INIT, 0), tok(_KIND_INIT, 1)
    mix = xp.float32(spec.order_mix)

    def next_tok(a, b, t):
        follow = _u01(xp, draw(_KIND_FOLLOW, t)) < mix
        return xp.where(follow, _rule32(xp, a, b, vocab), tok(_KIND_NOISE, t))

    toks = np.empty(rows_shape + (spec.seq_len + 1,), np.int32)
    toks[..., 0], toks[..., 1] = t0, t1
    for t in range(2, spec.seq_len + 1):
        toks[..., t] = next_tok(toks[..., t - 2], toks[..., t - 1], t)
    return toks


def _lanes(streams, steps):
    lo, hi = split_streams(streams)
    return np.asarray(steps, np.int64)[:, None], lo[:, None], hi[:, None]


def _one_batch(shard):
    lo, hi = split_stream(11)
    return (5,), 37, lo, hi, shard


def _population(streams, steps, b):
    st, lo, hi = _lanes(streams, steps)
    return (len(streams), b), st, lo, hi, 0


def _lane_window(streams, steps, n, b):
    lo, hi = split_streams(streams)
    st = np.asarray(steps, np.int64)
    step = st[None, :, None] + np.arange(n, dtype=np.int64)[:, None, None]
    return ((n, len(streams), b), step, lo[None, :, None], hi[None, :, None],
            0)


SMALL = dict(vocab_size=251, seq_len=33, global_batch=2, seed=3)

# (spec overrides, rows_shape/step/lo/hi/shard builder)
CASES = {
    "b-shard0": ({}, lambda: _one_batch(0)),
    "b-shard1": ({}, lambda: _one_batch(1)),
    "Kb-lane-cursors": ({}, lambda: _population([0, 1, 2], [0, 4, 9], 2)),
    "nKb-lane-window": ({}, lambda: _lane_window([2, 7], [3, 0], 4, 2)),
    "sentinel-streams": ({}, lambda: _population([-1, -2, -(2 ** 33)],
                                                 [1, 1, 2], 2)),
    "64bit-streams": ({}, lambda: _population(
        [2 ** 32, 2 ** 40 + 3, 2 ** 63 - 1], [0, 6, 6], 2)),
    "order-mix-0": ({"order_mix": 0.0},
                    lambda: _population([1, 2], [0, 3], 2)),
    "order-mix-1": ({"order_mix": 1.0},
                    lambda: _population([1, 2], [0, 3], 2)),
    "seq-1": ({"seq_len": 1}, lambda: _population([1, 2], [0, 3], 2)),
    "seq-2": ({"seq_len": 2}, lambda: _population([1, 2], [0, 3], 2)),
    "seq-33": ({"seq_len": 33}, lambda: _population([1, 2], [0, 3], 2)),
    "cell-widths": ({"vocab_size": 49152, "seq_len": 1024, "global_batch": 1,
                     "seed": 20240521},
                    lambda: _population([5, 6], [0, 17], 1)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_host_tokens_equal_per_position_loop(case):
    overrides, build = CASES[case]
    spec = SyntheticLM(**{**SMALL, **overrides})
    rows_shape, step, lo, hi, shard = build()
    got = synth_tokens(np, spec, rows_shape, step, lo, hi, shard=shard)
    want = oracle_tokens(spec, rows_shape, step, lo, hi, shard=shard)
    assert got.dtype == np.int32
    assert got.shape == rows_shape + (spec.seq_len + 1,)
    np.testing.assert_array_equal(got, want)


def test_lane_window_equals_oracle_per_step():
    """The ring's window, built in one call, stacks the oracle's lane
    blocks step by step."""
    spec = SyntheticLM(**SMALL)
    streams, steps, n = [4, -1, 2 ** 35], [2, 0, 9], 3
    got = SynthHostDataset(spec).lane_window(streams, steps, n)
    for i in range(n):
        st, lo, hi = _lanes(streams, [s + i for s in steps])
        want = oracle_tokens(spec, (len(streams), spec.global_batch), st,
                             lo, hi)
        np.testing.assert_array_equal(got[i], want)


def test_host_population_batch_equals_jitted_device_branch():
    spec = SyntheticLM(vocab_size=49152, seq_len=64, global_batch=2, seed=9)
    lo, hi = split_streams([3, -1, 2 ** 36 + 1])
    steps = np.asarray([0, 5, 12], np.int64)
    host = synth_population_batch(spec, lo, hi, steps, xp=np)
    dev = jax.jit(lambda a, b, s: synth_population_batch(spec, a, b, s,
                                                         xp=jnp))(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(steps, jnp.int32))
    for key in host:
        assert host[key].dtype == np.asarray(dev[key]).dtype
        np.testing.assert_array_equal(host[key], np.asarray(dev[key]))
