"""Device-resident prefetch ring: host-fed fused scans without device stalls.

The fused multi-step scan engine (``--chunk-steps``) removed the per-step
host round-trip by synthesizing batches *inside* the compiled program — which
works only for the counter-based synthetic stream.  ``PrefetchRing`` opens
that engine to host-supplied data: a ring of ``windows`` chunk-windows of
per-lane token blocks lives ON DEVICE as one ``(capacity, K, batch,
seq_len+1)`` int32 array, a background host thread fills windows ahead of the
consumer (``HostDataset.lane_block`` -> ``jax.device_put`` -> a donated
``dynamic_update_slice`` write), and the ring scan indexes it by
``step % capacity`` — device compute only waits on the feed if the host
falls a full ring behind.

Fence protocol (single lock + condition, two monotone step pointers):

- ``_filled_to``: batches for global steps ``[..., _filled_to)`` are on
  device at the current lane generation.  Advanced only by the fill thread.
- ``_consumed_to``: the driver has dispatched every scan that reads steps
  below this.  Advanced only by ``consume_to``.  The filler never lets
  ``_filled_to - _consumed_to`` exceed ``capacity`` — an unconsumed slot is
  never overwritten.

``wait_filled(s, want)`` blocks until steps ``[s, s + want)`` are filled
(accumulating ``fill_wait_s`` — the host time the driver blocked on the
fill).  The driver asks for exactly the ``ChunkPlanner.chunk_to`` horizon it
is about to dispatch, so chunk horizons stay capped to filled windows while
the dispatch sequence remains bit-identical to the in-scan-synth engine —
a lagging fill costs wait time, never a different chunk split (which would
reorder result arrival under a stateful proposer).

Donation ordering makes the single device array safe to rotate from the fill
thread: the write donates the ring buffer, and the runtime sequences it after
every already-dispatched scan that reads the old value; the driver always
re-fetches the current handle via ``slots()`` under the lock.

``set_lanes(streams, offsets, at_step)`` re-keys the ring when the lane
table changes (refill splice, PBT clone, restored snapshot): it bumps a
generation counter so in-flight and already-filled windows are discarded and
the filler restarts from ``at_step`` with the new per-lane cursors.  Lane
``i``'s batch for global step ``s`` is ``dataset.lane_block`` at step
``offsets[i] + s`` — offsets carry each lane's private data cursor
(``base_data - start`` in the streaming driver), so crash-restored lanes
resume mid-stream exactly.  A ``set_lanes`` call with an UNCHANGED lane
table is a no-op: hp-only event boundaries (rung truncations, hparam
updates) re-key with the same (stream, cursor) table, and the prefetched
windows they would otherwise discard are still byte-correct.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional, Sequence

import numpy as np

from ..core import tracing


_RING_WRITE = None


def _ring_write(ring, block, slot0):
    """Write ``block`` (n, K, B, L+1) into the ring at ``slot0`` — the ring
    argument is DONATED, so rotation reuses the device buffer instead of
    doubling memory, and the runtime sequences the write after every
    in-flight scan that reads the old value."""
    global _RING_WRITE
    if _RING_WRITE is None:
        import jax

        def write(ring, block, slot0):
            return jax.lax.dynamic_update_slice_in_dim(
                ring, block, slot0, axis=0)

        _RING_WRITE = jax.jit(write, donate_argnums=(0,))
    return _RING_WRITE(ring, block, slot0)


class PrefetchRing:
    """W chunk-windows of per-lane token blocks on device, host-filled ahead.

    ``dataset`` is a ``repro.data.pipeline.HostDataset``; ``win_steps`` is
    the fused-scan chunk size (one window backs one maximal chunk);
    ``windows`` is the prefetch depth (2 = classic double buffering);
    ``sharding`` optionally places the lane axis on the ``pop`` mesh axis for
    the sharded engine (``NamedSharding(mesh, P(None, 'pop', None, None))``).
    """

    def __init__(self, dataset, population: int, win_steps: int,
                 windows: int = 2, sharding=None):
        import jax
        import jax.numpy as jnp

        assert windows >= 2, "need at least two windows to overlap fill"
        self.dataset = dataset
        self.population = int(population)
        self.win_steps = max(1, int(win_steps))
        self.windows = int(windows)
        self.capacity = self.windows * self.win_steps
        self._shape = (self.capacity, self.population,
                       int(dataset.global_batch), int(dataset.seq_len) + 1)
        self._sharding = sharding
        zeros = jnp.zeros(self._shape, jnp.int32)
        self._ring = (jax.device_put(zeros, sharding)
                      if sharding is not None else zeros)

        self._lock = threading.Condition()
        self._streams: Optional[np.ndarray] = None
        self._offsets: Optional[np.ndarray] = None
        self._gen = 0
        self._filled_to = 0
        self._consumed_to = 0
        self._stopped = False
        self._error: Optional[BaseException] = None

        # telemetry: time the consumer blocked on the feed vs time the host
        # spent producing — overlap_frac ~ 1 means fill fully hidden
        self.fill_wait_s = 0.0
        self.fill_busy_s = 0.0
        self.n_fills = 0
        self.n_invalidations = 0

        self._thread = threading.Thread(
            target=self._fill_loop, name="prefetch-ring-fill", daemon=True)
        self._thread.start()

    # -- driver-facing fences ---------------------------------------------------
    def set_lanes(self, streams: Sequence[int], offsets: Sequence[int],
                  at_step: int) -> None:
        """(Re)key the ring: lane ``i`` at global step ``s`` reads
        ``streams[i]`` at data step ``offsets[i] + s``.  Invalidate anything
        filled past ``at_step`` — the lane table changed under it."""
        assert len(streams) == self.population
        new_streams = np.asarray(list(streams), np.int64)
        new_offsets = np.asarray([int(o) for o in offsets], np.int64)
        with self._lock:
            if (self._streams is not None
                    and np.array_equal(self._streams, new_streams)
                    and np.array_equal(self._offsets, new_offsets)):
                # identical lane table: every filled window still maps the
                # same (stream, data-step) coordinates — keep the prefetch
                # instead of discarding it (hp-only event boundaries re-key
                # with an unchanged table every time)
                return
            if self._streams is not None and self._filled_to > int(at_step):
                self.n_invalidations += 1  # prefetched windows discarded
            self._streams = new_streams
            self._offsets = new_offsets
            self._gen += 1
            self._filled_to = int(at_step)
            self._consumed_to = int(at_step)
            self._lock.notify_all()

    def wait_filled(self, s: int, want: int = 1) -> int:
        """Block until batches for global steps ``[s, s + want)`` are on
        device; return the contiguous filled extent from ``s`` (>= ``want``).

        ``want`` must not exceed ``capacity``.  The driver asks for exactly
        the chunk it is about to dispatch, so the dispatch sequence is
        IDENTICAL to the in-scan-synth engine's — a lagging host fill shows
        up as ``fill_wait_s`` (and a lower ``overlap_frac``), never as a
        different chunk split, which would perturb result-arrival order under
        a stateful proposer."""
        want = max(1, min(int(want), self.capacity))
        t0 = None
        with tracing.span("ring.wait"), self._lock:
            while self._filled_to < s + want and self._error is None \
                    and not self._stopped:
                if t0 is None:
                    t0 = time.perf_counter()
                self._lock.wait(timeout=0.5)
            if t0 is not None:
                self.fill_wait_s += time.perf_counter() - t0
            if self._error is not None:
                raise RuntimeError("prefetch ring fill failed") \
                    from self._error
            if self._stopped:
                raise RuntimeError("prefetch ring stopped while waiting")
            return int(self._filled_to - s)

    def consume_to(self, s: int) -> None:
        """All scans reading steps below ``s`` are dispatched — their slots
        may be rewritten (donation sequences the rewrite after the reads)."""
        with self._lock:
            if s > self._consumed_to:
                self._consumed_to = int(s)
                self._lock.notify_all()

    @contextlib.contextmanager
    def reserve(self):
        """The current device ring array, pinned for one dispatch.

        Dispatch the ring scan INSIDE this block: the fill thread's donated
        rotation deletes the old python handle, so a handle fetched outside
        the lock can die between fetch and dispatch.  Holding the lock spans
        only the (async) dispatch call — once dispatched, the runtime owns
        the buffer dependency and the rotation sequences after the read.
        """
        with self._lock:
            yield self._ring

    @property
    def overlap_frac(self) -> float:
        """Fraction of host fill time hidden behind device compute."""
        if self.fill_busy_s <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.fill_wait_s / self.fill_busy_s))

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            self._lock.notify_all()
        self._thread.join(timeout=5.0)

    # -- fill thread ------------------------------------------------------------
    def _fill_loop(self) -> None:
        import jax
        import jax.numpy as jnp

        try:
            while True:
                with self._lock:
                    while not self._stopped and (
                            self._streams is None
                            or self._filled_to - self._consumed_to
                            >= self.capacity):
                        self._lock.wait(timeout=0.5)
                    if self._stopped:
                        return
                    gen = self._gen
                    s0 = self._filled_to
                    streams = self._streams.copy()
                    offsets = self._offsets.copy()
                    free = self.capacity - (s0 - self._consumed_to)
                    slot0 = s0 % self.capacity
                    n = min(self.win_steps, self.capacity - slot0, free)

                t0 = time.perf_counter()
                with tracing.span("ring.fill", steps=n):
                    window = getattr(self.dataset, "lane_window", None)
                    if window is not None:
                        # one vectorized call per window — amortizes the
                        # per-call synthesis overhead across all n steps
                        block = window(streams, offsets + s0, n)
                    else:
                        block = np.stack([
                            self.dataset.lane_block(streams, offsets + (s0 + t))
                            for t in range(n)
                        ])  # (n, K, B, L+1) int32
                    dev = jax.device_put(
                        jnp.asarray(block, jnp.int32), self._sharding) \
                        if self._sharding is not None else jnp.asarray(
                            block, jnp.int32)

                with self._lock:
                    if self._stopped:
                        return
                    if gen != self._gen:
                        continue  # lane table changed mid-build: discard
                    self._ring = _ring_write(
                        self._ring, dev, jnp.asarray(slot0, jnp.int32))
                    self._filled_to = s0 + n
                    self.n_fills += 1
                    self.fill_busy_s += time.perf_counter() - t0
                    self._lock.notify_all()
        except BaseException as e:  # propagate to the blocked consumer
            with self._lock:
                self._error = e
                self._lock.notify_all()
