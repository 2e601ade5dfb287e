"""HPO driver: the paper's Experiment loop over model-training trials.

This is Auptimizer's headline use-case on the training substrate: pick an
architecture (reduced config on CPU), define a search space over training
hyperparameters, and let any proposer drive trials through a resource
manager.  Switching HPO algorithms is exactly one flag (--proposer), the
paper's flexibility claim.

    PYTHONPATH=src python -m repro.launch.hpo --arch starcoder2-3b \\
        --proposer random --n-samples 8 --n-parallel 2 --steps 30

Each trial trains the smoke config for --steps on the deterministic
synthetic stream and reports -final_loss as the score.  All proposals and
results land in the tracking DB (--db) for post-hoc analysis / resume.

**Execution engines** (the HParams-as-traced-input contract):

* default (``--vectorize 0``) — compile-once serial: per-trial hyperparameters
  (lr / weight_decay / b2 / grad_clip / warmup / total steps) ride in a traced
  ``HParams`` pytree, so all trials of the architecture share ONE compiled
  step (``repro.train.train_step.get_compiled_train_step``) instead of paying
  an XLA recompile each (the pre-refactor behavior survives as
  ``make_trial`` / ``--legacy-recompile`` for benchmarking);
* ``--vectorize K`` — population mode: K slots are presented to the loop by
  ``VectorizedResourceManager``, the proposer is drained in batches
  (``get_params``), and each batch trains as one ``jax.vmap``-ed jitted
  program (``repro.train.population``) with divergence masking — a NaN trial
  freezes and reports the sentinel score, the batch lives on.  Partial
  batches are padded to K (padding trials get a 0-step budget) so the whole
  experiment still compiles exactly once per (architecture, K);
* ``--vectorize K --shard-population`` — the K-trial population axis is
  additionally split over every local device (``shard_map`` on a 1-D
  population mesh via ``ShardedPopulationResourceManager``): K/N trials per
  device, still ONE compiled program, no cross-trial communication.

Population trials consume **independent per-trial data streams** by default:
each trial's stream id (its ``job_id``, or an explicit ``stream`` config key)
is folded into the batch PRNG, in serial and population modes alike — so the
engines stay score-equivalent trial-for-trial.  ``--shared-stream`` restores
the legacy behavior where every trial sees the same seeded sequence.

With ``--inflight-stop`` and a rung proposer (asha / hyperband / bohb), the
proposer's successive-halving rule also runs *inside* each population flight:
at every rung boundary, losing lanes get their traced step budget truncated
mid-flight, the flush returns as soon as the survivors finish, and the freed
lanes immediately take the next batch of proposals.

``--lane-refill`` goes further: the flight never has to end for a freed lane
to be reused.  A retired lane (budget exhausted, rung-truncated, or diverged)
streams its result out immediately and is reset *in place* — a traced
per-lane mask re-inits its weights inside the compiled program — so the next
proposal starts training while the rest of the population keeps running.
This is Auptimizer Algorithm 1's every-resource-busy invariant enforced down
to individual population lanes: one continuous flight per experiment instead
of batch-synchronous flushes.  ``--per-trial-init`` additionally gives every
trial its own init weights (stream id folded into the init key, identically
in serial and population modes).

``--pbt-streaming`` puts Population-Based Training on the same streaming
engine (implies ``--lane-refill``): each PBT member owns a lane, trains one
round per job, and its next job carries a lane-lifecycle directive — ``keep``
(continue in place, no device op) or ``clone`` (the lane inherits a donor
lane's weights AND optimizer state through the compiled ``make_lane_clone``
op).  Exploit/explore runs as a quantile rule over a sliding member-score
window; by default rounds are gated so decisions match the generation-
barriered serial driver (``run_pbt_serial``) decision-for-decision, while
``--pbt-async`` unlocks the fully staggered rule.  Either way the weights
never visit the host — no ``pbt_ckpt`` checkpoint round-trip, no generation
bubble (``pbt_host_ckpt_roundtrips`` stays 0 in the CLI telemetry).

``--chunk-steps T`` fuses the innermost loop itself: instead of one host
dispatch (and one host-built batch) per population step, the engines scan up
to T steps inside one compiled program, synthesizing each step's batches *on
device* from the per-lane stream ids and a traced step counter
(``repro.data.pipeline.synth_batch`` runs bit-identically under NumPy and
XLA).  Chunk boundaries always land on host-known event steps — rung
boundaries, retirements, PBT round ends — and the divergence poll becomes
chunk-granular, so ``--chunk-steps 1`` reproduces the per-step loop
bit-for-bit while larger T trades divergence-reclaim latency for a ~T-fold
cut in host dispatches.

Vectorized/sharded mode is only valid when every proposal varies *traced*
knobs: all trials must share the architecture and batch geometry.  Per-trial
architecture params (d_model, n_layers, ... — e.g. the NAS/EAS space) change
the compiled program shape and MUST use serial mode.  Per-trial budgets
(``n_iterations`` from Hyperband/ASHA) are fine: ``hp.total_steps`` doubles
as a step budget and exhausted trials freeze in place.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from .chunkplan import (
    ChunkPlanner,
    device_dispatch_horizon as _device_dispatch_horizon,
    next_event_step as _next_event_step,
    poll_anchor as _poll_anchor,
    pow2_ceil as _pow2_ceil,
    pow2_floor as _pow2_floor,
)


def make_trial(arch: str, steps: int, batch: int, seq: int, seed: int):
    """Legacy trial callable: config dict -> score, recompiling per trial.

    Bakes the proposal into the TrainConfig closure, so every call pays a
    full XLA compile — kept as the baseline ``benchmarks/hpo_throughput.py``
    measures against.  Use ``PopulationTrial`` for real runs.
    """

    def trial(config: dict) -> float:
        import jax

        from ..configs import get_smoke_config
        from ..configs.base import ParallelConfig, TrainConfig
        from ..data.pipeline import HostPrefetcher, SyntheticLM
        from ..train.train_step import init_train_state, make_train_step

        cfg = get_smoke_config(arch)
        n_steps = int(config.get("n_iterations", 1) * steps)
        tc = TrainConfig(
            model=cfg,
            parallel=ParallelConfig(remat="none"),
            learning_rate=float(config["learning_rate"]),
            warmup_steps=max(1, int(config.get("warmup_frac", 0.1) * n_steps)),
            total_steps=n_steps,
            weight_decay=float(config.get("weight_decay", 0.1)),
            b2=float(config.get("b2", 0.95)),
            grad_clip=float(config.get("grad_clip", 1.0)),
            seed=seed,
        )
        data = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)
        state = init_train_state(jax.random.PRNGKey(seed), tc)
        step_fn = jax.jit(make_train_step(tc))
        # prefetch-ahead host feed: batch s+1 is built and device_put while
        # the (async-dispatched) step s still runs, BEFORE the blocking loss
        # read — same bytes as the direct make_batch path, less device idle
        feed = HostPrefetcher(data.make_batch)
        loss = float("inf")
        for s in range(n_steps):
            state, metrics = step_fn(state, feed.pop(s))
            if s + 1 < n_steps:
                feed.prefetch(s + 1)
            loss = float(metrics["loss"])
            if not np.isfinite(loss):
                return -1e9  # diverged
        return -loss

    return trial


class PopulationTrial:
    """Compile-once trial executor for one architecture.

    ``__call__(config)`` is the scalar protocol (local/subprocess managers);
    ``run_population(configs, mesh=None)`` is the batch protocol the
    vectorized/sharded managers use — K trials advance in one vmapped jitted
    program, split over ``mesh``'s population axis when one is given.  Either
    way the proposal's hyperparameters are *traced* inputs, so the experiment
    compiles once per (architecture, population size, mesh), not once per
    trial.

    ``per_trial_streams`` (default on) folds each trial's stream id — the
    ``stream`` config key, else its ``job_id``, else its lane position — into
    the batch PRNG, in the scalar and batch protocols alike, so every trial
    trains on its own independent data sequence and the engines remain
    score-equivalent trial-for-trial.

    ``early_stop`` may hold an in-flight hook (see
    ``repro.core.proposer.early_stop``): between population steps, at the
    hook's rung boundaries, losing lanes get their traced step budget
    truncated so the flight ends as soon as the surviving lanes finish.

    ``per_trial_init`` folds each trial's stream id into its *init* PRNG key
    as well, so every trial starts from its own weights — in serial and
    population modes alike (the engines stay score-equivalent).  Default off:
    the legacy behavior inits every trial from ``PRNGKey(seed)``.

    ``run_population(configs=[], scheduler=...)`` is the **streaming** (lane
    refill) protocol: instead of a positional batch, the engine leases jobs
    from the scheduler into freed lanes mid-flight (resetting the lane's
    train state inside the compiled program) and streams each job's result
    back the moment its lane retires.  See ``_run_streaming``.
    """

    DIVERGED_SCORE = -1e9

    def __init__(self, arch: str, steps: int, batch: int, seq: int, seed: int,
                 population: int = 0, per_trial_streams: bool = True,
                 early_stop=None, per_trial_init: bool = False,
                 refill_idle_grace_s: float = 0.25, lifecycle=None,
                 chunk_steps: int = 1, snapshot_every: int = 0,
                 snapshots=None, device_rules: bool = False,
                 elastic_regrid: bool = False, data_ring: bool = False,
                 ring_windows: int = 2, fused_rmsnorm: bool = False,
                 fused_attention: bool = False, fused_ssm: bool = False,
                 model_parallel: int = 1, model_overrides=None):
        self.arch = arch
        self.steps = int(steps)
        self.batch = int(batch)
        self.seq = int(seq)
        self.seed = int(seed)
        self.population = int(population)  # >0: pad batches to this fixed K
        self.per_trial_streams = bool(per_trial_streams)
        self.per_trial_init = bool(per_trial_init)
        self.early_stop = early_stop
        # fused multi-step dispatch: population engines advance up to this
        # many steps per device call (a lax.scan with on-device batch
        # synthesis), re-entering the host only at event steps.  1 = the
        # per-step loop, bit-for-bit.
        self.chunk_steps = max(1, int(chunk_steps))
        # --device-rules: evaluate the rung rule / PBT window quantile INSIDE
        # the fused scan (rule state carried by lax.scan), so chunk boundaries
        # no longer clamp to event-step gaps and the host only harvests
        # retirements from the scan's emitted event log
        self.device_rules = bool(device_rules)
        # --elastic-regrid: at rung boundaries (batch) / once the feed drains
        # (streaming), gather the surviving lanes into a smaller population
        # and re-lay it out over the freed devices (two-level (pop, model)
        # mesh when a lane pool is attached; plain lane-count shrink on the
        # single-device vmapped engine).  Resharding changes layout, never
        # math: scores reproduce the fixed-width run.
        self.elastic_regrid = bool(elastic_regrid)
        # --data-ring: feed the fused scan from a device-resident prefetch
        # ring host-filled ahead of the consumer (repro.data.ring) instead of
        # in-scan synthesis — the path real datasets take into the chunked
        # engine.  The synth-backed host adapter reproduces the in-scan
        # engine bit-for-bit.
        self.data_ring = bool(data_ring)
        self.ring_windows = max(2, int(ring_windows))
        self.host_dataset = None    # HostDataset override (default: synth)
        self.ring_fill_wait_s = 0.0   # device time spent waiting on host fill
        self.ring_fill_busy_s = 0.0   # host time spent producing windows
        self.ring_overlap_frac = 1.0  # fraction of fill hidden behind compute
        self.n_ring_fills = 0
        self.n_ring_invalidations = 0
        # --fused-rmsnorm: run the Pallas rmsnorm kernel (interpret mode off
        # TPU) inside the population train step instead of the reference norm
        self.fused_rmsnorm = bool(fused_rmsnorm)
        # --fused-attention / --fused-ssm: the rest of the Pallas kernel bank
        # (flash attention, chunked selective scan), same static-field keying
        self.fused_attention = bool(fused_attention)
        self.fused_ssm = bool(fused_ssm)
        # --model-parallel W: each lane's tensors split over a W-wide model
        # axis (two-level (pop, model) mesh) — width is layout, never math
        self.model_parallel = max(1, int(model_parallel))
        # static ModelConfig field replacements applied on top of the smoke
        # config (e.g. a head geometry whose dims divide a TP width) — part
        # of the compile-cache key like every other static model field
        self.model_overrides = dict(model_overrides or {})
        # wall-clock per train step between consecutive rung boundaries,
        # [[boundary_step, steps, s_per_step], ...] — the elastic/TP speedup
        # telemetry: later rungs should get *cheaper* per step
        self.per_rung_step_time_s: list = []
        self.model_axis_collectives = None  # per-step model-axis all-reduces
        self.n_regrids = 0          # lane-geometry changes executed
        self.lane_width_history: list = []  # [lanes, devices-per-lane] per regrid
        self.n_dispatches = 0       # device calls issued (steps + lane ops)
        self.n_train_steps = 0      # population steps those calls advanced
        # lane-lifecycle hook (streaming PBT): maps retire->refill directives
        # (keep / clone / init) onto compiled lane ops; wired by the
        # Experiment from the proposer's lifecycle_hook()
        self.lifecycle = lifecycle
        # how long an empty streaming flight lingers for late proposals before
        # returning its lanes (0 for self-contained feeds, e.g. benchmarks)
        self.refill_idle_grace_s = float(refill_idle_grace_s)
        # crash-safe streaming: harvest each live lane's full train state to
        # the snapshot store every N-th event boundary (0 = off); a lease
        # whose stream has a stored snapshot restores from it instead of
        # starting at step 0 (after a supervised restart or a --resume)
        self.snapshot_every = max(0, int(snapshot_every))
        self.snapshots = snapshots      # checkpoint.LaneSnapshotStore
        self.journal = None             # tracking.FlightJournal, wired by Experiment
        self.n_snapshots = 0            # lane snapshots harvested to host
        self.n_lane_restores = 0        # leases resumed from a snapshot
        self.resumed_from_steps: list = []  # lane-local step of each restore
        self._event_seq = 0             # streaming event boundaries, all flights
        # device dispatches from first-flight start to the first retirement
        # harvest — "the ladder": with --device-rules a whole multi-rung
        # cohort collapses to 1 (the headline claim CI gates on); host-rule
        # paths pay the init op plus one dispatch per event gap
        self.ladder_dispatches = None
        self.n_refills = 0          # lanes reused within a streaming flight
        self.n_clones = 0           # donor-clone lane ops executed on device
        self.n_splices = 0          # single-lane splice inits executed
        self.n_donor_waits = 0      # leases parked waiting on a busy donor lane
        self.n_lineage_resets = 0   # keep/clone downgraded to init (state lost)
        self.n_host_ckpt_roundtrips = 0  # weights ever pulled to host (serial PBT only)
        self._flight_epoch = 0
        self._tc = None
        self._data = None
        self._serial_seq = 0  # fallback stream counter for anonymous configs
        import threading

        self._setup_lock = threading.Lock()

    # lazy so the Experiment can be constructed without importing jax; locked
    # because local resource managers call trials from worker threads
    def _setup(self):
        with self._setup_lock:
            if self._tc is None:
                from ..configs import get_smoke_config
                from ..configs.base import ParallelConfig, TrainConfig
                from ..data.pipeline import SyntheticLM

                import dataclasses

                cfg = get_smoke_config(self.arch)
                if self.fused_rmsnorm:
                    # a *static* model field: the compile caches key on it via
                    # static_step_key, so fused and reference programs never mix
                    cfg = dataclasses.replace(cfg, fused_rmsnorm=True)
                if self.fused_attention:
                    cfg = dataclasses.replace(cfg, fused_attention=True)
                if self.fused_ssm:
                    cfg = dataclasses.replace(cfg, fused_ssm=True)
                if self.model_overrides:
                    cfg = dataclasses.replace(cfg, **self.model_overrides)
                self._data = SyntheticLM(cfg.vocab_size, self.seq, self.batch,
                                         seed=self.seed)
                self._tc = TrainConfig(model=cfg, parallel=ParallelConfig(remat="none"),
                                       seed=self.seed)
            return self._tc, self._data

    def _hparams(self, config: dict, n_steps: int):
        from ..optim.hparams import hparams_from_dict

        tc, _ = self._setup()
        return hparams_from_dict(dict(config, total_steps=n_steps), tc)

    def _n_steps(self, config: dict) -> int:
        return int(config.get("n_iterations", 1) * self.steps)

    def _stream_of(self, config: dict, fallback: int) -> int:
        """Per-trial data stream id: explicit ``stream`` key, else the job id
        (stable across serial vs population engines for the same proposal),
        else ``fallback`` (lane position / serial call order)."""
        if not self.per_trial_streams:
            return 0
        return int(config.get("stream", config.get("job_id", fallback)))

    def _serial_stream_of(self, config: dict) -> int:
        """Stream id for a serial call or a streaming lease.  Anonymous
        configs — no ``stream`` and no ``job_id`` — get distinct streams by
        call/lease order instead of all colliding on stream 0 (or on a reused
        lane's index), which silently re-shared data across trials despite
        ``per_trial_streams=True``."""
        if not self.per_trial_streams:
            return 0
        if "stream" in config or "job_id" in config:
            return self._stream_of(config, 0)
        with self._setup_lock:
            sid = self._serial_seq
            self._serial_seq += 1
        return sid

    def _init_key(self, stream: int):
        """Init PRNG key for a trial: the shared ``PRNGKey(seed)`` by default,
        or — with ``per_trial_init`` — the trial's stream id folded in, so the
        serial driver and every population engine derive the *same* per-trial
        weights (masked to uint32: sentinel streams are negative)."""
        import jax

        base = jax.random.PRNGKey(self.seed)
        if not self.per_trial_init:
            return base
        return jax.random.fold_in(base, int(stream) & 0xFFFFFFFF)

    def _make_ring(self, data, k: int, chunk: int, mesh=None):
        """Build the device-resident prefetch ring for a flight
        (``--data-ring``): ``ring_windows`` chunk-windows of per-lane token
        slabs, host-filled from ``host_dataset`` (default: the synth adapter
        — the bit-equality oracle for the in-scan engine).  On a mesh the
        lane axis shards over ``pop`` so each device holds only its own
        lanes' slabs."""
        from ..data.pipeline import SynthHostDataset
        from ..data.ring import PrefetchRing

        sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(
                mesh, PartitionSpec(None, "pop", None, None))
        ds = self.host_dataset if self.host_dataset is not None \
            else SynthHostDataset(data)
        return PrefetchRing(ds, population=k, win_steps=chunk,
                            windows=self.ring_windows, sharding=sharding)

    def _absorb_ring(self, ring) -> None:
        """Stop a flight's ring and roll its telemetry into the trial."""
        ring.stop()
        self.ring_fill_wait_s += ring.fill_wait_s
        self.ring_fill_busy_s += ring.fill_busy_s
        self.n_ring_fills += ring.n_fills
        self.n_ring_invalidations += ring.n_invalidations
        if self.ring_fill_busy_s > 0.0:
            self.ring_overlap_frac = max(0.0, min(
                1.0, 1.0 - self.ring_fill_wait_s / self.ring_fill_busy_s))

    def __call__(self, config: dict) -> float:
        """Serial protocol, sharing the process-wide compiled step."""
        return self.serial_score_at(config, None)

    def serial_score_at(self, config: dict, steps=None) -> float:
        """Serial driver score measured after ``steps`` applied steps (default:
        the config's full budget).  The LR schedule still spans the config's
        own total budget — so ``steps < budget`` reproduces exactly what a
        rung-truncated population lane reports: the ordinary trajectory, cut
        at the truncation step."""
        from ..data.pipeline import HostPrefetcher
        from ..train.train_step import get_compiled_train_step, init_train_state

        tc, data = self._setup()
        n_steps = self._n_steps(config)
        run_steps = n_steps if steps is None else min(int(steps), n_steps)
        stream = self._serial_stream_of(config)
        hp = self._hparams(config, n_steps)
        step_fn = get_compiled_train_step(tc)
        state = init_train_state(self._init_key(stream), tc)
        feed = HostPrefetcher(lambda t: data.make_batch(t, stream=stream))
        loss = float("inf")
        for s in range(run_steps):
            state, metrics = step_fn(state, feed.pop(s), hp)
            if s + 1 < run_steps:
                feed.prefetch(s + 1)
            loss = float(metrics["loss"])
            if not np.isfinite(loss):
                return self.DIVERGED_SCORE
        return -loss

    def run_population(self, configs, mesh=None, scheduler=None,
                       elastic=None) -> list:
        """Batch protocol: K trials in one vmapped (optionally sharded) device
        program.  With ``mesh`` the population axis splits over its devices;
        K is padded so it divides evenly (padding lanes get a 0-step budget).
        With ``scheduler`` the call switches to the streaming lane-refill
        protocol (``configs`` must be empty — jobs arrive via ``lease()`` and
        results leave via ``complete()``).  ``elastic`` is the sharded
        manager's ``ElasticLanePool`` (``--elastic-regrid``): rung survivors
        regrid onto wider lanes through its scale-out/in lease protocol.
        """
        import dataclasses

        import jax
        import jax.numpy as jnp

        from ..data.pipeline import split_stream, split_streams
        from ..optim.hparams import stack_hparams
        from ..train.population import (
            get_compiled_population_scan_step,
            get_compiled_population_step,
            get_compiled_sharded_population_step,
            init_population_state,
            init_population_state_from_keys,
            init_population_state_on_mesh,
            pad_population,
            population_scores,
            shard_population_state,
        )

        if scheduler is not None:
            if configs:
                raise ValueError(
                    "streaming mode: seed proposals through the scheduler, not configs"
                )
            return self._run_streaming(mesh, scheduler, elastic=elastic)

        tc, data = self._setup()
        budgets = np.array([float(self._n_steps(c)) for c in configs])
        streams = [self._stream_of(c, i) for i, c in enumerate(configs)]
        hps = [self._hparams(c, int(n)) for c, n in zip(configs, budgets)]
        k = pad_population(max(self.population, len(hps)), mesh)
        # pad partial batches to the fixed population size with 0-budget
        # trials (they freeze immediately) so K — and thus the compiled
        # program — never varies across batches; padding lanes get distinct
        # negative *sentinel* streams instead of all duplicating stream 0
        while len(hps) < k:
            hps.append(self._hparams({}, 0))
        streams += [-(i + 1) for i in range(len(streams), k)]
        budgets = np.concatenate([budgets, np.zeros(k - len(budgets))])
        php = stack_hparams(hps)
        elastic_on = elastic is not None or self.elastic_regrid
        if elastic_on and self.device_rules:
            raise ValueError(
                "--elastic-regrid and --device-rules are mutually exclusive: "
                "in-scan rule state is K-shaped, a regrid changes K mid-flight")
        if self.per_trial_init:
            keys = jnp.stack([self._init_key(s) for s in streams])
            pstate = init_population_state_from_keys(keys, tc)
        elif mesh is not None and not elastic_on:
            pstate = init_population_state_on_mesh(
                jax.random.PRNGKey(self.seed), tc, k, mesh)
        else:
            pstate = init_population_state(jax.random.PRNGKey(self.seed), tc, k)
        if elastic_on:
            scores = self._run_batch_elastic(
                tc, data, k, pstate, php, budgets, streams, hps,
                self.early_stop, elastic)
            return scores[: len(configs)]
        if mesh is not None:
            pstep = get_compiled_sharded_population_step(
                tc, k, mesh=mesh, per_trial_batch=self.per_trial_streams)
        else:
            pstep = get_compiled_population_step(
                tc, k, per_trial_batch=self.per_trial_streams)
        if mesh is not None:
            # tc routes width>1 meshes through the two-level placement so
            # width-sharded leaves land partitioned, not replicated
            pstate = shard_population_state(pstate, mesh, tc=tc)
        hook = self.early_stop
        if self.device_rules and hook is not None and hook.boundaries:
            scores = self._run_batch_device_rules(
                tc, data, k, mesh, pstate, php, budgets, streams, hook)
            return scores[: len(configs)]
        chunk = self.chunk_steps
        ring = None
        if chunk > 1:
            # fused dispatch: chunk boundaries align with the host-known event
            # steps (rung boundaries, flight end), so the rung rule below sees
            # exactly the state the per-step loop would at the same step
            if self.per_trial_streams:
                s_lo, s_hi = (jnp.asarray(w) for w in split_streams(streams))
            else:
                s_lo, s_hi = (jnp.uint32(w) for w in split_stream(0))

            def scan_of(t):
                return get_compiled_population_scan_step(
                    tc, k, data, t, mesh=mesh,
                    per_trial_batch=self.per_trial_streams)

            if self.data_ring:
                from ..train.population import \
                    get_compiled_population_ring_scan_step

                # every lane's data cursor IS the global step in the batch
                # protocol, so offsets are zero and lanes never re-key
                ring = self._make_ring(data, k, chunk, mesh=mesh)
                ring.set_lanes(streams, [0] * k, at_step=0)

                def ring_scan_of(t):
                    return get_compiled_population_ring_scan_step(
                        tc, k, data, t, ring.capacity, mesh=mesh)

        planner = ChunkPlanner(
            chunk_steps=chunk,
            boundaries=hook.boundaries if hook is not None else ())
        s = 0
        seg_t0, seg_s0 = time.perf_counter(), 0
        try:
            while s < int(budgets.max()):
                max_b = int(budgets.max())
                event = planner.next_cohort_event(s, max_b)
                t = planner.chunk_to(s, event)
                if ring is not None:
                    # chunk horizons stay capped to filled windows: block here
                    # until the host has staged exactly this chunk on device
                    # (counted as ring_fill_wait_s), so the dispatch sequence
                    # is identical to the in-scan engine's
                    ring.wait_filled(s, t)
                if t > 1 and ring is not None:
                    with ring.reserve() as slots:
                        pstate, _ = ring_scan_of(t)(
                            pstate, php, slots,
                            jnp.asarray(s % ring.capacity, jnp.int32))
                elif t > 1:
                    steps0 = (jnp.full((k,), s, jnp.int32)
                              if self.per_trial_streams
                              else jnp.asarray(s, jnp.int32))
                    pstate, _ = scan_of(t)(pstate, php, steps0, s_lo, s_hi)
                else:
                    if self.per_trial_streams:
                        batch = data.make_population_batch(s, streams)
                    else:
                        batch = data.make_batch(s)
                    pstate, _ = pstep(pstate, batch, php)
                self.n_dispatches += 1
                self.n_train_steps += t
                s += t
                if ring is not None:
                    ring.consume_to(s)
                if hook is not None and s in hook.boundaries:
                    new_budgets = hook(
                        s,
                        np.asarray(pstate["last_loss"]),
                        budgets,
                        np.asarray(pstate["diverged"]),
                    )
                    # the last_loss pull above synced the device, so this
                    # segment's wall-clock is honest: per-step time between
                    # consecutive rung boundaries
                    self.per_rung_step_time_s.append(
                        [int(s), int(s - seg_s0),
                         round((time.perf_counter() - seg_t0) / max(1, s - seg_s0), 6)])
                    seg_t0, seg_s0 = time.perf_counter(), s
                    if (new_budgets != budgets).any():
                        # the budget is a *traced* leaf: truncating it freezes
                        # the losing lanes on the next step without a recompile
                        budgets = new_budgets
                        php = dataclasses.replace(
                            php, total_steps=jnp.asarray(budgets, jnp.float32))
        finally:
            if ring is not None:
                self._absorb_ring(ring)
        # telemetry: how long the flight actually ran (in-flight stops shrink it)
        self.last_flight_steps = s
        scores = np.asarray(population_scores(pstate, self.DIVERGED_SCORE))
        if s > seg_s0:  # the tail past the last rung boundary (scores synced)
            self.per_rung_step_time_s.append(
                [int(s), int(s - seg_s0),
                 round((time.perf_counter() - seg_t0) / (s - seg_s0), 6)])
        return [float(x) for x in scores[: len(configs)]]

    def _run_batch_device_rules(self, tc, data, k, mesh, pstate, php, budgets,
                                streams, hook) -> list:
        """Batch-protocol flight with the cohort rung rule carried *in* the
        scan (``--device-rules``).

        The host loop no longer clamps chunks to rung boundaries or restacks
        hyperparameters after a cut: each scan step rebuilds the traced
        ``total_steps`` from the carried budgets and applies the cohort rule
        at boundaries on-device, so a whole ASHA ladder whose max budget fits
        one chunk is ONE dispatch.  Only the surviving budgets come back per
        dispatch (to bound the loop); the hook's truncation counters are
        reconstructed from the budget delta at the end.
        """
        import jax.numpy as jnp

        from ..data.pipeline import split_stream, split_streams
        from ..train.population import (
            cohort_rule_state,
            get_compiled_population_rule_scan_step,
            population_scores,
        )

        spec = hook.device_rule()
        chunk = self.chunk_steps
        # boundaries live in-scan: the planner only caps chunks at flight end
        planner = ChunkPlanner(chunk_steps=chunk)
        init_budgets = budgets.copy()
        if self.per_trial_streams:
            s_lo, s_hi = (jnp.asarray(w) for w in split_streams(streams))
        else:
            s_lo, s_hi = (jnp.uint32(w) for w in split_stream(0))
        s = 0
        while s < int(budgets.max()):
            t = planner.chunk_to(s, int(budgets.max()))
            rules = cohort_rule_state(
                budgets, np.zeros(k), np.full(k, s),
                spec.boundaries, spec.eta)
            steps0 = (jnp.full((k,), s, jnp.int32) if self.per_trial_streams
                      else jnp.asarray(s, jnp.int32))
            fn = get_compiled_population_rule_scan_step(
                tc, k, data, t, "cohort", mesh=mesh,
                per_trial_batch=self.per_trial_streams)
            (pstate, rout), _ = fn(pstate, php, steps0, s_lo, s_hi, rules)
            budgets = np.asarray(rout["budgets"], np.float64)
            self.n_dispatches += 1
            self.n_train_steps += t
            s += t
        spec.absorb_cuts(init_budgets, budgets, np.asarray(pstate["diverged"]))
        self.last_flight_steps = s
        scores = np.asarray(population_scores(pstate, self.DIVERGED_SCORE))
        return [float(x) for x in scores]

    def _run_batch_elastic(self, tc, data, k, pstate, php, budgets, streams,
                           hps, hook, pool) -> list:
        """Batch-protocol flight with elastic lane regrids
        (``--elastic-regrid``).

        At each rung boundary after the cohort rule fires the flight
        *regrids*: the surviving lanes' full train state is gathered into a
        smaller population via the ``regrid`` lane-lifecycle op, retired
        lanes' scores are harvested first, and — when a ``ElasticLanePool``
        is attached — the compact state is ``device_put`` onto a new
        two-level ``(pop, model)`` mesh whose lane rows are *wider*, so later
        rungs train fewer trials faster instead of stepping frozen lanes.
        Without a pool (single-device vectorized manager) the regrid still
        shrinks K to the next power of two, cutting the frozen lanes'
        dead compute.

        Engine choice per segment: width-1 rungs run the vmapped step on
        explicitly placed state (bit-identical to the fixed-width vmapped
        run).  Once a regrid widens the rows past 1, the segment switches to
        the tensor-parallel ``shard_map`` step on the pool's mesh — the same
        program ``--model-parallel`` pins — so each lane row computes its
        width-local parameter shards with explicit psum seams instead of
        GSPMD resharding replicated state every step.  That is what makes a
        regrid *shrink* later-rung wall-clock: the survivors' per-row compute
        drops with the width rather than being replicated W times.

        The invariant: resharding changes layout, never math.  Per-lane
        arithmetic is lane-independent under vmap, so survivor scores are
        bit-equal to the fixed-width run on the same engine family (and
        within 1e-6 across device placements, where cross-row reductions
        reassociate).
        """
        import dataclasses

        import jax
        import jax.numpy as jnp

        from ..data.pipeline import split_stream, split_streams
        from ..distributed.sharding import tp_module_flags
        from ..optim.hparams import stack_hparams
        from ..train.population import (
            get_compiled_population_scan_step,
            get_compiled_population_step,
            get_compiled_sharded_population_step,
            place_two_level,
            population_scores,
            regrid_population_state,
        )

        chunk = self.chunk_steps
        planner = ChunkPlanner(
            chunk_steps=chunk,
            boundaries=hook.boundaries if hook is not None else ())

        def _tp_mesh(m, w):
            # the pool mesh, when its rows genuinely tensor-parallel this
            # model (width > 1 and at least one module's dims divide) —
            # widths that shard nothing keep the vmapped engine
            if m is None or w <= 1:
                return None
            return m if any(tp_module_flags(tc.model, w).values()) else None

        tp_mesh = None
        if pool is not None:
            pstate = place_two_level(pstate, tc, pool.mesh())
            tp_mesh = _tp_mesh(pool.mesh(), pool.width)
        k0 = k
        orig = list(range(k))      # current lane -> original trial index
        final = np.full(k0, self.DIVERGED_SCORE, np.float64)
        budgets = np.asarray(budgets, np.float64)
        streams = list(streams)
        hps = list(hps)

        def splits():
            if self.per_trial_streams:
                return tuple(jnp.asarray(w) for w in split_streams(streams))
            return tuple(jnp.uint32(w) for w in split_stream(0))

        s = 0
        seg_t0, seg_s0 = time.perf_counter(), 0
        while len(budgets) and s < int(budgets.max()):
            t = planner.chunk_to(s, planner.next_cohort_event(
                s, int(budgets.max())))
            if t > 1:
                s_lo, s_hi = splits()
                steps0 = (jnp.full((k,), s, jnp.int32)
                          if self.per_trial_streams
                          else jnp.asarray(s, jnp.int32))
                scan = get_compiled_population_scan_step(
                    tc, k, data, t, mesh=tp_mesh,
                    per_trial_batch=self.per_trial_streams)
                pstate, _ = scan(pstate, php, steps0, s_lo, s_hi)
            else:
                batch = (data.make_population_batch(s, streams)
                         if self.per_trial_streams else data.make_batch(s))
                pstep = (get_compiled_sharded_population_step(
                             tc, k, mesh=tp_mesh,
                             per_trial_batch=self.per_trial_streams)
                         if tp_mesh is not None else
                         get_compiled_population_step(
                             tc, k, per_trial_batch=self.per_trial_streams))
                pstate, _ = pstep(pstate, batch, php)
            self.n_dispatches += 1
            self.n_train_steps += t
            s += t
            if hook is None or s not in hook.boundaries:
                continue
            new_budgets = np.asarray(hook(
                s, np.asarray(pstate["last_loss"]), budgets,
                np.asarray(pstate["diverged"])), np.float64)
            self.per_rung_step_time_s.append(
                [int(s), int(s - seg_s0),
                 round((time.perf_counter() - seg_t0) / max(1, s - seg_s0), 6)])
            seg_t0, seg_s0 = time.perf_counter(), s
            if (new_budgets != budgets).any():
                budgets = new_budgets
                php = dataclasses.replace(
                    php, total_steps=jnp.asarray(budgets, jnp.float32))
            # -- the regrid decision: can the survivors absorb freed lanes? --
            survivors = [i for i in range(k) if budgets[i] > s]
            if not 0 < len(survivors) < k:
                continue
            if pool is not None:
                _, width, k2 = pool.plan(len(survivors))
                shrink = k2 != k or width != pool.width
            else:
                width, k2 = 1, _pow2_ceil(len(survivors))
                shrink = k2 < k
            if not shrink:
                continue
            # harvest retired lanes' final scores BEFORE their state leaves
            # the population (their budgets froze them; the scores are final)
            cur = np.asarray(population_scores(pstate, self.DIVERGED_SCORE))
            live_set = set(survivors)
            for i in range(k):
                if i not in live_set:
                    final[orig[i]] = cur[i]
            mesh2 = None
            if pool is not None:
                _, mesh2 = pool.regrid(len(survivors))
            pstate = regrid_population_state(
                pstate, survivors, tc, mesh=mesh2, pad_to=k2)
            self.n_dispatches += 1
            pad = k2 - len(survivors)
            orig = [orig[i] for i in survivors] + [-1] * pad
            budgets = np.array([budgets[i] for i in survivors] + [0.0] * pad)
            streams = [streams[i] for i in survivors] \
                + [-(k0 + j + 1) for j in range(pad)]
            hps = [hps[i] for i in survivors] \
                + [self._hparams({}, 0) for _ in range(pad)]
            php = dataclasses.replace(
                stack_hparams(hps),
                total_steps=jnp.asarray(budgets, jnp.float32))
            k = k2
            tp_mesh = _tp_mesh(mesh2, width)
            self.n_regrids += 1
            self.lane_width_history.append([int(k2), int(width)])
        self.last_flight_steps = s
        cur = np.asarray(population_scores(pstate, self.DIVERGED_SCORE))
        if s > seg_s0:
            self.per_rung_step_time_s.append(
                [int(s), int(s - seg_s0),
                 round((time.perf_counter() - seg_t0) / (s - seg_s0), 6)])
        for j in range(k):
            if orig[j] >= 0:
                final[orig[j]] = cur[j]
        return [float(x) for x in final]

    def _run_streaming(self, mesh, scheduler, elastic=None) -> list:
        """Continuous lane-refill flight (Algorithm 1's busy-resource invariant
        *inside* one compiled program).

        Lane lifecycle: a lane **leases** a job from the scheduler, runs one
        lane-lifecycle op to take that trial's weights, trains on its own data
        stream, and **retires** when its budget runs out, the rung rule
        truncates it, or it diverges.  Retirement streams the job's result out
        immediately (``scheduler.complete``) and frees the lane for the next
        lease — so losing lanes hand their device time to fresh proposals
        mid-flight instead of idling until the whole batch drains.

        The lifecycle op per lease (all compiled, cached, never a host
        checkpoint round-trip):

        * default — **splice** (``make_lane_splice``): one fresh
          ``init_train_state`` written into exactly the target lane via
          ``dynamic_update_index_in_dim`` (not a K-wide vmap init);
        * ``pbt_lifecycle == "keep"`` — **no device op at all**: the member's
          lane keeps its weights + optimizer state; only the traced hparams /
          budget / data cursor advance to the next round;
        * ``pbt_lifecycle == "clone"`` — **donor clone**
          (``make_lane_clone``): the lane inherits the donor member's weights
          AND optimizer state across the population axis, with the proposer's
          perturbed hparams installed in the traced stack.  A clone whose
          donor lane is still mid-round is *parked* until the donor retires
          (donor lease pinning keeps the donor from starting its next round
          first), so the copy always reads round-boundary weights.

        Schedule/budget bases: a keep/clone lane's device step counter is
        cumulative across rounds, so its traced ``total_steps`` is (steps
        already applied in the inherited state) + (this round's budget), and
        its data cursor continues the member's own stream at
        ``round * round_steps``.

        The scheduler needs three things: ``lease() -> (handle, config) |
        None``, ``complete(handle, score, extra)``, and optionally a
        ``closed`` attribute (True = no more jobs are ever coming, skip the
        idle grace wait).  ``core.resource.vectorized.LaneScheduler`` is the
        Algorithm-1 adapter; benchmarks drive this with a plain queue.
        """
        import time as _time

        import jax
        import jax.numpy as jnp

        from ..data.pipeline import split_streams
        from ..optim.hparams import stack_hparams
        from ..train.population import (
            get_compiled_lane_op,
            get_compiled_population_rule_scan_step,
            get_compiled_population_scan_step,
            get_compiled_population_step,
            get_compiled_sharded_population_step,
            init_population_state_from_keys,
            pad_population,
            pbt_rule_state,
            shard_population_state,
            staggered_rule_state,
        )

        if not self.per_trial_streams:
            raise ValueError(
                "lane refill requires per-trial data streams: a refilled lane "
                "must replay its own stream from its own step 0 (drop "
                "--shared-stream)"
            )
        elastic_on = elastic is not None or self.elastic_regrid
        if elastic_on and self.device_rules:
            raise ValueError(
                "--elastic-regrid and --device-rules are mutually exclusive: "
                "in-scan rule state is K-shaped, a regrid changes K mid-flight")
        if elastic_on and self.lifecycle is not None:
            raise ValueError(
                "--elastic-regrid is incompatible with streaming PBT: "
                "keep/clone directives pin members to lanes a regrid reindexes")
        if elastic_on:
            # elastic flights run the vmapped engine with explicit placement
            # (the pool's two-level mesh); shard_map programs have a fixed K
            mesh = None
        tc, data = self._setup()
        k = pad_population(max(self.population, 1), mesh)

        def _ops(kk):
            """(Re)build the per-K compiled entry points — called once up
            front and again after every elastic regrid changes K."""
            ps = (get_compiled_sharded_population_step(
                      tc, kk, mesh=mesh, per_trial_batch=True)
                  if mesh is not None else
                  get_compiled_population_step(tc, kk, per_trial_batch=True))
            # single lane -> splice (one init, traced lane index); several
            # lanes in one round -> the masked from-keys reset (one dispatch)
            sp = get_compiled_lane_op(tc, kk, "splice", mesh=mesh)
            ini = get_compiled_lane_op(tc, kk, "init", mesh=mesh)
            # crash-safety pair: harvest a live lane to host / splice a
            # harvested snapshot back into a fresh flight's lane
            sn = rs = None
            if self.snapshots is not None:
                sn = get_compiled_lane_op(tc, kk, "snapshot", mesh=mesh)
                rs = get_compiled_lane_op(tc, kk, "restore", mesh=mesh)
            return ps, sp, ini, sn, rs

        pstep, splice_fn, init_fn, snap_fn, restore_fn = _ops(k)
        from ..core import faultinject
        fault_plan = faultinject.get_plan()
        chunk = self.chunk_steps

        def scan_of(t):
            return get_compiled_population_scan_step(tc, k, data, t, mesh=mesh)
        lifecycle = self.lifecycle
        clone_fn = (get_compiled_lane_op(tc, k, "clone", mesh=mesh)
                    if lifecycle is not None else None)
        self._flight_epoch += 1
        epoch = self._flight_epoch
        dispatches0 = self.n_dispatches

        # host-side lane table (lane-local: budgets/steps restart per lease;
        # lineage lanes additionally carry cumulative bases across rounds)
        handles: list = [None] * k
        used = [False] * k
        lineage: list = [None] * k           # member whose weights live here
        lane_round = [0] * k                 # pbt_round of the current lease
        rounds_done: dict = {}               # member -> rounds completed here
        starts = np.zeros(k, np.int64)       # global step of the lane's local 0
        base_data = np.zeros(k, np.int64)    # member data cursor at local 0
        applied0 = np.zeros(k, np.int64)     # device opt.step at lease time
        lane_applied = np.zeros(k, np.int64)  # device opt.step at last retire
        budgets = np.zeros(k, np.float64)    # this round's budget (lane-local)
        resumed_at = np.zeros(k, np.int64)   # lane-local step a restore resumed from
        streams = [-(i + 1) for i in range(k)]     # idle = sentinel stream
        hps = [self._hparams({}, 0) for _ in range(k)]
        lane_keys = [self._init_key(s) for s in streams]
        pstate = init_population_state_from_keys(jnp.stack(lane_keys), tc)
        if mesh is not None:
            pstate = shard_population_state(pstate, mesh, tc=tc)
        elif elastic is not None:
            from ..train.population import place_two_level

            pstate = place_two_level(pstate, tc, elastic.mesh())
        php = stack_hparams(hps)
        hook = self.early_stop
        # --device-rules: lower the rung rule (staggered/async-SHA) or the PBT
        # window quantile into the scan.  The host skips observe(), stops
        # clamping chunks to event-step gaps, and harvests retirements from
        # the scan's emitted budgets/verdicts instead of deciding them.
        device_spec = None
        if self.device_rules and hook is not None and hook.boundaries:
            device_spec = hook.device_rule()
        device_pbt = (self.device_rules and lifecycle is not None
                      and getattr(lifecycle, "device_rule_on", False))
        device_active = device_spec is not None or device_pbt
        batch_complete = (getattr(scheduler, "complete_retirements", None)
                          if device_active else None)
        ring = None
        if self.data_ring and chunk > 1 and not device_active \
                and not elastic_on:
            # host-fed fused scans: the ring re-keys at every lane-table
            # change (the php_dirty hook below) with each live lane's private
            # data-cursor offset, so refilled/restored lanes resume their own
            # stream mid-ring
            from ..train.population import \
                get_compiled_population_ring_scan_step

            ring = self._make_ring(data, k, chunk, mesh=mesh)

            def ring_scan_of(t):
                return get_compiled_population_ring_scan_step(
                    tc, k, data, t, ring.capacity, mesh=mesh)
        # device mode only: while True, pstate is still exactly its from-keys
        # init, so a first mass fill can rebuild it instead of dispatching a
        # masked reset — that free-ness is what lets a whole ladder be ONE call
        virgin = True

        def rule_scan_of(t, mode):
            return get_compiled_population_rule_scan_step(
                tc, k, data, t, mode, mesh=mesh)
        s = 0
        idle_deadline = None
        grace = self.refill_idle_grace_s
        if lifecycle is not None:
            # a lifecycle flight must survive the proposer's callback round
            # trip between rounds: losing the flight loses every member's
            # device state (keep/clone would degrade to re-inits)
            grace = max(grace, 2.0)
        parked: list = []   # leases that cannot run yet (busy donor / no lane)
        donor_waited: set = set()  # handles counted once, not per re-poll
        force_parked = False  # grace expired: degrade stuck directives to init
        # Retirements and rung boundaries happen at *host-known* global steps
        # (starts + budgets / starts + boundary), so the loop only materializes
        # device flags at those event steps instead of syncing every step —
        # between events it dispatches fused multi-step chunks (or, with
        # chunk_steps=1, compiled per-step programs back-to-back).
        # Divergence is the one async event; a capped gap bounds how long a
        # diverged (frozen, masked) lane can occupy its slot before reclaim.
        # Chunking makes that poll chunk-granular: the gap grows with the
        # chunk so big chunks are not split by it — the divergence-reclaim
        # latency is the price of fewer dispatches (shrink --chunk-steps if
        # your search space diverges a lot).
        planner = ChunkPlanner(
            chunk_steps=chunk,
            boundaries=hook.boundaries if hook is not None else ())
        next_event = 0
        s_lo, s_hi = (jnp.asarray(w) for w in split_streams(streams))

        def _next_event() -> int:
            live_now = [i for i in range(k) if handles[i] is not None]
            if device_active:
                # rung cuts and individual budget ends are in-scan events now;
                # the host only stops for the poll or the whole-flight drain
                return planner.device_horizon(s, starts, budgets, live_now)
            return planner.next_stream_event(s, starts, budgets, live_now)

        while True:
            live = [i for i in range(k) if handles[i] is not None]
            php_dirty = False
            if fault_plan is not None and live:
                # chaos hooks: raise@step (flight death -> the supervisor) and
                # nan@lane (set the divergence latch; the ordinary diverged-
                # lane retire path takes over)
                fault_plan.check("flight-step", step=s)
                poison = [i for i in fault_plan.poison_lanes(s) if i < k]
                if poison:
                    pmask = np.zeros(k, bool)
                    pmask[poison] = True
                    pstate = dict(pstate, diverged=jnp.logical_or(
                        pstate["diverged"], jnp.asarray(pmask)))
                    virgin = False
            # 1) at an event step: apply the rung rule, then retire lanes whose
            # budget is exhausted (incl. just-truncated) or that diverged
            if live and s >= next_event:
                self._event_seq += 1
                diverged = np.asarray(pstate["diverged"])
                last = np.asarray(pstate["last_loss"])
                # the device-side optimizer step counter is the exact number
                # of *applied* steps — a diverged lane froze there, however
                # late the capped divergence poll noticed it
                applied = np.asarray(pstate["inner"]["opt"]["step"])
                if (snap_fn is not None and self.snapshot_every
                        and self._event_seq % self.snapshot_every == 0):
                    # harvest BEFORE the retire/lease churn below: the journal
                    # row and the stored state describe this exact boundary.
                    # Diverged lanes are skipped (nothing worth resuming) and
                    # so are lifecycle (PBT) lanes — their keep/clone state is
                    # the proposer's, and a dead flight degrades them to the
                    # counted re-init path instead.
                    for lane in live:
                        local = int(s - starts[lane])
                        if (diverged[lane] or lineage[lane] is not None
                                or local <= 0 or local >= budgets[lane]):
                            continue
                        snap = jax.device_get(
                            snap_fn(pstate, jnp.asarray(lane, jnp.int32)))
                        self.n_dispatches += 1
                        self.n_snapshots += 1
                        self.snapshots.put(streams[lane], snap, {
                            "local": local,
                            "stream": int(streams[lane]),
                            "applied": int(applied[lane]),
                            "applied0": int(applied0[lane]),
                            "budget": float(budgets[lane]),
                            # the lane's data cursor at this boundary: a
                            # restored lease re-derives base_data from it so a
                            # ring-fed (or any host-fed) flight resumes the
                            # stream mid-window exactly
                            "data_cursor": int(base_data[lane] + local),
                        })
                        if self.journal is not None:
                            self.journal.append("snapshot", lane=lane, step=local,
                                                detail={"stream": int(streams[lane])})
                if fault_plan is not None:
                    # kill@event fires AFTER any due harvest: "crash at an
                    # arbitrary event boundary" with the snapshots on disk
                    fault_plan.check("event", event=self._event_seq)
                if hook is not None and device_spec is None:
                    local = np.array(
                        [s - starts[i] if handles[i] is not None else 0
                         for i in range(k)], np.float64)
                    budgets = np.asarray(
                        hook.observe(local, last, budgets, diverged), np.float64)
                retired: list = []  # device mode: one batch per event pass
                for lane in live:
                    local_s = int(s - starts[lane])
                    if diverged[lane] or local_s >= budgets[lane]:
                        bad = bool(diverged[lane]) or not np.isfinite(last[lane])
                        score = self.DIVERGED_SCORE if bad else -float(last[lane])
                        if (hook is not None and diverged[lane]
                                and budgets[lane] > applied[lane] - applied0[lane]):
                            # same telemetry the batch engine keeps: a diverged
                            # lane's remaining budget is dead weight reclaimed
                            hook.n_reclaimed += 1
                        extra = {
                            "steps": int(applied[lane] - applied0[lane]),
                            "total_steps": int(applied[lane]),
                            "diverged": bool(diverged[lane]),
                            "lane": lane,
                            "resumed_from_step": int(resumed_at[lane]),
                        }
                        if batch_complete is not None:
                            retired.append((handles[lane], score, extra))
                        else:
                            scheduler.complete(handles[lane], score, extra=extra)
                        if self.journal is not None:
                            self.journal.append(
                                "retire", lane=lane, step=local_s,
                                detail={"stream": int(streams[lane]),
                                        "score": score})
                        if self.snapshots is not None and lineage[lane] is None:
                            # the trial is done: its snapshots are dead weight
                            self.snapshots.forget(streams[lane])
                        resumed_at[lane] = 0
                        handles[lane] = None
                        budgets[lane] = 0.0
                        lane_applied[lane] = int(applied[lane])
                        if lineage[lane] is not None:
                            rounds_done[lineage[lane]] = lane_round[lane] + 1
                        if lineage[lane] is None:
                            streams[lane] = -(lane + 1)
                            hps[lane] = self._hparams({}, 0)
                            php_dirty = True  # restack: the retired lane freezes
                        # a lineage lane freezes without a restack: its device
                        # step counter equals its traced total_steps (or the
                        # divergence latch holds it) until the next directive
                if retired:
                    # the scan's emitted event log, settled in one call: the
                    # scheduler streams each result exactly as the host-rule
                    # path would, but with one host sync per dispatch
                    batch_complete(retired)
                retired_now = [i for i in range(k) if handles[i] is None
                               and i in live]
                if retired_now and self.ladder_dispatches is None:
                    self.ladder_dispatches = self.n_dispatches - dispatches0
                # the retire pass may have emptied the flight: recompute so the
                # loop idles/returns instead of dispatching a no-op step (or,
                # chunked, a whole no-op chunk) against all-frozen lanes
                live = [i for i in range(k) if handles[i] is not None]
            # 2) lease pending proposals (parked ones first) and dispatch each
            # through its lane-lifecycle op
            pending, parked = parked + self._drain_leases(scheduler), []
            if pending:
                # clones first: a clone must read its donor's round-boundary
                # weights, so it has to execute before a same-round keep
                # re-activates the donor lane (stable sort keeps arrival order
                # within each group)
                pending.sort(
                    key=lambda hc: hc[1].get("pbt_lifecycle") != "clone")
                free = [i for i in range(k)
                        if handles[i] is None and lineage[i] is None]
                clone_jobs: list = []   # (lane, donor_lane, cfg)
                splice_jobs: list = []  # lanes taking a fresh init
                for handle, cfg in pending:
                    directive = cfg.get("pbt_lifecycle")
                    member = cfg.get("pbt_member")
                    lane = donor_lane = None
                    if lifecycle is not None and directive in ("keep", "clone"):
                        lane = lifecycle.lane_of(member, epoch)
                        if force_parked:
                            if lane is not None and handles[lane] is not None:
                                # two stuck rounds of one member forced in the
                                # same pass: the first took the lane, the
                                # second waits for it (never overwrite a live
                                # lease's handle)
                                parked.append((handle, cfg))
                                continue
                            # the flight idled out with these leases stuck
                            # (dead-flight resume, a clone that will never
                            # arrive): degrade to a fresh init, loudly counted
                            self.n_lineage_resets += 1
                            if directive == "clone":
                                lifecycle.clone_done(cfg)
                            directive = "init" if lane is not None else None
                        else:
                            if lane is not None and handles[lane] is not None:
                                # async mode: member's lane is still mid-round
                                parked.append((handle, cfg))
                                continue
                            if int(cfg.get("pbt_round", 0)) \
                                    != rounds_done.get(member, 0):
                                # rounds run in round order: a later round
                                # offered early (raw feeds, resumes) waits for
                                # its predecessor instead of jumping the queue
                                parked.append((handle, cfg))
                                continue
                            if directive == "keep" and lane is not None \
                                    and lifecycle.pinned(member):
                                # donor lease pinning: a pending clone still
                                # needs this lane's weights — don't resume yet
                                if handle not in donor_waited:
                                    donor_waited.add(handle)
                                    self.n_donor_waits += 1
                                parked.append((handle, cfg))
                                continue
                            if directive == "clone" and lane is not None:
                                donor_lane = lifecycle.lane_of(
                                    cfg.get("pbt_donor"), epoch)
                                if donor_lane is not None and \
                                        handles[donor_lane] is not None:
                                    # donor mid-round: wait for its boundary so
                                    # the copy reads round-boundary weights
                                    if handle not in donor_waited:
                                        donor_waited.add(handle)
                                        self.n_donor_waits += 1
                                    parked.append((handle, cfg))
                                    continue
                                if donor_lane is None:
                                    # donor state lost (dead flight / resume):
                                    # degrade to a fresh init, loudly counted
                                    self.n_lineage_resets += 1
                                    lifecycle.clone_done(cfg)
                                    directive = "init"
                            if lane is None:
                                # keep/clone for a member whose state is gone
                                # (crash-resume): re-init it in a free lane
                                self.n_lineage_resets += 1
                                if directive == "clone":
                                    lifecycle.clone_done(cfg)
                                directive = None  # take the init path below
                    if lane is None:
                        if not free:
                            parked.append((handle, cfg))  # every lane is busy
                            continue
                        lane = free.pop(0)
                        directive = "init"
                        if lifecycle is not None and member is not None:
                            lifecycle.bind(member, lane, epoch)
                            lineage[lane] = member
                    # same resolution as the serial driver: explicit stream /
                    # job id, else a distinct lease-order stream — never the
                    # lane index, which repeats across refills of one lane
                    sid = self._serial_stream_of(cfg)
                    round_steps = int(self._n_steps(cfg))
                    handles[lane] = handle
                    starts[lane] = s
                    lane_round[lane] = int(cfg.get("pbt_round", 0))
                    base_data[lane] = lane_round[lane] * round_steps
                    budgets[lane] = float(round_steps)
                    streams[lane] = sid
                    if directive == "keep":
                        base_sched = int(lane_applied[lane])
                    elif directive == "clone":
                        base_sched = int(lane_applied[donor_lane])
                        clone_jobs.append((lane, donor_lane, cfg))
                    else:  # init / splice — or restore from a lane snapshot
                        stored = (self.snapshots.get(sid)
                                  if restore_fn is not None else None)
                        if stored is not None:
                            # this stream died mid-lane in an earlier flight
                            # (supervised restart or --resume): splice its
                            # harvested state back and continue from the
                            # snapshot's lane-local step instead of step 0
                            snap, meta = stored
                            local = int(meta["local"])
                            pstate = restore_fn(
                                pstate, jnp.asarray(lane, jnp.int32),
                                jax.device_put(snap))
                            virgin = False
                            self.n_dispatches += 1
                            self.n_lane_restores += 1
                            starts[lane] = s - local
                            resumed_at[lane] = local
                            self.resumed_from_steps.append(local)
                            if "data_cursor" in meta:
                                # restore the lane's data cursor too: the ring
                                # (and the in-scan cursors) replay the stream
                                # from exactly the snapshot's position
                                base_data[lane] = int(
                                    meta["data_cursor"]) - local
                            base_sched = int(meta.get("applied0", 0))
                            if self.journal is not None:
                                self.journal.append(
                                    "lane_restore", lane=lane, step=local,
                                    detail={"stream": sid})
                            if used[lane]:
                                self.n_refills += 1
                        else:
                            base_sched = 0
                            resumed_at[lane] = 0
                            lane_keys[lane] = self._init_key(sid)
                            splice_jobs.append(lane)
                            if used[lane]:
                                self.n_refills += 1
                    if directive == "clone" and used[lane]:
                        self.n_refills += 1
                    applied0[lane] = base_sched
                    used[lane] = True
                    hps[lane] = self._hparams(cfg, base_sched + round_steps)
                    php_dirty = True
                    if self.journal is not None:
                        self.journal.append(
                            "lease", job_id=cfg.get("job_id"), lane=lane,
                            step=int(s), detail={"stream": sid})
                # device ops: clones first (they read donor lanes, which are
                # never splice targets), then one splice per fresh-init lane
                if clone_jobs:
                    mask = np.zeros(k, bool)
                    donor_idx = np.arange(k)
                    for lane, donor_lane, _ in clone_jobs:
                        mask[lane] = True
                        donor_idx[lane] = donor_lane
                    pstate = clone_fn(pstate, jnp.asarray(mask),
                                      jnp.asarray(donor_idx, jnp.int32))
                    virgin = False
                    self.n_clones += len(clone_jobs)
                    self.n_dispatches += 1
                    for _, _, cfg in clone_jobs:
                        lifecycle.clone_done(cfg)
                if splice_jobs and virgin and device_active:
                    # first fill of a device-rule flight: nothing has trained
                    # yet, so rebuilding the whole population from the lane
                    # keys is bit-identical to the masked reset (idle lanes
                    # are exactly their sentinel-key inits) and costs no
                    # device dispatch — the ladder's single call stays single
                    pstate = init_population_state_from_keys(
                        jnp.stack(lane_keys), tc)
                    if mesh is not None:
                        pstate = shard_population_state(pstate, mesh)
                elif len(splice_jobs) == 1:
                    lane = splice_jobs[0]
                    pstate = splice_fn(
                        pstate, jnp.asarray(lane, jnp.int32), lane_keys[lane])
                    virgin = False
                    self.n_splices += 1
                    self.n_dispatches += 1
                elif splice_jobs:
                    # several lanes this round (initial fill, mass refill):
                    # one masked reset beats a dispatch per lane
                    reset_mask = np.zeros(k, bool)
                    reset_mask[splice_jobs] = True
                    pstate = init_fn(
                        pstate, jnp.asarray(reset_mask), jnp.stack(lane_keys))
                    virgin = False
                    self.n_dispatches += 1
                live = [i for i in range(k) if handles[i] is not None]
                force_parked = False
            # -- elastic regrid: once the feed has drained (scheduler closed,
            # nothing parked) and retirements have emptied at least half the
            # lanes, gather the survivors into a smaller population laid out
            # over the freed devices — later rungs train fewer trials wider
            # instead of stepping frozen lanes.  Ascending lane order is
            # preserved, so the staggered rule's history appends (and thus
            # every later cut) match the fixed-width run exactly.
            live = [i for i in range(k) if handles[i] is not None]
            if (elastic_on and live and not parked
                    and getattr(scheduler, "closed", False)
                    and len(live) <= k // 2):
                if elastic is not None:
                    _, width, k2 = elastic.plan(len(live))
                    shrink = k2 != k or width != elastic.width
                else:
                    width, k2 = 1, _pow2_ceil(len(live))
                    shrink = k2 < k
                if shrink:
                    mesh2 = None
                    if elastic is not None:
                        _, mesh2 = elastic.regrid(len(live))
                    from ..train.population import regrid_population_state

                    pstate = regrid_population_state(
                        pstate, live, tc, mesh=mesh2, pad_to=k2)
                    self.n_dispatches += 1
                    pad = k2 - len(live)

                    def _gather(seq, fill):
                        return [seq[i] for i in live] + \
                            [fill(j) for j in range(pad)]

                    def _garr(arr, dtype):
                        out = np.zeros(k2, dtype)
                        out[: len(live)] = [arr[i] for i in live]
                        return out

                    handles = _gather(handles, lambda j: None)
                    used = _gather(used, lambda j: True)
                    lineage = _gather(lineage, lambda j: None)
                    lane_round = _gather(lane_round, lambda j: 0)
                    hps = _gather(hps, lambda j: self._hparams({}, 0))
                    streams = _gather(
                        streams, lambda j: -(len(live) + j + 1))
                    lane_keys = _gather(
                        lane_keys,
                        lambda j: self._init_key(-(len(live) + j + 1)))
                    starts = _garr(starts, np.int64)
                    base_data = _garr(base_data, np.int64)
                    applied0 = _garr(applied0, np.int64)
                    lane_applied = _garr(lane_applied, np.int64)
                    budgets = _garr(budgets, np.float64)
                    resumed_at = _garr(resumed_at, np.int64)
                    k = k2
                    pstep, splice_fn, init_fn, snap_fn, restore_fn = _ops(k)
                    live = list(range(len(handles) - pad))
                    virgin = False
                    php_dirty = True
                    self.n_regrids += 1
                    self.lane_width_history.append([int(k2), int(width)])
            if php_dirty:
                php = stack_hparams(hps)
                s_lo, s_hi = (jnp.asarray(w) for w in split_streams(streams))
                if ring is not None:
                    # lane table changed: re-key the ring so lane i's slab at
                    # global step s' is its own stream at base_data + s' -
                    # starts (idle lanes fill from their sentinel stream —
                    # masked lanes never apply those batches)
                    offs = [int(base_data[i] - starts[i])
                            if handles[i] is not None else 0 for i in range(k)]
                    ring.set_lanes(streams, offs, at_step=s)
            if not live:
                # 3) flight idle: linger briefly for late proposals (Algorithm 1
                # may be mid-callback), then return the lanes
                if getattr(scheduler, "closed", False) and not parked:
                    break
                now = _time.time()
                if idle_deadline is None:
                    idle_deadline = now + grace
                if now >= idle_deadline:
                    if any(c.get("pbt_lifecycle") in ("keep", "clone")
                           for _, c in parked):
                        # stuck lifecycle leases (their predecessor/donor is
                        # never coming): re-init them instead of stranding
                        force_parked = True
                        idle_deadline = None
                        continue
                    break
                _time.sleep(0.002)
                continue
            idle_deadline = None
            next_event = _next_event()
            if next_event <= s:
                # an event is due NOW (e.g. a freshly leased zero-budget job):
                # loop back into the event pass instead of burning a dispatch
                # on steps nobody needs
                continue
            # 4) advance to the next event: lane i consumes ITS OWN stream at
            # ITS OWN cursor (a refilled lane replays from 0; a keep/clone
            # round continues the member's cursor at round * round_steps).
            # With --chunk-steps > 1 the gap is covered by fused scans whose
            # batches are synthesized on device — one dispatch per chunk
            # instead of one (plus K host-built batches) per step; chunk
            # boundaries land exactly on the event step.
            t = planner.chunk_to(s, next_event)
            if ring is not None:
                # chunk horizons stay capped to filled windows: block until
                # the host has staged exactly this chunk (counted as
                # ring_fill_wait_s) instead of shrinking the chunk — a
                # different chunk split would reorder result arrival under a
                # stateful proposer and break engine score-equivalence
                ring.wait_filled(s, t)
            if device_active:
                # rule-carrying scan (any t >= 1): budgets ride as scan state,
                # rung cuts / window verdicts land in-scan, and the emitted
                # rule state is the event log the host harvests from
                steps0 = np.zeros(k, np.int64)
                local0 = np.zeros(k, np.int64)
                for i in range(k):
                    if handles[i] is not None:
                        local0[i] = s - starts[i]
                        steps0[i] = base_data[i] + local0[i]
                if device_spec is not None:
                    counts_max = max((len(v) for v in
                                      hook._rung_history.values()), default=0)
                    cap = _pow2_ceil(counts_max + k)
                    hist, counts = device_spec.lower_history(cap)
                    rules = staggered_rule_state(
                        budgets, applied0, local0,
                        device_spec.boundaries, device_spec.eta, hist, counts)
                    mode = "staggered"
                else:
                    wentries = lifecycle.window_snapshot()
                    w = lifecycle.window.maxlen
                    wscore = np.zeros(w, np.float32)
                    for j, (_, sc, _) in enumerate(wentries):
                        wscore[j] = sc
                    rules = pbt_rule_state(
                        budgets, applied0, local0,
                        lifecycle.quantile, wscore, len(wentries))
                    mode = "pbt"
                (pstate, rout), _ = rule_scan_of(t, mode)(
                    pstate, php, jnp.asarray(steps0, jnp.int32), s_lo, s_hi,
                    rules)
                virgin = False
                if device_spec is not None:
                    new_budgets = np.asarray(rout["budgets"], np.float64)
                    # every device-side shrink here is a rung cut (the
                    # staggered rule skips diverged lanes; dead-budget reclaim
                    # stays with the host retire pass, counted there)
                    hook.n_truncated += int((new_budgets < budgets).sum())
                    device_spec.absorb_history(rout["hist"], rout["counts"])
                    budgets = new_budgets
                else:
                    vready = np.asarray(rout["vready"])
                    vbottom = np.asarray(rout["vbottom"])
                    vlo = np.asarray(rout["vlo"])
                    vhi = np.asarray(rout["vhi"])
                    for lane in range(k):
                        if vready[lane] and lineage[lane] is not None:
                            lifecycle.note_device_verdict(
                                lineage[lane], lane_round[lane],
                                bool(vbottom[lane]), float(vlo[lane]),
                                float(vhi[lane]))
            elif t > 1 and ring is not None:
                # ring-fed fused scan: slabs for steps [s, s+t) are already on
                # device (wait_filled capped t), so the per-lane cursors ride
                # in the ring contents, not in traced stream words
                with ring.reserve() as slots:
                    pstate, _ = ring_scan_of(t)(
                        pstate, php, slots,
                        jnp.asarray(s % ring.capacity, jnp.int32))
            elif t > 1:
                steps0 = np.zeros(k, np.int64)
                for i in range(k):
                    if handles[i] is not None:
                        steps0[i] = base_data[i] + s - starts[i]
                pstate, _ = scan_of(t)(
                    pstate, php, jnp.asarray(steps0, jnp.int32), s_lo, s_hi)
            else:
                # one vectorized synthesis call for all K lanes (idle lanes
                # consume their sentinel stream at step 0 — never applied)
                cursors = [int(base_data[i] + s - starts[i])
                           if handles[i] is not None else 0 for i in range(k)]
                batch = data.make_population_batch(cursors, streams)
                pstate, _ = pstep(pstate, batch, php)
            self.n_dispatches += 1
            self.n_train_steps += t
            s += t
            if ring is not None:
                ring.consume_to(s)
        if ring is not None:
            self._absorb_ring(ring)
        self.last_flight_steps = s
        return []

    @staticmethod
    def _drain_leases(scheduler) -> list:
        out = []
        while True:
            lease = scheduler.lease()
            if lease is None:
                return out
            out.append(lease)


class _ReplayJob:
    """Minimal duck-typed job for feeding a proposer outside Algorithm 1."""

    def __init__(self, cfg):
        self.config = cfg


def run_pbt_serial(trial: PopulationTrial, proposer) -> dict:
    """Generation-barriered serial PBT baseline (host checkpoint round-trips).

    Drives a *streaming-mode* ``PBTProposer`` with an explicit generation
    barrier: each pass collects one whole generation of member configs, runs
    every member's round serially (one trial at a time on the compile-once
    step), and takes weights according to the round's lifecycle directive
    from HOST checkpoints — ``keep`` reloads the member's own checkpoint,
    ``clone`` reloads the donor's (the pre-refactor ``pbt_ckpt`` protocol the
    streaming engine eliminates).  Every round costs two host round-trips
    (restore + checkpoint), counted in ``trial.n_host_ckpt_roundtrips``.

    Because the decision rule, RNG, per-member data streams, schedule bases
    and init keys are all shared with the streaming engine, a same-seed
    streaming run must reproduce these scores (this is the equivalence
    baseline the benchmarks and tests pin).  Returns ``{(member, round):
    score}``.
    """
    import jax

    from ..train.train_step import get_compiled_train_step, init_train_state

    tc, data = trial._setup()
    step_fn = get_compiled_train_step(tc)
    ckpts: dict = {}
    applied: dict = {}
    scores: dict = {}
    hook = proposer.lifecycle_hook()
    while not proposer.finished():
        gen = proposer.get_params(proposer.population)
        if not gen:
            break
        # exploit copies happen AT the barrier: a clone must read its donor's
        # end-of-previous-generation checkpoint, not a checkpoint the donor
        # already advanced while this generation ran member-by-member (the
        # streaming engine's donor pin enforces exactly this boundary)
        gen_ckpts, gen_applied = dict(ckpts), dict(applied)
        results = []
        for cfg in gen:
            m, r = int(cfg["pbt_member"]), int(cfg["pbt_round"])
            lc = cfg.get("pbt_lifecycle", "init")
            n_steps = trial._n_steps(cfg)
            stream = trial._stream_of(cfg, m)
            if lc == "keep":
                state = jax.device_put(ckpts[m])      # host -> device restore
                trial.n_host_ckpt_roundtrips += 1
                base_sched = applied[m]
            elif lc == "clone":
                donor = int(cfg["pbt_donor"])
                state = jax.device_put(gen_ckpts[donor])  # boundary snapshot
                trial.n_host_ckpt_roundtrips += 1
                base_sched = gen_applied[donor]
                if hook is not None:
                    hook.clone_done(cfg)  # pins are an engine concept
            else:
                state = init_train_state(trial._init_key(stream), tc)
                base_sched = 0
            hp = trial._hparams(cfg, base_sched + n_steps)
            base_data = r * n_steps
            from ..data.pipeline import HostPrefetcher

            feed = HostPrefetcher(
                lambda t: data.make_batch(base_data + t, stream=stream))
            loss, n_applied = float("inf"), 0
            for t in range(n_steps):
                state, metrics = step_fn(state, feed.pop(t), hp)
                if t + 1 < n_steps:
                    feed.prefetch(t + 1)
                loss = float(metrics["loss"])
                if not np.isfinite(loss):
                    break
                n_applied += 1
            score = -loss if n_applied == n_steps else trial.DIVERGED_SCORE
            ckpts[m] = jax.device_get(state)          # device -> host ckpt
            trial.n_host_ckpt_roundtrips += 1
            applied[m] = base_sched + n_applied
            scores[(m, r)] = score
            results.append((cfg, score))
        # the generation barrier: results feed back only when the whole
        # generation has run, in member order — the decision/RNG sequence the
        # synchronized streaming engine reproduces
        for cfg, score in results:
            proposer.update(score, _ReplayJob(cfg))
    return scores


SPACE = [
    {"name": "learning_rate", "type": "float", "range": [1e-4, 3e-2], "scale": "log"},
    {"name": "warmup_frac", "type": "float", "range": [0.02, 0.5]},
    {"name": "weight_decay", "type": "float", "range": [0.0, 0.3]},
    {"name": "b2", "type": "float", "range": [0.9, 0.999]},
    {"name": "grad_clip", "type": "choice", "range": [0.5, 1.0, 2.0]},
]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="starcoder2-3b")
    p.add_argument("--proposer", default="random",
                   help="random | grid | gp | tpe | hyperband | bohb | asha | pbt")
    p.add_argument("--n-samples", type=int, default=8)
    p.add_argument("--n-parallel", type=int, default=2)
    p.add_argument("--steps", type=int, default=30, help="train steps per unit budget")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--db", default="", help="sqlite path ('' = in-memory)")
    p.add_argument("--deadline", type=float, default=0.0, help="per-job seconds (straggler kill)")
    p.add_argument("--vectorize", type=int, default=0, metavar="K",
                   help="train K trials as one vmapped program (0 = serial compile-once)")
    p.add_argument("--shard-population", action="store_true",
                   help="with --vectorize: split the K-trial population axis over "
                        "all local devices (shard_map; K is padded to a multiple "
                        "of the device count)")
    p.add_argument("--shared-stream", action="store_true",
                   help="legacy data mode: every trial consumes the same seeded "
                        "batch stream (default: independent per-trial streams)")
    p.add_argument("--inflight-stop", action="store_true",
                   help="with --vectorize and asha/hyperband/bohb: apply the "
                        "rung rule mid-flight, truncating losing lanes' budgets "
                        "so they free up before the batch ends")
    p.add_argument("--lane-refill", action="store_true",
                   help="with --vectorize: continuous streaming flights — a "
                        "retired lane (budget done / rung-truncated / diverged) "
                        "is reset in place inside the compiled program and "
                        "immediately takes the next proposal; results stream "
                        "out per lane instead of at flight end")
    p.add_argument("--pbt-streaming", action="store_true",
                   help="with --proposer pbt and --vectorize K: run PBT on the "
                        "streaming lane engine (implies --lane-refill) — a "
                        "losing member's lane inherits a donor lane's weights "
                        "and optimizer state via a compiled clone op instead "
                        "of a pbt_ckpt host round-trip; no generation bubble")
    p.add_argument("--pbt-async", action="store_true",
                   help="with --pbt-streaming: drop the round gate so members "
                        "run fully asynchronously — exploit/explore decisions "
                        "come from the sliding member-score window alone "
                        "(default: rounds are gated, matching the "
                        "generation-barriered driver decision-for-decision)")
    p.add_argument("--pbt-perturb", type=float, default=1.2,
                   help="PBT explore factor: floats scale by this (or its "
                        "inverse) through the unit cube")
    p.add_argument("--pbt-quantile", type=float, default=0.25,
                   help="PBT exploit quantile: members in the bottom fraction "
                        "clone a top-fraction donor")
    p.add_argument("--pbt-window", type=int, default=0,
                   help="sliding member-score window for streaming PBT "
                        "decisions (0 = population size)")
    p.add_argument("--pbt-rounds", type=int, default=0,
                   help="training rounds per PBT member (0 = n-samples / "
                        "population)")
    p.add_argument("--chunk-steps", type=int, default=1, metavar="T",
                   help="with --vectorize: fuse up to T population steps into "
                        "one device dispatch (lax.scan with on-device batch "
                        "synthesis); chunk boundaries align with rung/"
                        "retirement/PBT-round event steps, and T=1 reproduces "
                        "the per-step loop bit-for-bit.  Larger T = fewer "
                        "host dispatches but coarser divergence polling")
    p.add_argument("--device-rules", action="store_true",
                   help="with --vectorize: evaluate the scheduling rules "
                        "INSIDE the fused scan — rung cuts (--inflight-stop) "
                        "and the PBT window quantile (--pbt-async) ride as "
                        "lax.scan carry state, so chunk boundaries stop "
                        "clamping to event-step gaps and a whole ASHA ladder "
                        "can run as ONE device dispatch; the host only "
                        "harvests retirements from the scan's emitted event "
                        "log")
    p.add_argument("--elastic-regrid", action="store_true",
                   help="with --vectorize and a rung rule (--inflight-stop): "
                        "at rung boundaries, gather the surviving lanes into "
                        "a smaller population laid out over the freed devices "
                        "— a two-level (pop, model) mesh with wider lane rows "
                        "under --shard-population, a lane-count shrink on the "
                        "single-device engine — so later rungs train fewer "
                        "trials faster instead of stepping frozen lanes; "
                        "streaming flights (--lane-refill) shrink the same "
                        "way once the proposal feed drains.  Resharding "
                        "changes layout, never math: per-trial scores "
                        "reproduce the fixed-width run")
    p.add_argument("--data-ring", action="store_true",
                   help="with --vectorize and --chunk-steps T > 1: feed the "
                        "fused scans from a device-resident prefetch ring "
                        "(repro.data.ring) host-filled ahead of the consumer "
                        "instead of in-scan batch synthesis — the path real "
                        "host datasets take into the chunked engine.  The "
                        "default synth-backed fill reproduces the in-scan "
                        "engine's scores bit-for-bit; telemetry lands in the "
                        "CLI JSON (ring_fill_wait_s, overlap_frac)")
    p.add_argument("--ring-windows", type=int, default=2, metavar="W",
                   help="with --data-ring: prefetch depth in chunk-windows "
                        "(>= 2; 2 = classic double buffering — one window "
                        "training, one filling)")
    p.add_argument("--fused-rmsnorm", action="store_true",
                   help="run the Pallas rmsnorm kernel (interpret mode off "
                        "TPU) inside the train step instead of the reference "
                        "norm — the kernel-revival path for the population "
                        "engines")
    p.add_argument("--fused-attention", action="store_true",
                   help="run the Pallas flash-attention kernel (interpret "
                        "mode off TPU) inside the train step instead of the "
                        "reference attention; decode/cached paths keep the "
                        "reference op")
    p.add_argument("--fused-ssm", action="store_true",
                   help="run the Pallas chunked selective-scan kernel "
                        "(interpret mode off TPU) inside the train step for "
                        "SSM/hybrid archs; the backward pass replays the "
                        "reference scan")
    p.add_argument("--model-parallel", type=int, default=1, metavar="W",
                   help="with --shard-population: fold the device grid into "
                        "a two-level (pop, model) mesh of W-device lane rows "
                        "— each lane's attention heads and MLP/SSM channels "
                        "split over its row (shard_map with explicit psum "
                        "seams), so the model axis carries compute instead "
                        "of replication and per-lane optimizer state shrinks "
                        "~1/W per device.  Width is layout, never math: "
                        "scores match the width-1 run on the same trials")
    p.add_argument("--per-trial-init", action="store_true",
                   help="fold each trial's stream/job id into its init PRNG "
                        "key so trials start from distinct weights (serial and "
                        "population engines fold identically; default: shared "
                        "init from --seed)")
    p.add_argument("--legacy-recompile", action="store_true",
                   help="pre-refactor baseline: bake hparams into the closure, recompile per trial")
    p.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                   help="with --lane-refill: harvest every live lane's train "
                        "state to host at every N-th streaming event boundary "
                        "(persisted next to --db), so a crashed run resumes "
                        "each lane from its last snapshot instead of step 0 "
                        "(0 = off)")
    p.add_argument("--snapshot-dir", default="",
                   help="lane-snapshot directory (default: <db>.lanes)")
    p.add_argument("--resume", nargs="?", type=int, const=-1, default=None,
                   metavar="EXP_ID",
                   help="resume a crashed experiment from --db (no id = the "
                        "latest): replays finished jobs into the proposer, "
                        "re-queues the ones mid-flight at the crash, and "
                        "restores snapshotted lanes from --snapshot-dir")
    p.add_argument("--max-flight-restarts", type=int, default=2,
                   help="supervised streaming-flight restarts (with backoff) "
                        "before the survivors fail for good")
    p.add_argument("--fault-spec", default="",
                   help="deterministic fault injection, e.g. 'raise@step=20' "
                        "or 'kill@event=3' (see repro.core.faultinject; also "
                        "armable via REPRO_FAULT_SPEC)")
    args = p.parse_args(argv)

    from ..core import faultinject
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    from ..core.experiment import Experiment
    from ..core.tracking.database import TrackingDB

    if args.fault_spec:
        faultinject.arm(args.fault_spec)

    resume_db = None
    resume_exp_id = None
    if args.resume is not None:
        if not args.db:
            p.error("--resume needs --db (the tracking DB to resume from)")
        resume_db = TrackingDB(args.db)
        resume_exp_id = (resume_db.latest_experiment_id()
                         if args.resume == -1 else args.resume)
        if resume_exp_id is None:
            p.error(f"--resume: no experiment found in {args.db!r}")
        row = resume_db.get_experiment(resume_exp_id)
        if row is None:
            p.error(f"--resume: experiment {resume_exp_id} not in {args.db!r}")
        # the stored CLI geometry wins: the trial must be rebuilt exactly as
        # the crashed run built it (arch / steps / engine / chunking / seed),
        # or the resumed lanes would not be score-equivalent
        for key, val in (row["exp_config"].get("cli") or {}).items():
            setattr(args, key, val)

    exp_cfg = {
        "proposer": args.proposer,
        "parameter_config": SPACE,
        "n_samples": args.n_samples,
        "n_parallel": args.n_parallel,
        "target": "max",
        "random_seed": args.seed,
        "resource": "local",
    }
    if args.db:
        exp_cfg["db_path"] = args.db
    if args.deadline:
        exp_cfg["job_deadline_s"] = args.deadline
    exp_cfg["max_flight_restarts"] = args.max_flight_restarts
    if args.snapshot_every:
        exp_cfg["snapshot_every"] = args.snapshot_every

    if args.pbt_streaming:
        if args.proposer != "pbt":
            p.error(f"--pbt-streaming needs --proposer pbt, got {args.proposer!r}")
        if args.vectorize <= 0:
            p.error("--pbt-streaming requires --vectorize K (members live in "
                    "population lanes)")
        args.lane_refill = True  # streaming PBT rides the lane-refill engine
        exp_cfg.update(
            streaming=True,
            sync_rounds=not args.pbt_async,
            population=args.vectorize,
            perturb=args.pbt_perturb,
            quantile=args.pbt_quantile,
            window=args.pbt_window,
        )
        if args.pbt_rounds:
            exp_cfg["n_generations"] = args.pbt_rounds
    elif args.pbt_async:
        p.error("--pbt-async only applies with --pbt-streaming")
    if args.vectorize <= 0 and (args.shard_population or args.inflight_stop
                                or args.lane_refill):
        p.error("--shard-population/--inflight-stop/--lane-refill require "
                "--vectorize K (they act on the population engines)")
    if args.lane_refill and args.shared_stream:
        p.error("--lane-refill needs per-trial data streams (a refilled lane "
                "replays its own stream from step 0); drop --shared-stream")
    if args.chunk_steps > 1 and args.vectorize <= 0:
        p.error("--chunk-steps acts on the population engines; it requires "
                "--vectorize K")
    if args.snapshot_every and not args.lane_refill:
        p.error("--snapshot-every snapshots streaming lanes; it requires "
                "--lane-refill")
    if args.device_rules:
        if args.vectorize <= 0:
            p.error("--device-rules acts on the population engines; it "
                    "requires --vectorize K")
        if not (args.inflight_stop or (args.pbt_streaming and args.pbt_async)):
            p.error("--device-rules needs an in-scan rule: --inflight-stop "
                    "(rung cuts) or --pbt-streaming with --pbt-async "
                    "(window-quantile verdicts)")
    if args.legacy_recompile and (args.fused_rmsnorm or args.fused_attention
                                  or args.fused_ssm):
        p.error("--fused-rmsnorm/--fused-attention/--fused-ssm act on the "
                "compile-once train step; the --legacy-recompile baseline "
                "predates the kernel bank and would silently ignore them")
    if args.fused_attention or args.fused_ssm:
        # fail loudly instead of silently training the reference op: the
        # fused flags are per-module, and the module must exist in the arch
        from ..configs import get_smoke_config
        _cfg = get_smoke_config(args.arch)
        if args.fused_attention and not _cfg.has_attention:
            p.error(f"--fused-attention: arch {args.arch!r} has no attention "
                    "mixer (it would silently run unfused)")
        if args.fused_ssm and not _cfg.has_mamba:
            p.error(f"--fused-ssm: arch {args.arch!r} has no SSM mixer "
                    "(it would silently run unfused)")
    if args.model_parallel < 1:
        p.error("--model-parallel must be >= 1")
    if args.model_parallel > 1:
        if not args.shard_population:
            p.error("--model-parallel W splits each lane's tensors over a "
                    "W-device row of the population mesh; it requires "
                    "--vectorize K with --shard-population")
        if args.elastic_regrid:
            p.error("--model-parallel is incompatible with --elastic-regrid: "
                    "elastic flights lease their own lane widths through the "
                    "ElasticLanePool (the regrid IS the width change)")
    if args.elastic_regrid:
        if args.vectorize <= 0:
            p.error("--elastic-regrid acts on the population engines; it "
                    "requires --vectorize K")
        if args.device_rules:
            p.error("--elastic-regrid is incompatible with --device-rules: "
                    "in-scan rule state is K-shaped, a regrid changes K "
                    "mid-flight")
        if args.pbt_streaming:
            p.error("--elastic-regrid is incompatible with --pbt-streaming: "
                    "keep/clone directives pin members to lanes a regrid "
                    "reindexes")
    if args.data_ring:
        if args.vectorize <= 0 or args.chunk_steps <= 1:
            p.error("--data-ring feeds the fused scans; it requires "
                    "--vectorize K and --chunk-steps T > 1")
        if args.shared_stream:
            p.error("--data-ring fills per-lane slabs; drop --shared-stream")
        if args.device_rules:
            p.error("--data-ring is incompatible with --device-rules: the "
                    "rule-carrying scan synthesizes its own batches (in-scan "
                    "cursors ride the rule state)")
        if args.elastic_regrid:
            p.error("--data-ring is incompatible with --elastic-regrid: the "
                    "ring's lane axis is K-shaped, a regrid changes K "
                    "mid-flight")
        if args.ring_windows < 2:
            p.error("--ring-windows must be >= 2 (one window training, one "
                    "filling)")
    per_trial_streams = not args.shared_stream
    # lane-snapshot store: armed when snapshots are being taken OR when a
    # resume may need to restore lanes a previous run persisted
    snap_store = None
    if args.lane_refill and (args.snapshot_every > 0 or args.resume is not None):
        from ..checkpoint import LaneSnapshotStore

        snap_root = args.snapshot_dir or (args.db + ".lanes" if args.db else None)
        snap_store = LaneSnapshotStore(root=snap_root)
    if args.vectorize > 0:
        exp_cfg["resource"] = "sharded" if args.shard_population else "vectorized"
        exp_cfg["n_parallel"] = args.vectorize
        if args.lane_refill:
            exp_cfg["lane_refill"] = True
        if args.elastic_regrid and args.shard_population:
            exp_cfg["elastic_regrid"] = True
        if args.model_parallel > 1:
            exp_cfg["model_parallel"] = args.model_parallel
        trial = PopulationTrial(args.arch, args.steps, args.batch, args.seq,
                                args.seed, population=args.vectorize,
                                per_trial_streams=per_trial_streams,
                                per_trial_init=args.per_trial_init,
                                chunk_steps=args.chunk_steps,
                                snapshot_every=args.snapshot_every,
                                snapshots=snap_store,
                                device_rules=args.device_rules,
                                elastic_regrid=args.elastic_regrid,
                                data_ring=args.data_ring,
                                ring_windows=args.ring_windows,
                                fused_rmsnorm=args.fused_rmsnorm,
                                fused_attention=args.fused_attention,
                                fused_ssm=args.fused_ssm,
                                model_parallel=args.model_parallel)
    elif args.legacy_recompile:
        trial = make_trial(args.arch, args.steps, args.batch, args.seq, args.seed)
    else:
        trial = PopulationTrial(args.arch, args.steps, args.batch, args.seq,
                                args.seed, per_trial_streams=per_trial_streams,
                                per_trial_init=args.per_trial_init,
                                fused_rmsnorm=args.fused_rmsnorm,
                                fused_attention=args.fused_attention,
                                fused_ssm=args.fused_ssm)
    # the stored CLI geometry is what --resume rebuilds the trial from
    exp_cfg["cli"] = {k: getattr(args, k) for k in (
        "arch", "steps", "batch", "seq", "seed", "vectorize",
        "shard_population", "chunk_steps", "per_trial_init", "shared_stream",
        "lane_refill", "inflight_stop", "snapshot_every", "snapshot_dir",
        "legacy_recompile", "pbt_streaming", "pbt_async", "device_rules",
        "elastic_regrid", "data_ring", "ring_windows", "fused_rmsnorm",
        "fused_attention", "fused_ssm", "model_parallel",
        "max_flight_restarts")}
    t0 = time.time()
    if resume_db is not None:
        exp = Experiment.resume(resume_db, trial, exp_id=resume_exp_id)
    else:
        exp = Experiment(exp_cfg, trial)
    # incremental result telemetry: with streaming flights, results land while
    # the batch is still running — record when each settles
    result_times: list = []
    exp.add_result_callback(lambda job: result_times.append(time.time()))
    if args.inflight_stop:
        hook_factory = getattr(exp.proposer, "inflight_hook", None)
        if hook_factory is None:
            p.error(f"--inflight-stop needs a rung proposer (asha/hyperband/bohb), "
                    f"got {args.proposer!r}")
        trial.early_stop = hook_factory(steps_per_unit=args.steps)
    if args.device_rules and args.pbt_streaming:
        # switch decide() to consume scan-emitted window-quantile verdicts
        exp.proposer.lifecycle_hook().enable_device_rule()
    best = exp.run()
    dt = time.time() - t0
    engine = ("legacy-recompile" if args.legacy_recompile else
              "serial" if args.vectorize == 0 else
              "sharded" if args.shard_population else "vmapped")
    out = {
        "proposer": args.proposer,
        "arch": args.arch,
        "engine": engine + (f"+tp{args.model_parallel}"
                            if args.model_parallel > 1 else "")
                         + ("+refill" if args.lane_refill else "")
                         + ("+chunked" if args.chunk_steps > 1 else "")
                         + ("+ring" if args.data_ring else "")
                         + ("+devrules" if args.device_rules else "")
                         + ("+elastic" if args.elastic_regrid else ""),
        "vectorize": args.vectorize,
    }
    if args.device_rules:
        out["device_rules"] = True
    if args.elastic_regrid:
        out["regrids"] = trial.n_regrids
        out["lane_width_history"] = trial.lane_width_history
    if args.vectorize > 0:
        # always emitted for the population engines: a zero-budget /
        # all-quarantined flight reports its dispatch count with a null
        # ratio instead of dividing by zero (or silently dropping the block)
        trained = int(getattr(trial, "n_train_steps", 0))
        out["chunk_steps"] = args.chunk_steps
        out["device_dispatches"] = getattr(trial, "n_dispatches", 0)
        out["trained_steps"] = trained
        out["dispatches_per_step"] = (
            round(trial.n_dispatches / trained, 3) if trained else None)
    if args.data_ring:
        out["ring_windows"] = args.ring_windows
        out["ring_fills"] = trial.n_ring_fills
        out["ring_invalidations"] = trial.n_ring_invalidations
        out["ring_fill_wait_s"] = round(trial.ring_fill_wait_s, 4)
        out["ring_fill_busy_s"] = round(trial.ring_fill_busy_s, 4)
        out["overlap_frac"] = round(trial.ring_overlap_frac, 4)
    if args.fused_rmsnorm:
        out["fused_rmsnorm"] = True
    if args.fused_attention:
        out["fused_attention"] = True
    if args.fused_ssm:
        out["fused_ssm"] = True
    if args.vectorize > 0 and args.shard_population and not args.elastic_regrid:
        # static telemetry off the lowered per-step program: how many
        # all-reduces the model axis contributes per train step (0 at width 1
        # — the whole point of the width-is-layout invariant)
        from ..train.population import (count_model_axis_collectives,
                                        pad_population)
        tc_, data_ = trial._setup()
        mesh_ = getattr(exp.rm, "mesh", None)
        if mesh_ is not None:
            out["model_parallel"] = args.model_parallel
            trial.model_axis_collectives = count_model_axis_collectives(
                tc_, pad_population(max(args.vectorize, 1), mesh_), mesh_,
                data_, per_trial_batch=per_trial_streams)
            out["model_axis_collectives"] = trial.model_axis_collectives
    if getattr(trial, "per_rung_step_time_s", None):
        out["per_rung_step_time_s"] = trial.per_rung_step_time_s
    if getattr(trial, "early_stop", None) is not None:
        out["inflight_truncated_lanes"] = trial.early_stop.n_truncated
        out["inflight_reclaimed_diverged_lanes"] = trial.early_stop.n_reclaimed
    if args.lane_refill:
        if getattr(trial, "ladder_dispatches", None) is not None:
            # the first cohort's cost: 1 under --device-rules (the whole
            # multi-rung ladder in one fused dispatch), init + one dispatch
            # per event gap otherwise
            out["ladder_device_dispatches"] = trial.ladder_dispatches
        out["lane_refills"] = trial.n_refills
        out["streamed_results"] = exp.rm.n_streamed
        out["refill_flights"] = exp.rm.n_refill_flights
        out["flight_deaths"] = getattr(exp.rm, "n_flight_deaths", 0)
        out["flight_restarts"] = getattr(exp.rm, "n_flight_restarts", 0)
        out["quarantined"] = getattr(exp.rm, "n_quarantined", 0)
    if args.snapshot_every or args.resume is not None:
        out["snapshots"] = getattr(trial, "n_snapshots", 0)
        out["resumed"] = args.resume is not None
        out["resumed_lanes"] = getattr(trial, "n_lane_restores", 0)
        out["resumed_from_steps"] = list(
            getattr(trial, "resumed_from_steps", []))
    if args.pbt_streaming:
        hook = exp.proposer.lifecycle_hook()
        out["pbt_clones"] = trial.n_clones
        out["pbt_splices"] = trial.n_splices
        out["pbt_keeps"] = hook.n_keeps
        out["pbt_donor_waits"] = trial.n_donor_waits + hook.n_donor_waits
        out["pbt_lineage_resets"] = trial.n_lineage_resets
        # the streaming engine's whole point: weights never visit the host
        out["pbt_host_ckpt_roundtrips"] = trial.n_host_ckpt_roundtrips
        if args.device_rules:
            out["pbt_device_verdicts"] = hook.n_device_verdicts
    if result_times:
        out["first_result_s"] = round(result_times[0] - t0, 2)
        out["last_result_s"] = round(result_times[-1] - t0, 2)
    print(json.dumps(dict(out, **{
        "best_score": best["score"],
        "best_config": {k: v for k, v in best["config"].items()
                        if not k.startswith(("hb_", "asha_", "pbt_"))
                        and k not in ("job_id", "stream")},
        "n_jobs": best.get("n_jobs"),
        "seconds": round(dt, 1),
    }), default=float, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
