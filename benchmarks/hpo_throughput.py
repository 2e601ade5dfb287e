"""HPO trial-engine throughput: serial-recompile vs compile-once vs vmapped
vs mesh-sharded.

The pre-refactor Experiment loop baked each proposal's hyperparameters into
the ``TrainConfig`` closure, so every trial paid a full XLA compile and the
device ran one small model at a time.  This benchmark quantifies the fixes on
the CPU smoke config:

* **serial_recompile** — the legacy path: fresh ``jax.jit(make_train_step)``
  per trial (compiles grow O(n_trials));
* **compile_once**     — hyperparameters as a traced ``HParams`` argument via
  ``get_compiled_train_step``: one compile serves every trial;
* **vmapped**          — ``repro.train.population``: K trials advance in one
  jitted ``vmap`` program (one compile per (arch, K), amortized dispatch);
* **sharded**          — the K-trial population axis split over an
  8-virtual-device CPU mesh with ``shard_map`` (K/N trials per device, still
  one compiled program).  Runs in a subprocess because the device count must
  be forced before jax initializes; the same subprocess re-times the vmapped
  engine so the sharded-vs-vmapped ratio is apples-to-apples;
* **inflight_stop**    — an ASHA-ladder workload (mixed per-trial budgets) in
  batch-synchronous flights on the mesh, with the rung rule truncating losing
  lanes mid-flight (``--inflight-stop``): freed lanes still idle until each
  flight drains;
* **refill**           — the same ladder workload as ONE continuous streaming
  flight (``--lane-refill``): a retired lane is reset in place inside the
  compiled program and immediately leases the next trial, so the inter-flight
  bubble disappears.  Wall-clock must be <= the inflight_stop row, and each
  trial's score must match the serial driver replayed at the trial's
  *effective* budget (truncations included);
* **chunked**          — **fused multi-step dispatch** (``--chunk-steps``):
  up to CHUNK_STEPS population steps run as ONE ``lax.scan`` program whose
  batches are synthesized *on device* (``repro.data.pipeline.synth_batch`` is
  bit-identical under NumPy and XLA), so the host re-enters only at event
  steps.  Measured per-step-vs-chunked across all four engines — ``vmapped``
  and ``sharded`` batch flights, the ``refill`` streaming ladder, and
  ``pbt_stream`` — at the PBT row's dispatch-bound geometry and a longer
  ladder budget unit (``CHUNK_UNIT``: chunk sizes are bounded by the gap
  between scheduler events, so trials must train long enough between
  retirements for chunks to form).  Gate (on the refill ladder, the hot-path
  engine):
  wall-clock must beat the per-step loop by ``CHUNKED_FLOOR``, scores must
  match within ``CHUNKED_SCORE_TOL`` (the engines are bit-equal by
  construction), and the host-dispatch ratio (device calls per trained step)
  must drop below 1 — the T-fold dispatch collapse this engine exists for;
* **data_ring**        — **device-resident prefetch ring** (``--data-ring``):
  host-supplied data on the fused-scan engine.  The baseline is the per-step
  host-feed loop (chunk 1: the host builds every batch and dispatches one
  step at a time — the only way host data could ride the engines before the
  ring); the ring flight runs the same trials as ``RING_CHUNK``-step fused
  scans indexing a ring of pre-staged per-lane token slabs, the host filler
  running ahead *behind* device compute.  The workload is a uniform
  one-trial-per-lane streaming flight on the sharded engine at
  ``RING_BATCH x RING_SEQ`` (more dispatch-bound than the PBT geometry): no
  lane splices mid-flight, so the lane table never changes and the row
  isolates the feed path itself.  Gate: best-of-``RING_REPS`` wall-clock
  must beat the per-step host-feed loop by ``DATA_RING_FLOOR``,
  ``overlap_frac`` (the fraction of host fill time hidden behind device
  compute) must reach ``RING_OVERLAP_FLOOR``, the ring actually filled,
  dispatches per trained step must drop below 1, and scores must match the
  per-step loop within ``CHUNKED_SCORE_TOL`` (the synth adapter is the
  in-scan synthesis bit-for-bit, so host-fed chunks change nothing about
  the math);
* **device_rules**     — **device-side decision rules** (``--device-rules``):
  the rung rule runs *inside* the fused scan (scan-carried per-lane budgets +
  per-rung loss histories), so chunk boundaries no longer clamp to rung /
  retirement event steps and a whole multi-rung ASHA ladder drains as ONE
  device dispatch, the host harvesting retirements from the scan's emitted
  event log afterwards.  Measured host-rule vs device-rule on a ladder sized
  to exactly the population (one trial per lane: with queued refills the
  device path's batched retirement harvest can reorder rung arrivals — a
  legitimate but *different* SHA schedule — so the trial-identical workload
  is what makes bit-equality a fair gate), on both the vmapped and sharded
  engines.  Gate: the device-rule flight's whole ladder costs exactly ONE
  dispatch (vmapped and sharded), scores and effective budgets match the
  host-rule path within ``CHUNKED_SCORE_TOL``, and the rule actually cut
  lanes (a ladder with nothing to truncate would gate nothing);
* **elastic_regrid**   — **elastic two-level regrid** (``--elastic-regrid``):
  at every rung boundary the survivors' full train state is re-laid-out from
  K lanes x W devices-per-lane to K' x W' (``make_lane_regrid`` +
  ``plan_regrid``), so later rungs train fewer trials wider and faster
  instead of idling freed devices.  Measured fixed-width sharded flight vs
  the elastic flight leasing an ``ElasticLanePool``, on a shrink-heavy
  ladder (one trial per lane, most lanes retiring at the first rung) at a
  heavier per-lane geometry (``ELASTIC_BATCH`` x ``ELASTIC_SEQ``) where the
  per-lane FLOP reduction dominates dispatch overhead.  Gate: at least one
  regrid fired, the pod stays fully leased after every cut (rows x width
  tiles the device count), wall-clock beats the fixed-width flight by
  ``ELASTIC_FLOOR``, scores match within ``CHUNKED_SCORE_TOL`` (resharding
  changes layout, never math) and the rung rule truncated the same trials;
* **tp_width**         — **tensor-parallel population step**
  (``--model-parallel``): ``TP_LANES`` survivors hold the whole 8-device pod
  at widths 1 / 2 / 4 on a compute-bound geometry (``TP_D_MODEL`` /
  ``TP_FF``, well above the smoke config).  Width 1 pads to one lane per
  device, so most devices burn full-model compute on frozen padding lanes;
  width W pads to 8/W rows with each live lane's heads and ff dims split W
  ways behind psum seams.  The virtual devices share the container's single
  core, so per-step wall-clock tracks TOTAL device compute — the width-2
  ratio is a direct witness that the model axis *partitions* compute (the
  pre-TP replicating regrid would time ~1.0x).  Gate: width-2 per-step
  wall-clock beats width-1 by ``TP_FLOOR``, the lowered width-2 step carries
  model-axis all-reduces while width-1 carries exactly zero, and the
  survivors' scores match across widths within ``TP_SCORE_TOL`` (width is
  layout, never math);
* **pbt_stream**       — Population-Based Training on the streaming engine
  (``--pbt-streaming``): members live in lanes, exploit is a compiled donor
  clone (``make_lane_clone``) and weights never visit the host — measured
  against the generation-barriered *serial* PBT driver (``run_pbt_serial``:
  one member at a time, host checkpoint restore + save every round) at equal
  total train steps and shared decision RNG.  Scores must match per
  (member, round); wall-clock must beat the serial driver by
  ``PBT_STREAM_FLOOR`` on the 8-virtual-device mesh; the streaming side must
  report ZERO host checkpoint round-trips;
* **pbt_async_quality** — ``--pbt-async`` drops the round gate, so by
  construction it has no serial equivalence baseline; this row quantifies
  what that costs on a longer workload: gated vs async best score, the
  clone/keep decision mix, and a *decision-lag histogram* (how many rounds
  stale each window entry behind an exploit/explore decision was — all zeros
  when gated, spread when async).  Informational — no pass criterion;
* **sha_rule_compare** — the cohort rung rule (batch-synchronous
  ``--inflight-stop`` flights) vs the staggered history rule (the refill
  engine's ``observe``) on a longer-horizon ASHA ladder: both are valid SHA
  variants that can cut *different* lanes; this row quantifies how far their
  cut counts and scores drift (informational — no pass criterion);
* **recovery**         — the crash-safety story end to end.  (a) *snapshot
  overhead*: the refill ladder with ``--snapshot-every 1`` (every live lane
  harvested to a disk-backed ``LaneSnapshotStore`` at every event boundary)
  vs snapshots off — the harvest must cost <= ``SNAPSHOT_OVERHEAD_CEIL``
  extra wall-clock AND <= ``SNAPSHOT_COST_CEIL_S`` per harvested snapshot
  (the absolute bound is the regression-proof one: a faster baseline flight
  inflates the ratio without any snapshot getting more expensive); (b)
  *quarantine*: a deterministic repeat-crash fault
  (``raise@step=...,times=...``) drives the supervised flight through its
  restart budget and the poison lane must be quarantined; (c)
  *kill/resume equivalence*: a CLI run SIGKILLed at an event boundary
  (``kill@event=K``) and resumed with ``--resume`` must report
  lanes restored from a snapshot step > 0 and end with per-trial scores
  within ``RECOVERY_SCORE_TOL`` of an uninterrupted run's.

All engines fold a per-trial ``stream`` id into the batch PRNG (independent
per-trial data streams), so scores must agree trial-for-trial across engines.

Emits ``BENCH_hpo_throughput.json`` (repo root) and returns the result dict
for ``benchmarks/run.py``.  Pass criteria: vmapped >= 3x serial trials/sec,
sharded >= 1x the vmapped trials/sec on the same mesh, compile-once /
vmapped / sharded each compile exactly once, vmapped + sharded scores match
the compile-once scores within tolerance, refill wall-clock no worse than the
inflight_stop flights (ratio floor ``REFILL_FLOOR`` absorbs shared-runner
timer noise), and refill scores match the serial replay within tolerance.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

OUT_PATH = "BENCH_hpo_throughput.json"
SPEEDUP_FLOOR = 3.0
SHARDED_FLOOR = 1.0  # sharded engine must not be slower than vmapped
# refill must beat (or at worst tie, within shared-runner timer noise) the
# batch-synchronous inflight-stop flights on the same ladder; the committed
# run shows ~1.4-1.5x
REFILL_FLOOR = 0.95
SCORE_TOL = 1e-3
MESH_DEVICES = 8
# fused multi-step dispatch: chunk length for the chunked row, its wall-clock
# floor against the per-step refill row on the same ladder (the committed run
# shows ~2-3.5x; host batch-building and per-step dispatch dominate at smoke
# scale), and its score tolerance (the scan engine is bit-equal to the
# per-step loop by construction, so this is the acceptance tolerance, not an
# engine-noise tolerance)
CHUNK_STEPS = 8
CHUNKED_FLOOR = 1.5
CHUNKED_SCORE_TOL = 1e-6
# budget unit (steps) for the chunked row's ladder: chunk sizes are bounded
# by the gap between scheduler events (retirements, rung boundaries), so the
# trials must train long enough between events for T-step chunks to form at
# all — the REFILL_UNIT=2 ladder retires a lane nearly every step and no
# dispatch scheme could fuse across that.  Same ASHA shape, longer unit.
CHUNK_UNIT = 8
# device-resident prefetch ring: the ring-fed fused flight vs the per-step
# host-feed loop on a uniform one-trial-per-lane streaming flight (no lane
# splices, so the row isolates the feed path; splice invalidation is covered
# by the crash/refill tests).  The ring removes BOTH the per-step dispatch
# and the synchronous host batch build from the hot loop; the overlap floor
# is the acceptance bar for the ring actually hiding host fill behind device
# compute rather than serializing it at chunk boundaries.  RING_BATCH x
# RING_SEQ is even more dispatch-bound than the PBT geometry — the regime
# the ring exists for — and wall-clock is best-of-RING_REPS because the
# shared-CPU container's scheduler noise swamps single-shot timings.
DATA_RING_FLOOR = 2.0
RING_OVERLAP_FLOOR = 0.5
RING_WINDOWS = 4
RING_CHUNK = 32
RING_UNITS = 16
RING_BATCH = 1
RING_SEQ = 8
RING_REPS = 5
# async-PBT quality probe: longer horizon than the equivalence row so the
# gated and staggered rules have room to diverge
PBT_QUALITY_ROUNDS = 5
# ASHA-ladder workload for the inflight-stop vs lane-refill comparison:
# many cheap rung-0 trials, a few expensive promotions (units of REFILL_UNIT
# steps).  Batch-synchronous flights pad every flight to its max surviving
# budget; the refill engine packs retired lanes instead.
REFILL_UNIT = 2            # train steps per budget unit
REFILL_LADDER = [1] * 8 + [2] * 4 + [4] * 2 + [8] * 2
# the rung boundary sits at 8 steps: on this synthetic LM the per-step batch
# loss only orders by lr reliably from ~8 steps on (earlier it is transient
# noise and the rule would cut at random)
REFILL_MIN_ITER_UNITS = 4

# device-rule row: a multi-rung ladder sized to exactly the population (one
# trial per lane — no refill contention, so host-rule and device-rule flights
# lease identical trials and must score bit-equal; see the docstring bullet),
# in units of CHUNK_UNIT steps.  eta=2 with min_iter=CHUNK_UNIT puts rung
# boundaries at 8 and 16 steps inside the 32-step max budget, and the chunk
# covers the whole ladder so the device path drains in ONE dispatch while the
# host-rule path still re-enters at every event step.
DEVRULES_LADDER = [1, 1, 2, 2, 2, 4, 4, 4]
DEVRULES_CHUNK = 32

# elastic-regrid row: a shrink-heavy ladder (one trial per lane, most lanes
# retiring at the first rung) at a heavier per-lane batch geometry than the
# other rows — the row measures the *compute* the regrid removes from later
# rungs (fewer, wider lanes), which at the smoke batch sizes is drowned by
# per-op dispatch overheads that do not scale with lane count.  Rung-0 lanes
# get a deliberately dead lr so the promotions reliably survive the cut and
# the flight actually regrids.  The fixed-width baseline runs the same ladder
# sharded over the same mesh; the two flights do identical work up to the
# first cut, so the whole-flight ratio is attributable to the later rungs.
ELASTIC_UNITS = [1, 1, 1, 1, 2, 2, 8, 8]
ELASTIC_LR = {1: 1e-5, 2: 1e-3, 8: 2e-3}
ELASTIC_BATCH = 8
ELASTIC_SEQ = 64
# The row's model swaps the smoke GQA geometry (4 heads, kv 2 — TP-degenerate:
# kv%width blocks attention sharding past width 2) for MHA 8x8 heads, so every
# pool width the planner picks (2/4/8) shards attention AND the MLP.  Later
# rungs then run width-local compute on the survivors' rows — what the regrid
# actually removes — instead of rows of mostly-replicated math.
ELASTIC_OVERRIDES = {"n_heads": 8, "n_kv_heads": 8, "head_dim": 8}
# committed 8-virtual-device run shows ~2.3x; the floor absorbs CI timer noise
ELASTIC_FLOOR = 1.1

# tensor-parallel width row: TP_LANES survivors holding the full 8-device pod
# at widths 1 / 2 / 4.  Width 1 pads to one lane per device (6 padding lanes
# burning full-model compute); width W pads to 8/W rows with each live lane's
# heads/ff split W ways, so total device compute — which IS wall-clock on the
# single-core container — drops roughly with the padded lane count times the
# width-local shard fraction.  The floor gates that the model axis carries
# compute (pure replication would time ~1.0x); scores must not move (width is
# layout, never math).  Geometry is compute-bound: d_model/ff well above the
# smoke config so matmuls dominate dispatch.
TP_LANES = 2
TP_STEPS = 4
TP_REPS = 3
TP_D_MODEL = 256
TP_FF = 1024
TP_BATCH = 4
TP_SEQ = 32
TP_FLOOR = 1.3
TP_SCORE_TOL = 1e-5

# streaming PBT vs the generation-barriered serial driver: equal total steps,
# shared RNG.  The serial baseline runs K*ROUNDS rounds one member at a time
# with 2 host checkpoint round-trips each; streaming runs ROUNDS*STEPS pop
# steps with exploit as a device clone.  The committed 8-virtual-device run
# shows well above the floor.
PBT_STREAM_FLOOR = 1.2
PBT_ROUNDS = 3
PBT_ROUND_STEPS = 4
# the PBT row times the dispatch/checkpoint overheads the streaming engine
# eliminates, so it uses a smaller batch geometry than the throughput rows
# (per-step compute on the 2-core CPU container would otherwise drown them);
# the vmapped engine runs the flight — on virtual devices the sharded twin
# adds only cross-device dispatch overhead at this scale and is covered by
# the equivalence tests instead
PBT_BATCH = 2
PBT_SEQ = 16
# streaming PBT reproduces the generation-barriered serial driver bit-for-bit
# on this workload (shared decision RNG, shared per-member streams/init keys,
# donor copies at round boundaries) — gate at the acceptance tolerance, well
# below the engine-equivalence SCORE_TOL
PBT_SCORE_TOL = 1e-6
# lr capped below the divergence zone so the comparison is not hostage to a
# borderline NaN flipping between engines
PBT_SPACE = [
    {"name": "learning_rate", "type": "float", "range": [1e-4, 5e-3], "scale": "log"},
    {"name": "weight_decay", "type": "float", "range": [0.0, 0.2]},
    {"name": "b2", "type": "float", "range": [0.9, 0.99]},
]

# longer-horizon ladder for the cohort-vs-staggered rung-rule comparison
# (units of REFILL_UNIT steps; boundaries at 2/6/18 steps with eta=3)
LONG_LADDER = [1] * 6 + [3] * 3 + [9] * 2 + [27] * 1
LONG_MIN_ITER_UNITS = 1

# crash-safety row: per-event lane harvests must stay cheap relative to the
# ladder (the snapshot is one lane's smoke-model state; device_get + npz),
# and the kill/resume round trip must reproduce the uninterrupted scores.
# The ratio ceiling is wider than it once was for an honest reason: the
# prefetch-ahead host feed shortened the snapshot-free per-step flight, so
# the same fixed ~10ms/harvest now reads as a larger *fraction* of this
# sub-second probe — the absolute per-snapshot cost is therefore gated too
# (the quantity a cost regression would actually move).
SNAPSHOT_OVERHEAD_CEIL = 1.40
SNAPSHOT_COST_CEIL_S = 0.030  # wall-clock per harvested snapshot
RECOVERY_SCORE_TOL = 1e-6
RECOVERY_KILL_EVENT = 3


def _sample_configs(n_trials: int, seed: int):
    from repro.core.search_space import SearchSpace
    from repro.launch.hpo import SPACE

    space = SearchSpace.from_json(SPACE)
    rng = np.random.default_rng(seed)
    # explicit per-trial stream ids: every engine (serial / vmapped / sharded)
    # then trains trial i on the same independent data sequence
    return [dict(space.sample(rng), stream=i) for i in range(n_trials)]


# ASHA promotes its *best* trials, so big-budget jobs usually carry good
# configs: lr improves with budget (by step 8 on this synthetic LM, higher lr
# means lower loss) so promotions stay on top at the rung the way a real ASHA
# run's do.  One of the two top promotions is deliberately *bad* — the rung
# rule must have something real to cut mid-flight in both engines.  Its lr
# sits well below the rung-0 lrs: at the 8-step boundary the counter-based
# stream's batch-to-batch noise is ~the gap between adjacent ladder lrs, so
# only a wide gap orders reliably against the rung history.
_LADDER_LR = {1: 2e-4, 2: 5e-4, 4: 1e-3, 8: 2e-3}
_LADDER_BAD_LR = 1e-5


def _ladder_workload(seed: int):
    """Deterministic mixed-budget configs (shared by probe and main process)."""
    cfgs = _sample_configs(len(REFILL_LADDER), seed + 1)
    order = np.random.default_rng(seed + 1).permutation(len(REFILL_LADDER))
    units = np.asarray(REFILL_LADDER)[order]
    bad_promotion = int(np.flatnonzero(units == max(REFILL_LADDER))[-1])
    for i, (c, u) in enumerate(zip(cfgs, units)):
        c["n_iterations"] = int(u)
        c["learning_rate"] = _LADDER_LR[int(u)] * (1.0 + 0.05 * (i % 3))
        # short warmup for every budget: a promotion's longer schedule must
        # not leave it crawling at rung boundaries it already passed once
        c["warmup_frac"] = 0.05
    cfgs[bad_promotion]["learning_rate"] = _LADDER_BAD_LR
    return cfgs


def _refill_hook():
    from repro.core.proposer.early_stop import InFlightSuccessiveHalving

    return InFlightSuccessiveHalving(
        eta=2.0, min_iter=REFILL_MIN_ITER_UNITS * REFILL_UNIT,
        max_iter=max(REFILL_LADDER) * REFILL_UNIT)


def _devrules_workload(seed: int, population: int):
    """One trial per lane, budgets cycled from DEVRULES_LADDER, with one
    deliberately bad max-budget promotion for the rung rule to cut."""
    units = [DEVRULES_LADDER[i % len(DEVRULES_LADDER)]
             for i in range(population)]
    cfgs = _sample_configs(population, seed + 5)
    bad_promotion = int(np.flatnonzero(np.asarray(units) == max(units))[-1])
    for i, (c, u) in enumerate(zip(cfgs, units)):
        c["n_iterations"] = int(u)
        c["learning_rate"] = _LADDER_LR[int(u)] * (1.0 + 0.05 * (i % 3))
        c["warmup_frac"] = 0.05
    cfgs[bad_promotion]["learning_rate"] = _LADDER_BAD_LR
    return cfgs


_LONG_LR = {1: 2e-4, 3: 5e-4, 9: 1e-3, 27: 2e-3}


def _elastic_workload(seed: int, population: int):
    """One trial per lane, budgets from ELASTIC_UNITS: rung-0 lanes carry a
    dead lr, promotions a live one, so the first boundary reliably leaves a
    strict subset of lanes alive and every later rung runs post-regrid."""
    cfgs = _sample_configs(population, seed + 7)
    for i, (c, u) in enumerate(zip(cfgs, ELASTIC_UNITS)):
        c["n_iterations"] = int(u)
        c["learning_rate"] = ELASTIC_LR[int(u)] * (1.0 + 0.05 * (i % 3))
        c["warmup_frac"] = 0.05
    return cfgs


def _long_ladder_workload(seed: int):
    """Longer-horizon mixed-budget configs for the rung-rule comparison."""
    cfgs = _sample_configs(len(LONG_LADDER), seed + 2)
    order = np.random.default_rng(seed + 2).permutation(len(LONG_LADDER))
    units = np.asarray(LONG_LADDER)[order]
    bad_promotion = int(np.flatnonzero(units == max(LONG_LADDER))[-1])
    for i, (c, u) in enumerate(zip(cfgs, units)):
        c["n_iterations"] = int(u)
        c["learning_rate"] = _LONG_LR[int(u)] * (1.0 + 0.05 * (i % 3))
        c["warmup_frac"] = 0.05
    cfgs[bad_promotion]["learning_rate"] = _LONG_LR[1]
    return cfgs


def _long_hook():
    from repro.core.proposer.early_stop import InFlightSuccessiveHalving

    return InFlightSuccessiveHalving(
        eta=3.0, min_iter=LONG_MIN_ITER_UNITS * REFILL_UNIT,
        max_iter=max(LONG_LADDER) * REFILL_UNIT)


def _dispatch_row(seconds: float, trial) -> dict:
    """One chunked-row engine entry — single source of the field shape
    ``run()`` consumes for every mode (per_step AND fused, all four engines)."""
    return {
        "seconds": seconds,
        "dispatches": trial.n_dispatches,
        "trained_steps": trial.n_train_steps,
        "dispatches_per_step": trial.n_dispatches / max(1, trial.n_train_steps),
    }


def _feed_scheduler(cfgs):
    """The shared streaming-feed adapter (fixed queue, ends when drained)."""
    from repro.core.resource.vectorized import QueueFeedScheduler

    return QueueFeedScheduler(cfgs)


def _probe_sharded(arch: str, n_trials: int, population: int, steps: int,
                   batch: int, seq: int, seed: int) -> dict:
    """Time vmapped + sharded inside a fresh process with a forced
    MESH_DEVICES-wide virtual CPU mesh (must happen before jax init).

    The child runs on the CPU by design (``JAX_PLATFORMS=cpu``): this parent
    has already touched JAX and holds the accelerator, and a child that
    asked for it would fail or hang."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={MESH_DEVICES}"
    ).strip()
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.hpo_throughput", "--probe-sharded",
           arch, str(n_trials), str(population), str(steps), str(batch),
           str(seq), str(seed)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"sharded probe failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def _probe_main(argv) -> None:
    arch, n_trials, population, steps, batch, seq, seed = (
        argv[0], *(int(x) for x in argv[1:]))
    import jax

    from repro.distributed.sharding import population_mesh
    from repro.launch.hpo import PopulationTrial
    from repro.train import population as pop

    cfgs = _sample_configs(n_trials, seed)
    trial = PopulationTrial(arch, steps, batch, seq, seed, population=population)
    tc, _ = trial._setup()
    mesh = population_mesh()
    res = {"n_devices": jax.device_count()}
    for name, kw in (("vmapped", {}), ("sharded", {"mesh": mesh})):
        pop.clear_population_cache()
        t0 = time.time()
        scores = []
        for i in range(0, n_trials, population):
            scores.extend(trial.run_population(cfgs[i:i + population], **kw))
        dt = time.time() - t0
        if name == "sharded":
            compiles = pop.get_compiled_sharded_population_step(
                tc, population, mesh=mesh, per_trial_batch=True)._cache_size()
        else:
            compiles = pop.get_compiled_population_step(
                tc, population, per_trial_batch=True)._cache_size()
        res[name] = {"seconds": dt, "trials_per_sec": n_trials / dt,
                     "population": population, "compiles": compiles,
                     "scores": scores}

    # -- inflight-stop flights vs one continuous refill flight (same mesh) -----
    lcfgs = _ladder_workload(seed)
    # warm the step + lane-op compiles so both rows time pre-compiled programs
    # (the streaming engine uses the masked init for multi-lane rounds and the
    # single-lane splice for one-at-a-time refills — warm both)
    warm = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed,
                           population=population, refill_idle_grace_s=0.0)
    warm.run_population([], mesh=mesh, scheduler=_feed_scheduler(
        _sample_configs(2, seed)))
    wkeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(0),
        jax.numpy.arange(population, dtype=jax.numpy.uint32))
    wst = pop.shard_population_state(
        pop.init_population_state_from_keys(wkeys, tc), mesh)
    pop.get_compiled_lane_op(tc, population, "splice", mesh=mesh)(
        wst, jax.numpy.asarray(0, jax.numpy.int32), jax.random.PRNGKey(1))

    itrial = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed,
                             population=population, early_stop=_refill_hook())
    t0 = time.time()
    for i in range(0, len(lcfgs), population):
        itrial.run_population(lcfgs[i:i + population], mesh=mesh)
    dt = time.time() - t0
    # scores are not shipped: truncation makes them budget-dependent, and only
    # the refill row's scores are checked (against the serial replay)
    res["inflight_stop"] = {
        "seconds": dt, "trials_per_sec": len(lcfgs) / dt,
        "trials": len(lcfgs), "population": population,
        "truncated": itrial.early_stop.n_truncated,
        "reclaimed": itrial.early_stop.n_reclaimed,
    }

    rtrial = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed,
                             population=population, early_stop=_refill_hook(),
                             refill_idle_grace_s=0.0)
    feed = _feed_scheduler(lcfgs)
    t0 = time.time()
    rtrial.run_population([], mesh=mesh, scheduler=feed)
    dt = time.time() - t0
    res["refill"] = {
        "seconds": dt, "trials_per_sec": len(lcfgs) / dt,
        "trials": len(lcfgs), "population": population,
        "truncated": rtrial.early_stop.n_truncated,
        "refills": rtrial.n_refills,
        "flight_steps": rtrial.last_flight_steps,
        "dispatches": rtrial.n_dispatches,
        "trained_steps": rtrial.n_train_steps,
        "scores": feed.ordered_scores(len(lcfgs)),
        "eff_steps": [int(feed.extras[i]["steps"]) for i in range(len(lcfgs))],
        "diverged": [bool(feed.extras[i]["diverged"]) for i in range(len(lcfgs))],
    }

    # -- fused chunked dispatch: per-step vs chunked across all four engines ---
    # Dispatch-bound geometry (the PBT row's) and a longer budget unit
    # (CHUNK_UNIT): the row measures the per-step dispatch + host-batch-
    # synthesis overheads that chunking eliminates, on a ladder whose trials
    # train long enough between scheduler events for chunks to form.  Each
    # (mode, chunk) pair runs once to warm every power-of-two scan compile,
    # then times a fresh trial on the same ladder.
    from repro.core.proposer.early_stop import InFlightSuccessiveHalving

    def _chunk_hook():
        return InFlightSuccessiveHalving(
            eta=2.0, min_iter=REFILL_MIN_ITER_UNITS * CHUNK_UNIT,
            max_iter=max(REFILL_LADDER) * CHUNK_UNIT)

    def _chunk_trial(chunk):
        return PopulationTrial(
            arch, CHUNK_UNIT, PBT_BATCH, PBT_SEQ, seed,
            population=population, chunk_steps=chunk,
            early_stop=_chunk_hook(), refill_idle_grace_s=0.0)

    def _timed_pair(measure, equiv=None):
        """The ONE pairing protocol every engine mode goes through:
        ``measure(chunk) -> (seconds, scores, trial)`` is timed at chunk 1
        (per_step) and CHUNK_STEPS (fused), rows share ``_dispatch_row``'s
        shape, and ``equiv`` compares the two score sets (default: listwise
        max abs diff)."""
        out = {}
        scores = {}
        for name, chunk in (("per_step", 1), ("fused", CHUNK_STEPS)):
            seconds, scores[name], trial = measure(chunk)
            out[name] = _dispatch_row(seconds, trial)
        out["speedup"] = out["per_step"]["seconds"] / out["fused"]["seconds"]
        eq = equiv or (lambda a, b: float(max(abs(x - y)
                                              for x, y in zip(a, b))))
        out["equivalence_max_abs_diff"] = eq(scores["per_step"],
                                             scores["fused"])
        return out

    def _ladder_measure(run_of):
        """Warm a fresh trial (compiles + tracing), then time another;
        ``run_of(trial)`` drives the ladder and returns ordered scores."""
        def measure(chunk):
            run_of(_chunk_trial(chunk))
            trial = _chunk_trial(chunk)
            t0 = time.time()
            scores = run_of(trial)
            return time.time() - t0, scores, trial
        return measure

    def _batch_flights(mkw):
        def run(trial):
            scores = []
            for i in range(0, len(lcfgs), population):
                scores.extend(
                    trial.run_population(lcfgs[i:i + population], **mkw))
            return scores
        return run

    def _refill_flight(trial):
        feedc = _feed_scheduler(lcfgs)
        trial.run_population([], mesh=mesh, scheduler=feedc)
        return feedc.ordered_scores(len(lcfgs))

    res["chunked"] = {
        "chunk_steps": CHUNK_STEPS, "trials": len(lcfgs),
        "budget_unit": CHUNK_UNIT,
        "population": population, "batch": PBT_BATCH, "seq": PBT_SEQ,
        "vmapped": _timed_pair(_ladder_measure(_batch_flights({}))),
        "sharded": _timed_pair(_ladder_measure(_batch_flights({"mesh": mesh}))),
        "refill": _timed_pair(_ladder_measure(_refill_flight)),
    }

    # -- device-resident prefetch ring: host-fed data on the fused scan --------
    # Per-step host-feed baseline (chunk 1, no ring: the host builds every
    # batch and dispatches one step at a time) vs the ring-fed fused flight
    # (chunk RING_CHUNK, --data-ring: the scan indexes pre-staged device
    # slabs the host filler keeps ahead of consumption).  Uniform budgets,
    # one trial per lane on the sharded streaming engine: no lane splices
    # mid-flight, so the ring's lane table never changes and the row isolates
    # the feed path itself (splice-heavy invalidation is covered by the
    # crash/refill tests, not this row).  RING_BATCH x RING_SEQ is even more
    # dispatch-bound than the PBT geometry — the regime the ring exists for.
    # The synth adapter is the in-scan synthesis bit-for-bit, so scores must
    # not move.  Best-of-RING_REPS wall-clock: on a shared-CPU container the
    # scheduler noise on single-shot timings exceeds the effect under test.
    rcfgs = _sample_configs(population, seed + 9)
    for cfg in rcfgs:
        cfg["n_iterations"] = RING_UNITS
        cfg["warmup_frac"] = 0.05

    def _ring_trial(chunk, ring):
        return PopulationTrial(
            arch, CHUNK_UNIT, RING_BATCH, RING_SEQ, seed,
            population=population, chunk_steps=chunk,
            refill_idle_grace_s=0.0,
            data_ring=ring, ring_windows=RING_WINDOWS)

    def _ring_flight(trial):
        feedr = _feed_scheduler([dict(c) for c in rcfgs])
        trial.run_population([], mesh=mesh, scheduler=feedr)
        return feedr.ordered_scores(len(rcfgs))

    def _ring_measure(chunk, ring):
        _ring_flight(_ring_trial(chunk, ring))  # warm compiles + ring path
        best = scores = trial = None
        for _ in range(RING_REPS):
            cand = _ring_trial(chunk, ring)
            t0 = time.time()
            s = _ring_flight(cand)
            dt = time.time() - t0
            if best is None or dt < best:
                best, scores, trial = dt, s, cand
        return best, scores, trial

    ring_ps_s, ring_ps_scores, ring_ps_trial = _ring_measure(1, False)
    ring_s, ring_scores, ring_trial = _ring_measure(RING_CHUNK, True)
    res["data_ring"] = {
        "chunk_steps": RING_CHUNK, "ring_windows": RING_WINDOWS,
        "trials": len(rcfgs), "population": population,
        "budget_unit": CHUNK_UNIT, "units_per_trial": RING_UNITS,
        "batch": RING_BATCH, "seq": RING_SEQ, "reps": RING_REPS,
        "per_step": _dispatch_row(ring_ps_s, ring_ps_trial),
        "ring": dict(
            _dispatch_row(ring_s, ring_trial),
            ring_fills=ring_trial.n_ring_fills,
            overlap_frac=ring_trial.ring_overlap_frac,
            fill_wait_s=ring_trial.ring_fill_wait_s,
        ),
        "speedup": ring_ps_s / ring_s,
        "equivalence_max_abs_diff": float(max(
            abs(a - b) for a, b in zip(ring_ps_scores, ring_scores))),
    }

    # -- streaming PBT vs generation-barriered serial PBT ----------------------
    from repro.core.experiment import Experiment
    from repro.core.proposer import make_proposer
    from repro.core.search_space import SearchSpace
    from repro.launch.hpo import run_pbt_serial

    pbt_space = SearchSpace.from_json(PBT_SPACE)

    def _pbt_proposer():
        return make_proposer(
            "pbt", pbt_space, maximize=True, seed=seed + 3,
            population=population, n_generations=PBT_ROUNDS, streaming=True,
            quantile=0.25)

    def _pbt_stream(n_generations, chunk=1):
        trial = PopulationTrial(arch, PBT_ROUND_STEPS, PBT_BATCH, PBT_SEQ,
                                seed, population=population,
                                per_trial_init=True, chunk_steps=chunk)
        exp = Experiment({
            "proposer": "pbt", "parameter_config": PBT_SPACE,
            "n_samples": population * n_generations, "n_parallel": population,
            "target": "max", "seed": seed + 3, "population": population,
            "n_generations": n_generations, "streaming": True,
            "quantile": 0.25, "resource": "vectorized", "lane_refill": True},
            trial)
        scores = {}
        exp.add_result_callback(lambda job: scores.__setitem__(
            (job.config.get("pbt_member"), job.config.get("pbt_round")),
            job.result.score if job.result else None))
        t0 = time.time()
        exp.run()
        return time.time() - t0, scores, trial, exp

    # warm every compiled program both drivers touch so the row times steady
    # state: a one-round streaming experiment (pop step + splice + clone at
    # the PBT batch geometry) and one serial hparam-step call
    _pbt_stream(1)
    wtrial = PopulationTrial(arch, PBT_ROUND_STEPS, PBT_BATCH, PBT_SEQ, seed,
                             per_trial_init=True)
    wtrial.serial_score_at({"learning_rate": 1e-3, "stream": -7}, 1)
    wstate = pop.init_population_state_from_keys(
        jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.PRNGKey(0),
            jax.numpy.arange(population, dtype=jax.numpy.uint32)), tc)
    pop.get_compiled_lane_op(tc, population, "clone")(
        wstate, jax.numpy.zeros(population, bool),
        jax.numpy.arange(population, dtype=jax.numpy.int32))

    ptrial_serial = PopulationTrial(arch, PBT_ROUND_STEPS, PBT_BATCH, PBT_SEQ,
                                    seed, per_trial_init=True)
    t0 = time.time()
    serial_pbt = run_pbt_serial(ptrial_serial, _pbt_proposer())
    dt_serial = time.time() - t0

    dt_stream, stream_pbt, ptrial, exp = _pbt_stream(PBT_ROUNDS)
    pbt_equiv = max(
        abs(stream_pbt[k2] - serial_pbt[k2]) for k2 in serial_pbt
    ) if set(stream_pbt) == set(serial_pbt) else float("inf")
    res["pbt_stream"] = {
        "serial_seconds": dt_serial, "stream_seconds": dt_stream,
        "speedup": dt_serial / dt_stream,
        "members": population, "rounds": PBT_ROUNDS,
        "round_steps": PBT_ROUND_STEPS,
        "batch": PBT_BATCH, "seq": PBT_SEQ,
        "clones": ptrial.n_clones, "splices": ptrial.n_splices,
        "keeps": exp.proposer.lifecycle_hook().n_keeps,
        "donor_waits": ptrial.n_donor_waits
                       + exp.proposer.lifecycle_hook().n_donor_waits,
        "serial_host_ckpt_roundtrips": ptrial_serial.n_host_ckpt_roundtrips,
        "stream_host_ckpt_roundtrips": ptrial.n_host_ckpt_roundtrips,
        "equivalence_max_abs_diff": pbt_equiv,
    }

    # chunked PBT: same streaming engine, rounds dispatched as fused chunks
    # (round ends are host-known events, so decisions are unchanged) — same
    # pairing protocol as the other three engines, dict-keyed scores
    _pbt_stream(1, chunk=CHUNK_STEPS)  # warm the PBT-geometry scan compiles

    def _pbt_measure(chunk):
        dtc, sc, ptrialc, _ = _pbt_stream(PBT_ROUNDS, chunk=chunk)
        return dtc, sc, ptrialc

    res["chunked"]["pbt_stream"] = _timed_pair(
        _pbt_measure,
        equiv=lambda a, b: float(max(abs(a[k2] - b[k2]) for k2 in a))
        if set(a) == set(b) else float("inf"))

    # -- device-side decision rules: the whole ladder as ONE dispatch ----------
    # Host-rule vs device-rule on a trial-per-lane ladder (no refill
    # contention — with queued trials the device path's batched retirement
    # harvest could reorder rung arrivals into a different, equally valid SHA
    # schedule), chunk covering the max budget: the host path still stops at
    # every rung boundary / budget end, the device path runs start-to-drain
    # as one scan and only harvests the emitted event log.
    devcfgs = _devrules_workload(seed, population)

    def _devrules_hook():
        return InFlightSuccessiveHalving(
            eta=2.0, min_iter=CHUNK_UNIT,
            max_iter=max(DEVRULES_LADDER) * CHUNK_UNIT)

    def _devrules_trial(device):
        return PopulationTrial(
            arch, CHUNK_UNIT, PBT_BATCH, PBT_SEQ, seed,
            population=population, chunk_steps=DEVRULES_CHUNK,
            early_stop=_devrules_hook(), refill_idle_grace_s=0.0,
            device_rules=device)

    def _devrules_cell(device, mkw):
        def flight():
            trial = _devrules_trial(device)
            feedd = _feed_scheduler(devcfgs)
            t0 = time.time()
            trial.run_population([], scheduler=feedd, **mkw)
            return time.time() - t0, feedd, trial
        flight()  # warm the scan / rule-state compiles
        dt, feedd, trial = flight()
        row = _dispatch_row(dt, trial)
        row["ladder_device_dispatches"] = trial.ladder_dispatches
        row["truncated"] = trial.early_stop.n_truncated
        row["reclaimed"] = trial.early_stop.n_reclaimed
        row["scores"] = feedd.ordered_scores(len(devcfgs))
        row["eff_steps"] = [int(feedd.extras[i]["steps"])
                            for i in range(len(devcfgs))]
        return row

    def _devrules_pair(host, dev):
        return {
            "host": host, "device": dev,
            "speedup": host["seconds"] / dev["seconds"],
            "equivalence_max_abs_diff": float(max(
                abs(a - b) for a, b in zip(host["scores"], dev["scores"]))),
            "eff_steps_equal": host["eff_steps"] == dev["eff_steps"],
            "truncated_equal": host["truncated"] == dev["truncated"],
        }

    res["device_rules"] = {
        "trials": len(devcfgs), "population": population,
        "ladder_units": DEVRULES_LADDER, "budget_unit": CHUNK_UNIT,
        "chunk_steps": DEVRULES_CHUNK,
        "vmapped": _devrules_pair(_devrules_cell(False, {}),
                                  _devrules_cell(True, {})),
        "sharded": _devrules_pair(_devrules_cell(False, {"mesh": mesh}),
                                  _devrules_cell(True, {"mesh": mesh})),
    }

    # -- elastic two-level regrid: survivors absorb freed devices --------------
    # Fixed-width sharded baseline vs the elastic engine with a leased
    # ElasticLanePool on the same shrink-heavy ladder: identical work up to
    # the first cut, then the elastic flight trains fewer, wider lanes.
    from repro.core.resource.sharded import ElasticLanePool

    ecfgs = _elastic_workload(seed, population)

    def _elastic_hook():
        return InFlightSuccessiveHalving(
            eta=2.0, min_iter=CHUNK_UNIT,
            max_iter=max(ELASTIC_UNITS) * CHUNK_UNIT)

    def _elastic_trial(elastic):
        return PopulationTrial(
            arch, CHUNK_UNIT, ELASTIC_BATCH, ELASTIC_SEQ, seed,
            population=population, chunk_steps=CHUNK_STEPS,
            early_stop=_elastic_hook(), refill_idle_grace_s=0.0,
            elastic_regrid=elastic, model_overrides=ELASTIC_OVERRIDES)

    def _fixed_flight():
        trial = _elastic_trial(False)
        t0 = time.time()
        scores = trial.run_population(list(ecfgs), mesh=mesh)
        return time.time() - t0, scores, trial

    def _elastic_flight():
        trial = _elastic_trial(True)
        pool = ElasticLanePool()
        t0 = time.time()
        scores = trial.run_population(list(ecfgs), elastic=pool)
        return time.time() - t0, scores, trial, pool

    _fixed_flight()    # warm the sharded step/scan compiles at this geometry
    _elastic_flight()  # warm the per-K elastic programs + regrid gathers
    fixed_s, fixed_scores, ftrial = _fixed_flight()
    elastic_s, elastic_scores, etrial, pool = _elastic_flight()
    n_dev = jax.device_count()
    res["elastic_regrid"] = {
        "trials": len(ecfgs), "population": population,
        "ladder_units": ELASTIC_UNITS, "budget_unit": CHUNK_UNIT,
        "batch": ELASTIC_BATCH, "seq": ELASTIC_SEQ,
        "chunk_steps": CHUNK_STEPS, "n_devices": n_dev,
        "model_overrides": ELASTIC_OVERRIDES,
        "per_rung_step_time_s": etrial.per_rung_step_time_s,
        "fixed_seconds": fixed_s, "elastic_seconds": elastic_s,
        "later_rung_speedup": fixed_s / elastic_s,
        "regrids": etrial.n_regrids,
        "lane_width_history": etrial.lane_width_history,
        "pool_width_history": pool.width_history,
        # rows = n/width device rows, each carrying lanes/rows trials: the
        # pod is fully re-leased after every cut, no partial rows
        "full_occupancy": all(
            n_dev % w == 0 and l % (n_dev // w) == 0
            for l, w in etrial.lane_width_history),
        "equivalence_max_abs_diff": float(max(
            abs(a - b) for a, b in zip(fixed_scores, elastic_scores))),
        "truncated_equal": (ftrial.early_stop.n_truncated
                            == etrial.early_stop.n_truncated),
    }

    # -- tensor-parallel width: per-step wall-clock for survivors on a full pod
    # TP_LANES survivors hold the whole 8-device pod.  At width 1 the flight
    # pads to one lane per device (rows == devices), so 6 of 8 devices burn
    # full-model compute on frozen padding lanes; at width W the pod regrids
    # to 8/W rows — fewer padding lanes, each live lane computing on
    # width-local shards (heads/W, ff/W) with psum seams.  On this container
    # the virtual devices share one core, so wall-clock tracks TOTAL device
    # compute: the per-step ratio is therefore a direct witness that the
    # model axis partitions compute — a replicating model axis (the pre-TP
    # regrid) would keep every device at full-model cost and time ~1.0x.
    # The geometry is deliberately compute-bound (bigger d_model/ff than the
    # smoke config) so matmul work, not dispatch, dominates the step.
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.optim.hparams import hparams_from_config, stack_hparams

    jnp = jax.numpy
    tp_model = dataclasses.replace(
        get_smoke_config(arch), name=f"{arch}-tpbench",
        d_model=TP_D_MODEL, head_dim=TP_D_MODEL // 4, d_ff=TP_FF)
    tp_tc = TrainConfig(model=tp_model, parallel=ParallelConfig(remat="none"),
                        learning_rate=1e-3, warmup_steps=1,
                        total_steps=TP_STEPS, seed=seed)
    tp_data = SyntheticLM(tp_model.vocab_size, TP_SEQ, TP_BATCH, seed=seed)
    tp_batches = [tp_data.make_batch(s, stream=0) for s in range(TP_STEPS)]

    def _tp_cell(width, count_psums=True):
        m = population_mesh(width=None if width == 1 else width)
        k = pop.pad_population(TP_LANES, m)
        # live lanes carry distinct lrs (a trivial equivalence would not
        # notice a lane permutation); padding lanes freeze at budget 0
        php = stack_hparams([
            hparams_from_config(dataclasses.replace(
                tp_tc, learning_rate=1e-3 * (1.0 + 0.1 * i),
                total_steps=TP_STEPS if i < TP_LANES else 0))
            for i in range(k)])
        step = pop.get_compiled_sharded_population_step(tp_tc, k, mesh=m)

        def _flight():
            keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                jax.random.PRNGKey(seed),
                jnp.arange(k, dtype=jnp.uint32))
            st = pop.shard_population_state(
                pop.init_population_state_from_keys(keys, tp_tc), m,
                tc=tp_tc)
            jax.block_until_ready(st)
            t0 = time.perf_counter()
            for b in tp_batches:
                st, _ = step(st, b, php)
            jax.block_until_ready(st["last_loss"])
            return (time.perf_counter() - t0) / TP_STEPS, st

        _flight()  # warm the compile + placement
        per_step, st = _flight()
        for _ in range(TP_REPS - 1):
            per_step = min(per_step, _flight()[0])
        return {
            "width": width, "lanes": k,
            "padding_lanes": k - TP_LANES,
            "per_step_seconds": per_step,
            "collectives": (pop.count_model_axis_collectives(
                tp_tc, k, m, tp_data) if count_psums else None),
            "scores": [float(x) for x in np.asarray(
                pop.population_scores(st))[:TP_LANES]],
        }

    tp_w1 = _tp_cell(1)
    tp_w2 = _tp_cell(2)
    tp_w4 = _tp_cell(4, count_psums=False)  # informational: kv=2 drops attn
    res["tp_width"] = {
        "trials": TP_LANES, "steps": TP_STEPS, "reps": TP_REPS,
        "d_model": TP_D_MODEL, "d_ff": TP_FF,
        "batch": TP_BATCH, "seq": TP_SEQ, "n_devices": jax.device_count(),
        "w1": tp_w1, "w2": tp_w2, "w4": tp_w4,
        "w2_vs_w1_per_step_speedup": (tp_w1["per_step_seconds"]
                                      / tp_w2["per_step_seconds"]),
        "w4_vs_w1_per_step_speedup": (tp_w1["per_step_seconds"]
                                      / tp_w4["per_step_seconds"]),
        "equivalence_max_abs_diff": float(max(
            abs(a - b)
            for ws in (tp_w2["scores"], tp_w4["scores"])
            for a, b in zip(tp_w1["scores"], ws))),
    }

    # -- async vs gated PBT: search quality on a longer horizon ----------------
    def _pbt_quality(sync: bool) -> dict:
        trial = PopulationTrial(arch, PBT_ROUND_STEPS, PBT_BATCH, PBT_SEQ,
                                seed, population=population,
                                per_trial_init=True)
        exp = Experiment({
            "proposer": "pbt", "parameter_config": PBT_SPACE,
            "n_samples": population * PBT_QUALITY_ROUNDS,
            "n_parallel": population, "target": "max", "seed": seed + 4,
            "population": population, "n_generations": PBT_QUALITY_ROUNDS,
            "streaming": True, "sync_rounds": sync, "quantile": 0.25,
            "resource": "vectorized", "lane_refill": True}, trial)
        scores: dict = {}
        exp.add_result_callback(lambda job: scores.__setitem__(
            (job.config.get("pbt_member"), job.config.get("pbt_round")),
            job.result.score if job.result else None))
        t0 = time.time()
        exp.run()
        dt = time.time() - t0
        hook = exp.proposer.lifecycle_hook()
        lags = [int(x) for x in hook.decision_lags]
        finals = [s for (m, r), s in scores.items()
                  if r == PBT_QUALITY_ROUNDS - 1 and s is not None]
        return {
            "seconds": dt,
            "best_score": max(s for s in scores.values() if s is not None),
            "best_final_round_score": max(finals) if finals else None,
            "clones": trial.n_clones, "keeps": hook.n_keeps,
            "splices": trial.n_splices,
            "donor_waits": trial.n_donor_waits + hook.n_donor_waits,
            "decision_lag_hist": np.bincount(lags).tolist() if lags else [],
            "decision_lag_mean": float(np.mean(lags)) if lags else 0.0,
            "decision_lag_max": int(max(lags)) if lags else 0,
        }

    res["pbt_async_quality"] = {
        "members": population, "rounds": PBT_QUALITY_ROUNDS,
        "round_steps": PBT_ROUND_STEPS,
        "gated": _pbt_quality(True),
        "async": _pbt_quality(False),
    }

    # -- cohort vs staggered rung rule on the longer-horizon ladder ------------
    long_cfgs = _long_ladder_workload(seed)
    chook = _long_hook()
    ctrial = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed,
                             population=population, early_stop=chook)
    t0 = time.time()
    cohort_scores = []
    for i in range(0, len(long_cfgs), population):
        cohort_scores.extend(
            ctrial.run_population(long_cfgs[i:i + population], mesh=mesh))
    dt_cohort = time.time() - t0
    shook = _long_hook()
    strial2 = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed,
                              population=population, early_stop=shook,
                              refill_idle_grace_s=0.0)
    sfeed = _feed_scheduler(long_cfgs)
    t0 = time.time()
    strial2.run_population([], mesh=mesh, scheduler=sfeed)
    dt_stag = time.time() - t0
    stag_scores = sfeed.ordered_scores(len(long_cfgs))
    n_disagree = sum(1 for a, b in zip(cohort_scores, stag_scores)
                     if abs(a - b) > 1e-3)
    res["sha_rule_compare"] = {
        "trials": len(long_cfgs), "population": population,
        "ladder_units": LONG_LADDER,
        "cohort": {"seconds": dt_cohort, "truncated": chook.n_truncated,
                   "reclaimed": chook.n_reclaimed,
                   "best_trial": int(np.argmax(cohort_scores)),
                   "best_score": float(max(cohort_scores))},
        "staggered": {"seconds": dt_stag, "truncated": shook.n_truncated,
                      "reclaimed": shook.n_reclaimed,
                      "best_trial": int(np.argmax(stag_scores)),
                      "best_score": float(max(stag_scores)),
                      "eff_steps": [int(sfeed.extras[i]["steps"])
                                    for i in range(len(long_cfgs))]},
        "n_score_disagreements": n_disagree,
        "same_best_trial": int(np.argmax(cohort_scores)) == int(np.argmax(stag_scores)),
    }
    print(json.dumps(res))


def _recovery_row(arch: str, population: int, batch: int, seq: int,
                  seed: int) -> dict:
    """Crash-safety: snapshot overhead, quarantine, kill/resume equivalence."""
    import shutil
    import signal
    import tempfile

    from repro.checkpoint import LaneSnapshotStore
    from repro.core import faultinject
    from repro.core.job import Job, JobStatus
    from repro.core.resource.vectorized import VectorizedResourceManager
    from repro.core.tracking.database import TrackingDB
    from repro.launch.hpo import PopulationTrial

    out: dict = {}
    lcfgs = _ladder_workload(seed)
    tmp = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        # -- (a) snapshot overhead on the refill ladder (vmapped engine) -------
        def _refill_seconds(snapshot_every, store):
            trial = PopulationTrial(
                arch, REFILL_UNIT, batch, seq, seed, population=population,
                early_stop=_refill_hook(), refill_idle_grace_s=0.0,
                snapshot_every=snapshot_every, snapshots=store)
            feed = _feed_scheduler(lcfgs)
            t0 = time.time()
            trial.run_population([], scheduler=feed)
            return time.time() - t0, trial

        # warm both variants (step/lane-op/snapshot compiles + tracing)
        _refill_seconds(0, None)
        _refill_seconds(1, LaneSnapshotStore(root=os.path.join(tmp, "warm")))
        plain_s, _ = _refill_seconds(0, None)
        snap_s, strial = _refill_seconds(
            1, LaneSnapshotStore(root=os.path.join(tmp, "lanes")))
        out["snapshot_overhead"] = {
            "plain_seconds": plain_s, "snapshot_seconds": snap_s,
            "ratio": snap_s / plain_s, "snapshots": strial.n_snapshots,
        }

        # -- (b) poison-lane quarantine under a repeat-crash fault -------------
        faultinject.arm("raise@step=2,times=3")
        try:
            qtrial = PopulationTrial(arch, 6, batch, seq, seed, population=2,
                                     refill_idle_grace_s=0.1)
            rm = VectorizedResourceManager(n_parallel=2, lane_refill=True,
                                           restart_backoff_s=0.001)
            jobs = [Job(i, {"learning_rate": 1e-3, "stream": 50 + i},
                        f"slot{i}", lambda j: None) for i in range(2)]
            for j in jobs:
                rm._busy[j.resource_id] = None
                rm.run(j, qtrial)
            for j in jobs:
                assert j.wait(300.0), "quarantine probe timed out"
        finally:
            faultinject.disarm()
        out["quarantine"] = {
            "flight_deaths": rm.n_flight_deaths,
            "flight_restarts": rm.n_flight_restarts,
            "quarantined": rm.n_quarantined,
            "failed_jobs": sum(j.status == JobStatus.FAILED for j in jobs),
        }

        # -- (c) CLI kill at an event boundary + --resume ----------------------
        # the CLI children run on the CPU by design: this process already
        # holds the accelerator, and one chip serves one process at a time
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")

        def _cli(db, extra, fault=None):
            e = dict(env)
            if fault:
                e[faultinject.ENV_VAR] = fault
            cmd = [sys.executable, "-m", "repro.launch.hpo",
                   "--proposer", "random", "--vectorize", "4", "--lane-refill",
                   "--n-samples", "8", "--steps", "12", "--batch", "2",
                   "--seq", "16", "--seed", str(seed), "--db", db] + extra
            return subprocess.run(cmd, env=e, capture_output=True, text=True,
                                  timeout=1800)

        def _scores(db):
            t = TrackingDB(db)
            eid = t.latest_experiment_id()
            return {r["config"].get("stream", r["job_id"]): r["score"]
                    for r in t.jobs(eid) if r["status"] == "finished"}

        base_db = os.path.join(tmp, "base.sqlite")
        kill_db = os.path.join(tmp, "kill.sqlite")
        r = _cli(base_db, ["--snapshot-every", "1"])
        if r.returncode != 0:
            raise RuntimeError(f"recovery baseline failed:\n{r.stderr[-2000:]}")
        r = _cli(kill_db, ["--snapshot-every", "1"],
                 fault=f"kill@event={RECOVERY_KILL_EVENT}")
        killed_rc = r.returncode
        if killed_rc not in (-signal.SIGKILL, 128 + signal.SIGKILL):
            raise RuntimeError(
                f"kill@event did not SIGKILL the run (rc={killed_rc}):\n"
                f"{r.stderr[-2000:]}")
        r = _cli(kill_db, ["--resume"])
        if r.returncode != 0:
            raise RuntimeError(f"--resume failed:\n{r.stderr[-2000:]}")
        resumed = json.loads(r.stdout[r.stdout.index("{"):])
        a, b = _scores(base_db), _scores(kill_db)
        equiv = (max(abs(a[k] - b[k]) for k in a)
                 if set(a) == set(b) and a else float("inf"))
        out["kill_resume"] = {
            "trials": len(a), "killed_rc": killed_rc,
            "kill_event": RECOVERY_KILL_EVENT,
            "resumed_lanes": resumed.get("resumed_lanes", 0),
            "resumed_from_steps": resumed.get("resumed_from_steps", []),
            "equivalence_max_abs_diff": equiv,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run(arch: str = "starcoder2-3b", n_trials: int = 8, population: int = 8,
        steps: int = 6, batch: int = 4, seq: int = 32, seed: int = 0):
    import jax

    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.hpo import PopulationTrial
    from repro.train import population as pop
    from repro.train import train_step as ts

    cfgs = _sample_configs(n_trials, seed)

    results = {}

    # -- serial_recompile: the legacy closure-over-hparams path ----------------
    ts.clear_step_cache()
    model_cfg = get_smoke_config(arch)
    data = SyntheticLM(model_cfg.vocab_size, seq, batch, seed=seed)
    t0 = time.time()
    compiles = 0
    serial_scores = []
    for cfg in cfgs:
        tc = TrainConfig(
            model=model_cfg, parallel=ParallelConfig(remat="none"),
            learning_rate=float(cfg["learning_rate"]),
            warmup_steps=max(1, int(cfg.get("warmup_frac", 0.1) * steps)),
            total_steps=steps,
            weight_decay=float(cfg.get("weight_decay", 0.1)),
            b2=float(cfg.get("b2", 0.95)),
            grad_clip=float(cfg.get("grad_clip", 1.0)),
            seed=seed,
        )
        state = ts.init_train_state(jax.random.PRNGKey(seed), tc)
        step_fn = jax.jit(ts.make_train_step(tc))
        score = -1e9
        for s in range(steps):
            state, metrics = step_fn(state, data.make_batch(s, stream=int(cfg["stream"])))
            loss = float(metrics["loss"])
            if not np.isfinite(loss):
                break
            score = -loss
        serial_scores.append(score)
        compiles += step_fn._cache_size()
    dt = time.time() - t0
    results["serial_recompile"] = {
        "seconds": dt, "trials_per_sec": n_trials / dt, "compiles": compiles,
    }

    # -- compile_once: HParams as a traced argument ----------------------------
    ts.clear_step_cache()
    trial = PopulationTrial(arch, steps, batch, seq, seed)
    t0 = time.time()
    once_scores = [trial(cfg) for cfg in cfgs]
    dt = time.time() - t0
    tc_static, _ = trial._setup()
    results["compile_once"] = {
        "seconds": dt, "trials_per_sec": n_trials / dt,
        "compiles": ts.get_compiled_train_step(tc_static)._cache_size(),
    }

    # -- vmapped: K trials in one device program -------------------------------
    pop.clear_population_cache()
    vtrial = PopulationTrial(arch, steps, batch, seq, seed, population=population)
    t0 = time.time()
    vmap_scores = []
    for i in range(0, n_trials, population):
        vmap_scores.extend(vtrial.run_population(cfgs[i:i + population]))
    dt = time.time() - t0
    tc_static, _ = vtrial._setup()
    results["vmapped"] = {
        "seconds": dt, "trials_per_sec": n_trials / dt, "population": population,
        "compiles": pop.get_compiled_population_step(
            tc_static, population, per_trial_batch=True)._cache_size(),
    }

    # -- sharded: population axis over an 8-virtual-device CPU mesh ------------
    probe = _probe_sharded(arch, n_trials, population, steps, batch, seq, seed)
    sharded_scores = probe["sharded"].pop("scores")
    probe_vmap_scores = probe["vmapped"].pop("scores")
    results["sharded"] = dict(probe["sharded"], n_devices=probe["n_devices"],
                              vmapped_same_mesh=probe["vmapped"])

    # -- streaming PBT + rung-rule comparison (same 8-device subprocess) -------
    results["pbt_stream"] = dict(probe["pbt_stream"])
    results["pbt_async_quality"] = dict(probe["pbt_async_quality"])
    results["sha_rule_compare"] = dict(probe["sha_rule_compare"])

    # -- inflight-stop flights vs one continuous refill flight -----------------
    results["inflight_stop"] = dict(probe["inflight_stop"])
    refill = dict(probe["refill"])
    refill_scores = refill.pop("scores")
    refill_eff = refill.pop("eff_steps")
    refill_div = refill.pop("diverged")
    results["refill"] = refill

    # -- crash-safe snapshots: overhead, quarantine, kill/resume ---------------
    results["recovery"] = _recovery_row(arch, population, batch, seq, seed)
    rec = results["recovery"]
    snapshot_overhead = rec["snapshot_overhead"]["ratio"]
    snap_pair = rec["snapshot_overhead"]
    snapshot_cost_s = ((snap_pair["snapshot_seconds"]
                        - snap_pair["plain_seconds"])
                       / max(1, snap_pair["snapshots"]))
    recovery_equiv = rec["kill_resume"]["equivalence_max_abs_diff"]
    resumed_steps = rec["kill_resume"]["resumed_from_steps"]

    # -- fused chunked dispatch vs the per-step loops (all four engines) -------
    chunked = dict(probe["chunked"])
    results["chunked"] = chunked
    chrefill = chunked["refill"]
    chunked_equiv = float(max(
        chunked[m]["equivalence_max_abs_diff"]
        for m in ("vmapped", "sharded", "refill", "pbt_stream")))
    chunked_vs_refill = chrefill["speedup"]
    chunked_dispatch_ratio = chrefill["fused"]["dispatches_per_step"]

    # -- device-resident prefetch ring vs the per-step host-feed loop ----------
    dring = dict(probe["data_ring"])
    results["data_ring"] = dring
    data_ring_ok = (
        dring["speedup"] >= DATA_RING_FLOOR
        and dring["ring"]["overlap_frac"] >= RING_OVERLAP_FLOOR
        and dring["ring"]["ring_fills"] >= 1
        and dring["ring"]["dispatches_per_step"] < 1.0
        and dring["equivalence_max_abs_diff"] <= CHUNKED_SCORE_TOL
    )

    # -- device-side decision rules: one dispatch drains the whole ladder ------
    devrules = dict(probe["device_rules"])
    results["device_rules"] = devrules
    devrules_equiv = float(max(devrules[m]["equivalence_max_abs_diff"]
                               for m in ("vmapped", "sharded")))
    devrules_dispatches = max(
        devrules[m]["device"]["ladder_device_dispatches"]
        for m in ("vmapped", "sharded"))
    devrules_ok = (
        devrules_dispatches == 1
        and devrules_equiv <= CHUNKED_SCORE_TOL
        and all(devrules[m]["eff_steps_equal"]
                and devrules[m]["truncated_equal"]
                and devrules[m]["device"]["truncated"] >= 1
                and devrules[m]["host"]["dispatches"] > 1
                for m in ("vmapped", "sharded"))
    )

    # -- elastic two-level regrid: survivors absorb freed devices --------------
    elastic = dict(probe["elastic_regrid"])
    results["elastic_regrid"] = elastic
    elastic_ok = (
        elastic["regrids"] >= 1
        and elastic["full_occupancy"]
        and elastic["later_rung_speedup"] >= ELASTIC_FLOOR
        and elastic["equivalence_max_abs_diff"] <= CHUNKED_SCORE_TOL
        and elastic["truncated_equal"]
    )

    # -- tensor-parallel width: the model axis must carry compute --------------
    tp = dict(probe["tp_width"])
    results["tp_width"] = tp
    tp_ok = (
        tp["w2_vs_w1_per_step_speedup"] >= TP_FLOOR
        and tp["equivalence_max_abs_diff"] <= TP_SCORE_TOL
        and tp["w1"]["collectives"] == 0
        and tp["w2"]["collectives"] > 0
    )

    # refill equivalence: every trial must score exactly what the serial
    # driver scores at the trial's *effective* step count — the original
    # budget's LR schedule, cut at the truncation step (early-stop semantics);
    # diverged lanes must report the sentinel
    lcfgs = _ladder_workload(seed)
    strial = PopulationTrial(arch, REFILL_UNIT, batch, seq, seed)
    refill_equiv = 0.0
    for cfg, score, eff, div in zip(lcfgs, refill_scores, refill_eff, refill_div):
        if div:
            refill_equiv = max(refill_equiv, abs(score - strial.DIVERGED_SCORE))
            continue
        serial_score = strial.serial_score_at(dict(cfg), eff)
        refill_equiv = max(refill_equiv, abs(score - serial_score))

    def max_diff(a, b):
        return float(max(abs(x - y) for x, y in zip(a, b)))

    equiv = max(max_diff(once_scores, vmap_scores),
                max_diff(once_scores, sharded_scores),
                max_diff(once_scores, probe_vmap_scores))
    speedup_vmap = results["vmapped"]["trials_per_sec"] / results["serial_recompile"]["trials_per_sec"]
    speedup_once = results["compile_once"]["trials_per_sec"] / results["serial_recompile"]["trials_per_sec"]
    # same-process, same-mesh comparison: sharded vs vmapped on 8 devices
    sharded_vs_vmapped = (results["sharded"]["trials_per_sec"]
                          / results["sharded"]["vmapped_same_mesh"]["trials_per_sec"])
    refill_vs_inflight = (results["inflight_stop"]["seconds"]
                          / results["refill"]["seconds"])
    pbt = results["pbt_stream"]
    ok = (
        speedup_vmap >= SPEEDUP_FLOOR
        and sharded_vs_vmapped >= SHARDED_FLOOR
        and results["compile_once"]["compiles"] == 1
        and results["vmapped"]["compiles"] == 1
        and results["sharded"]["compiles"] == 1
        and equiv <= SCORE_TOL
        and refill_vs_inflight >= REFILL_FLOOR
        and refill_equiv <= SCORE_TOL
        and chunked_vs_refill >= CHUNKED_FLOOR
        and chunked_equiv <= CHUNKED_SCORE_TOL
        and chunked_dispatch_ratio < 1.0
        and data_ring_ok
        and devrules_ok
        and elastic_ok
        and tp_ok
        and pbt["speedup"] >= PBT_STREAM_FLOOR
        and pbt["equivalence_max_abs_diff"] <= PBT_SCORE_TOL
        and pbt["stream_host_ckpt_roundtrips"] == 0
        and snapshot_overhead <= SNAPSHOT_OVERHEAD_CEIL
        and snapshot_cost_s <= SNAPSHOT_COST_CEIL_S
        and rec["quarantine"]["quarantined"] >= 1
        and recovery_equiv <= RECOVERY_SCORE_TOL
        and rec["kill_resume"]["resumed_lanes"] >= 1
        and bool(resumed_steps) and max(resumed_steps) > 0
    )
    out = {
        "arch": arch, "n_trials": n_trials, "steps": steps,
        "batch": batch, "seq": seq,
        "modes": results,
        "speedup_vmapped_vs_serial": speedup_vmap,
        "speedup_compile_once_vs_serial": speedup_once,
        "sharded_vs_vmapped_same_mesh": sharded_vs_vmapped,
        "refill_vs_inflight_stop_speedup": refill_vs_inflight,
        "chunked_vs_refill_speedup": chunked_vs_refill,
        "chunked_dispatches_per_step": chunked_dispatch_ratio,
        "data_ring_vs_per_step_speedup": dring["speedup"],
        "data_ring_overlap_frac": dring["ring"]["overlap_frac"],
        "data_ring_equivalence_max_abs_diff":
            dring["equivalence_max_abs_diff"],
        "pbt_stream_vs_serial_speedup": pbt["speedup"],
        "equivalence_max_abs_diff": equiv,
        "refill_equivalence_max_abs_diff": refill_equiv,
        "chunked_equivalence_max_abs_diff": chunked_equiv,
        "device_rules_ladder_dispatches": devrules_dispatches,
        "device_rules_equivalence_max_abs_diff": devrules_equiv,
        "elastic_regrid_later_rung_speedup": elastic["later_rung_speedup"],
        "elastic_regrid_equivalence_max_abs_diff":
            elastic["equivalence_max_abs_diff"],
        "tp_width_w2_per_step_speedup": tp["w2_vs_w1_per_step_speedup"],
        "tp_width_model_axis_collectives": tp["w2"]["collectives"],
        "tp_width_equivalence_max_abs_diff": tp["equivalence_max_abs_diff"],
        "pbt_equivalence_max_abs_diff": pbt["equivalence_max_abs_diff"],
        "recovery_snapshot_overhead_ratio": snapshot_overhead,
        "recovery_snapshot_cost_s": snapshot_cost_s,
        "recovery_equivalence_max_abs_diff": recovery_equiv,
        "pass": bool(ok),
        "paper_claim": (
            f"population engines: vmapped {speedup_vmap:.1f}x trials/sec over "
            f"serial recompile (floor {SPEEDUP_FLOOR}x); sharded over "
            f"{results['sharded']['n_devices']} devices {sharded_vs_vmapped:.2f}x "
            f"vmapped on the same mesh; continuous lane refill "
            f"{refill_vs_inflight:.2f}x the inflight-stop flights on the same "
            f"ASHA ladder (scores = serial driver at effective budgets); "
            f"fused chunked dispatch {chunked_vs_refill:.2f}x the per-step "
            f"refill loop on the same ladder (scores bit-equal across all "
            f"four engines, {chrefill['per_step']['dispatches']} -> "
            f"{chrefill['fused']['dispatches']} device dispatches, "
            f"{chunked_dispatch_ratio:.2f} per trained step); the "
            f"device-resident prefetch ring feeds host-supplied data to the "
            f"same fused scans {dring['speedup']:.2f}x faster than the "
            f"per-step host-feed loop (floor {DATA_RING_FLOOR}x), hiding "
            f"{100 * dring['ring']['overlap_frac']:.0f}% of host fill behind "
            f"device compute (floor {100 * RING_OVERLAP_FLOOR:.0f}%) at "
            f"unchanged scores (max diff "
            f"{dring['equivalence_max_abs_diff']:.2g}); device-side "
            f"decision rules run the whole "
            f"{len(devrules['ladder_units'])}-trial multi-rung ladder as "
            f"{devrules_dispatches} device dispatch on both the vmapped and "
            f"sharded engines (host-rule path: "
            f"{devrules['vmapped']['host']['dispatches']} dispatches), scores "
            f"and effective budgets equal to the host-rule path "
            f"(max diff {devrules_equiv:.2g}); elastic two-level regrid "
            f"re-leases the pod at every rung cut "
            f"({elastic['regrids']} regrids, lane/width history "
            f"{elastic['lane_width_history']}) and runs the same shrink-heavy "
            f"ladder {elastic['later_rung_speedup']:.2f}x faster than the "
            f"fixed-width sharded flight (floor {ELASTIC_FLOOR}x, scores "
            f"within {elastic['equivalence_max_abs_diff']:.2g}); the "
            f"tensor-parallel model axis carries real compute: "
            f"{tp['trials']} survivors on the full {tp['n_devices']}-device "
            f"pod step {tp['w2_vs_w1_per_step_speedup']:.2f}x faster at "
            f"width 2 than width 1 (floor {TP_FLOOR}x; "
            f"{tp['w2']['collectives']} model-axis all-reduces vs "
            f"{tp['w1']['collectives']} at width 1, scores within "
            f"{tp['equivalence_max_abs_diff']:.2g}); "
            f"streaming PBT {pbt['speedup']:.1f}x the generation-barriered "
            f"serial PBT driver at equal total steps (scores equal, "
            f"{pbt['serial_host_ckpt_roundtrips']} -> 0 host checkpoint "
            f"round-trips); crash-safe streaming: per-event lane snapshots "
            f"cost {100 * (snapshot_overhead - 1):.1f}% wall-clock, a SIGKILL "
            f"at an event boundary resumes {rec['kill_resume']['resumed_lanes']} "
            f"lanes from their snapshot step with per-trial scores equal to "
            f"the uninterrupted run (max diff {recovery_equiv:.2g}); compiles "
            f"{results['serial_recompile']['compiles']} -> 1"
        ),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--probe-sharded":
        _probe_main(sys.argv[2:])
    else:
        print(json.dumps(run(), indent=1))
