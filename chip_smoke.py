#!/usr/bin/env python3
"""Chip smoke: the population engine's main path on a TPU at the published
widths of starcoder2-3b.

    python3 chip_smoke.py            # one chip: phases A and B
    python3 chip_smoke.py --chips 4  # four chips: sharded width 1 vs width 2 only

Run it from the repository root; it puts ``src`` on ``sys.path`` itself.  It
exits non-zero, and prints no ok line, unless JAX's first device is a TPU.

Model: ``configs/starcoder2_3b.py`` at its published widths — d_model 3072,
24 heads, GQA kv 2, head_dim 128, d_ff 12288, vocab 49152, tied embeddings —
handed to ``PopulationTrial(model_overrides=...)`` on top of the starcoder2
preset, with float32 params and compute and random weights from ``--seed``.
Each of K lanes trains per-lane batch 1 at seq 1024 (K=2 on one chip, K=4
on four).

reduced:
  - n_layers 30 -> 2 (depth is the only cut; widths and seq are published size)

Phase A, batch flight: the ``Experiment`` that ``repro.launch.hpo`` builds
for ``--vectorize 2`` (random proposer, 2 samples, 3 steps), then the serial
compile-once driver on the same configs and streams; every lane's score must
match its serial twin within ``REL_TOL``.
Phase B, streaming flight: ``--lane-refill --chunk-steps 4 --inflight-stop``
under ASHA, 4 sampled configs over 2 lanes; every job streams out, at least
one lane is refilled, and no flight dies.
Four chips: the sharded population (K=4, one lane per chip), then model
width 2 (2 rows x 2); every lane's score must match width 1 within
``REL_TOL``, and width 2 must lower model-axis collectives.

In every phase each job must finish on its first attempt: statuses and
retries are read from ``exp.job_log``, because the engine would otherwise
retry or quarantine a failure and still return a best score.

Each phase prints one JSON line (set-up seconds — tracing, lowering and
compiling or loading from the compile cache — steady step seconds after
``block_until_ready``, host seconds to build one per-step batch, losses,
whether ``tpu_custom_call`` is in the compiled step, device bytes, and
``reduced``).  The last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "starcoder2-3b"
REDUCED = ["n_layers 30 -> 2"]
# Lane-vs-reference agreement.  Both sides run the same float32 math, but
# as differently batched programs (vmapped vs serial, one device vs a
# tensor-parallel row), so matmul accumulation order differs, and at the
# TPU's default matmul precision each f32 dot multiplies bf16-rounded
# operands.  Over 3 AdamW steps that moves a ~10.8-nat loss by far less
# than 1e-3 of itself; a wrong lane, stream or config moves it by more.
REL_TOL = 1e-3
# the published widths taken over from the full config; everything else
# (float32 dtypes, depth) comes from the starcoder2 preset
WIDTH_FIELDS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "vocab_size", "tie_embeddings", "rope_theta", "activation",
                "pattern")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One trial geometry: ``overrides`` go on top of the arch's preset."""

    overrides: dict
    batch: int
    seq: int
    steps: int
    lanes: int


def published_geometry(lanes: int) -> Geometry:
    from repro.configs import get_config

    full = get_config(ARCH)
    over = {f: getattr(full, f) for f in WIDTH_FIELDS}
    over.update(name=f"{ARCH}-2L", n_layers=2)
    return Geometry(overrides=over, batch=1, seq=1024, steps=3, lanes=lanes)


class PhaseFailed(RuntimeError):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# -- set-up clock -----------------------------------------------------------------
# JAX reports tracing, lowering and backend compilation (which includes a
# compile-cache read) as duration events; their sum over a phase is its
# set-up time, and a warm compile cache shows up as a smaller sum.
_SETUP = {"s": 0.0}
_SETUP_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")


def _install_setup_clock() -> None:
    import jax

    if _SETUP.get("installed"):
        return

    def listen(event, duration, **_):
        if event in _SETUP_EVENTS:
            _SETUP["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    _SETUP["installed"] = True


def _setup_seconds() -> float:
    return _SETUP["s"]


# -- shared helpers -----------------------------------------------------------------
def _exp_config(proposer: str, n_samples: int, n_parallel: int,
                resource: str, seed: int, **extra) -> dict:
    """The exp_config ``repro.launch.hpo.main`` builds for these flags."""
    from repro.launch.hpo import SPACE

    cfg = {
        "proposer": proposer,
        "parameter_config": SPACE,
        "n_samples": n_samples,
        "n_parallel": n_parallel,
        "target": "max",
        "random_seed": seed,
        "resource": resource,
        "max_flight_restarts": 2,
    }
    cfg.update(extra)
    return cfg


def _settled_jobs(exp) -> list:
    """Every job of ``exp``, each required to have finished on its first
    attempt with a finite, non-sentinel score."""
    from repro.core.job import JobStatus
    from repro.launch.hpo import PopulationTrial

    jobs = list(exp.job_log)
    _require(jobs, "the experiment ran no job")
    bad = [(j.job_id, j.status.value) for j in jobs
           if j.status != JobStatus.FINISHED]
    _require(not bad, f"jobs did not finish: {bad}")
    retried = [j.job_id for j in jobs if getattr(j, "retries", 0)]
    _require(not retried, f"jobs were retried: {retried}")
    for j in jobs:
        s = None if j.result is None else j.result.score
        _require(s is not None and math.isfinite(s)
                 and s > PopulationTrial.DIVERGED_SCORE / 2,
                 f"job {j.job_id} scored {s!r}")
    return jobs


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _memory_per_device() -> dict:
    import jax

    return {str(d.id): _memory(d) for d in jax.local_devices()}


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def _timed_steps(run, n: int = 3) -> float:
    """Median seconds of ``run()`` (which must block on its result) over
    ``n`` calls after one warm call."""
    run()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _lane_hparams(trial, configs, n_steps: int):
    from repro.optim.hparams import stack_hparams

    return stack_hparams([trial._hparams(c, n_steps) for c in configs])


# -- phase A: batch flight vs the serial driver -------------------------------------
def phase_batch(geo: Geometry, seed: int = 0) -> dict:
    import jax

    from repro.core.experiment import Experiment
    from repro.launch.hpo import PopulationTrial
    from repro.train.population import (get_compiled_population_step,
                                        init_population_state)

    setup0 = _setup_seconds()
    trial = PopulationTrial(ARCH, geo.steps, geo.batch, geo.seq, seed,
                            population=geo.lanes,
                            model_overrides=geo.overrides)
    tc, data = trial._setup()
    # the flight's step, compiled ahead of time for its text: whether a
    # Pallas kernel is inside (the flight's first call reads the same
    # program back from the compile cache)
    step = get_compiled_population_step(tc, geo.lanes, per_trial_batch=True)
    shapes = jax.eval_shape(lambda: (
        init_population_state(jax.random.PRNGKey(seed), tc, geo.lanes),
        data.make_population_batch(0, list(range(geo.lanes))),
        _lane_hparams(trial, [{}] * geo.lanes, geo.steps)))
    kernel = "tpu_custom_call" in step.lower(*shapes).compile().as_text()

    exp = Experiment(_exp_config("random", geo.lanes, geo.lanes,
                                 "vectorized", seed), trial)
    exp.run()
    jobs = _settled_jobs(exp)
    configs = [dict(j.config) for j in jobs]
    scores = [float(j.result.score) for j in jobs]

    streams = [trial._stream_of(c, i) for i, c in enumerate(configs)]
    php = _lane_hparams(trial, configs, 1 << 20)
    state = {"p": init_population_state(jax.random.PRNGKey(seed), tc,
                                        geo.lanes)}
    cursor = {"s": 0}

    def one_step():
        batch = data.make_population_batch(cursor["s"], streams)
        state["p"], metrics = step(state["p"], batch, php)
        jax.block_until_ready(metrics["loss"])
        cursor["s"] += 1

    step_s = _timed_steps(one_step)
    del state["p"]
    batch_s = _timed_steps(lambda: data.make_population_batch(0, streams))

    serial = [float(trial(c)) for c in configs]
    rel = [_rel(p, s) for p, s in zip(scores, serial)]
    _require(all(r <= REL_TOL for r in rel),
             f"lanes disagree with their serial twins: {scores} vs {serial}")
    return {
        "phase": "A-batch",
        "setup_s": _setup_seconds() - setup0,
        "steady_step_s": step_s,
        "host_batch_s": batch_s,
        "lane_losses": [-s for s in scores],
        "serial_losses": [-s for s in serial],
        "max_rel_diff": max(rel),
        "rel_tol": REL_TOL,
        "tpu_custom_call": kernel,
        **_memory(jax.local_devices()[0]),
        "reduced": REDUCED,
    }


# -- phase B: streaming flight --------------------------------------------------------
def phase_stream(geo: Geometry, seed: int = 0, chunk: int = 4,
                 n_samples: int = 4) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.experiment import Experiment
    from repro.data.pipeline import split_streams
    from repro.launch.hpo import PopulationTrial
    from repro.train.population import (get_compiled_population_scan_step,
                                        init_population_state)

    setup0 = _setup_seconds()
    trial = PopulationTrial(ARCH, geo.steps, geo.batch, geo.seq, seed,
                            population=geo.lanes, chunk_steps=chunk,
                            model_overrides=geo.overrides)
    tc, data = trial._setup()
    scan = get_compiled_population_scan_step(tc, geo.lanes, data, chunk)
    k = geo.lanes
    lane_args = (jnp.zeros((k,), jnp.int32),) + tuple(
        jnp.asarray(w) for w in split_streams(list(range(k))))
    shapes = jax.eval_shape(lambda: (
        init_population_state(jax.random.PRNGKey(seed), tc, k),
        _lane_hparams(trial, [{}] * k, geo.steps)) + lane_args)
    kernel = "tpu_custom_call" in scan.lower(*shapes).compile().as_text()

    exp = Experiment(_exp_config("asha", n_samples, k, "vectorized", seed,
                                 lane_refill=True), trial)
    trial.early_stop = exp.proposer.inflight_hook(steps_per_unit=geo.steps)
    exp.run()
    jobs = _settled_jobs(exp)
    rm = exp.rm
    counts = {
        "jobs": len(jobs),
        "sampled_configs": len(exp.proposer.configs),
        "streamed_results": rm.n_streamed,
        "lane_refills": trial.n_refills,
        "flight_deaths": rm.n_flight_deaths,
        "quarantined": rm.n_quarantined,
    }
    _require(counts["sampled_configs"] == n_samples, f"sampled: {counts}")
    # ASHA promotes into extra jobs; every one of them must stream out
    _require(counts["streamed_results"] == len(jobs), f"not streamed: {counts}")
    _require(counts["lane_refills"] >= 1, f"no lane was refilled: {counts}")
    _require(counts["flight_deaths"] == 0 and counts["quarantined"] == 0,
             f"flights died: {counts}")

    php = _lane_hparams(trial, [dict(j.config) for j in jobs[:k]], 1 << 20)
    state = {"p": init_population_state(jax.random.PRNGKey(seed), tc, k)}

    def one_chunk():
        state["p"], metrics = scan(state["p"], php, *lane_args)
        jax.block_until_ready(metrics["loss"])

    step_s = _timed_steps(one_chunk) / chunk
    del state["p"]
    return dict({
        "phase": "B-stream",
        "setup_s": _setup_seconds() - setup0,
        "steady_step_s": step_s,
        "chunk_steps": chunk,
        "losses": [-float(j.result.score) for j in jobs],
        "tpu_custom_call": kernel,
        **_memory(jax.local_devices()[0]),
        "reduced": REDUCED,
    }, **counts)


# -- four chips: sharded population at widths 1 and 2 ------------------------------
def phase_four_chips(geo: Geometry, seed: int = 0) -> dict:
    import jax

    from repro.core.experiment import Experiment
    from repro.launch.hpo import PopulationTrial
    from repro.train.population import (count_model_axis_collectives,
                                        get_compiled_sharded_population_step,
                                        init_population_state_on_mesh,
                                        pad_population)

    out = {"phase": "four-chip", "reduced": REDUCED, "rel_tol": REL_TOL}
    scores = {}
    for width in (1, 2):
        setup0 = _setup_seconds()
        trial = PopulationTrial(ARCH, geo.steps, geo.batch, geo.seq, seed,
                                population=geo.lanes, model_parallel=width,
                                model_overrides=geo.overrides)
        extra = {"model_parallel": width} if width > 1 else {}
        exp = Experiment(_exp_config("random", geo.lanes, geo.lanes,
                                     "sharded", seed, **extra), trial)
        exp.run()
        jobs = _settled_jobs(exp)
        scores[width] = {j.job_id: float(j.result.score) for j in jobs}
        tc, data = trial._setup()
        mesh = exp.rm.mesh
        k = pad_population(geo.lanes, mesh)
        collectives = count_model_axis_collectives(
            tc, k, mesh, data, per_trial_batch=True)

        step = get_compiled_sharded_population_step(
            tc, k, mesh=mesh, per_trial_batch=True)
        configs = [dict(j.config) for j in jobs][:k]
        configs += [{}] * (k - len(configs))
        streams = [trial._stream_of(c, i) for i, c in enumerate(configs)]
        php = _lane_hparams(trial, configs, 1 << 20)
        state = {"p": init_population_state_on_mesh(
            jax.random.PRNGKey(seed), tc, k, mesh)}
        # the population state alone is alive here: wait for the init, whose
        # replicated template lane is a transient on every device
        jax.block_until_ready(state["p"])
        live = _memory_per_device()
        cursor = {"s": 0}

        def one_step():
            batch = data.make_population_batch(cursor["s"], streams)
            state["p"], metrics = step(state["p"], batch, php)
            jax.block_until_ready(metrics["loss"])
            cursor["s"] += 1

        step_s = _timed_steps(one_step)
        del state["p"]
        out[f"width{width}"] = {
            "setup_s": _setup_seconds() - setup0,
            "steady_step_s": step_s,
            "host_batch_s": _timed_steps(
                lambda: data.make_population_batch(0, streams)),
            "lanes": k,
            "losses": [-s for s in scores[width].values()],
            "model_axis_collectives": collectives,
            "bytes_in_use_with_state": {d: m["bytes_in_use"]
                                        for d, m in live.items()},
            "peak_bytes_in_use": {d: m["peak_bytes_in_use"]
                                  for d, m in _memory_per_device().items()},
        }
    _require(scores[2].keys() == scores[1].keys(),
             "width 2 ran other jobs than width 1")
    rel = max(_rel(scores[2][j], scores[1][j]) for j in scores[1])
    out["width2"]["max_rel_diff_vs_width1"] = rel
    _require(rel <= REL_TOL,
             f"width 2 disagrees with width 1: {scores[2]} vs {scores[1]}")
    _require(out["width2"]["model_axis_collectives"] > 0,
             "width 2 lowered no model-axis collective")
    return out


# -- entry point ----------------------------------------------------------------------
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: phases A and B; 4: the sharded width-1 vs "
                        "width-2 comparison only")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _install_setup_clock()
    if args.chips == 4:
        phases = [lambda: phase_four_chips(published_geometry(lanes=4),
                                           args.seed)]
    else:
        geo = published_geometry(lanes=2)
        phases = [lambda: phase_batch(geo, args.seed),
                  lambda: phase_stream(geo, args.seed)]
    try:
        for phase in phases:
            r = phase()
            print(json.dumps(dict(r, compile_cache=cache_dir)), flush=True)
            _require(r.get("tpu_custom_call", True),
                     f"phase {r['phase']}: no Pallas kernel in the step")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
