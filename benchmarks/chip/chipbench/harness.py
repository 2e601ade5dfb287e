"""One run of one cell: set-up, the measured window, the check, one line.

The window drives the system's normal entry: an ``Experiment`` over a
``PopulationTrial`` with the cell's proposer and resource manager, built as a
user of ``repro.launch.hpo`` builds it.  The benchmark's own wrappers stop
the proposer when the window closes (the job then drains, and only work done
inside the window counts), time the proposer and the host batch builder, and
annotate host spans for the trace.  No program file is changed.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from . import spec as S
from . import trace as TR

MODEL_KEYS = ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "activation", "tie_embeddings",
              "rope_theta", "norm_eps", "param_dtype", "compute_dtype")
TRACE_DIR = os.path.join(S.REPO_ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


class Spans:
    """Host-clock spans by name (wall seconds), and a trace annotation each."""

    def __init__(self):
        self.lock = threading.Lock()
        self.s: Dict[str, List[tuple]] = {}

    def wrap(self, name: str, fn):
        import jax

        label = TR.SPAN_PREFIX + name

        def wrapped(*a, **k):
            t = time.time()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*a, **k)
            with self.lock:
                self.s.setdefault(name, []).append((t, time.time()))
            return out

        return wrapped

    def within(self, name: str, t0: float, t1: float) -> List[float]:
        return [b - a for a, b in self.s.get(name, []) if t0 <= a < t1]


class CompileCounter:
    """JAX's compile duration events (tracing, lowering, and backend compiles
    or compile-cache reads), as ``chip_smoke.py`` sums them for set-up."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((time.time(), event, float(duration)))

    def seconds(self) -> float:
        return sum(d for _, _, d in self.events)

    def backend_between(self, t0: float, t1: float) -> int:
        """Backend compiles (or cache reads) that started in [t0, t1)."""
        return sum(1 for t, e, d in self.events
                   if e == self.EVENTS[2] and t0 <= t - d < t1)


# -- the system under test -------------------------------------------------------
def model_overrides(config: Dict[str, Any], control: bool) -> Dict[str, Any]:
    over = {k: config[k] for k in MODEL_KEYS}
    over["name"] = config["name"]
    if control:
        over.update(config["control"])
    return over


def build_trial(cell: S.Cell, seed: int, control: bool = False):
    from repro.launch.hpo import PopulationTrial

    tr = cell.traffic
    return PopulationTrial(
        cell.config["preset"], tr["steps_per_unit"], tr["batch"], tr["seq"],
        seed, population=tr["lanes"], chunk_steps=tr["chunk_steps"],
        model_parallel=tr.get("model_parallel", 1),
        model_overrides=model_overrides(cell.config, control))


def build_experiment(cell: S.Cell, trial, seed: int, n_samples: int):
    from repro.core.experiment import Experiment

    tr = cell.traffic
    cfg = {
        "proposer": tr["proposer"],
        "parameter_config": tr["space"],
        "n_samples": int(n_samples),
        "n_parallel": tr["lanes"],
        "target": "max",
        # a traffic file may fix the search's own seed, so that every run
        # proposes the same configurations while --seed draws the weights
        # and the data
        "seed": int(tr.get("search_seed", seed)),
        "resource": tr["resource"],
        # a failure must show as a failed job, not vanish into a retry
        "max_retries": 0,
        "max_flight_restarts": 0,
    }
    cfg.update(tr.get("proposer_args", {}))
    if tr.get("lane_refill"):
        cfg["lane_refill"] = True
    if tr.get("model_parallel", 1) > 1:
        cfg["model_parallel"] = tr["model_parallel"]
    exp = Experiment(cfg, trial)
    trial.early_stop = (exp.proposer.inflight_hook(
        steps_per_unit=tr["steps_per_unit"]) if tr.get("inflight_stop") else None)
    return exp


def close_at(proposer, deadline: float, spans: Optional[Spans]) -> None:
    """Stop proposing at ``deadline`` (wall clock): the job then drains its
    in-flight trials and ends.  Times each proposer call."""
    get, upd, fin = proposer.get_params, proposer.update, proposer.finished

    def get_params(k):
        return [] if time.time() >= deadline else get(k)

    proposer.get_params = spans.wrap("proposer", get_params) if spans else get_params
    proposer.update = spans.wrap("proposer", upd) if spans else upd
    proposer.finished = lambda: time.time() >= deadline or fin()


def _job_rows(exp, trial) -> List[Dict[str, Any]]:
    from repro.core.job import JobStatus

    rows = []
    for j in exp.job_log:
        res = j.result
        ok = (j.status == JobStatus.FINISHED and res is not None
              and res.score is not None and math.isfinite(res.score)
              and res.score > trial.DIVERGED_SCORE / 2)
        extra = res.extra if res is not None and isinstance(res.extra, dict) else {}
        cfg = dict(j.config)
        budget = trial._n_steps(cfg)
        rows.append({
            "job_id": int(j.job_id), "config": cfg, "ok": bool(ok),
            "start": j.start_time, "end": j.end_time,
            "budget": int(budget),
            "steps": int(extra.get("steps", budget)) if ok else 0,
            "score": float(res.score) if ok else None,
        })
    return rows


def _in_window(row, t0: float, t1: float) -> float:
    """Share of a trial's steps that ran inside [t0, t1): its steps are
    spread evenly over its lease (start) to its result (end)."""
    a, b = row["start"], row["end"]
    if a is None or b is None or b <= a:
        return 1.0 if (b is not None and t0 <= b < t1) else 0.0
    return max(0.0, min(b, t1) - max(a, t0)) / (b - a)


# -- one run ---------------------------------------------------------------------
def devices_for(cell: S.Cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
        if len(devs) < cell.chips:
            raise NoChip(f"cell {cell.name} needs {cell.chips} chips, "
                         f"found {len(devs)}")
    return devs[: cell.chips]


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             control: bool = False,
             cell: Optional[S.Cell] = None, log=sys.stderr) -> Dict[str, Any]:
    cell = cell or S.resolve(cell_name)
    import jax

    devs = devices_for(cell, require_chip)
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    compiles = CompileCounter()
    tr = cell.traffic
    spans = Spans()

    # set-up: the trial, its data, and every program of the cell's job
    # compiled or read from the cache by a short job of the same kind
    phases = {"start": time.time() - t_process}
    trial = build_trial(cell, seed, control)
    _, data = trial._setup()
    data.make_population_batch = spans.wrap("host_batch",
                                            data.make_population_batch)
    trial._drain_leases = spans.wrap("lease", trial._drain_leases)
    trial._hparams = spans.wrap("hparams", trial._hparams)
    warm = build_experiment(cell, trial, seed, tr["warmup_samples"])
    warm.run()
    warm_failed = sum(1 for r in _job_rows(warm, trial) if not r["ok"])
    del warm
    gc.collect()
    phases["warm_job"] = time.time() - t_process
    phases["compile_s"] = compiles.seconds()

    exp = build_experiment(cell, trial, seed, tr["n_samples"])
    if trial.early_stop is not None:
        trial.early_stop.observe = spans.wrap("rung_rule",
                                              trial.early_stop.observe)
    counters0 = (trial.n_dispatches, trial.n_train_steps)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python function tracing (it slows the host loops it watches);
        # the benchmark's own annotations and the device's ops are enough
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t0 = time.time()
    setup_s = t0 - t_process
    t1 = t0 + float(seconds)
    close_at(exp.proposer, t1, spans)
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        exp.run()
    t_end = time.time()
    if trace:
        jax.profiler.stop_trace()
    counters = {"dispatches": trial.n_dispatches - counters0[0],
                "train_steps": trial.n_train_steps - counters0[1]}
    rows = _job_rows(exp, trial)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    in_window_compiles = compiles.backend_between(t0, t_end)
    del exp
    gc.collect()

    seq_tokens = tr["batch"] * tr["seq"]
    lane_steps = sum(r["steps"] * _in_window(r, t0, t1) for r in rows)
    e2e = {"setup_s": setup_s, "tokens_per_s": lane_steps * seq_tokens / seconds}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    run = {
        "cell": cell, "seed": seed, "t0": t0, "t1": t1, "t_end": t_end,
        "seconds": float(seconds), "rows": rows, "counters": counters,
        "spans": spans, "device": device, "trace": None,
        "peaks": S.peaks(d0.device_kind) if require_chip else None,
    }
    out: Dict[str, Any] = {}
    if trace:
        path = TR.find_xplane(TRACE_DIR)
        names = [f"/device:TPU:{d.id}" for d in devs]
        tdata = TR.load(path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red = TR.reduce(tdata, [n for n in names if n in tdata["devices"]])
        run["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = TR.breakdown(red)
        metrics = {}
        for m in cell.per_layer:
            v = S.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the check: a sample of the window's trials replayed by the reference
    from . import compare

    t_check = time.time()
    checks = compare.check(cell, seed, rows, log=log)
    phases["check_s"] = time.time() - t_check
    phases["after_window_s"] = t_check - t_end
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"chipbench: {cell.name} seed {seed}: set-up {setup_s:.3f} s, "
          f"{len(rows)} trials ({sum(r['ok'] for r in rows)} ok), "
          f"{counters['train_steps']} population steps, "
          f"compiles in window {in_window_compiles}, warm-up failures "
          f"{warm_failed}, peak {peak} B, phases "
          f"{json.dumps({k: round(v, 3) for k, v in phases.items()})}",
          file=log)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=log)
    out = {
        "correct": bool(correct),
        "attempted": len(rows),
        "failed": sum(1 for r in rows if not r["ok"]),
        "metrics": metrics,
        "device": device,
        **out,
        "compiles_in_window": in_window_compiles,
        "checks": checks,
    }
    return out
