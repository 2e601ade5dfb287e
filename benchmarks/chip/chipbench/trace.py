"""Reduction of a profiler trace to device busy time, op time and gaps.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
a small plain form (the same form the tests keep as a recorded fixture):

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the events of a device's "XLA Ops" line (one per HLO op that
ran), ``modules`` those of its "XLA Modules" line (one per program
execution), and ``host`` the host spans this benchmark annotates
(``chipbench.*``).  ``reduce`` works on that form only, so it needs no
profiler and no chip.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
# ops that only hold other ops (their bodies show as ops of their own)
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def op_name(text: str) -> str:
    """An op's short name from the trace's HLO text
    (``%fusion.576 = f32[2,49152,3072]{2,1,0:T(8,128)} fusion(...)``):
    the instruction name and its result shape, without layouts
    (``fusion.576 f32[2,49152,3072]``)."""
    head, _, rest = text.partition(" = ")
    shape = rest.split(" ", 1)[0] if rest else ""
    if shape.startswith("("):
        shape = "(tuple)"
    while "{" in shape:
        a = shape.find("{")
        b = shape.find("}", a)
        shape = shape[:a] + shape[b + 1:] if b > a else shape[:a]
    return (head.lstrip("%") + " " + shape).strip()


def op_kind(name: str) -> str:
    """The instruction name without its number: ``rmsnorm_pallas.12 f32[..]``
    -> ``rmsnorm_pallas``."""
    base = name.split(" ", 1)[0]
    stem, dot, num = base.rpartition(".")
    return stem if dot and num.isdigit() else base


def load(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([op_name(ev.name), float(ev.start_ns),
                                     float(ev.duration_ns)])
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def window_bounds(trace: Dict[str, Any]) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _gap_label(gap: Tuple[float, float], spans) -> str:
    """The innermost (shortest) benchmark span open at the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    open_ = [(b - a, n) for n, a, b in spans if a <= mid <= b]
    return min(open_)[1][len(SPAN_PREFIX):] if open_ else "unattributed"


def reduce(trace: Dict[str, Any],
           devices: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Busy and idle time in the benchmark's window, op time by name (loops
    and calls left out: their bodies count), program executions by name,
    and the idle gaps by the host span open in them.  ``devices`` limits the reduction to the chips a cell uses
    (default: every device plane in the trace)."""
    w0, w1 = window_bounds(trace)
    names = sorted(devices or trace["devices"])
    busy, op_s, mod = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for i, dev in enumerate(names):
        d = trace["devices"][dev]
        iv = [(s, s + du) for _, s, du in d["ops"]]
        u = list(_clip(_union(iv), w0, w1))
        busy.append(sum(b - a for a, b in u) * 1e-9)
        for n, s, du in d["ops"]:
            if w0 <= s < w1 and op_kind(n) not in CONTAINERS:
                c, t = op_s.get(n, (0, 0.0))
                op_s[n] = (c + 1, t + du * 1e-9)
        for n, s, du in d["modules"]:
            if w0 <= s < w1:
                c, t = mod.get(n, (0, 0.0))
                mod[n] = (c + 1, t + du * 1e-9)
        if i == 0:
            edges = [w0] + [x for ab in u for x in ab] + [w1]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    n_dev = max(len(names), 1)
    host = [(n, s, s + d) for n, s, d in trace["host"]
            if n != WINDOW_SPAN]
    by_label: Dict[str, float] = {}
    for g in gaps:
        lab = _gap_label(g, host)
        by_label[lab] = by_label.get(lab, 0.0) + (g[1] - g[0]) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "ops": {k: (c / n_dev, t / n_dev) for k, (c, t) in op_s.items()},
        "modules": {k: (c / n_dev, t / n_dev) for k, (c, t) in mod.items()},
        "idle_by_span_s": by_label,
        "devices": names,
    }


def breakdown(red: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    ops = sorted(((n, t) for n, (_, t) in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_by_span_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
