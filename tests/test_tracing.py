"""The program's span recorder (``repro.core.tracing``): off unless a
profiler session collects or ``enable()`` was called; parents, threads,
attributes and a bounded buffer; every span on the profiler's own timeline;
and a streaming flight whose scores and counts do not move when it is on."""
import glob
import threading
import time

import numpy as np
import pytest

from repro.core import tracing
from repro.core.basic_config import BasicConfig
from repro.core.job import Job
from repro.core.proposer.early_stop import InFlightSuccessiveHalving
from repro.core.resource.vectorized import LaneScheduler
from repro.launch.hpo import PopulationTrial

ARCH, SEQ, BATCH, LANES, CHUNK = "starcoder2-3b", 16, 2, 2, 4
SPACE = [{"name": "learning_rate", "type": "float", "range": [3e-4, 4e-3]}]


@pytest.fixture(autouse=True)
def _recorder_off():
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


def _ladder(n=6):
    """Budgets of 2/4/8 steps with rung boundaries at 2 and 4, so lanes end
    at, pass through and are cut at a boundary, and lanes refill."""
    lrs = np.geomspace(3e-4, 4e-3, n)
    units = ([1, 2, 4] * n)[:n]
    return [{"learning_rate": float(lr), "stream": i, "n_iterations": u,
             "job_id": i} for i, (lr, u) in enumerate(zip(lrs, units))]


def _flight(cfgs):
    """A streaming ASHA flight on K=2 lanes with fused 4-step scans, fed
    through the Algorithm-1 lane scheduler."""
    trial = PopulationTrial(
        ARCH, steps=2, batch=BATCH, seq=SEQ, seed=0, population=LANES,
        early_stop=InFlightSuccessiveHalving(eta=2.0, min_iter=2, max_iter=8),
        refill_idle_grace_s=0.0, chunk_steps=CHUNK)
    scores = {}
    sch = LaneScheduler()
    for c in cfgs:
        job = Job(c["job_id"], BasicConfig(**c), f"slot{c['job_id']}",
                  lambda j: scores.__setitem__(j.job_id, j.result.score))
        sch.offer(job)
    trial.run_population([], scheduler=sch)
    assert len(scores) == len(cfgs), "every offered job must stream a result"
    return trial, [scores[c["job_id"]] for c in cfgs]


def test_recorder_is_off_without_a_profiler_or_enable():
    from repro.core.experiment import Experiment

    trial = PopulationTrial(ARCH, steps=2, batch=BATCH, seq=SEQ, seed=0,
                            population=LANES, refill_idle_grace_s=0.0,
                            chunk_steps=CHUNK)
    exp = Experiment({"proposer": "random", "parameter_config": SPACE,
                      "n_samples": 4, "n_parallel": LANES, "seed": 1,
                      "resource": "vectorized", "lane_refill": True}, trial)
    exp.run()
    assert trial.n_train_steps > 0
    assert not tracing.on()
    assert tracing.spans() == []


def test_parents_threads_attributes_and_the_bound():
    rec = tracing.Recorder(max_spans=4)
    with rec.span("outer", job_id=3):
        pass
    assert rec.spans() == []          # off: nothing recorded
    rec.enable()
    with rec.span("outer", job_id=3):
        with rec.span("inner", op="splice"):
            pass
        queued = rec.begin("cross")

    def other():
        rec.record("cross", queued, time.time(), job_id=5)

    t = threading.Thread(target=other, name="flight-worker")
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    by = {s.name: s for s in rec.spans()}
    assert by["outer"].parent is None and by["outer"].attrs == {"job_id": 3}
    assert by["inner"].parent == by["outer"].id
    assert by["inner"].attrs == {"op": "splice"}
    assert by["outer"].start <= by["inner"].start <= by["inner"].end \
        <= by["outer"].end
    # an interval begun inside "outer" and closed on another thread
    assert by["cross"].parent == by["outer"].id
    assert by["cross"].thread == "flight-worker"
    assert by["cross"].attrs == {"job_id": 5}
    assert by["cross"].start >= by["outer"].start
    # the window cut: spans that start in [t0, t1)
    assert [s.name for s in rec.spans(t0=by["inner"].start)] == ["inner",
                                                               "cross"]
    assert rec.spans("outer", t1=by["outer"].start) == []
    # bounded: past max_spans the oldest go
    for i in range(6):
        rec.record(f"s{i}", float(i), float(i) + 0.5)
    assert [s.name for s in rec.spans()] == ["s2", "s3", "s4", "s5"]
    rec.clear()
    assert rec.spans() == []


def test_every_span_lands_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.on()
        with tracing.span("t.outer", job_id=1):
            time.sleep(0.02)
            with tracing.span("t.inner", lane=0):
                time.sleep(0.01)
        begun = tracing.begin("t.cross")

        def other():
            time.sleep(0.015)
            tracing.record("t.cross", begun, time.time(), job_id=2)

        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.on()
    mine = sorted((s for s in tracing.spans() if s.name.startswith("t.")),
                  key=lambda s: s.start)
    assert [s.name for s in mine] == ["t.outer", "t.inner", "t.cross"]
    path = sorted(glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")))
    events = {}
    for plane in ProfileData.from_file(path[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("t."):
                    events[ev.name] = (ev.start_ns * 1e-9,
                                       ev.duration_ns * 1e-9,
                                       dict(ev.stats))
    assert set(events) == {s.name for s in mine}
    first_trace = events[mine[0].name][0]
    for s in mine:
        start, dur, stats = events[s.name]
        assert abs(dur - s.seconds) < 1e-3, (s.name, dur, s.seconds)
        assert abs((start - first_trace) - (s.start - mine[0].start)) < 1e-3
        for k, v in s.attrs.items():
            assert stats.get(k) == v, (s.name, stats)


def test_a_traced_flight_computes_what_an_untraced_one_does():
    cfgs = _ladder()
    tracing.enable()
    t0 = time.time()
    traced, traced_scores = _flight(cfgs)
    tracing.disable()
    spans = tracing.spans(t0=t0)
    tracing.clear()
    plain, plain_scores = _flight(cfgs)
    assert tracing.spans() == []
    assert traced_scores == plain_scores
    assert (traced.n_dispatches, traced.n_train_steps) \
        == (plain.n_dispatches, plain.n_train_steps)
    assert (traced.n_lane_steps, traced.n_live_lane_steps) \
        == (plain.n_lane_steps, plain.n_live_lane_steps)

    names = {s.name for s in spans}
    for name in ("flight.sync", "flight.event", "flight.rung_rule",
                 "flight.retire", "flight.lease", "flight.lane_op",
                 "flight.restack", "flight.dispatch", "trial.queued"):
        assert name in names, name
    dispatch = [s for s in spans if s.name == "flight.dispatch"]
    assert sum(s.attrs["steps"] for s in dispatch) == traced.n_train_steps
    assert all(0 <= s.attrs["live"] <= s.attrs["lanes"] == LANES
               for s in dispatch)
    assert {s.attrs["program"] for s in dispatch} == {"scan"}
    lane_steps = sum(s.attrs["steps"] * s.attrs["lanes"] for s in dispatch)
    live_steps = sum(s.attrs["steps"] * s.attrs["live"] for s in dispatch)
    assert (lane_steps, live_steps) == (traced.n_lane_steps,
                                        traced.n_live_lane_steps)
    assert 0 < live_steps < lane_steps   # lanes idle once the feed drains
    queued = [s.attrs["job_id"] for s in spans if s.name == "trial.queued"]
    assert sorted(queued) == [c["job_id"] for c in cfgs]
    leased = [s.attrs["job_id"] for s in spans if s.name == "flight.lease"]
    retired = [s.attrs["job_id"] for s in spans if s.name == "flight.retire"]
    assert sorted(leased) == sorted(retired) == sorted(queued)
    events = [s for s in spans if s.name == "flight.event"]
    assert sum(s.attrs["retired"] for s in events) <= len(cfgs)
    assert all(s.end >= s.start for s in spans)
