"""The per-layer metrics read from the program's own spans
(``repro.core.tracing``): each reader on hand-built spans, the window cut,
nothing to read without spans, and nothing to read from a program that
keeps no recorder."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import spec as S  # noqa: E402
from repro.core import tracing  # noqa: E402

T0, T1 = 100.0, 120.0
RUN = {"t0": T0, "t1": T1}
READERS = ("lane_occupancy", "lease_wait_ms", "event_pass_ms")


@pytest.fixture
def recorder():
    tracing.clear()
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.clear()


def _dispatch(start, steps, live, lanes=2):
    tracing.record("flight.dispatch", start, start + 0.001, steps=steps,
                   lanes=lanes, live=live, program="scan")


def test_lane_occupancy_weighs_live_lanes_by_steps(recorder):
    _dispatch(T0 - 1, 4, 0)        # before the window: left out
    _dispatch(T0, 4, 2)
    _dispatch(T0 + 1, 4, 1)
    _dispatch(T0 + 2, 2, 2)
    _dispatch(T1, 4, 0)            # at the close: left out
    got = S.metric_reader("lane_occupancy")(RUN)
    assert got == pytest.approx(100.0 * (8 + 4 + 4) / (8 + 8 + 4))


def test_lease_wait_is_the_median_queued_span(recorder):
    for start, wait in ((T0 - 5, 9.0), (T0, 0.010), (T0 + 1, 0.300),
                        (T0 + 2, 0.020), (T1 + 1, 9.0)):
        tracing.record("trial.queued", start, start + wait, job_id=1)
    assert S.metric_reader("lease_wait_ms")(RUN) == pytest.approx(20.0)


def test_event_pass_is_the_mean_event_span(recorder):
    for start, dur in ((T0 - 1, 5.0), (T0 + 3, 0.004), (T0 + 4, 0.008),
                       (T1, 5.0)):
        tracing.record("flight.event", start, start + dur, step=4,
                       retired=1, leased=1)
    assert S.metric_reader("event_pass_ms")(RUN) == pytest.approx(6.0)


def test_readers_find_nothing_without_spans(recorder):
    _dispatch(T0 - 1, 4, 2)
    tracing.record("trial.queued", T1 + 1, T1 + 2, job_id=1)
    for name in READERS:
        assert S.metric_reader(name)(RUN) is None, name


def test_readers_find_nothing_in_a_program_without_the_recorder(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    for name in READERS:
        assert S.metric_reader(name)(RUN) is None, name
