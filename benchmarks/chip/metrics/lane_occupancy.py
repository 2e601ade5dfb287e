"""Share of the lane-steps the drivers dispatched in the window whose lane
held a trial with budget left, from the program's ``flight.dispatch`` spans
(steps x live over steps x lanes).  Nothing to read where the program keeps
no spans."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.spans("flight.dispatch", run["t0"], run["t1"])
    total = sum(s.attrs["steps"] * s.attrs["lanes"] for s in spans)
    if not total:
        return None
    live = sum(s.attrs["steps"] * s.attrs["live"] for s in spans)
    return 100.0 * live / total
