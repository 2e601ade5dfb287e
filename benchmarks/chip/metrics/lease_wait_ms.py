"""Median host milliseconds a proposal waited between its offer to the
streaming flight and its lease into a lane (the program's ``trial.queued``
spans that start in the window).  Nothing to read where the program keeps no
spans or the cell streams no proposals."""

import statistics


def read(run):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.spans("trial.queued", run["t0"], run["t1"])
    if not spans:
        return None
    return 1e3 * statistics.median(s.end - s.start for s in spans)
