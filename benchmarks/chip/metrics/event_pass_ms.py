"""Mean host milliseconds of one event pass of the streaming flight: from
the device results of an event step (or the end of an empty flight's
linger) to the enqueue of the next training dispatch (the program's
``flight.event`` spans that start in the window).  Nothing to read where the
program keeps no spans or the cell runs no streaming flight."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.spans("flight.event", run["t0"], run["t1"])
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)
