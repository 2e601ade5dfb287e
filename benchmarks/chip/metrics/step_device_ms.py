"""Device milliseconds of the population step and fused-scan programs per
population step advanced, from the trace's program executions."""

PROGRAMS = ("jit_pop_step", "jit_scan_chunk")


def read(run):
    red, steps = run["trace"], run["counters"]["train_steps"]
    if red is None or not steps:
        return None
    t = sum(s for name, (_, s) in red["modules"].items()
            if name.split("(")[0] in PROGRAMS)
    return 1e3 * t / steps if t > 0 else None
