"""Host milliseconds spent in the proposer (drawing configs and taking
results) per trial whose result reached the experiment inside the window."""


def read(run):
    t0, t1 = run["t0"], run["t1"]
    done = [r for r in run["rows"] if r["ok"] and t0 <= r["end"] < t1]
    if not done:
        return None
    return 1e3 * sum(run["spans"].within("proposer", t0, t1)) / len(done)
