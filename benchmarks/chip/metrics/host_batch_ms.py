"""Mean host milliseconds of one population batch built on the host
(``make_population_batch``) inside the window."""


def read(run):
    spans = run["spans"].within("host_batch", run["t0"], run["t1"])
    return 1e3 * sum(spans) / len(spans) if spans else None
