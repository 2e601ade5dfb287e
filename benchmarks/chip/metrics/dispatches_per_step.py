"""Device dispatches the drivers issued per population step they advanced,
over the whole job (the program's own exact counters)."""


def read(run):
    c = run["counters"]
    return c["dispatches"] / c["train_steps"] if c["train_steps"] else None
