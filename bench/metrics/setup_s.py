"""``setup_s``: seconds from the start of the process's benchmark code to
the window: JAX's start-up, data made from the seed, the system built,
every shape of the cell's traffic warmed (and compiled, in a run whose
cache is cold)."""


def read(run: dict):
    return run["setup_s"]
