"""``gathers_per_lookup``: dependent gathers of the served read path's
traversal loop (two a lane-step without foresight) over the keys it
looked up, in the window (the program's counters, ``repro.obs``)."""
from bench import counters


def read(run: dict):
    c = counters.of_window()
    return c["gathers"] / c["keys"] if c else None
