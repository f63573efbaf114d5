"""``traversal_lane_use``: percent of the lock-step lane slots of the
served read path's traversal loop (trips times keys, per call) in which a
lane was still searching, in the window: the slowest lane of a batch sets
its trips (the program's counters, ``repro.obs``)."""
from bench import counters


def read(run: dict):
    c = counters.of_window()
    return 100.0 * c["lane_steps"] / c["lane_slots"] if c else None
