"""``routed_lane_balance``: over the mesh read calls of the traced window,
the most lanes one chip received in each call times the chip count, summed,
over the lanes the calls routed (the program's counters ``routed_max`` and
``routed_lanes``, ``repro.obs``): 1.0 when every call split its lanes
evenly over the chips, the chip count when every lane went to one chip.
``None`` where the program has no such counters or routed nothing."""


def read(run: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    if not hasattr(obs, "ROUTED"):
        return None
    c = obs.snapshot(obs.ROUTED)
    return c["routed_max"] / c["routed_lanes"] if c["routed_lanes"] else None
