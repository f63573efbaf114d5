"""``hbm_bytes_per_key``: the device's ``peak_bytes_in_use`` at the end of
the window over the keys (or pages) live at that moment, as the traffic
counts them.  Read by the benchmark from the device's allocator."""


def read(run: dict):
    if run["peak_bytes"] <= 0 or run["live"] <= 0:
        return None
    return run["peak_bytes"] / run["live"]
