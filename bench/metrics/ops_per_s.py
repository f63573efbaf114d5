"""``ops_per_s``: index operations completed in the window over the
window's wall time (host clock; the window ends with the last unit that
started inside it)."""


def read(run: dict):
    return run["ops"] / run["window_s"] if run["window_s"] > 0 else None
