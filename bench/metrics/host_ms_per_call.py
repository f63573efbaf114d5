"""``host_ms_per_call``: mean over the entry-point calls of the window of
the call's wall time less the device's busy time inside it, in
milliseconds: what the host spends per call in dispatch, compilation,
copies and its own bookkeeping (profiler trace)."""


def read(run: dict):
    t = run["trace"]
    kinds = [(t or {}).get("kinds", {}).get(k) for k in ("read", "write")]
    calls = sum(k["calls"] for k in kinds if k)
    if not calls:
        return None
    host = sum(k["wall_s"] - k["device_s"] for k in kinds if k)
    return 1e3 * host / calls
