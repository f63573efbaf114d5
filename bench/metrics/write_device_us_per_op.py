"""``write_device_us_per_op``: device time inside the write calls' spans
(store ``ingest``, page-table ``alloc`` and ``release``) over the keys
they wrote, in microseconds (profiler trace)."""


def read(run: dict):
    t = run["trace"]
    k = (t or {}).get("kinds", {}).get("write")
    if not k or not k["ops"]:
        return None
    return 1e6 * k["device_s"] / k["ops"]
