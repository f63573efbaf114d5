"""``read_device_us_per_op``: device time inside the read calls' spans
(store ``get_batch``, page-table ``lookup``) over the keys they looked
up, in microseconds (profiler trace)."""


def read(run: dict):
    t = run["trace"]
    k = (t or {}).get("kinds", {}).get("read")
    if not k or not k["ops"]:
        return None
    return 1e6 * k["device_s"] / k["ops"]
