"""``p95_ms``: 95th percentile, over every unit of the window (a client
batch of the store, a decode step's page-table work), of its wall time
to completion, in milliseconds (host clock)."""
import numpy as np


def read(run: dict):
    lat = run["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if len(lat) else None
