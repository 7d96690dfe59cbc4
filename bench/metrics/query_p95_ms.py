"""95th percentile (numpy's linear interpolation) of the latencies of every
TSBS query completed in the window."""
import numpy as np


def read(run):
    lat = run.counters.get("query_latency_ms")
    return float(np.percentile(lat, 95)) if lat else None
