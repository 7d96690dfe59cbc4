"""Median latency of the TSBS queries completed in the window; one query is
all of its engine calls."""
import numpy as np


def read(run):
    lat = run.counters.get("query_latency_ms")
    return float(np.percentile(lat, 50)) if lat else None
