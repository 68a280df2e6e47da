"""90th percentile (nearest rank) of request latency, every request."""

import math


def read(run):
    lat = sorted((r["t1"] - r["t0"]) * 1e3 for r in run.records)
    if not lat:
        return None
    return lat[math.ceil(0.9 * len(lat)) - 1]
