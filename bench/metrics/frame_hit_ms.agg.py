"""Median wall time of ``JoinServer.frame`` answered from the cache, timed
by the benchmark around the call."""

from bench.readers import median_of


def read(run):
    return median_of(run.records, "frame_s", 1e3,
                     where=lambda r: r.get("source") == "memory")
