"""The filtered sum's segment sums' share of the HBM roofline, in percent.

The bytes counted as ``segsum_roofline.agg`` counts them (20 B per entry,
8 B per output segment, unpadded), for what a ``filter_sum`` request needs:

* the filter's propagation, once a request: one segment sum of the deepest
  level's runs into each shallower level's runs;
* each ``sum(v)`` on the filtered frame: one pass over v's level;
* ``count()``: none (the root level's weights, summed on the host).

Divided by the device time of the same programs in the traced window.
"""

from bench.harness import HERE, load_module
from bench.trace_reduce import roofline_share

AGG = load_module(HERE / "metrics" / "segsum_roofline.agg.py")


def propagation_bytes(run) -> int:
    *upper, deep = run.summary_levels
    return sum(20 * deep["runs"] + 8 * lvl["runs"] for lvl in upper)


def needed_bytes(run, record) -> int:
    nbytes, bands = 0, set()
    for op, params, _ in record.get("answers", ()):
        if op != "filter_sum":
            continue
        band = (params["key"], params["lo"], params["hi"])
        if band not in bands:
            bands.add(band)
            nbytes += propagation_bytes(run)
        if params["value"] is not None:
            nbytes += AGG.needed_bytes(run, "sum", {"var": params["value"]},
                                       None)
    return nbytes


def read(run):
    if run.trace is None:
        return None
    nbytes = sum(needed_bytes(run, r) for r in run.records)
    return roofline_share(run.trace.program_seconds(AGG.PROGRAMS), run.peaks,
                          nbytes=nbytes)
