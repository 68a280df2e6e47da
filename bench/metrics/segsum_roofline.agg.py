"""Segment sums' share of the HBM roofline, in percent.

The bytes a weighted segment sum must move, whatever program does it: per
entry a 4 B segment id, an 8 B value and an 8 B weight, and 8 B per output
segment, over unpadded sizes.  The entries each request needs:

* ``count``: none (the root level's weights, summed on the host);
* ``sum(v)``: one pass over v's level;
* ``group_by([k], count, sum v)``: two passes (count and sum) over the
  deeper of k's and v's levels, one output per group.

Divided by the device time of the programs below in the traced window.
"""

from bench.readers import level_of
from bench.trace_reduce import roofline_share

PROGRAMS = ["jit__segsum_padded", "jit_mul_segsum"]


def needed_bytes(run, op, params, answer):
    if op == "sum":
        return 20 * level_of(run, params["var"])["runs"] + 8
    if op == "group_by":
        n = max(level_of(run, params["key"])["runs"],
                level_of(run, params["value"])["runs"])
        groups = len(answer[params["key"]])
        return 2 * (20 * n + 8 * groups)
    return 0


def read(run):
    if run.trace is None:
        return None
    nbytes = sum(needed_bytes(run, *a) for r in run.records
                 for a in r.get("answers", ()))
    return roofline_share(run.trace.program_seconds(PROGRAMS), run.peaks,
                          nbytes=nbytes)
