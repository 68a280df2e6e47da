"""``JoinServer.frame``: the summary, from the cache or built.

Checked: the last summary built in the window, expanded plainly run by
run, against the reference's join size, column sums and row fingerprint.
"""

import time

import numpy as np

from bench.reference import (quantity_gaps, reference_quantities,
                             row_quantities)

LIMITS = {"rows_gap": 0, "colsum_gap": 0, "fingerprint_mismatch": 0}


def run(ctx, step, rec):
    t0 = time.perf_counter()
    reply = ctx.server.frame(ctx.query)
    rec["frame_s"] = time.perf_counter() - t0
    rec["source"] = reply.source
    rec["timings"] = dict(reply.timings)
    ctx.reply = reply
    if reply.source == "computed":
        ctx.kept["frame"] = reply.frame.gfjs


def expand_summary(gfjs):
    """A summary's rows, run by run, in raw values: each level's runs
    repeated by their lengths (the GFJS definition, nothing else)."""
    out = {}
    for lvl in gfjs.levels:
        for v in lvl.vars:
            out[v] = gfjs.domains[v].values[np.repeat(lvl.key_cols[v],
                                                      lvl.freq)]
    return out


def check(join, records, gfjs, salt, control):
    if gfjs is None:
        return {}
    got = (reference_quantities(join, salt, control=True) if control
           else row_quantities(expand_summary(gfjs), join.occs, salt))
    return quantity_gaps(got, reference_quantities(join, salt))
