"""Mean wall time of the request's ``SummaryFrame`` call, timed by the
benchmark around the call.  A mean, not a median: the mix's kinds differ
by tens of times, so a median lands on whichever kind sits in the middle."""

import statistics


def read(run):
    vals = [r["algebra_s"] * 1e3 for r in run.records if "algebra_s" in r]
    return statistics.fmean(vals) if vals else None
