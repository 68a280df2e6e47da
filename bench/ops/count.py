"""``SummaryFrame.count()`` of the last frame, checked against the join
size."""

LIMITS = {"count_gap": 0}


def run(ctx, step, rec):
    ctx.answer(rec, "count", {}, lambda f: f.count())


def reference(join, params, dtype):
    return join.total(join.ones(), dtype)


def gap(got, want):
    return abs(int(got) - int(want))
