"""``SummaryFrame.sum(var)``; ``var`` names a role of the configuration.
Checked against the column's sum over the join."""

LIMITS = {"sum_gap": 0}


def run(ctx, step, rec):
    var = ctx.role(step["var"])
    ctx.answer(rec, "sum", {"var": var}, lambda f: f.sum(var))


def reference(join, params, dtype):
    return join.total(join.value_weights(params["var"]), dtype)


def gap(got, want):
    return abs(int(got) - int(want))
