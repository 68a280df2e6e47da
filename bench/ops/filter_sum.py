"""``SummaryFrame.filter({key: lo <= v < hi})``, then ``sum(value)`` on the
filtered frame: a measure summed over a band of the key's values.  ``key``
and ``value`` name roles of the configuration; the band is ``share`` of the
key's distinct values wide (``hi - lo``) and starts at one of them, drawn
per request from the run's seed.

The filter masks the deepest level's weights and propagates them to every
shallower level; ``sum(value)`` reads only the level that holds ``value``.
So the same filtered frame also answers ``count()`` (the root level's
weights) and the sum of one variable of each other level above the
deepest (that level's weights, run by run): every level the propagation
wrote is read back, and the masked deepest weights through the level above
them, whose runs are their segment sums.  The filter runs once a request;
the other answers are cheap reads of it (the deepest level, the largest,
is summed only when it holds ``value``).

Each answer is checked against the reference's masked total over the join:
a 0/1 weight on the key (inside the band or not) times the summed
variable's own weight (none for the count), from the base tables."""

import numpy as np

from bench.reference import occurrences

LIMITS = {"filter_sum_gap": 0}


def key_values(cfg, tables, var):
    """The sorted distinct values of ``var`` in the base tables."""
    return np.unique(np.concatenate([
        tables[o.table][o.var_column(var)]
        for o in occurrences(cfg["query"]) if var in o.variables]))


def level_vars(frame, value):
    """One variable of each level above the deepest that does not hold
    ``value``."""
    return [lvl.vars[0] for lvl in frame.gfjs.levels[:-1]
            if value not in lvl.vars]


def run(ctx, step, rec):
    key, value = ctx.role(step["key"]), ctx.role(step["value"])
    values = key_values(ctx.cfg, ctx.tables, key)
    width = max(1, int(step["share"] * len(values)))
    lo = int(values[ctx.rng.integers(0, len(values) - width + 1)])
    hi = lo + width
    band = {"key": key, "lo": lo, "hi": hi}
    kept = {}

    def filtered(f):
        kept["frame"] = f.filter({key: lambda v: (v >= lo) & (v < hi)})
        return kept["frame"].sum(value)

    ctx.answer(rec, "filter_sum", {**band, "value": value}, filtered)
    ctx.answer(rec, "filter_sum", {**band, "value": None},
               lambda f: kept["frame"].count())
    for var in level_vars(kept["frame"], value):
        ctx.answer(rec, "filter_sum", {**band, "value": var},
                   lambda f, v=var: kept["frame"].sum(v))


def reference(join, params, dtype):
    ws = join.ones() if params["value"] is None \
        else join.value_weights(params["value"])
    i = join.first_with(params["key"])
    v = join.col(i, params["key"])
    band = ((v >= params["lo"]) & (v < params["hi"])).astype(np.int64)
    ws[i] = band if ws[i] is None else band * ws[i]
    return join.total(ws, dtype)


def gap(got, want):
    return abs(int(got) - int(want))
