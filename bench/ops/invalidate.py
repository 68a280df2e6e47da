"""Drop every cached summary, plan and message of the query's tables, so
the next ``frame`` is a cold build from the base tables.

Checked: every request that invalidated was answered by a build, never
from a cache (``builds_not_computed``)."""

LIMITS = {"builds_not_computed": 0}


def run(ctx, step, rec):
    for table in sorted({qt.table for qt in ctx.query.tables}):
        ctx.service.invalidate(table)
    rec["cold"] = True


def check(join, records, kept, salt, control):
    cold = [r for r in records if r.get("cold") and "source" in r]
    if not cold:
        return {}
    return {"builds_not_computed": sum(r["source"] != "computed"
                                       for r in cold)}
