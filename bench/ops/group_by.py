"""``SummaryFrame.group_by([key], n="count", s=("sum", value))``.

Checked per group against the reference's grouped count and sum; the
distance sums both columns' differences over the union of the groups."""

import numpy as np

LIMITS = {"group_by_gap": 0}


def run(ctx, step, rec):
    key, value = ctx.role(step["key"]), ctx.role(step["value"])
    ctx.answer(rec, "group_by", {"key": key, "value": value},
               lambda f: f.group_by([key], n="count", s=("sum", value)))


def _align(keys, vals, want) -> np.ndarray:
    """``vals`` by ``keys``, at the positions of ``want`` (0 where absent)."""
    out = np.zeros(len(want), vals.dtype)
    pos = np.searchsorted(keys, want)
    ok = pos < len(keys)
    ok[ok] = keys[pos[ok]] == want[ok]
    out[ok] = vals[pos[ok]]
    return out


def reference(join, params, dtype):
    key = params["key"]
    k, n = join.grouped(key, join.ones(), dtype)
    ks, s = join.grouped(key, join.value_weights(params["value"]), dtype)
    return {key: k, "n": n, "s": _align(ks, s, k)}


def gap(got, want):
    key = next(k for k in want if k not in ("n", "s"))
    gk, wk = np.asarray(got[key]), np.asarray(want[key])
    union = np.union1d(gk, wk)
    out = 0
    for col in ("n", "s"):
        g = _align(gk, np.asarray(got[col]).astype(np.int64), union)
        w = _align(wk, np.asarray(want[col]).astype(np.int64), union)
        out += int(np.abs(g - w).sum())
    return out
