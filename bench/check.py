"""Whether a run's answers are right: each one against the plain reference.

Nothing here knows an operation or the shape of a query.  An operation
``bench/ops/<op>.py`` whose output is checked says how, in its own file:

* an answer (a ``SummaryFrame`` call, kept by ``Context.answer``):
  ``reference(join, params, dtype)`` is the reference's answer and
  ``gap(got, want)`` its distance; the run's ``<op>_gap`` is the largest
  over every answer of that kind in the window;
* a whole result (a built summary, rows read back): ``check(join, records,
  kept, salt, control)`` returns named distances, from what ``run`` kept
  in ``ctx.kept[<op>]``.

Each such operation gives the limit of each of its numbers in ``LIMITS``.
Every distance is 0 when the answer is exact; the configurations state
exact answers, so every limit is 0.  ``failed_requests`` counts the
requests that raised.

``join`` is the configuration's reference (see ``reference.py``).  With
``control`` the program's answers are replaced by the reference's own in
the next lower precision (``CONTROL_DTYPE``), which must come out not
correct.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

import numpy as np

LIMITS = {"failed_requests": 0}
EXACT_DTYPE = np.int64
CONTROL_DTYPE = np.float32


def limits(ops: Mapping[str, object]) -> Dict[str, int]:
    """Every number's limit: this module's and each operation's own."""
    out = dict(LIMITS)
    for mod in ops.values():
        out.update(getattr(mod, "LIMITS", {}))
    return out


def compare(records: List[dict], ops: Mapping[str, object], join, salt: int,
            kept: Mapping[str, object], *,
            control: bool = False) -> Dict[str, int]:
    """Every number the run is judged by (see the module docstring).

    ``ops`` are the operation modules the run used, by name.
    """
    gaps: Dict[str, int] = {"failed_requests": sum(
        1 for r in records if r.get("error"))}
    memo: Dict[tuple, object] = {}

    def want(op: str, params: dict, dtype):
        key = (op, tuple(sorted(params.items())), np.dtype(dtype).name)
        if key not in memo:
            memo[key] = ops[op].reference(join, params, dtype)
        return memo[key]

    for rec in records:
        for op, params, got in rec.get("answers", ()):
            if control:
                got = want(op, params, CONTROL_DTYPE)
            name = f"{op}_gap"
            gaps[name] = max(gaps.get(name, 0), ops[op].gap(
                got, want(op, params, EXACT_DTYPE)))
    for op, mod in sorted(ops.items()):
        if hasattr(mod, "check"):
            for name, value in mod.check(join, records, kept.get(op), salt,
                                         control).items():
                gaps[name] = max(gaps.get(name, 0), value)
    return gaps
