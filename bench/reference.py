"""The plain reference: join quantities computed straight from base tables.

Nothing here imports the program under test.  A configuration's tables are
plain ``{column: np.ndarray}`` dicts and its query a list of table
occurrences, each naming the variable of each of its columns.  For an
acyclic query (a path, a star, a chain of foreign keys, any query with a
join tree) :class:`JoinTree` gives every quantity the benchmark checks.
Each is a sum over join rows of a product of per-occurrence row weights,
and one message pass over the join tree gives it in O(table rows), never
O(join rows):

* the join size (all weights 1);
* the sum of one column over the join (that column's values at one
  occurrence that holds it);
* a grouped count or sum (contributions grouped at an occurrence holding
  the group variable);
* a multiset fingerprint, ``sum over rows of prod_i mix(occurrence i's
  values)`` mod 2**64: two row multisets that differ give different
  fingerprints except with probability about 2**-64 per difference.

An operation's own reference (``bench/ops/<op>.py``) builds its weights
from :meth:`JoinTree.col` and asks :meth:`JoinTree.total` or
:meth:`JoinTree.grouped`.  A configuration whose query has no join tree
(a cycle) gives its own reference instead: ``reference(tables, cfg)`` in
``bench/configs/<config>.py``, returning an object with the same
``occs``, ``col``, ``first_with``, ``ones``, ``value_weights``,
``hash_weights``, ``total`` and ``grouped``.

The program's side of each whole-result comparison is computed from the
rows it returned (:func:`row_quantities`), with the same mixing function.

``dtype`` selects the arithmetic: ``int64`` (exact), ``uint64`` (the
fingerprint's wrapping ring), or a lower precision for the control
(``float32`` sums, ``uint32`` fingerprints), which must read as wrong.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Tables = Dict[str, Dict[str, np.ndarray]]
Weights = List[Optional[np.ndarray]]

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_BLOCK = 1 << 16         # rows per block of a result's fingerprint


def mix(x: np.ndarray, tmp: np.ndarray) -> None:
    """splitmix64's finalizer over uint64, in place (``tmp`` is scratch)."""
    for shift, mult in ((30, 0xBF58476D1CE4E5B9), (27, 0x94D049BB133111EB),
                        (31, None)):
        np.right_shift(x, np.uint64(shift), out=tmp)
        np.bitwise_xor(x, tmp, out=x)
        if mult is not None:
            np.multiply(x, np.uint64(mult), out=x)


def row_hash(salt: int, cols: Sequence[np.ndarray]) -> np.ndarray:
    """One uint64 per row from its values (in the table's column order)."""
    n = len(cols[0])
    h = np.full(n, np.uint64(salt & 0xFFFFFFFFFFFFFFFF), np.uint64)
    tmp = np.empty(n, np.uint64)
    with np.errstate(over="ignore"):
        for c in cols:
            np.multiply(np.asarray(c).astype(np.int64, copy=False)
                        .view(np.uint64), _GOLDEN, out=tmp)
            np.bitwise_xor(h, tmp, out=h)
            mix(h, tmp)
    return h


@dataclass(frozen=True)
class Occurrence:
    """One table of the query: its column -> variable map, in column order."""

    table: str
    columns: Tuple[str, ...]
    variables: Tuple[str, ...]

    def var_column(self, var: str) -> str:
        return self.columns[self.variables.index(var)]


def occurrences(query: dict) -> List[Occurrence]:
    """The query's occurrences from its JSON form."""
    occs = [Occurrence(t, tuple(m), tuple(m.values()))
            for t, m in query["tables"]]
    for o in occs:
        if len(set(o.variables)) != len(o.variables):
            raise ValueError(f"{o.table} names a variable twice")
    return occs


def join_tree(occs: Sequence[Occurrence]) -> List[Tuple[int, int]]:
    """A join tree's edges: a maximum spanning tree of the occurrences by
    shared variables, which is a join tree exactly when the query is
    acyclic (each variable's occurrences then form a connected subtree)."""
    vs = [set(o.variables) for o in occs]
    inside, edges = {0}, []
    while len(inside) < len(occs):
        w, i, j = max((len(vs[i] & vs[j]), -i, -j) for i in inside
                      for j in range(len(occs)) if j not in inside)
        if w == 0:
            raise ValueError("the query is not connected")
        edges.append((-i, -j))
        inside.add(-j)
    for v in set().union(*vs):
        holders = sum(v in s for s in vs)
        linked = sum(v in vs[i] and v in vs[j] for i, j in edges)
        if linked != holders - 1:
            raise ValueError(
                f"the query is cyclic (variable {v!r}): it has no join tree, "
                "so its configuration gives its own reference(tables, cfg)")
    return edges


def _codes(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                  int]:
    """Dense codes of the key rows of ``a`` and ``b`` in one key space."""
    both = np.concatenate([a, b])
    if both.shape[1] == 1:
        uniq, inv = np.unique(both[:, 0], return_inverse=True)
    else:
        uniq, inv = np.unique(both, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    return inv[:len(a)], inv[len(a):], len(uniq)


class JoinTree:
    """Message passing over an acyclic query's join tree."""

    def __init__(self, tables: Tables, query: dict) -> None:
        self.tables = tables
        self.occs = occurrences(query)
        self.adj: List[List[int]] = [[] for _ in self.occs]
        self._links: Dict[Tuple[int, int], Tuple] = {}
        for i, j in join_tree(self.occs):
            self.adj[i].append(j)
            self.adj[j].append(i)

    def col(self, i: int, var: str) -> np.ndarray:
        occ = self.occs[i]
        return self.tables[occ.table][occ.var_column(var)]

    def size(self, i: int) -> int:
        return len(self.col(i, self.occs[i].variables[0]))

    def first_with(self, var: str) -> int:
        return next(i for i, o in enumerate(self.occs) if var in o.variables)

    def _keys(self, i: int, sep: Sequence[str]) -> np.ndarray:
        return np.stack([np.asarray(self.col(i, v)) for v in sep], axis=1)

    def _message(self, src: int, dst: int, weights: Weights,
                 dtype) -> np.ndarray:
        """Per row of ``dst``: the weighted count of partial join rows of
        the subtree behind ``src`` that agree with it."""
        w = self._weight(src, dst, weights, dtype)
        if (src, dst) not in self._links:     # the same for every weight
            sep = [v for v in self.occs[src].variables
                   if v in self.occs[dst].variables]
            self._links[src, dst] = _codes(self._keys(src, sep),
                                           self._keys(dst, sep))
        cs, cd, n = self._links[src, dst]
        sums = np.zeros(n, dtype)
        with np.errstate(over="ignore"):
            np.add.at(sums, cs, w)
        return sums[cd]

    def _weight(self, at: int, skip: Optional[int], weights: Weights,
                dtype) -> np.ndarray:
        """Occurrence ``at``'s own weight times the messages of every
        neighbour but ``skip``."""
        w = (np.ones(self.size(at), dtype) if weights[at] is None
             else np.asarray(weights[at]).astype(dtype))
        with np.errstate(over="ignore"):
            for j in self.adj[at]:
                if j != skip:
                    w = w * self._message(j, at, weights, dtype)
        return w

    def contributions(self, weights: Weights, at: int, dtype) -> np.ndarray:
        """Per row of occurrence ``at``: the weighted count of join rows
        through it."""
        return self._weight(at, None, weights, dtype)

    def total(self, weights: Weights, dtype):
        with np.errstate(over="ignore"):
            return self.contributions(weights, 0, dtype).sum(dtype=dtype)

    def grouped(self, var: str, weights: Weights,
                dtype) -> Tuple[np.ndarray, np.ndarray]:
        """(sorted group values, weighted total per group), empty groups
        left out."""
        at = self.first_with(var)
        row = self.contributions(weights, at, dtype)
        uniq, inv = np.unique(self.col(at, var), return_inverse=True)
        sums = np.zeros(len(uniq), dtype)
        with np.errstate(over="ignore"):
            np.add.at(sums, inv.reshape(-1), row)
        live = sums != 0
        return uniq[live], sums[live]

    # -- weights ------------------------------------------------------------
    def ones(self) -> Weights:
        return [None] * len(self.occs)

    def value_weights(self, var: str) -> Weights:
        """Weights whose total is the sum of ``var`` over the join."""
        ws = self.ones()
        i = self.first_with(var)
        ws[i] = self.col(i, var)
        return ws

    def hash_weights(self, salt: int) -> Weights:
        return [row_hash(salt + i, [self.tables[o.table][c]
                                    for c in o.columns])
                for i, o in enumerate(self.occs)]


@dataclass
class Quantities:
    """What the checks compare of a whole join result."""

    rows: int
    colsums: Dict[str, int]
    fingerprint: int


def reference_quantities(join, salt: int, *,
                         control: bool = False) -> Quantities:
    """Join size, column sums and fingerprint from the base tables.

    ``control`` computes them one precision lower (float32 sums, uint32
    fingerprint): the control that the comparison has to refuse.
    """
    num = np.float32 if control else np.int64
    ring = np.uint32 if control else np.uint64
    variables = sorted({v for o in join.occs for v in o.variables})
    return Quantities(
        rows=int(join.total(join.ones(), num)),
        colsums={v: int(join.total(join.value_weights(v), num))
                 for v in variables},
        fingerprint=int(join.total(join.hash_weights(salt), ring)))


def row_quantities(rows: Dict[str, np.ndarray], occs: Sequence[Occurrence],
                   salt: int) -> Quantities:
    """The same quantities from a materialized result (raw values), in
    blocks of rows so that the scratch arrays stay small.  Columns of
    different lengths are no result: their fingerprint is -1, which no
    reference has."""
    lengths = {len(c) for c in rows.values()}
    n = max(lengths, default=0)
    total = np.uint64(0)
    with np.errstate(over="ignore"):
        for lo in range(0, n if len(lengths) == 1 else 0, _BLOCK):
            fp = np.ones(min(_BLOCK, n - lo), np.uint64)
            for i, o in enumerate(occs):
                fp *= row_hash(salt + i, [rows[v][lo:lo + _BLOCK]
                                          for v in o.variables])
            total += fp.sum(dtype=np.uint64)
    return Quantities(
        rows=n,
        colsums={v: int(np.asarray(c).sum(dtype=np.int64))
                 for v, c in sorted(rows.items())},
        fingerprint=int(total) if len(lengths) <= 1 else -1)


def quantity_gaps(got: Quantities, want: Quantities) -> Dict[str, int]:
    """Distance of each quantity from the reference's (0 when exact)."""
    cols = set(got.colsums) | set(want.colsums)
    return {
        "rows_gap": abs(got.rows - want.rows),
        "colsum_gap": max((abs(got.colsums.get(v, 0) - want.colsums.get(v, 0))
                           for v in cols), default=0),
        "fingerprint_mismatch": int(got.fingerprint != want.fingerprint),
    }
