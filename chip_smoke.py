#!/usr/bin/env python3
"""Bring-up smoke run of the join engine's served path on one TPU chip.

    python3 chip_smoke.py [--seed N]

Builds the seeded Last.FM-like catalog (``relational/synth.py::lastfm_like``)
at the published size of the HetRec 2011 Last.FM dataset and drives the
paper's lastFM A1 query through the entry points a user calls:

* ``JoinServer(JoinService(catalog, incremental=False))`` — a cold
  ``frame`` (GFJS generated on the device) and its ``count()``, a warm
  ``frame`` answered from the cache, a ``lookup`` of a few ``U1`` keys with
  ``count`` and ``sum``, and a scalar ``sum``;
* ``GraphicalJoin.desummarize`` — the full join expanded on the device.

Every answer is checked against the numpy engine on the same data (the
GFJS level for level, the expanded rows exactly, the aggregates from those
rows), and one small query against ``core/oracle.py``.  Earlier lines give
per-phase wall times, the plan's backends and the host-fallback counts
(which must be zero).  The last line is one JSON object naming the device::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero, without that line, when JAX finds no TPU, when
the ``repro`` package is not beside it, or when any check fails.  The
timings are a bring-up record, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: HetRec 2011 Last.FM: 1,892 users, 17,632 artists, ~92.8k user-artist
#: rows, 12,717 friend pairs (artists_per_user * n_users ~= 92.8k draws)
LASTFM = dict(n_users=1892, n_artists=17632, artists_per_user=49,
              friends_per_user=7)
#: small enough for the row-by-row oracle join
ORACLE = dict(n_users=40, n_artists=30, artists_per_user=3,
              friends_per_user=2)
LOOKUP_KEYS = (0, 1, 7, 1891, -1)          # -1 is no user: a zero row


class CheckFailed(AssertionError):
    """An answer of the device engine differs from the reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


@contextlib.contextmanager
def phase(name: str, walls: dict):
    """Wall time of one phase; callers end it on host data (synced)."""
    t0 = time.perf_counter()
    yield
    walls[name] = time.perf_counter() - t0
    print(f"phase {name}: {walls[name]!r} s", flush=True)


def gfjs_equal(a, b) -> bool:
    import numpy as np
    return (a.join_size == b.join_size
            and list(a.column_order) == list(b.column_order)
            and len(a.levels) == len(b.levels)
            and all(la.vars == lb.vars
                    and np.array_equal(la.freq, lb.freq)
                    and all(np.array_equal(la.key_cols[v], lb.key_cols[v])
                            for v in la.vars)
                    for la, lb in zip(a.levels, b.levels)))


def run(seed: int = 0, sizes: dict = LASTFM) -> dict:
    """Every phase and check; returns the phase wall times."""
    import numpy as np
    from repro import device
    from repro.core.api import GraphicalJoin
    from repro.core.gfjs import desummarize
    from repro.core.oracle import oracle_join, sort_rows
    from repro.relational.encoding import encode_query
    from repro.relational.synth import lastfm_like
    from repro.serve.server import JoinServer
    from repro.summary.service import JoinService

    walls: dict = {}
    device_engine = {"summarize": "jax", "desummarize": "jax"}
    with phase("data", walls):
        cat, queries = lastfm_like(**sizes, seed=seed)
        q = queries["lastfm_A1"]
    print(f"catalog: user_artists={cat['user_artists'].num_rows} rows, "
          f"user_friends={cat['user_friends'].num_rows} rows")

    # -- the served path ---------------------------------------------------
    with phase("service", walls):
        server = JoinServer(JoinService(cat, incremental=False))
    with phase("plan", walls):
        plan = server.service.compile(q)
    print("plan backends: " + ", ".join(
        f"{k}={v}" for k, v in sorted(plan.backends.items())))
    check(plan.backends == device_engine,
          f"served plan backends {plan.backends}, want {device_engine}")
    with phase("cold_frame", walls):
        cold = server.frame(q, plan=plan)
        count = cold.frame.count()
    check(cold.source == "computed", f"cold frame came from {cold.source}")
    print(f"join size: {count}")
    print(f"cold frame phases (s): {dict(cold.timings)}")
    with phase("warm_frame", walls):
        warm = server.frame(q, plan=plan)
    check(warm.source == "memory", f"warm frame came from {warm.source}")
    keys = np.asarray(LOOKUP_KEYS)
    with phase("lookup", walls):
        probe = server.lookup(q, "U1", keys, {"n": "count",
                                              "s": ("sum", "A2")}, plan=plan)
    with phase("sum", walls):
        total = server.frame(q, plan=plan).frame.sum("A2")

    # -- device desummarize through the library entry point ----------------
    gj = GraphicalJoin(cat, q)
    with phase("device_build", walls):
        g_dev = gj.run()
    check(gj.plan().backends == device_engine,
          f"GraphicalJoin plan backends {gj.plan().backends}")
    with phase("desummarize", walls):
        rows_dev = gj.desummarize(g_dev, decode=False)
    print(f"device build phases (s): {dict(gj.timings)}")

    # -- the numpy engine on the same data ---------------------------------
    with phase("numpy_reference", walls):
        ref = GraphicalJoin(cat, q, elimination_order=plan.order,
                            generation_backend="numpy")
        g_np = ref.run()
        rows_np = desummarize(g_np, decode=False)
    check(gfjs_equal(cold.frame.gfjs, g_np),
          "served GFJS differs from the numpy engine's")
    check(gfjs_equal(g_dev, g_np),
          "GraphicalJoin GFJS differs from the numpy engine's")
    check(count == g_np.join_size, f"count {count} != {g_np.join_size}")
    for v in g_np.column_order:
        check(np.array_equal(rows_dev[v], rows_np[v]),
              f"desummarized column {v} differs from numpy")
    u1 = g_np.domains["U1"].decode(rows_np["U1"])
    a2 = g_np.domains["A2"].decode(rows_np["A2"]).astype(np.int64)
    check(total == int(a2.sum()), f"sum(A2) {total} != {int(a2.sum())}")
    want = np.zeros((len(keys), 2), np.float32)
    for i, k in enumerate(keys):
        hit = u1 == k
        want[i] = (np.float32(int(hit.sum())), np.float32(int(a2[hit].sum())))
    check(np.array_equal(probe, want), f"lookup {probe.tolist()} != "
                                       f"{want.tolist()}")
    del rows_np, rows_dev, u1, a2

    # -- one small query against the row-by-row oracle ---------------------
    with phase("oracle", walls):
        cat_s, queries_s = lastfm_like(**ORACLE, seed=seed)
        gj_s = GraphicalJoin(cat_s, queries_s["lastfm_A1"])
        got = gj_s.desummarize(gj_s.run(), decode=False)
        oracle = oracle_join(encode_query(cat_s, queries_s["lastfm_A1"]))
    order = sorted(oracle)
    check(gj_s.plan().backends == device_engine,
          f"oracle-query plan backends {gj_s.plan().backends}")
    check(np.array_equal(sort_rows(got, order), sort_rows(oracle, order)),
          "small lastfm_A1 rows differ from the oracle join")

    fallbacks = device.host_fallbacks()
    print(f"host fallbacks: {sum(fallbacks.values())} {fallbacks}")
    check(not any(fallbacks.values()), f"host fallbacks {fallbacks}")
    return walls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated catalog")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import device
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    platform = device.platform()
    if platform != "tpu":
        print(f"chip_smoke: JAX runs on {platform!r}, not on a TPU",
              file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()
    try:
        run(args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device peak_bytes_in_use: {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
