"""TPC-H at scale factor 1, the columns of Q10's join, made from a seed.

dbgen's rules for the join columns (TPC-H 3.0.1, 4.2.3):

* ``customer``: c_custkey 1..SF*150,000, c_nationkey uniform over 0..24;
* ``orders``: SF*1,500,000 rows, o_orderkey sparse (the first 8 of every 32
  keys), o_custkey uniform over the customers whose key is not divisible
  by 3 (a third of customers place no order);
* ``lineitem``: 1-7 per order, l_partkey uniform over 1..SF*200,000;
* ``nation``: the 25 fixed nations with their n_regionkey.

The lineitem counts are a fixed multiset (each of 1..7 equally often) in a
seed-dependent order, so every seed builds a join of the same size.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def generate(cfg: dict, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    rng = np.random.default_rng([seed, 0x79C4])
    n_c, n_o, n_p = cfg["customers"], cfg["orders"], cfg["parts"]
    stride, used = cfg["order_key_stride"], cfg["order_keys_per_stride"]
    lo, hi = cfg["lineitems_per_order"]
    regions = np.asarray(cfg["nation_regions"], np.int64)
    if len(regions) != cfg["nations"]:
        raise ValueError("one region per nation")

    custkey = np.arange(1, n_c + 1, dtype=np.int64)
    nationkey = rng.integers(0, cfg["nations"], n_c)
    i = np.arange(n_o, dtype=np.int64)
    orderkey = (i // used) * stride + i % used + 1
    buyers = custkey[custkey % 3 != 0]
    o_custkey = buyers[rng.integers(0, len(buyers), n_o)]
    per_order = rng.permutation(np.resize(np.arange(lo, hi + 1), n_o))
    l_orderkey = np.repeat(orderkey, per_order)
    l_partkey = rng.integers(1, n_p + 1, len(l_orderkey))
    return {
        "lineitem": {"l_orderkey": l_orderkey, "l_partkey": l_partkey},
        "orders": {"o_orderkey": orderkey, "o_custkey": o_custkey},
        "customer": {"c_custkey": custkey, "c_nationkey": nationkey},
        "nation": {"n_nationkey": np.arange(cfg["nations"], dtype=np.int64),
                   "n_regionkey": regions},
    }
