"""HetRec 2011 Last.FM at its published size, made from a seed.

The published files hold each user's top 50 artists (``user_artists``:
userID, artistID, weight = play count) and a symmetric friend graph
(``user_friends``, every pair stored both ways).  The generator keeps the
published counts exactly: every seed draws the same multiset of per-user
list lengths and the same friend-degree weights, assigned to users in a
seed-dependent order, so seeds change which users and artists meet, not
how much work a build is.

* list lengths: 50 for most users, the shortfall to the published row total
  spread as 49, 48, ..., 1, 49, ... over as few users as it takes;
* artists: every artist is placed once (the published file lists no artist
  without a listener), the remaining slots are drawn per user without
  replacement by Zipf popularity (Gumbel top-k);
* friends: Chung-Lu pairs by Zipf degree weights until the published number
  of distinct pairs, then stored both ways.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

_CHUNK = 128            # users per Gumbel top-k block (bounded memory)


def list_lengths(users: int, rows: int, cap: int) -> np.ndarray:
    """Per-user artist counts summing to ``rows``; seed-independent."""
    short = users * cap - rows
    if short < 0 or rows < users:
        raise ValueError(f"{rows} rows cannot fill {users} users of at "
                         f"most {cap} artists, one at least")
    counts = np.full(users, cap, np.int64)
    j = 0
    while short > 0:
        cut = min(cap - 1 - j % (cap - 1), short)
        counts[j % users] -= cut
        short -= cut
        j += 1
    return counts


def _zipf(n: int, alpha: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    return p / p.sum()


def _friend_pairs(rng: np.random.Generator, users: int, pairs: int,
                  alpha: float) -> np.ndarray:
    """``pairs`` distinct undirected pairs (u < v), Chung-Lu by weight."""
    weight = rng.permutation(_zipf(users, alpha))
    got = np.zeros((0, 2), np.int64)
    while len(got) < pairs:
        m = 2 * (pairs - len(got)) + 64
        ends = rng.choice(users, size=(m, 2), p=weight)
        ends = ends[ends[:, 0] != ends[:, 1]]
        cand = np.concatenate([got, np.sort(ends, axis=1)])
        _, first = np.unique(cand, axis=0, return_index=True)
        got = cand[np.sort(first)]          # keep draw order: deterministic
    return got[:pairs]


def generate(cfg: dict, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """``user_artists`` and ``user_friends`` for ``seed`` (ids from 1)."""
    rng = np.random.default_rng([seed, 0x1A57F])
    users, artists = cfg["users"], cfg["artists"]
    rows, cap = cfg["user_artists_rows"], cfg["max_artists_per_user"]
    assumed = cfg["assumed"]
    counts = rng.permutation(list_lengths(users, rows, cap))
    owner = np.repeat(np.arange(users), counts)
    # every artist once, in slots drawn without replacement: one listener
    # each, never twice for one user since each artist is placed once
    if rows < artists:
        raise ValueError("fewer rows than artists")
    placed = np.full(rows, -1, np.int64)
    placed[rng.choice(rows, artists, replace=False)] = \
        rng.permutation(artists)
    logp = np.log(rng.permutation(
        _zipf(artists, assumed["artist_popularity_zipf"])))
    starts = np.concatenate([[0], np.cumsum(counts)])
    picks = placed.copy()
    for lo in range(0, users, _CHUNK):
        hi = min(lo + _CHUNK, users)
        score = logp + rng.gumbel(size=(hi - lo, artists))
        for u in range(lo, hi):
            mine = placed[starts[u]:starts[u + 1]]
            have = mine[mine >= 0]
            need = counts[u] - len(have)
            if need == 0:
                continue
            s = score[u - lo]
            s[have] = -np.inf
            top = np.argpartition(-s, need - 1)[:need]
            slot = starts[u] + np.flatnonzero(mine < 0)
            picks[slot] = top
    mu, sigma = assumed["weight_lognormal"]
    weight = np.ceil(np.exp(rng.normal(mu, sigma, rows))).astype(np.int64)
    order = np.lexsort((picks, owner))
    pairs = _friend_pairs(rng, users, cfg["friend_pairs"],
                          assumed["friend_degree_zipf"])
    both = np.concatenate([pairs, pairs[:, ::-1]])
    both = both[np.lexsort((both[:, 1], both[:, 0]))]
    return {
        "user_artists": {"userID": owner[order] + 1,
                         "artistID": picks[order] + 1,
                         "weight": weight},
        "user_friends": {"userID": both[:, 0] + 1,
                         "friendID": both[:, 1] + 1},
    }
