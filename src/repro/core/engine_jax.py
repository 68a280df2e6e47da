"""JAX engine for the GJ hot phases (the TPU execution path).

The numpy engine (default) is the dynamic-shape oracle; this module provides
jit-compiled, Pallas-backed implementations of the two phases that dominate
GJ runtime — quantitative learning (GROUP BY count) and desummarization
(RLE expansion) — using the bucketized-padding scheme from DESIGN.md §2:
irregular sizes are rounded up to power-of-two buckets so the jit cache
holds O(log max-size) entries.

Frequencies here ride in int64 (joins overflow int32); x64 is enabled
process-wide at import, which is safe for the LM stack because it pins
explicit dtypes everywhere.

Dense-vs-COO dispatch: `maybe_dense_message` routes the sum-product
contraction to the MXU matmul kernel when the densified key space is small
(fill-ratio budget), else to the COO segment-sum path — a beyond-paper
optimization measured in benchmarks/table5_inmemory.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after x64 flag)

from repro import device  # noqa: E402
from repro.core.elimination import Generator, Psi  # noqa: E402
from repro.core.gfjs import GFJS, LevelSummary, generate_gfjs  # noqa: E402
from repro.core.potentials import INT, Factor, pack_keys  # noqa: E402
from repro.obs.metrics import REGISTRY  # noqa: E402
from repro.obs.trace import span as _span  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import expand_fused as _expand_fused  # noqa: E402

I32_MAX = (1 << 31) - 1
DENSE_BUDGET = 1 << 22   # max densified cells for the MXU message path
PACK_SENTINEL = np.int64(1 << 62)  # > any packed key (pack_keys caps at 2**62)
# run counts below this: the host argsort beats device round-trips
GROUP_DEVICE_MIN_RUNS = 1 << 15


def group_device_enabled() -> bool:
    """Route group_by sorts to the device only when a real accelerator is
    attached: on CPU jax's sort pays dispatch + sentinel padding for
    nothing (measured ~3x slower than np.argsort at 1e6 runs)."""
    return not ops.default_interpret()


# ---------------------------------------------------------------------------
# quantitative learning (potential build)
# ---------------------------------------------------------------------------

def build_factor_jax(
    cols: Dict[str, np.ndarray], sizes: Dict[str, int],
    *, interpret: Optional[bool] = None,
) -> Factor:
    """GROUP BY count on-device: pack -> sort -> run_boundaries -> segsum."""
    names = tuple(cols.keys())
    keys = np.stack([np.asarray(cols[v], dtype=INT) for v in names], axis=1)
    sz = tuple(int(sizes[v]) for v in names)
    n = keys.shape[0]
    if n == 0:
        return Factor(names, keys, np.zeros(0, INT), np.zeros(0, INT), sz)
    try:
        packed = pack_keys(keys, sz)
        packable = bool(np.all(packed <= I32_MAX))
    except OverflowError:
        packable = False
    if not packable:  # fall back to the numpy oracle for huge key spaces
        return Factor.from_columns(cols, sizes)

    sp = jnp.sort(jnp.asarray(packed, jnp.int32))
    flags = ops.run_boundaries(sp, interpret=interpret)
    seg = jnp.cumsum(flags) - 1
    num = int(jnp.sum(flags))
    ones = jnp.ones_like(sp, dtype=jnp.float32)
    counts = ops.mul_segsum(seg, ones, ones, num, interpret=interpret)
    # unique packed keys = sorted packed values at boundary positions
    upacked = np.asarray(sp)[np.asarray(flags, bool)]
    # unpack mixed radix
    ukeys = np.empty((num, len(names)), dtype=INT)
    rem = upacked.astype(np.int64)
    for j in range(len(names) - 1, -1, -1):
        s = max(sz[j], 1)
        ukeys[:, j] = rem % s
        rem //= s
    return Factor(names, ukeys, np.asarray(counts, dtype=INT),
                  np.ones(num, INT), sz)


# ---------------------------------------------------------------------------
# message passing (sum-product contraction)
# ---------------------------------------------------------------------------

def maybe_dense_message(
    phi: Factor, child: str, msg_vals: np.ndarray,
    *, interpret: Optional[bool] = None,
) -> Optional[np.ndarray]:
    """MXU path: densify phi(parentxchild) if small and contract.

    Returns per-parent-code sums, or None if the dense route is off-budget
    (caller then uses the COO segment-sum path).  Exact below 2**24.
    """
    if len(phi.vars) != 2 or child not in phi.vars:
        return None
    ci = phi.var_index(child)
    pi = 1 - ci
    P, V = phi.sizes[pi], phi.sizes[ci]
    if P * V > DENSE_BUDGET:
        return None
    vals = phi.bucket * phi.fac
    if vals.max(initial=0) >= (1 << 24) or msg_vals.max(initial=0) >= (1 << 24):
        return None
    dense = np.zeros((P, V), np.float32)
    dense[phi.keys[:, pi], phi.keys[:, ci]] = vals
    out = ops.dense_message(jnp.asarray(dense),
                            jnp.asarray(msg_vals, jnp.float32)[:, None],
                            interpret=interpret)
    return np.asarray(out[:, 0]).astype(INT)


# ---------------------------------------------------------------------------
# summary-side reductions (repro.summary.algebra's hot loop)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_segments", "acc_dtype"))
def _segsum_padded(seg, x, w, *, num_segments: int, acc_dtype):
    """Fused multiply + segment-sum on bucket-padded inputs (DESIGN.md §2)."""
    prod = x.astype(acc_dtype) * w.astype(acc_dtype)
    return jax.ops.segment_sum(prod, seg, num_segments=num_segments)


def _f32_exact_conclusive(values: np.ndarray, weights: np.ndarray, n: int,
                          bound: Optional[float]) -> bool:
    """Can the f32 kernel accumulate sum|values*weights| exactly?

    Kernel-pick guard in O(1) whenever possible: first the dtype-range
    bound (narrow integer dtypes can't overflow f32-exact at this length no
    matter the data), then the caller's ``bound`` hint (summary algebra
    passes ``count * max|domain value|`` — both O(1) facts: every level of a
    frame sums to the same filtered count, and dictionary values are sorted
    so the extreme is an endpoint read).  Only when both are inconclusive
    does the historical full O(n) float64 abs-product scan run.
    """
    if values.dtype.kind in "iu" and weights.dtype.kind in "iu":
        iv, iw = np.iinfo(values.dtype), np.iinfo(weights.dtype)
        vmax = max(abs(int(iv.min)), int(iv.max))
        wmax = max(abs(int(iw.min)), int(iw.max))
        if n * vmax * wmax < ops.F32_EXACT:   # python ints: no overflow
            return True
    if bound is not None:
        return float(bound) < ops.F32_EXACT
    total = float(np.abs(values.astype(np.float64)
                         * weights.astype(np.float64)).sum())
    return total < ops.F32_EXACT


def segment_weighted_sum(
    seg_ids: np.ndarray, values: np.ndarray, weights: np.ndarray,
    num_segments: int, *, interpret: Optional[bool] = None,
    bound: Optional[float] = None,
) -> np.ndarray:
    """Per-segment sum of values*weights over sorted dense segment ids.

    The dispatch point for every summary-side aggregate: on TPU, integer
    inputs whose total magnitude fits f32-exact range ride the Pallas
    ``mul_segsum`` kernel (MXU one-hot matmul per tile); everything else —
    including all CPU traffic, where the kernel would only run interpreted —
    takes a jit'd XLA segment-sum with bucketized padding (int64 exact for
    integers, f64 for floats), so the jit cache stays O(log^2 max-size).

    ``bound``: optional caller-known upper bound on sum|values*weights|,
    letting the kernel pick skip its O(n) exactness scan (see
    :func:`_f32_exact_conclusive`).  A too-large bound only costs the fast
    path, never correctness.
    """
    values = np.asarray(values)
    weights = np.asarray(weights)
    n = len(values)
    floaty = values.dtype.kind == "f" or weights.dtype.kind == "f"
    if n == 0:
        return np.zeros(num_segments, np.float64 if floaty else np.int64)
    interpret = ops.default_interpret() if interpret is None else interpret
    if not floaty and not interpret and \
            _f32_exact_conclusive(values, weights, n, bound):
        out = ops.mul_segsum(seg_ids, values, weights, num_segments,
                             interpret=interpret)
        return _segsum_wait(out).astype(INT)
    # exact path: pad entries + segment count to power-of-two buckets;
    # padding rows land in a dead trailing segment that gets sliced off
    acc = jnp.float64 if floaty else jnp.int64
    s_pad = ops.next_bucket(num_segments + 1)
    n_pad = ops.next_bucket(n)
    seg_p = np.full(n_pad, s_pad - 1, np.int32)
    seg_p[:n] = seg_ids
    x_p = np.zeros(n_pad, values.dtype)
    x_p[:n] = values
    w_p = np.zeros(n_pad, weights.dtype)
    w_p[:n] = weights
    out = _segsum_padded(jnp.asarray(seg_p), jnp.asarray(x_p),
                         jnp.asarray(w_p), num_segments=s_pad, acc_dtype=acc)
    res = _segsum_wait(out)[:num_segments]
    return res if floaty else res.astype(INT)


def _segsum_wait(out: jax.Array) -> np.ndarray:
    """Wait for a segment sum and copy it to the host, under a
    ``segsum:wait`` span."""
    with _span("segsum:wait", cat="algebra", device=True):
        return np.asarray(out)


def weighted_total(
    values: np.ndarray, weights: np.ndarray,
    *, interpret: Optional[bool] = None, bound: Optional[float] = None,
):
    """sum(values * weights) — a one-segment reduction."""
    seg = np.zeros(len(np.asarray(values)), np.int32)
    out = segment_weighted_sum(seg, values, weights, 1, interpret=interpret,
                               bound=bound)
    return out[0] if len(out) else out.dtype.type(0)


# ---------------------------------------------------------------------------
# on-device grouped-run sort (summary algebra's group_by hot loop)
# ---------------------------------------------------------------------------

@jax.jit
def _sorted_runs(ranks_p: jax.Array):
    """argsort + run boundaries of sentinel-padded packed ranks."""
    order = jnp.argsort(ranks_p)          # stable; pads sort to the tail
    s = ranks_p[order]
    new = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
    seg = (jnp.cumsum(new) - 1).astype(jnp.int32)
    return order.astype(jnp.int32), new, seg


def group_runs_device(ranks: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray, int]:
    """Grouped-run decomposition via an on-device packed-key sort.

    Input: packed int64 ranks (one per live run, ``pack_keys`` semantics so
    every rank < 2**62).  Output matches the host path of
    ``SummaryFrame.group_by``: (sort order, dense segment ids, group starts,
    group count).  The O(n log n) sort runs on the accelerator with
    bucketized sentinel padding (pads sort past every real key and are
    sliced off); only the O(n) boundary scan stays on the host.
    """
    n = len(ranks)
    if n == 0:
        return (np.zeros(0, INT), np.zeros(0, np.int32),
                np.zeros(0, INT), 0)
    n_pad = ops.next_bucket(n)
    r_p = np.full(n_pad, PACK_SENTINEL, np.int64)
    r_p[:n] = ranks
    order, new, seg = _sorted_runs(jnp.asarray(r_p))
    with _span("sort:wait", cat="algebra", device=True):
        order = np.asarray(order[:n])
        new = np.asarray(new[:n])
        seg = np.asarray(seg[:n])
    starts = np.flatnonzero(new)
    return order.astype(INT), seg, starts, int(len(starts))


# ---------------------------------------------------------------------------
# desummarization
# ---------------------------------------------------------------------------

def desummarize_jax(
    gfjs: GFJS, *, decode: bool = False, interpret: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """RLE-expand every level with the fused `expand_gather_many` kernel.

    One kernel launch per *level* (not per column): the level's columns ride
    as a [K, runs] payload stack, the run search is amortized over all K,
    and the launch metadata (padded bounds + tile starts) is memoized on the
    summary so repeated desummarization skips the per-call searchsorted.
    """
    if gfjs.join_size > I32_MAX:
        raise ValueError("join size exceeds the int32 TPU kernel range; "
                         "use range-sharded desummarization (repro.dist)")
    out: Dict[str, np.ndarray] = {}
    total = gfjs.join_size
    t_pad = ops.next_bucket(max(total, 1))
    for li, lvl in enumerate(gfjs.levels):
        with _span(f"desummarize:level:{li}", cat="gen", backend="jax",
                   device=True, runs=len(lvl.freq)):
            if any(lvl.key_cols[v].size
                   and int(lvl.key_cols[v].max()) > I32_MAX
                   for v in lvl.vars):
                # codes past the int32 kernel range (domains >= 2**31
                # values): numpy-expand this level instead of wrapping
                device.count_host_fallback("codes_past_int32")
                for v in lvl.vars:
                    col = np.repeat(lvl.key_cols[v], lvl.freq)
                    out[v] = gfjs.domains[v].decode(col) if decode else col
                continue
            meta = ops.gfjs_expand_meta(gfjs, li, t_pad)
            payloads = jnp.stack(
                [jnp.asarray(lvl.key_cols[v], jnp.int32) for v in lvl.vars])
            expanded = ops.rle_expand_many(payloads, None, total,
                                           interpret=interpret, meta=meta)
            with _span("desummarize:d2h", cat="gen", device=True) as csp:
                cols = np.asarray(expanded)
                csp.set(bytes=cols.nbytes)
            for k, v in enumerate(lvl.vars):
                out[v] = gfjs.domains[v].decode(cols[k]) if decode \
                    else cols[k]
    return {v: out[v] for v in gfjs.column_order}


# ---------------------------------------------------------------------------
# device-resident GFJS generation (Algorithms 3/4 on the accelerator)
# ---------------------------------------------------------------------------
#
# The frontier (`cols`, `p_bucket`, per-level `fac_acc`) stays on-device as
# bucket-padded jnp arrays with an explicit live count ``n``: group lookup is
# a packed-key `jnp.searchsorted` against each psi's pre-packed parent keys,
# and expansion is ONE fused `expand_gather_many` launch per psi that carries
# every frontier column plus the (src, CSR start, offset) index columns in
# the same pass.  The host sees one scalar per psi (the new frontier size,
# needed to pick the next padding bucket) and the final per-level arrays when
# a LevelSummary is emitted.  numpy (`generate_gfjs`) remains the
# dynamic-shape oracle; `generate_gfjs_jax` falls back to it (and counts the
# fallback) whenever the int32/packing preconditions don't hold.


@dataclass
class _DevicePsi:
    """One psi, uploaded once: packed parent keys + padded CSR arrays."""

    child: str
    parents: Tuple[str, ...]
    radices: Tuple[int, ...]   # parent domain sizes (packing, static)
    keys_p: jax.Array          # [g_pad] int64, sentinel-padded packed keys
    start_p: jax.Array         # [g_pad] int32
    count_p: jax.Array         # [g_pad] int32
    child_p: jax.Array         # [m_pad] int32
    bucket_p: jax.Array        # [m_pad] int64
    fac_p: jax.Array           # [m_pad] int64


def _radix_packable(sizes: Sequence[int]) -> bool:
    total = 1
    for s in sizes:
        total *= max(int(s), 1)
        if total >= (1 << 62):
            return False
    return True


def jax_generable(gen: Generator) -> bool:
    """Do the int32-kernel / int64-packing preconditions hold?"""
    if gen.join_size > I32_MAX or len(gen.root_codes) > I32_MAX:
        return False
    if len(gen.root_codes) and int(gen.root_codes.max()) > I32_MAX:
        return False
    for level in gen.levels:
        for psi in level:
            if not _radix_packable(psi.parent_sizes):
                return False
            if psi.child_size > I32_MAX or psi.num_entries > I32_MAX \
                    or psi.num_groups > I32_MAX:
                return False
            if any(s > I32_MAX for s in psi.parent_sizes):
                return False
    return True


def _device_psi(psi: Psi) -> _DevicePsi:
    """Pack + pad + upload one psi (memoized on the Psi object)."""
    cached = getattr(psi, "_device", None)
    if cached is not None:
        return cached
    g = psi.num_groups
    g_pad = ops.next_bucket(max(g, 1))
    packed = pack_keys(psi.parent_keys, list(psi.parent_sizes)) if g else \
        np.zeros(0, INT)
    keys_p = np.full(g_pad, PACK_SENTINEL, np.int64)
    keys_p[:g] = packed
    start_p = np.zeros(g_pad, np.int32)
    start_p[:g] = psi.start
    count_p = np.zeros(g_pad, np.int32)
    count_p[:g] = psi.count
    m = psi.num_entries
    m_pad = ops.next_bucket(max(m, 1))
    child_p = np.zeros(m_pad, np.int32)
    child_p[:m] = psi.child_codes
    bucket_p = np.zeros(m_pad, np.int64)
    bucket_p[:m] = psi.bucket
    fac_p = np.zeros(m_pad, np.int64)
    fac_p[:m] = psi.fac
    dp = _DevicePsi(psi.child, psi.parents, tuple(int(s) for s in psi.parent_sizes),
                    jnp.asarray(keys_p), jnp.asarray(start_p),
                    jnp.asarray(count_p), jnp.asarray(child_p),
                    jnp.asarray(bucket_p), jnp.asarray(fac_p))
    psi._device = dp
    return dp


@functools.partial(jax.jit, static_argnames=("radices",))
def _frontier_lookup(parent_cols, n, keys_p, start_p, count_p, *, radices):
    """Packed-key group lookup + expansion counts for one psi.

    ``parent_cols`` is [P, n_pad] int32 (P == len(radices), possibly 0 for a
    parentless psi — the empty pack is key 0, matching `pack_keys` of a
    zero-width row).  Rows at or past the live count ``n`` and rows whose
    key misses psi's parent groups get count 0 — exactly the numpy
    `_lookup_groups` miss semantics.
    """
    n_pad = parent_cols.shape[1]
    key = jnp.zeros((n_pad,), jnp.int64)
    for j, s in enumerate(radices):
        key = key * max(int(s), 1) + parent_cols[j].astype(jnp.int64)
    pos = jnp.clip(jnp.searchsorted(keys_p, key), 0,
                   keys_p.shape[0] - 1).astype(jnp.int32)
    live = jax.lax.iota(jnp.int32, n_pad) < n
    hit = (keys_p[pos] == key) & live
    counts = jnp.where(hit, count_p[pos], 0).astype(jnp.int32)
    bounds = jnp.cumsum(counts, dtype=jnp.int32)
    start_g = jnp.where(hit, start_p[pos], 0).astype(jnp.int32)
    return counts, bounds, start_g, bounds - counts


@jax.jit
def _psi_weights(src_x, start_x, offs_x, child_p, bucket_p, fac_p,
                 p_bucket, fac_acc):
    """Recover cidx from the expanded index columns; gather psi payloads.

    ``cidx = start[g[src]] + within`` where ``within = t - offsets[src]`` —
    both ingredients were expanded by the fused kernel, so this is pure
    gathers.  Rows past the live total produce clipped garbage that the
    caller never reads (sliced off at LevelSummary emission).
    """
    t = jax.lax.iota(jnp.int32, src_x.shape[0])
    cidx = jnp.clip(start_x + (t - offs_x), 0, child_p.shape[0] - 1)
    src = jnp.clip(src_x, 0, p_bucket.shape[0] - 1)
    child = child_p[cidx]
    pb = p_bucket[src] * bucket_p[cidx]
    fa = fac_acc[src] * fac_p[cidx]
    return child, pb, fa


def expand_level_jax(
    cols: Dict[str, jax.Array], p_bucket: jax.Array,
    level: Sequence[Psi], n: int, *, interpret: Optional[bool] = None,
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array, Tuple[str, ...], int]:
    """Device-resident `expand_level`: one fused kernel launch per psi.

    ``cols``/``p_bucket`` are bucket-padded device arrays with ``n`` live
    rows.  Returns ``(cols, p_bucket, freq, new_vars, n_new)`` with ``freq``
    still on-device ([t_pad], slice [:n_new] when emitting).  The only host
    syncs are the per-psi frontier totals (one scalar each, needed to pick
    the next padding bucket).
    """
    interpret = ops.default_interpret() if interpret is None else interpret
    fac_acc = jnp.ones_like(p_bucket)
    new_vars: List[str] = []
    names = list(cols.keys())
    for psi in level:
        dp = _device_psi(psi)
        parent_cols = (jnp.stack([cols[p] for p in dp.parents])
                       if dp.parents
                       else jnp.zeros((0,) + p_bucket.shape, jnp.int32))
        counts, bounds, start_g, offs = _frontier_lookup(
            parent_cols, jnp.int32(n), dp.keys_p, dp.start_p, dp.count_p,
            radices=dp.radices)
        with _span("gfjs:sync", cat="gen", device=True,
                   child=dp.child) as ssp:
            total = int(bounds[-1])      # host sync: one scalar per psi
            ssp.set(total=total)
        if total == 0:
            # dead frontier: keep padded shapes, mark zero live rows — the
            # remaining psis of the level still bind their (empty) children
            # so the emitted LevelSummary names every child, like numpy
            cols = dict(cols)
            cols[dp.child] = jnp.zeros(p_bucket.shape, jnp.int32)
            names.append(dp.child)
            new_vars.append(dp.child)
            n = 0
            continue
        t_pad = ops.next_bucket(total)
        src_iota = jax.lax.iota(jnp.int32, p_bucket.shape[0])
        payloads = jnp.concatenate([
            jnp.stack([cols[v] for v in names]),
            src_iota[None], start_g[None], offs[None]])
        expanded = _expand_fused.expand_gather_many(
            payloads, bounds, t_pad=t_pad, interpret=interpret)
        child, p_bucket, fac_acc = _psi_weights(
            expanded[-3], expanded[-2], expanded[-1],
            dp.child_p, dp.bucket_p, dp.fac_p, p_bucket, fac_acc)
        cols = {v: expanded[i] for i, v in enumerate(names)}
        cols[dp.child] = child
        names.append(dp.child)
        new_vars.append(dp.child)
        n = total
    return cols, p_bucket, p_bucket * fac_acc, tuple(new_vars), n


def _emit_level(depth: int, new_vars: Tuple[str, ...],
                cols: Dict[str, jax.Array], freq: jax.Array,
                n: int) -> LevelSummary:
    """Copy a level's ``n`` live runs to the host as a LevelSummary.

    The wait for the device programs that compute the level (the last
    psi's weights above all) is a ``gfjs:wait:<depth>`` span of its own,
    so the ``gfjs:emit:<depth>`` span after it holds only the slice, the
    device-to-host copy and the widening, with the device bytes copied.
    """
    with _span(f"gfjs:wait:{depth}", cat="gen", device=True):
        jax.block_until_ready([freq] + [cols[v] for v in new_vars])
    with _span(f"gfjs:emit:{depth}", cat="gen", device=True,
               runs=n) as sp:
        nbytes = 0
        keys = {}
        for v in new_vars:
            col = np.asarray(cols[v][:n])
            nbytes += col.nbytes
            keys[v] = col.astype(INT)
        runs = np.asarray(freq[:n])
        nbytes += runs.nbytes
        level = LevelSummary(new_vars, keys, runs.astype(INT))
        sp.set(bytes=nbytes)
    return level


def generate_gfjs_jax(
    gen: Generator, domains: Dict[str, "Domain"],
    *, interpret: Optional[bool] = None,
) -> GFJS:
    """Device-resident Algorithms 3/4; falls back to the numpy oracle.

    Level-for-level identical to :func:`repro.core.gfjs.generate_gfjs`
    (expansion is order-preserving in both engines).  The numpy path remains
    authoritative for dynamic shapes, trace recording (incremental
    maintenance needs host (src, cidx) caches), and any generator outside
    the int32/packing envelope (`jax_generable`) — counted as a host
    fallback.
    """
    if not jax_generable(gen):
        device.count_host_fallback("not_generable")
        return generate_gfjs(gen, domains)

    levels_out: List[LevelSummary] = [
        LevelSummary((gen.root,), {gen.root: gen.root_codes}, gen.root_freq)]

    n = len(gen.root_codes)
    n_pad = ops.next_bucket(max(n, 1))
    root_p = np.zeros(n_pad, np.int32)
    root_p[:n] = gen.root_codes
    cols: Dict[str, jax.Array] = {gen.root: jnp.asarray(root_p)}
    p_bucket = jnp.ones((n_pad,), jnp.int64)

    runs_hist = REGISTRY.histogram("gfjs.runs_per_level", unit="runs")
    runs_hist.observe(n)
    for depth, level in enumerate(gen.levels):
        children = tuple(p.child for p in level)
        if n == 0:     # dead frontier: remaining levels are all empty
            levels_out.append(LevelSummary(
                children, {v: np.zeros(0, INT) for v in children},
                np.zeros(0, INT)))
            for p in level:
                cols[p.child] = jnp.zeros((0,), jnp.int32)
            runs_hist.observe(0)
            continue
        with _span(f"gfjs:level:{depth}", cat="gen", backend="jax",
                   device=True, depth=depth) as sp:
            cols, p_bucket, freq, new_vars, n = expand_level_jax(
                cols, p_bucket, level, n, interpret=interpret)
            sp.set(runs=n, vars=",".join(new_vars))
        runs_hist.observe(n)
        levels_out.append(_emit_level(depth, new_vars, cols, freq, n))

    return GFJS(levels_out, list(gen.column_order), gen.join_size, domains)
