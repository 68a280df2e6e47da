"""Public jit'd wrappers around the Pallas kernels.

These are what the JAX GJ engine calls.  Responsibilities:

* interpret-mode dispatch: on CPU backends the kernels execute their Python
  bodies (`interpret=True`); on TPU they compile to Mosaic.  The platform
  comes from the one probe in `repro.device`.
* bucketized padding: output sizes are data-dependent in GJ, so callers pass
  the exact total and we round up to the next power-of-two bucket — jit
  caches stay bounded at O(log max-size) entries (DESIGN.md §2).
* dtype guards: the TPU kernels accumulate in f32 (exact < 2**24); wrappers
  fall back to exact XLA int64 paths above that.  On this CPU container the
  fallbacks also serve as the measured engine, with kernels validated via
  interpret mode in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import device
from repro.kernels import boundaries as _boundaries
from repro.kernels import dense_contract as _dense
from repro.kernels import expand as _expand
from repro.kernels import expand_fused as _expand_fused
from repro.kernels import segsum as _segsum
from repro.obs.trace import span as _span

F32_EXACT = 1 << 24


def _launch(kernel: str, **args):
    """Open a device-annotated ``kernel:<name>`` span around a launch —
    `jax.profiler.TraceAnnotation` rides along so host spans line up with
    device traces.  The ambient no-op when tracing is off."""
    return _span(f"kernel:{kernel}", cat="kernel", device=True, **args)


def default_interpret() -> bool:
    """Interpret the kernels unless the platform probe found a TPU."""
    return device.platform() != "tpu"


def next_bucket(n: int, floor: int = 512) -> int:
    """Next power-of-two padding bucket (>= floor)."""
    b = floor
    while b < n:
        b <<= 1
    return b


def rle_expand(payload, bounds, total: int, *, interpret: bool | None = None,
               meta=None):
    """Expand RLE runs to a flat array of ``total`` elements.

    ``meta`` is an optional ``(bounds_p, start_block)`` pair from
    `expand_meta`/`gfjs_expand_meta` — the memoized-launch path for levels
    expanded repeatedly.
    """
    interpret = default_interpret() if interpret is None else interpret
    t_pad = next_bucket(max(total, 1))
    payload = jnp.asarray(payload, jnp.int32)
    with _launch("rle_expand", expanded_bytes=total * 4, total=total):
        if meta is None:
            out = _expand.expand_gather(
                payload, jnp.asarray(bounds, jnp.int32),
                t_pad=t_pad, interpret=interpret)
        else:
            bounds_p, start_block = meta
            payload_p = jnp.pad(payload,
                                (0, bounds_p.shape[0] - payload.shape[0]))
            out = _expand.expand_gather_with_meta(
                payload_p, bounds_p, start_block, t_pad=t_pad,
                interpret=interpret)
    return out[:total]


def rle_expand_many(payloads, bounds, total: int, *,
                    interpret: bool | None = None, meta=None):
    """Expand K payload rows sharing one RLE — a single fused kernel launch.

    ``payloads`` is [K, Np]; the result is [K, total].  The fused kernel
    recovers each output tile's run index once and amortizes it over all K
    payload rows (codes of every variable in a GFJS level, plus the `src` /
    CSR-offset index columns of frontier expansion) — K times fewer kernel
    launches, bounds-window reads, and run searches than the per-column path.
    """
    interpret = default_interpret() if interpret is None else interpret
    t_pad = next_bucket(max(total, 1))
    payloads = jnp.asarray(payloads, jnp.int32)
    with _launch("rle_expand_many",
                 expanded_bytes=int(payloads.shape[0]) * total * 4,
                 k=int(payloads.shape[0]), total=total):
        if meta is None:
            out = _expand_fused.expand_gather_many(
                payloads, jnp.asarray(bounds, jnp.int32),
                t_pad=t_pad, interpret=interpret)
        else:
            bounds_p, start_block = meta
            payloads_p = jnp.pad(
                payloads,
                ((0, 0), (0, bounds_p.shape[0] - payloads.shape[1])))
            out = _expand_fused.expand_gather_many_with_meta(
                payloads_p, bounds_p, start_block, t_pad=t_pad,
                interpret=interpret)
    return out[:, :total]


def expand_meta(bounds, t_pad: int):
    """`launch_meta` for arbitrary bounds: (padded bounds, tile starts)."""
    return _expand.launch_meta(jnp.asarray(bounds, jnp.int32), t_pad=t_pad)


def gfjs_expand_meta(gfjs, level: int, t_pad: int):
    """Memoized launch metadata for expanding one GFJS level.

    Cached on ``GFJS._launch`` alongside the ``_bounds`` prefix sums —
    repeated expansion of the same level (the serve path's repeated
    desummarize, benchmarks, range shards sharing a bucket) skips the
    per-invocation host `searchsorted` over all output tiles.  One entry
    per level: a different ``t_pad`` replaces the cached pair, so the memo
    stays bounded and `GFJS.aux_nbytes` can account for it.
    """
    hit = gfjs._launch.get(level)
    if hit is None or hit[0] != t_pad:
        bounds = jnp.asarray(gfjs.bounds(level), jnp.int32)
        hit = (t_pad, _expand.launch_meta(bounds, t_pad=t_pad))
        gfjs._launch[level] = hit
    return hit[1]


def expand_indices(bounds, total: int, *, interpret: bool | None = None):
    """Source-run index per output position (frontier expansion's `src`)."""
    n = bounds.shape[0]
    payload = jnp.arange(n, dtype=jnp.int32)
    return rle_expand(payload, bounds, total, interpret=interpret)


def mul_segsum(seg_ids, x, y, num_segments: int, *,
               interpret: bool | None = None, exact: bool = False):
    """Per-segment sum of x*y.  ``exact=True`` forces the int64 XLA path."""
    interpret = default_interpret() if interpret is None else interpret
    if exact:
        idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        return jax.ops.segment_sum(
            jnp.asarray(x, idt) * jnp.asarray(y, idt),
            jnp.asarray(seg_ids, jnp.int32), num_segments=num_segments)
    with _launch("mul_segsum", segments=num_segments):
        out = _segsum.mul_segsum(
            jnp.asarray(seg_ids, jnp.int32),
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            num_segments=num_segments, interpret=interpret)
    return out


def run_boundaries(keys, *, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    with _launch("run_boundaries"):
        return _boundaries.run_boundaries(jnp.asarray(keys, jnp.int32),
                                          interpret=interpret)


def dense_message(phi, m, *, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    with _launch("dense_message"):
        return _dense.dense_message(jnp.asarray(phi, jnp.float32),
                                    jnp.asarray(m, jnp.float32),
                                    interpret=interpret)


def group_by_count(keys, *, interpret: bool | None = None):
    """GROUP BY sorted keys: (segment ids, counts, num_groups).

    Composition of the two build kernels: run_boundaries -> cumsum ->
    mul_segsum(ones, ones).
    """
    flags = run_boundaries(keys, interpret=interpret)
    seg = jnp.cumsum(flags) - 1
    num = int(flags.sum())
    ones = jnp.ones_like(seg, dtype=jnp.float32)
    counts = mul_segsum(seg, ones, ones, num, interpret=interpret)
    return seg, counts, num
