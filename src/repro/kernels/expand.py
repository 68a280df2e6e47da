"""`expand_gather` — the RLE-expansion Pallas TPU kernel.

This is GJ's hottest primitive: desummarization writes |Q| × width bytes and
nothing else, so the roofline is pure HBM bandwidth (DESIGN.md §2).  The
kernel maps one grid step to one *output* tile of ``OT`` elements and must
answer, for every output position t, "which run am I in?".

TPU adaptation of the CPU algorithm (which is just ``np.repeat``):

* Run boundaries are an inclusive prefix sum ``bounds`` (monotone).  An
  output tile [t0, t0+OT) overlaps at most OT+1 runs because every run has
  length >= 1.  We therefore prefetch, per tile, a window of TWO consecutive
  run-blocks of size RB=OT each (`PrefetchScalarGridSpec`): the scalar
  argument ``start_block`` (computed with one cheap jnp.searchsorted on the
  host side of the jit) tells the BlockSpec index_map where the window
  starts.  start offset <= RB-1 plus OT+1 live runs always fits in 2*RB.
* Inside the kernel the run index is recovered *without* vector gathers
  (TPU Pallas has no general VMEM gather): a comparison matrix
  ``bounds_window[j] <= t`` summed over j gives the run index, and the
  payload is picked with a select-and-sum over the same window.  That costs
  2*RB integer VPU ops per output element — ~1k ops against an 8x128x8-lane
  VPU, i.e. still comfortably below the HBM-bandwidth bound of this kernel
  (napkin: 4 B/element out at 819 GB/s vs ~1k int-ops at ~100 Tops/s).

Padding contract: runs [num_runs..Np) must have bounds == bounds[num_runs-1]
(zero-length), outputs [total..T_pad) produce payload of the last live run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Output tile and run-block sizes.  8x128 = one float32 VREG tile; OT is a
# multiple so stores are lane-aligned.
OT = 512
RB = OT


def _expand_kernel(start_block, bounds0, bounds1, payload0, payload1,
                   out_ref):
    """One output tile: recover run indices once, gather K payload rows.

    The window is two [1, RB] bounds blocks and two [K, RB] payload blocks.
    Each half is searched and picked on its own — Mosaic cannot concatenate
    vectors at offsets past the first tile — and the run index is the sum
    of the two halves' counts.  Rows run along sublanes and runs along
    lanes, so the per-payload results come out as [OT, 1] columns; they
    are packed into the lanes of one [OT, 128] tile and transposed once
    into the lane-dense [K, OT] output block.
    """
    i = pl.program_id(0)
    k = payload0.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (OT, RB), 0) + i * OT
    j = jax.lax.broadcasted_iota(jnp.int32, (OT, RB), 1)
    # comparison-matrix run search: idx[t] = #j with bounds[j] <= t.  Pin
    # the accumulator dtypes: x64 mode would promote these sums to int64
    idx = (jnp.sum((bounds0[...] <= t).astype(jnp.int32), axis=1,
                   keepdims=True, dtype=jnp.int32)
           + jnp.sum((bounds1[...] <= t).astype(jnp.int32), axis=1,
                     keepdims=True, dtype=jnp.int32))           # [OT, 1]
    idx = jnp.minimum(idx, 2 * RB - 1)
    dt = out_ref.dtype
    pick0 = (j == idx).astype(dt)                               # [OT, RB]
    pick1 = (j + RB == idx).astype(dt)
    p0, p1 = payload0[...], payload1[...]
    # select-and-sum payload pick (exact for any int payload)
    lane = jax.lax.broadcasted_iota(jnp.int32, (OT, 128), 1)
    res = jnp.zeros((OT, 128), dt)
    for q in range(k):
        col = (jnp.sum(pick0 * p0[q:q + 1, :], axis=1, keepdims=True,
                       dtype=dt)
               + jnp.sum(pick1 * p1[q:q + 1, :], axis=1, keepdims=True,
                         dtype=dt))                             # [OT, 1]
        res = jnp.where(lane == q, col, res)
    out_ref[...] = res.T[:k, :]


def expand_call(payloads_p: jax.Array, bounds_p: jax.Array,
                start_block: jax.Array, *, t_pad: int,
                interpret: bool) -> jax.Array:
    """The kernel launch: [K, pad_to] payloads -> [K, t_pad] expansion.

    K <= 128 (the payload rows share one [OT, 128] transpose tile).  Index
    maps return int32 literals: under x64 a bare ``0`` would be an int64
    constant, which Mosaic cannot legalize.
    """
    assert t_pad % OT == 0, "t_pad must be a multiple of the output tile"
    k, pad_to = payloads_p.shape
    assert k <= 128, "at most 128 payload rows per launch"
    assert pad_to == bounds_p.shape[0], "payloads must match bounds padding"

    def row(block):
        return jnp.int32(0), block

    return pl.pallas_call(
        _expand_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t_pad // OT,),
            in_specs=[
                pl.BlockSpec((1, RB), lambda i, sb: row(sb[i])),
                pl.BlockSpec((1, RB), lambda i, sb: row(sb[i] + 1)),
                pl.BlockSpec((k, RB), lambda i, sb: row(sb[i])),
                pl.BlockSpec((k, RB), lambda i, sb: row(sb[i] + 1)),
            ],
            out_specs=pl.BlockSpec((k, OT), lambda i, sb: row(i)),
        ),
        out_shape=jax.ShapeDtypeStruct((k, t_pad), payloads_p.dtype),
        interpret=interpret,
    )(start_block, bounds_p[None], bounds_p[None], payloads_p, payloads_p)


@functools.partial(jax.jit, static_argnames=("t_pad",))
def launch_meta(bounds: jax.Array, *, t_pad: int):
    """Per-level launch metadata: padded bounds + per-tile window starts.

    The `start_block` scalar-prefetch argument is a host-side
    ``jnp.searchsorted`` over all output tiles — cheap, but it depends only
    on (bounds, t_pad), never on the payload.  Splitting it out lets callers
    that expand the same GFJS level repeatedly memoize it (``GFJS._launch``,
    populated by `repro.kernels.ops.gfjs_expand_meta`) and lets the fused
    multi-payload kernel share one computation across K columns.
    """
    n = bounds.shape[0]
    num_blocks = max(-(-n // RB), 1)
    pad_to = num_blocks * RB + RB  # +RB so block b0+1 always exists
    total = bounds[-1] if n else jnp.int32(0)
    # pad bounds with `total` so idx saturates into the dead region
    bounds_p = jnp.full((pad_to,), total, dtype=jnp.int32).at[:n].set(bounds)

    grid = t_pad // OT
    tile_lo = jax.lax.iota(jnp.int32, grid) * OT
    start_run = jnp.searchsorted(bounds_p[:n] if n else bounds_p[:1],
                                 tile_lo, side="right").astype(jnp.int32)
    start_block = jnp.clip(start_run // RB, 0, num_blocks - 1).astype(jnp.int32)
    return bounds_p, start_block


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def expand_gather_with_meta(
    payload_p: jax.Array,    # [pad_to] — pre-padded payload
    bounds_p: jax.Array,     # [pad_to] int32 — padded prefix sums
    start_block: jax.Array,  # [t_pad // OT] int32
    *,
    t_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """Expansion against precomputed `launch_meta` (memoized-level path)."""
    return expand_call(payload_p[None], bounds_p, start_block, t_pad=t_pad,
                       interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def expand_gather(
    payload: jax.Array,   # [Np] int32 — per-run payload (values or indices)
    bounds: jax.Array,    # [Np] int32 — inclusive prefix sums of run lengths
    *,
    t_pad: int,           # static padded output length (multiple of OT)
    interpret: bool = False,
) -> jax.Array:
    """RLE-expand ``payload`` by run lengths encoded in ``bounds``."""
    bounds_p, start_block = launch_meta(bounds, t_pad=t_pad)
    payload_p = jnp.pad(payload, (0, bounds_p.shape[0] - payload.shape[0]))
    return expand_gather_with_meta(payload_p, bounds_p, start_block,
                                   t_pad=t_pad, interpret=interpret)
