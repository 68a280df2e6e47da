"""`mul_segsum` — fused multiply + segment-sum Pallas kernel.

This is the sum half of GJ's sum-product operation (message passing): given
entries sorted by (dense) segment id, compute ``out[s] = sum_i x[i]*y[i]``
over each segment.  On TPU the per-tile reduction is a one-hot matrix
product — an [T, T] f32 matmul that runs on the MXU — and the cross-tile
stitch (segments spanning tile boundaries add partials into the same slot)
is a tiny scatter-add done by XLA on the [num_tiles, T] partial matrix.

Why this shape: segment ids are *dense* (0..S-1, no gaps) by construction in
GJ (they come from run-boundary cumsums), so a tile of T entries touches at
most T distinct segments and the relative id ``seg - seg_first(tile)`` fits
in [0, T).  That bound is what lets the one-hot matrix be a fixed [T, T]
MXU tile instead of an unbounded scatter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

T = 512  # entries per tile; [T, T] one-hot fits VMEM (1 MiB f32)


def _mul_segsum_kernel(first_ref, seg_ref, x_ref, y_ref, part_ref):
    """Per-tile partial segment sums, relative to the tile's first id.

    Blocks are [1, T] rows.  ``first_ref`` is the scalar-prefetched
    per-tile first segment id (computed by the wrapper, so the kernel has
    no scalar output).  The one-hot is laid out [slot, entry] and
    contracted against the product row on its entry axis, which gives the
    per-slot sums directly as a lane-dense [1, T] row.
    """
    rel = seg_ref[...] - first_ref[pl.program_id(0)]         # [1, T] in [0, T)
    prod = x_ref[...] * y_ref[...]                           # [1, T] f32
    s = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)       # out slot
    onehot = (s == rel).astype(jnp.float32)                  # [T, T]
    # MXU: [1, T] x [T, T]^T — HIGHEST keeps integer products below 2**24
    # exact (the default f32 matmul may round operands to bf16)
    part_ref[...] = jax.lax.dot_general(
        prod, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def mul_segsum(
    seg_ids: jax.Array,   # [N] int32, sorted ascending, dense ids
    x: jax.Array,         # [N]
    y: jax.Array,         # [N]
    *,
    num_segments: int,
    interpret: bool = False,
) -> jax.Array:
    """sum_i x[i]*y[i] per segment; f32 accumulate (exact below 2**24)."""
    n = seg_ids.shape[0]
    n_pad = max(-(-n // T), 1) * T
    # pad with an out-of-range segment id so padding lands in a dead slot
    seg_p = jnp.full((n_pad,), num_segments, jnp.int32).at[:n].set(seg_ids)
    x_p = jnp.zeros((n_pad,), jnp.float32).at[:n].set(x)
    y_p = jnp.zeros((n_pad,), jnp.float32).at[:n].set(y)
    grid = n_pad // T
    first = seg_p[::T]                                       # [grid]

    def row(i, first_ref):
        # int32 literal: under x64 a bare 0 is an int64 Mosaic rejects
        return jnp.int32(0), i

    parts = pl.pallas_call(
        _mul_segsum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[pl.BlockSpec((1, T), row)] * 3,
            out_specs=pl.BlockSpec((1, T), row),
        ),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(first, seg_p[None], x_p[None], y_p[None])

    # stitch: scatter-add each tile's T relative slots at its first id
    parts = parts.reshape(grid, T)
    out = jnp.zeros((num_segments + T,), jnp.float32)
    idx = first[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(idx, num_segments + T - 1)
    out = out.at[idx.reshape(-1)].add(parts.reshape(-1))
    return out[:num_segments]
