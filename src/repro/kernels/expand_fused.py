"""`expand_gather_many` — fused multi-payload RLE-expansion Pallas kernel.

Desummarization and frontier expansion both expand *several* payload columns
by the *same* run-length structure: every variable of a GFJS level shares the
level's bounds, and a generation step needs (src, CSR start, offsets) plus
every frontier column expanded by one psi's counts.  Expanding column by
column (`expand_gather`, a K=1 launch) pays the 2*RB comparison-matrix run
search — the dominant VPU cost — once per column, plus one kernel launch
and one pass over the bounds window per column.

The kernel (`expand.py::_expand_kernel`, shared by both entry points)
recovers each output tile's run index **once** and then gathers
K payload rows with the same one-hot pick matrix: per output element the
search costs 2*RB int ops regardless of K, and the per-payload select-and-sum
is the only K-proportional term.  HBM traffic drops too — the bounds window
is read once instead of K times, and the scalar-prefetch `start_block`
metadata is computed (and memoizable, see `GFJS._launch`) once per level
instead of once per column.

Payloads ride as one [K, Np] int32 array; blocks are [K, RB] windows so the
whole payload stack for a run window is VMEM-resident (K * RB * 4 bytes —
kilobytes for any realistic level width).  The padding contract matches
`expand_gather`: runs [num_runs..Np) must carry bounds == total (zero
length), outputs [total..T_pad) replicate whatever the saturated run index
picks — callers slice [:, :total].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.expand import expand_call, launch_meta


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def expand_gather_many_with_meta(
    payloads: jax.Array,     # [K, pad_to] int32 — pre-padded payload stack
    bounds_p: jax.Array,     # [pad_to] int32 — padded inclusive prefix sums
    start_block: jax.Array,  # [t_pad // OT] int32 — per-tile window starts
    *,
    t_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused expansion against precomputed launch metadata ([K, t_pad])."""
    return expand_call(payloads, bounds_p, start_block, t_pad=t_pad,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def expand_gather_many(
    payloads: jax.Array,  # [K, Np] int32 — payload rows sharing one RLE
    bounds: jax.Array,    # [Np] int32 — inclusive prefix sums of run lengths
    *,
    t_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """RLE-expand K payload rows by the shared ``bounds`` in one pass."""
    bounds_p, start_block = launch_meta(bounds, t_pad=t_pad)
    pad_to = bounds_p.shape[0]
    payloads_p = jnp.pad(payloads, ((0, 0), (0, pad_to - payloads.shape[1])))
    return expand_gather_many_with_meta(
        payloads_p, bounds_p, start_block, t_pad=t_pad, interpret=interpret)
