"""Pallas kernel: in-place chunk-slab writeback into the persistent store.

The chunk-resident cohort store (DESIGN.md §16) runs each chunk's scan with
only the compact (U, d) slab of touched client rows in the carry, then
writes the slab back into the persistent (n, d) array ONCE per chunk.  On
backends with buffer donation this writeback should be truly in-place,
not an O(n·d) copy-and-update, which is what ``input_output_aliases``
expresses: the store is operand and output, and every tile the kernel does
not visit keeps its bytes because the output buffer IS the input buffer.

Index contract: ``idx`` holds each slab row's global client id, sorted
unique, padded to a static length with the sentinel ``n`` (one past the
last valid row), so the caller keeps shapes static across chunks however
many rows a chunk actually touched.  Sentinel rows are dropped, exactly as
XLA's ``mode="drop"`` scatter drops them.  ``accumulate=True`` switches the
row store to a read-add-write (scatter-accumulate), for callers that fold
partial slabs.

Layout: a TPU keeps an (n, d) f32 array with d < 128 column-major, so the
kernel works on the transposed (d, n) view, which is the same bytes: client
i is column i.  The grid walks the slab one row per step.  The row ids and
the count of valid rows arrive by scalar prefetch in SMEM, and the store's
BlockSpec index map reads them, so the pipeline DMAs only the (d, 128) lane
tiles that hold addressed clients between HBM and VMEM; the store itself
never enters VMEM.  Consecutive ids in one tile reuse the resident block.
Each step rotates its slab column onto the client's lane and selects it in
(a rotation and a select move bits exactly).  A row-wise DMA would be
simpler, but Mosaic refuses a slice of a 64-wide row of a 128-lane tile.
Sentinel steps map to the last valid tile and write nothing, so the tile
goes back to HBM with its updates intact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dasha_update import LANE


def slab_writeback_pallas(full: jax.Array, idx: jax.Array, rows: jax.Array,
                          *, accumulate: bool = False,
                          interpret: bool = True) -> jax.Array:
    """Scatter ``rows`` (U, d) into ``full`` (n, d) at ``idx`` (U,) int32.

    ``idx`` is sorted unique with the sentinel ``n`` padding its tail;
    sentinel entries are dropped.  Returns the updated store, aliased onto
    the ``full`` operand.
    """
    n, d = full.shape
    u = idx.shape[0]
    count = jnp.sum(idx < n, dtype=jnp.int32).reshape(1)

    def store_tile(j, idx_ref, cnt_ref):
        # sentinel steps revisit the last valid tile (the last tile when
        # no row is valid)
        last = jnp.maximum(cnt_ref[0] - 1, 0)
        return 0, jnp.minimum(idx_ref[jnp.minimum(j, last)], n - 1) // LANE

    def kernel(idx_ref, cnt_ref, cur_ref, new_ref, out_ref):
        j = pl.program_id(0)
        tile = store_tile(j, idx_ref, cnt_ref)[1]
        prev = store_tile(jnp.maximum(j - 1, 0), idx_ref, cnt_ref)[1]

        @pl.when((j == 0) | (tile != prev))
        def _load():
            out_ref[...] = cur_ref[...]

        @pl.when(j < cnt_ref[0])
        def _write():
            lane = idx_ref[j] % LANE
            new = pltpu.roll(new_ref[...], (lane - j % LANE) % LANE, 1)
            old = out_ref[...]
            if accumulate:
                new = old + new
            lanes = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
            out_ref[...] = jnp.where(lanes == lane, new, old)

    store_spec = pl.BlockSpec((d, LANE), store_tile)
    slab_spec = pl.BlockSpec((d, LANE), lambda j, idx_ref, cnt_ref:
                             (0, j // LANE))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(u,),
            in_specs=[store_spec, slab_spec], out_specs=store_spec),
        out_shape=jax.ShapeDtypeStruct((d, n), full.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="slab_writeback",
    )(idx, count, full.T, rows.T)
    return out.T
