"""Sparse per-row gather from a [M, I] table (TPU Pallas).

The sparse decremental paths (core.updates.apply_del_basket_batch /
apply_del_item_batch, DESIGN.md §3.5) and the sparse add path both need
the *current raw values* of a [M, I] state table at a per-event support
``(rows[U], ids[U, W])`` with W ≪ I:

    vals[r, w] = table[rows[r], ids[r, w]]          (PAD ids give 0)

This is the read half of the ``sparse_row_scatter`` pair.  TPUs dislike
data-dependent gather, so per tile the read is a compare + reduce: the
[W, bi] one-hot of the row's ids against the item tile's iota,
contracted with the tile values.

Like the scatter, the grid is driven by a **touched-tile plan**
(kernels.tile_plan): grid ``(U, T_max)`` with the scalar-prefetched plan
arrays naming each step's ``(row, tile)``, so a step DMAs only the
``[8, bi]`` row group around a tile the row's ids actually touch (HBM
slices must be (8, 128)-aligned) from the table, which
stays in HBM (``memory_space=pl.ANY``) — O(U·W) HBM traffic, matching
the XLA reference path (kernels.ref.sparse_row_gather_ref, the CPU/GPU
path).  The plan keeps ``order="batch"``: reads commute, so duplicate
target rows need no sorting.  ``ids`` and the ``[U, W]`` output are
whole-array VMEM blocks; a step adds ``tile[1, bi] · onehot[bi, W]``
(one MXU contraction at HIGHEST precision, exact for a one-hot) into
its batch row.  Padding steps repeat the row's last real tile (no new
DMA) and are ``pl.when``-guarded out of the compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import vmem as _avmem
from repro.analysis.contracts import KernelContract, register
from repro.kernels.tile_plan import build_plan


def _kernel(pbatch_ref, prow_ref, ptile_ref, pvalid_ref, ids_ref, tab_hbm,
            out_ref, buf, sem, *, bi: int, t_max: int):
    del pbatch_ref  # the batch row is the grid row (order="batch")
    r = pl.program_id(0)
    t = pl.program_id(1)
    s = r * t_max + t
    row = prow_ref[s]
    tile = ptile_ref[s]
    sp = jnp.maximum(s - 1, 0)
    prev_same = (s > 0) & (prow_ref[sp] == row) & (ptile_ref[sp] == tile)

    @pl.when(s == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_not(prev_same))
    def _load():
        # HBM slices must be (8, 128)-aligned: fetch the row's 8-row group
        block = tab_hbm.at[pl.ds(pl.multiple_of(row // 8 * 8, 8), 8),
                           pl.ds(pl.multiple_of(tile * bi, bi), bi)]
        pltpu.make_async_copy(block, buf, sem).start()
        pltpu.make_async_copy(block, buf, sem).wait()

    @pl.when(pvalid_ref[s] == 1)
    def _accumulate():
        ids = ids_ref[pl.ds(r, 1), :]                # [1, W] i32, PAD=-1
        items = tile * bi + jax.lax.broadcasted_iota(
            jnp.int32, (bi, ids.shape[1]), 0)
        onehot = (items == ids).astype(jnp.float32)  # [bi, W]; PAD misses
        out_ref[pl.ds(r, 1), :] += jax.lax.dot_general(
            buf[pl.ds(row % 8, 1), :], onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)      # [1, W]


@functools.partial(jax.jit, static_argnames=("bi", "t_max", "interpret"))
def sparse_row_gather(table, rows, ids, bi: int = 512,
                      t_max: int | None = None, interpret: bool = False):
    """vals f32[U, W] = table[rows i32[U], ids i32[U, W]] (PAD ids → 0).

    Requires I % bi == 0 and ``t_max`` >= the largest per-row
    touched-tile count (None picks the always-safe ``min(W, I/bi)``) —
    the ops.py dispatcher selects both.
    """
    m, n_items = table.shape
    u, w = ids.shape
    bi = min(bi, n_items)
    assert n_items % bi == 0, (n_items, bi)
    n_tiles = n_items // bi
    if t_max is None:
        t_max = min(w, n_tiles)
    t_max = max(1, min(t_max, w, n_tiles))
    rows = jnp.clip(rows, 0, m - 1).astype(jnp.int32)
    ids = ids.astype(jnp.int32)
    plan = build_plan(rows, ids, bi=bi, t_max=t_max, order="batch")
    if interpret and m % 8:
        # the chip's HBM layout rounds rows up to the 8-row tile (see
        # sparse_row_scatter); the interpreter needs the same rows
        table = jnp.pad(table, ((0, -m % 8), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(u, t_max),
        in_specs=[
            pl.BlockSpec((u, w), lambda r, t, pb, pr, pt, pv: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((u, w), lambda r, t, pb, pr, pt, pv: (0, 0)),
        scratch_shapes=[pltpu.VMEM((8, bi), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bi=bi, t_max=t_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((u, w), jnp.float32),
        interpret=interpret,
    )(plan.batch, plan.row, plan.tile, plan.valid, ids, table)


# Kernel contract (DESIGN.md §10.1): plan-driven grid; the one scratch
# buffer is the DMA landing tile (the DMA semaphore is not a buffer and
# is not listed); divisible=True records the I % bi == 0 precondition
# asserted above.
register(KernelContract(
    module="repro.kernels.sparse_row_gather",
    entry="sparse_row_gather",
    body="_kernel",
    grid_rank=2,
    scalar_prefetch=4,
    divisible=True,
    accumulators=("float32",),
    vmem_model=_avmem.sparse_row_gather_block_bytes,
    max_shapes={"u": 256, "w": 2048, "bi": 512},
))
