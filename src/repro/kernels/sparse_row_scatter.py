"""Sparse per-row scatter-add into a [M, I] table (TPU Pallas).

The batched add path (core.updates.apply_add_batch, DESIGN.md §3.3)
produces per-event deltas whose support is only the touched items:
``(rows[U], ids[U, W], vals[U, W])`` with W ≪ I.  This kernel applies

    table[rows[r], ids[r, w]] += vals[r, w]        (PAD ids skipped)

in place (``input_output_aliases``), so the full [M, I] state never
leaves HBM.  TPUs dislike data-dependent scatter, so per tile the update
is a compare + reduce: the [W, bi] one-hot of a row's ids against the
item tile's iota, contracted with vals.

The grid is driven by a **touched-tile plan** (kernels.tile_plan): the
``(U, T_max)`` step sequence enumerates only the ``(target row, item
tile)`` blocks some row's ids actually touch, sorted by (row, tile) so
every block's visits — including visits contributed by duplicate
target rows — are *consecutive* grid steps.  The table stays in HBM
(``memory_space=pl.ANY``, aliased in place): the first step of a run
DMAs the ``[8, bi]`` row group holding the target row into a VMEM
accumulator (HBM slices must be (8, 128)-aligned), every valid step adds
its row's contribution to that row's sublane, and the run's last step
DMAs the group back.  Padding steps clone the previous work item and
are ``pl.when``-guarded out of the compute, so HBM traffic is O(U·W) —
matching the XLA reference path's asymptotics
(kernels.ref.sparse_row_scatter_ref, the CPU/GPU path) and the paper's
flat latency-vs-vocabulary curve.

``ids``/``vals`` are whole-array VMEM blocks (only ``[U, W]``); a step
reads its batch row ``plan.batch[s]`` from them.  Per step the update
is one MXU contraction ``vals[1, W] · onehot[bi, W]ᵀ`` against the
item tile's iota, at HIGHEST precision so each one-hot product is the
exact f32 value.  The DMAs are synchronous, so a later run on a
neighbouring row of the same 8-row group always reads what an earlier
run wrote.
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


def _kernel(pbatch_ref, prow_ref, ptile_ref, pvalid_ref, ids_ref, vals_ref,
            tab_hbm, out_hbm, acc, sem, *, bi: int, t_max: int):
    del tab_hbm  # aliased to out_hbm: every read and write goes there
    r = pl.program_id(0)
    t = pl.program_id(1)
    s = r * t_max + t
    ns = pl.num_programs(0) * t_max

    row = prow_ref[s]
    tile = ptile_ref[s]
    sp = jnp.maximum(s - 1, 0)
    sn = jnp.minimum(s + 1, ns - 1)
    prev_same = (s > 0) & (prow_ref[sp] == row) & (ptile_ref[sp] == tile)
    next_same = (s < ns - 1) & (prow_ref[sn] == row) & (ptile_ref[sn] == tile)
    # HBM slices must be (8, 128)-aligned: move the 8-row group holding
    # ``row`` and work on its sublane ``row % 8``
    block = out_hbm.at[pl.ds(pl.multiple_of(row // 8 * 8, 8), 8),
                       pl.ds(pl.multiple_of(tile * bi, bi), bi)]

    @pl.when(jnp.logical_not(prev_same))
    def _load():
        pltpu.make_async_copy(block, acc, sem).start()
        pltpu.make_async_copy(block, acc, sem).wait()

    @pl.when(pvalid_ref[s] == 1)
    def _accumulate():
        b = pbatch_ref[s]
        ids = ids_ref[pl.ds(b, 1), :]                # [1, W] i32, PAD=-1
        vals = vals_ref[pl.ds(b, 1), :]              # [1, W] f32
        items = tile * bi + jax.lax.broadcasted_iota(
            jnp.int32, (bi, ids.shape[1]), 0)
        onehot = (items == ids).astype(jnp.float32)  # [bi, W]; PAD misses
        acc[pl.ds(row % 8, 1), :] += jax.lax.dot_general(
            vals, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)      # [1, bi]

    @pl.when(jnp.logical_not(next_same))
    def _store():
        pltpu.make_async_copy(acc, block, sem).start()
        pltpu.make_async_copy(acc, block, sem).wait()


@functools.partial(jax.jit, static_argnames=("bi", "t_max", "interpret"))
def sparse_row_scatter(table, rows, ids, vals, bi: int = 512,
                       t_max: int | None = None, interpret: bool = False):
    """Scatter-add sparse per-row deltas into ``table`` in place.

    table f32[M, I] += scatter(rows i32[U], ids i32[U, W] PAD=-1,
    vals f32[U, W]); returns the updated table (aliased via
    ``input_output_aliases``).  Duplicate rows are handled (the tile
    plan sorts every (row, tile) block's visits onto consecutive grid
    steps, accumulating).  Requires I % bi == 0 and ``t_max`` >= the
    largest per-row touched-tile count (None picks the always-safe
    ``min(W, I/bi)``); the ops.py dispatcher selects both.
    """
    m, n_items = table.shape
    u, w = ids.shape
    bi = min(bi, n_items)
    assert n_items % bi == 0, (n_items, bi)
    n_tiles = n_items // bi
    if t_max is None:
        t_max = min(w, n_tiles)
    t_max = max(1, min(t_max, w, n_tiles))
    order = jnp.argsort(rows, stable=True)
    rows_s = jnp.clip(rows[order], 0, m - 1).astype(jnp.int32)
    ids_s = ids[order].astype(jnp.int32)
    vals_s = jnp.where(ids_s >= 0, vals[order], 0.0).astype(jnp.float32)
    plan = build_plan(rows_s, ids_s, bi=bi, t_max=t_max, order="target")
    pad = -m % 8
    if interpret and pad:
        # the chip's HBM layout rounds rows up to the 8-row tile, so the
        # last group's DMA stays in bounds there; the interpreter needs
        # the same rows to exist
        table = jnp.pad(table, ((0, pad), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(u, t_max),
        in_specs=[
            pl.BlockSpec((u, w), lambda r, t, pb, pr, pt, pv: (0, 0)),
            pl.BlockSpec((u, w), lambda r, t, pb, pr, pt, pv: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((8, bi), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bi=bi, t_max=t_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={6: 0},   # table (after prefetch + ids/vals)
        interpret=interpret,
    )(plan.batch, plan.row, plan.tile, plan.valid, ids_s, vals_s, table)
    return out[:m] if interpret and pad else out


# Kernel contract (DESIGN.md §10.1).  The (U, T_max) grid axes are
# plan-driven (neither cdiv nor exact division of an array axis);
# divisible=True records the I % bi == 0 precondition asserted above.
# The DMA semaphore is not a buffer and is not listed.
register(KernelContract(
    module="repro.kernels.sparse_row_scatter",
    entry="sparse_row_scatter",
    body="_kernel",
    grid_rank=2,
    scalar_prefetch=4,
    divisible=True,
    accumulators=("float32",),
    vmem_model=_avmem.sparse_row_scatter_block_bytes,
    max_shapes={"u": 256, "w": 2048, "bi": 512},
))
