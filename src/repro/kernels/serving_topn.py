"""Fused neighbour-blend + top-n Pallas kernels (serving stage B).

The TIFU-kNN prediction a request needs per (query q, item i) is

    p[q, i] = alpha · corpus[uid_q, i]
            + (1 − alpha)/k · Σ_{j ∈ topk(q)} corpus[j, i]

followed by a top-n over i.  The reference path materializes the
neighbour gather ``corpus[idx]`` — [Q, k, I] in HBM (80 GB at Q=4096,
k=300, I=16k) — plus a [Q, I] prediction round-trip.  These kernels
keep both on chip (DESIGN.md §8):

``blend_topn_onehot`` — the single-corpus fused path.  grid =
(⌈Q/bq⌉, ⌈I/bi⌉, ⌈M/bm⌉), M innermost: per item tile the neighbour sum
accumulates as a **one-hot matmul** ``member[bq, bm] @ corpus[bm, bi]``
on the MXU (the [Q, M] membership counts are one XLA scatter of the
[Q, k] index lists, read tile by tile beside the corpus), the query row
is recovered the same way (``uid`` one-hot — no [Q, I] query gather at
all), and after the last corpus tile the blended prediction tile merges
into a running [bq, n] top-n buffer.  Only [Q, n] leaves the chip; HBM
traffic is O(Q/bq · M · I) corpus reads + O(Q · M · I/bi) count reads.

``blend_topn_rows`` — the cross-shard path (DESIGN.md §7.3), where the
k selected neighbour rows were already fetched from their owner shards
([Q, k, I] is the unavoidable cross-shard traffic).  grid =
(⌈Q/bq⌉, ⌈I/bi⌉): mean-over-k + blend + running top-n per item tile —
the [Q, I] prediction intermediate never exists.

Every merge is ``kernels.topk_merge.merge_topk``, which keeps
lax.top_k's lowest-index tie-break.  Tail blocks in Q, I and M are
masked in-kernel, so no dimension needs to divide its block size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import vmem as _avmem
from repro.analysis.contracts import OOB_WRITE, KernelContract, register
from repro.kernels.topk_merge import init_topk, merge_topk

_HIGHEST = jax.lax.Precision.HIGHEST


def _onehot_kernel(uid_ref, cnt_ref, c_ref, vals_ref, ids_ref, acc_self,
                   acc_nbr, top_vals, top_idx, new_vals, new_idx, *, k: int,
                   alpha: float, topn: int, bm: int, bi: int, m: int,
                   n_items: int):
    ii = pl.program_id(1)
    mi = pl.program_id(2)
    ni = pl.num_programs(1)
    nm = pl.num_programs(2)

    @pl.when((ii == 0) & (mi == 0))
    def _init_topn():
        init_topk(top_vals, top_idx)

    @pl.when(mi == 0)
    def _init_acc():
        acc_self[...] = jnp.zeros_like(acc_self)
        acc_nbr[...] = jnp.zeros_like(acc_nbr)

    rows = mi * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    c = c_ref[...]                                    # [bm, bi]
    # tail corpus rows carry garbage (OOB block read) — zero them so the
    # contraction below cannot leak NaN into valid accumulator lanes
    row_col = mi * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bi), 0)
    c = jnp.where(row_col < m, c, 0.0)
    uid = uid_ref[...]                                # [bq, 1]
    # self row via one-hot matmul: exactly corpus[uid] (one 1.0 per row)
    self_sel = (uid == rows).astype(jnp.float32)
    acc_self[...] += jax.lax.dot_general(
        self_sel, c, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    # neighbour membership counts of this corpus tile (tail columns are
    # out-of-bounds garbage like the corpus rows)
    member = jnp.where(rows < m, cnt_ref[...], 0.0)   # [bq, bm]
    acc_nbr[...] += jax.lax.dot_general(
        member, c, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(mi == nm - 1)
    def _merge():
        pred = (alpha * acc_self[...]
                + (1.0 - alpha) * acc_nbr[...] / k)   # [bq, bi]
        item_ids = ii * bi + jax.lax.broadcasted_iota(jnp.int32,
                                                      pred.shape, 1)
        acc_self[...] = jnp.where(item_ids >= n_items, -jnp.inf, pred)
        merge_topk(top_vals, top_idx, new_vals, new_idx, acc_self, ii * bi,
                   topn)

    @pl.when((ii == ni - 1) & (mi == nm - 1))
    def _done():
        vals_ref[...] = top_vals[...]
        ids_ref[...] = top_idx[...]


@functools.partial(jax.jit, static_argnames=("alpha", "topn", "bq", "bm",
                                             "bi", "n_items", "interpret"))
def blend_topn_onehot(corpus, user_ids, nbr_idx, alpha: float, topn: int,
                      bq: int = 128, bm: int = 512, bi: int = 512,
                      n_items: int | None = None, interpret: bool = False):
    """Fused one-hot blend + top-n over the corpus (stage B, §8.1).

    corpus [M, I] × user_ids i32[Q] × nbr_idx i32[Q, k] →
    (vals f32[Q, topn], item ids i32[Q, topn]).  ``nbr_idx`` are local corpus rows (entries of −1 contribute zero but
    still count toward the mean divisor k, matching the reference mean
    over a fixed k).  ``user_ids`` select the query rows — the alpha
    term reads them through the same one-hot contraction, so the [Q, I]
    query gather never materializes.  Only the first ``n_items``
    columns (None: all) are candidates for the top-n.
    """
    q_n = user_ids.shape[0]
    m, n_cols = corpus.shape
    n_items = n_cols if n_items is None else n_items
    k = nbr_idx.shape[1]
    if q_n == 0 or m == 0:
        return (jnp.full((q_n, topn), -jnp.inf, jnp.float32),
                jnp.zeros((q_n, topn), jnp.int32))
    bq = min(bq, q_n)
    bm = min(bm, m)
    bi = min(bi, n_cols)
    grid = (pl.cdiv(q_n, bq), pl.cdiv(n_cols, bi), pl.cdiv(m, bm))
    kernel = functools.partial(_onehot_kernel, k=k, alpha=float(alpha),
                               topn=topn, bm=bm, bi=bi, m=m,
                               n_items=n_items)
    # membership counts [Q, M]; −1 entries scatter out of range (dropped)
    counts = jnp.zeros((q_n, m), jnp.float32).at[
        jnp.arange(q_n)[:, None], jnp.where(nbr_idx >= 0, nbr_idx, m)
    ].add(1.0, mode="drop")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda qi, ii, mi: (qi, 0)),
            pl.BlockSpec((bq, bm), lambda qi, ii, mi: (qi, mi)),
            pl.BlockSpec((bm, bi), lambda qi, ii, mi: (mi, ii)),
        ],
        out_specs=[
            pl.BlockSpec((bq, topn), lambda qi, ii, mi: (qi, 0)),
            pl.BlockSpec((bq, topn), lambda qi, ii, mi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, topn), jnp.float32),
            jax.ShapeDtypeStruct((q_n, topn), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bi), jnp.float32),    # alpha (self) partial
            pltpu.VMEM((bq, bi), jnp.float32),    # neighbour-sum partial
            pltpu.VMEM((bq, topn), jnp.float32),  # running top-n vals
            pltpu.VMEM((bq, topn), jnp.int32),    # running top-n ids
            pltpu.VMEM((bq, topn), jnp.float32),  # merge output vals
            pltpu.VMEM((bq, topn), jnp.int32),    # merge output ids
        ],
        interpret=interpret,
    )(user_ids.astype(jnp.int32)[:, None], counts, corpus)


def _rows_kernel(q_ref, nbr_ref, vals_ref, ids_ref, tile, top_vals, top_idx,
                 new_vals, new_idx, *, alpha: float, topn: int, bi: int,
                 n_items: int):
    ii = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(ii == 0)
    def _init():
        init_topk(top_vals, top_idx)

    neighbors = jnp.mean(nbr_ref[...], axis=1)        # [bq, bi]
    pred = (alpha * q_ref[...] + (1.0 - alpha) * neighbors
            ).astype(jnp.float32)
    item_ids = ii * bi + jax.lax.broadcasted_iota(jnp.int32, pred.shape, 1)
    tile[...] = jnp.where(item_ids >= n_items, -jnp.inf, pred)
    merge_topk(top_vals, top_idx, new_vals, new_idx, tile, ii * bi, topn)

    @pl.when(ii == ni - 1)
    def _done():
        vals_ref[...] = top_vals[...]
        ids_ref[...] = top_idx[...]


@functools.partial(jax.jit, static_argnames=("alpha", "topn", "bq", "bi",
                                             "n_items", "interpret"))
def blend_topn_rows(queries, neighbor_rows, alpha: float, topn: int,
                    bq: int = 8, bi: int = 512, n_items: int | None = None,
                    interpret: bool = False):
    """Blend pre-fetched neighbour rows and emit top-n (stage B, §7.3).

    queries [Q, I] × neighbor_rows [Q, k, I] →
    (vals f32[Q, topn], item ids i32[Q, topn]).
    The cross-shard final stage: the k rows were already fetched, so the
    fusion win is skipping the [Q, I] prediction intermediate — mean,
    blend and the top-n merge run per item tile.  ``bq`` defaults low:
    a [bq, k, bi] neighbour block must fit VMEM.  ``n_items`` as in
    :func:`blend_topn_onehot`.
    """
    q_n, n_cols = queries.shape
    n_items = n_cols if n_items is None else n_items
    k = neighbor_rows.shape[1]
    if q_n == 0:
        return (jnp.full((0, topn), -jnp.inf, jnp.float32),
                jnp.zeros((0, topn), jnp.int32))
    bq = min(bq, q_n)
    bi = min(bi, n_cols)
    grid = (pl.cdiv(q_n, bq), pl.cdiv(n_cols, bi))
    kernel = functools.partial(_rows_kernel, alpha=float(alpha), topn=topn,
                               bi=bi, n_items=n_items)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bi), lambda qi, ii: (qi, ii)),
            pl.BlockSpec((bq, k, bi), lambda qi, ii: (qi, 0, ii)),
        ],
        out_specs=[
            pl.BlockSpec((bq, topn), lambda qi, ii: (qi, 0)),
            pl.BlockSpec((bq, topn), lambda qi, ii: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, topn), jnp.float32),
            jax.ShapeDtypeStruct((q_n, topn), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bi), jnp.float32),    # prediction tile
            pltpu.VMEM((bq, topn), jnp.float32),  # running top-n vals
            pltpu.VMEM((bq, topn), jnp.int32),    # running top-n ids
            pltpu.VMEM((bq, topn), jnp.float32),  # merge output vals
            pltpu.VMEM((bq, topn), jnp.int32),    # merge output ids
        ],
        interpret=interpret,
    )(queries, neighbor_rows)


def _rows_quant_kernel(q_ref, qs_ref, nbr_ref, ns_ref, vals_ref, ids_ref,
                       tile, top_vals, top_idx, new_vals, new_idx, *,
                       alpha: float, topn: int, bi: int, n_items: int):
    ii = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(ii == 0)
    def _init():
        init_topk(top_vals, top_idx)

    # dequantize in VMEM: only int8 rows crossed HBM (4× less traffic)
    nbr = nbr_ref[...].astype(jnp.float32) * ns_ref[...][:, :, None]
    neighbors = jnp.mean(nbr, axis=1)                 # [bq, bi]
    q = q_ref[...].astype(jnp.float32) * qs_ref[...]
    pred = (alpha * q + (1.0 - alpha) * neighbors).astype(jnp.float32)
    item_ids = ii * bi + jax.lax.broadcasted_iota(jnp.int32, pred.shape, 1)
    tile[...] = jnp.where(item_ids >= n_items, -jnp.inf, pred)
    merge_topk(top_vals, top_idx, new_vals, new_idx, tile, ii * bi, topn)

    @pl.when(ii == ni - 1)
    def _done():
        vals_ref[...] = top_vals[...]
        ids_ref[...] = top_idx[...]


@functools.partial(jax.jit, static_argnames=("alpha", "topn", "bq", "bi",
                                             "n_items", "interpret"))
def blend_topn_rows_quant(queries_q, q_scale, neighbor_rows_q, n_scale,
                          alpha: float, topn: int, bq: int = 8,
                          bi: int = 512, n_items: int | None = None,
                          interpret: bool = False):
    """Quantized stage-B blend (DESIGN.md §8.4), int8 rows in VMEM.

    queries_q int8[Q, I] × neighbor_rows_q int8[Q, k, I] →
    (vals f32[Q, topn], ids i32[Q, topn]).  The int8 twin of :func:`blend_topn_rows`: the k selected rows cross
    HBM quantized (¼ the bytes of the fp32 fetch) with their per-row
    scales (``q_scale`` f32[Q], ``n_scale`` f32[Q, k]), and are
    dequantized in VMEM — exact elementwise multiplies, so the blended
    prediction matches ``ref.blend_topn_rows_quant_ref`` on the same
    operands.  Mean divisor, tail-mask and the lowest-index tie-break
    follow :func:`blend_topn_rows`.  VMEM per step is O(bq·k·bi) int8 +
    f32 dequant scratch; ``bq`` defaults low accordingly.  ``n_items``
    as in :func:`blend_topn_onehot`.
    """
    q_n, n_cols = queries_q.shape
    n_items = n_cols if n_items is None else n_items
    k = neighbor_rows_q.shape[1]
    if q_n == 0:
        return (jnp.full((0, topn), -jnp.inf, jnp.float32),
                jnp.zeros((0, topn), jnp.int32))
    bq = min(bq, q_n)
    bi = min(bi, n_cols)
    grid = (pl.cdiv(q_n, bq), pl.cdiv(n_cols, bi))
    kernel = functools.partial(_rows_quant_kernel, alpha=float(alpha),
                               topn=topn, bi=bi, n_items=n_items)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bi), lambda qi, ii: (qi, ii)),
            pl.BlockSpec((bq, 1), lambda qi, ii: (qi, 0)),
            pl.BlockSpec((bq, k, bi), lambda qi, ii: (qi, 0, ii)),
            pl.BlockSpec((bq, k), lambda qi, ii: (qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, topn), lambda qi, ii: (qi, 0)),
            pl.BlockSpec((bq, topn), lambda qi, ii: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, topn), jnp.float32),
            jax.ShapeDtypeStruct((q_n, topn), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bi), jnp.float32),    # prediction tile
            pltpu.VMEM((bq, topn), jnp.float32),  # running top-n vals
            pltpu.VMEM((bq, topn), jnp.int32),    # running top-n ids
            pltpu.VMEM((bq, topn), jnp.float32),  # merge output vals
            pltpu.VMEM((bq, topn), jnp.int32),    # merge output ids
        ],
        interpret=interpret,
    )(queries_q, q_scale[:, None], neighbor_rows_q, n_scale)


# Kernel contracts (DESIGN.md §10.1).  Query-axis tails are handled by
# Pallas OOB write masking; item/corpus axes by the quoted in-kernel
# masks.
register(KernelContract(
    module="repro.kernels.serving_topn",
    entry="blend_topn_onehot",
    body="_onehot_kernel",
    grid_rank=3,
    tail={0: OOB_WRITE, 1: "item_ids >= n_items", 2: "row_col < m"},
    accumulators=("float32", "float32", "float32", "int32", "float32",
                  "int32"),
    vmem_model=_avmem.blend_topn_onehot_block_bytes,
    max_shapes={"topn": 512, "bq": 128, "bm": 512,
                "bi": 512},
))
register(KernelContract(
    module="repro.kernels.serving_topn",
    entry="blend_topn_rows",
    body="_rows_kernel",
    grid_rank=2,
    tail={0: OOB_WRITE, 1: "item_ids >= n_items"},
    accumulators=("float32", "float32", "int32", "float32", "int32"),
    vmem_model=_avmem.blend_topn_rows_block_bytes,
    max_shapes={"k": 300, "topn": 512, "bq": 8, "bi": 512},
))
register(KernelContract(
    module="repro.kernels.serving_topn",
    entry="blend_topn_rows_quant",
    body="_rows_quant_kernel",
    grid_rank=2,
    tail={0: OOB_WRITE, 1: "item_ids >= n_items"},
    accumulators=("float32", "float32", "int32", "float32", "int32"),
    vmem_model=_avmem.blend_topn_rows_quant_block_bytes,
    max_shapes={"k": 900, "topn": 512, "bq": 8, "bi": 512},
))
