"""Fused similarity × streaming top-k Pallas kernel (TPU target).

Serves TIFU-kNN neighbour search (paper §2.2) and the two-tower /
bert4rec ``retrieval_cand`` cells: Q queries against M corpus rows,
returning per-query top-k WITHOUT materializing the [Q, M] score matrix
in HBM — the win over the reference path at M = 10⁶.

Design (DESIGN.md §3.4 / §8):
  grid = (⌈Q/bq⌉, ⌈M/bm⌉), M innermost (sequential).  Per step the MXU
  computes a [bq, bm] score tile in VMEM (2·q@cᵀ − |c|², the monotone
  euclidean surrogate); a running [bq, k] top-k buffer lives in VMEM
  scratch and is merged tile-by-tile; only [Q, k] leaves the chip.

  Neither Q nor M needs to divide its block size: tail blocks are
  masked inside the kernel (out-of-range corpus columns score −inf,
  out-of-range query rows are write-masked by Pallas), so prime-sized
  request batches and corpora run the same schedule — no host-side
  padding copy of the corpus.

  Self-exclusion is fused into the scan: when ``query_gids`` is given,
  a column whose GLOBAL id equals the query's global id is masked to
  −inf in its score tile.  Column global ids are
  ``local_idx · col_stride + col_offset`` — identity for a single
  corpus, ``(row · n_shards + shard)`` for one shard of a user-axis
  sharded corpus (DESIGN.md §7.1), so a query user is excluded only on
  its owner shard.

  The merge is ``kernels.topk_merge.merge_topk`` (Mosaic has no
  ``top_k``): k extraction passes over the running list and the score
  tile in lax.top_k's order — score descending, lowest index first on
  ties (pinned by tests/test_serving_pipeline.py).  Stage A runs its
  f32 contractions at HIGHEST precision.

D-tiled variant (DESIGN.md §8.4): ``knn_topk`` holds full [bq, D] /
[bm, D] blocks in VMEM — an O(bq·D) residency that walls out at
D ≈ 64k items.  ``knn_topk_dtiled`` adds a third (innermost) grid axis
over D-tiles: per (qi, mi) the q·cᵀ contraction accumulates into a
running [bq, bm] f32 block accumulator over ⌈D/bd⌉ steps, and only at
the LAST D-tile are the scores finished (norm terms, tail mask, fused
self-exclusion — the same contracts as the monolithic kernel) and
merged into the running [bq, k] top-k.  VMEM residency is O(bq·bd +
bm·bd + bq·bm), flat in D.  The same kernel serves an int8 per-row
quantized corpus (DESIGN.md §8.4): each D-tile's partial dot runs on
the MXU in int8→int32 (exact for bd ≤ 1024: |Σ| ≤ bd·127² < 2²⁴, so
the per-tile partial converts to f32 exactly), the cross-tile f32
accumulation is order-fixed, and the per-row scales are applied once
at score-finish time — which makes the int8 scores bit-for-bit
reproducible against the XLA oracle (``kernels.ref.dtiled_topk_ref``).
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


def _corpus_sqnorm(c):
    """|c|² of each corpus row in a block, as a [1, bm] row, on the MXU.

    One fixed contraction per row whichever rows share the block, so a
    row's norm — and every score — does not depend on how the corpus is
    partitioned across shards; an XLA reduction's order depends on the
    array's shape.  Exact for int8 rows (integer squares < 2¹⁴, sums of
    one D-tile < 2²⁴).
    """
    cf = c.astype(jnp.float32)
    return jax.lax.dot_general(
        jnp.ones((1, c.shape[1]), jnp.float32), cf * cf,
        (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel(qid_ref, q_ref, c_ref, vals_ref, idx_ref, tile, acc_vals, acc_idx,
            new_vals, new_idx, *, k: int, bm: int, metric: str, m: int,
            col_offset: int, col_stride: int, sub_qnorm: bool):
    mi = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(mi == 0)
    def _init():
        init_topk(acc_vals, acc_idx)

    q = q_ref[...]                                   # [bq, D]
    c = c_ref[...]                                   # [bm, D]
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)          # [bq, bm]
    if metric == "euclidean":
        scores = 2.0 * scores - _corpus_sqnorm(c)
        if sub_qnorm:
            # full −|q−c|²: the shard-candidate path emits these scores
            # into the cross-shard merge, where they must be the same
            # per-pair values the reference path computes (§7.3); the
            # per-query constant is rank-irrelevant, so the single-
            # corpus path skips it.
            qf = q.astype(jnp.float32)
            scores = scores - jnp.sum(qf * qf, axis=1, keepdims=True)
    tile_idx = mi * bm + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    # tail mask: columns past the corpus end carry garbage (the block
    # read is out of bounds); they must never win the merge
    scores = jnp.where(tile_idx >= m, -jnp.inf, scores)
    # fused self-exclusion on GLOBAL ids (qid = -1 disables: gids >= 0)
    col_gid = tile_idx * col_stride + col_offset
    scores = jnp.where(col_gid == qid_ref[...], -jnp.inf, scores)

    tile[...] = scores
    merge_topk(acc_vals, acc_idx, new_vals, new_idx, tile, mi * bm, k)

    @pl.when(mi == nm - 1)
    def _done():
        vals_ref[...] = acc_vals[...]
        idx_ref[...] = acc_idx[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "bq", "bm", "metric", "interpret",
                                    "col_offset", "col_stride",
                                    "sub_qnorm"))
def knn_topk(queries, corpus, k: int, bq: int = 128, bm: int = 512,
             metric: str = "euclidean", interpret: bool = False,
             query_gids=None, col_offset: int = 0, col_stride: int = 1,
             sub_qnorm: bool = False):
    """queries [Q, D] × corpus [M, D] → (vals [Q, k], idx [Q, k]).

    ``idx`` are LOCAL corpus row indices; ``query_gids`` (i32[Q],
    optional) excludes the column whose global id
    ``idx·col_stride + col_offset`` equals the query's global id.
    Q and M need not divide ``bq``/``bm`` (masked tail blocks).  When
    ``k > M`` the trailing entries are −inf with unspecified indices —
    callers clamp (``ops.fused_recommend`` does).  ``sub_qnorm`` makes
    the euclidean scores the full −|q−c|² (the shard-candidate merge
    needs comparable values); off, they are the monotone surrogate
    2qc − |c|².
    """
    qn, d = queries.shape
    m = corpus.shape[0]
    if qn == 0 or m == 0:
        return (jnp.full((qn, k), -jnp.inf, jnp.float32),
                jnp.zeros((qn, k), jnp.int32))
    bq = min(bq, qn)
    bm = min(bm, m)
    if query_gids is None:
        query_gids = jnp.full((qn,), -1, jnp.int32)
    grid = (pl.cdiv(qn, bq), pl.cdiv(m, bm))
    # per-row side vectors travel as [Q, 1] columns / [1, M] rows: a 1-D
    # block does not match the chip's layout for a 1-D operand
    kernel = functools.partial(_kernel, k=k, bm=bm, metric=metric, m=m,
                               col_offset=col_offset, col_stride=col_stride,
                               sub_qnorm=sub_qnorm)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda qi, mi: (qi, 0)),
            pl.BlockSpec((bq, d), lambda qi, mi: (qi, 0)),
            pl.BlockSpec((bm, d), lambda qi, mi: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda qi, mi: (qi, 0)),
            pl.BlockSpec((bq, k), lambda qi, mi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bm), jnp.float32),  # score tile
            pltpu.VMEM((bq, k), jnp.float32),   # running top-k vals
            pltpu.VMEM((bq, k), jnp.int32),     # running top-k idx
            pltpu.VMEM((bq, k), jnp.float32),   # merge output vals
            pltpu.VMEM((bq, k), jnp.int32),     # merge output idx
        ],
        interpret=interpret,
    )(query_gids.astype(jnp.int32)[:, None], queries, corpus)


# ---------------------------------------------------------------------------
# D-tiled stage A (DESIGN.md §8.4): O(bq·bd) VMEM residency, int8 corpus
# ---------------------------------------------------------------------------

def tiled_sqnorm(x, bd: int):
    """Per-row squared norm Σᵢ x[r, i]², accumulated in D-tile order.

    Returns f32[M].  int8 rows sum each bd-wide tile exactly in int32
    (bd ≤ 1024 keeps the per-tile partial below 2²⁴, so the f32 convert
    is exact); f32 rows sum per tile in f32.  The cross-tile f32
    accumulation order is fixed (tile 0 first), matching the kernel's
    block accumulator — ``kernels.ref`` duplicates this function
    verbatim so the oracle stays import-free (parity pinned by
    tests/test_quantized_serving.py).
    """
    m, d = x.shape
    bd = max(1, min(bd, d))
    nt = pl.cdiv(d, bd)
    pad = nt * bd - d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    xt = x.reshape(m, nt, bd)
    if x.dtype == jnp.int8:
        per_tile = jnp.sum(xt.astype(jnp.int32) ** 2,
                           axis=-1).astype(jnp.float32)
    else:
        xf = xt.astype(jnp.float32)
        per_tile = jnp.sum(xf * xf, axis=-1)
    return jnp.cumsum(per_tile, axis=1)[:, -1]


def _dtiled_kernel(qid_ref, q_ref, c_ref, qn_ref, qs_ref, cs_ref, vals_ref,
                   idx_ref, acc, cn, acc_vals, acc_idx, new_vals, new_idx, *,
                   k: int,
                   bm: int, bd: int, m: int, d: int, col_offset: int,
                   col_stride: int, sub_qnorm: bool, quantized: bool):
    mi = pl.program_id(1)
    di = pl.program_id(2)
    nm = pl.num_programs(1)
    nd = pl.num_programs(2)

    @pl.when((mi == 0) & (di == 0))
    def _init_topk():
        init_topk(acc_vals, acc_idx)

    @pl.when(di == 0)
    def _init_acc():
        acc[...] = jnp.zeros_like(acc)
        cn[...] = jnp.zeros_like(cn)

    q = q_ref[...]                                   # [bq, bd]
    c = c_ref[...]                                   # [bm, bd]
    # tail D-lanes carry garbage (OOB block read): zero BOTH operands so
    # the contraction contributes exactly 0 (0·NaN would poison f32)
    lane = di * bd + jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
    q = jnp.where(lane < d, q, jnp.zeros_like(q))
    c = jnp.where(lane < d, c, jnp.zeros_like(c))
    cn[...] += _corpus_sqnorm(c)                     # [1, bm]
    if quantized:
        # exact int32 partial per tile; f32 convert exact for bd <= 1024
        part = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc[...] += part.astype(jnp.float32)
    else:
        acc[...] += jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _merge():
        qs = qs_ref[...]                             # [bq, 1]
        cs = cs_ref[...]                             # [1, bm]
        scores = (2.0 * (qs * cs) * acc[...] - (cs * cs) * cn[...])
        if sub_qnorm:
            scores = scores - qs * qs * qn_ref[...]
        tile_idx = mi * bm + jax.lax.broadcasted_iota(jnp.int32,
                                                      scores.shape, 1)
        scores = jnp.where(tile_idx >= m, -jnp.inf, scores)
        col_gid = tile_idx * col_stride + col_offset
        scores = jnp.where(col_gid == qid_ref[...], -jnp.inf, scores)
        acc[...] = scores
        merge_topk(acc_vals, acc_idx, new_vals, new_idx, acc, mi * bm, k)

    @pl.when((mi == nm - 1) & (di == nd - 1))
    def _done():
        vals_ref[...] = acc_vals[...]
        idx_ref[...] = acc_idx[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "bq", "bm", "bd", "interpret",
                                    "col_offset", "col_stride",
                                    "sub_qnorm"))
def knn_topk_dtiled(queries, corpus, k: int, bq: int = 128, bm: int = 512,
                    bd: int = 512, interpret: bool = False,
                    query_gids=None, col_offset: int = 0,
                    col_stride: int = 1, sub_qnorm: bool = False,
                    q_scale=None, c_scale=None):
    """D-tiled streaming top-k for million-item corpora (§8.4).

    queries [Q, D] × corpus [M, D] →
    (vals f32[Q, k], local idx i32[Q, k]).  The D axis is the third (innermost) grid dimension: VMEM residency
    is O(bq·bd + bm·bd + bq·bm) instead of the monolithic kernel's
    O(bq·D) — flat in D (DESIGN.md §8.4).  Scoring, tail-mask,
    self-exclusion (``query_gids``/``col_offset``/``col_stride``),
    ``sub_qnorm`` and the lowest-index tie-break follow :func:`knn_topk`
    exactly.  When ``queries``/``corpus`` are int8 (per-row symmetric
    quantization), ``q_scale`` f32[Q] / ``c_scale`` f32[M] are required:
    each D-tile's partial dot accumulates exactly in int32 on the MXU
    and the scales are applied once at score-finish, so the euclidean
    surrogate is ``2·s_q·s_c·(q₈·c₈) − s_c²·|c₈|²`` — bit-for-bit the
    XLA oracle's value (``ref.dtiled_topk_ref``; the scales must be the
    power-of-two ones of ``optim.compression.quantize_int8_rows``,
    which make every scale application an exact exponent shift and the
    score FMA-contraction-invariant).  Euclidean only; k >
    M leaves trailing −inf entries with unspecified indices (callers
    clamp, as in :func:`knn_topk`).  ``bd`` must stay ≤ 1024 on the
    int8 path (exact f32 convert of the per-tile int32 partial).
    """
    qn_, d = queries.shape
    m = corpus.shape[0]
    quantized = corpus.dtype == jnp.int8
    if quantized and (q_scale is None or c_scale is None):
        raise ValueError("int8 corpus requires q_scale and c_scale")
    if quantized and bd > 1024:
        raise ValueError(f"bd={bd} > 1024 breaks the exact int8 "
                         "per-tile f32 convert (DESIGN.md §8.4)")
    if qn_ == 0 or m == 0:
        return (jnp.full((qn_, k), -jnp.inf, jnp.float32),
                jnp.zeros((qn_, k), jnp.int32))
    bq = min(bq, qn_)
    bm = min(bm, m)
    bd = min(bd, d)
    if query_gids is None:
        query_gids = jnp.full((qn_,), -1, jnp.int32)
    qnorm = (tiled_sqnorm(queries, bd) if sub_qnorm
             else jnp.zeros((qn_,), jnp.float32))
    if q_scale is None:
        q_scale = jnp.ones((qn_,), jnp.float32)
        c_scale = jnp.ones((m,), jnp.float32)
    grid = (pl.cdiv(qn_, bq), pl.cdiv(m, bm), pl.cdiv(d, bd))
    kernel = functools.partial(_dtiled_kernel, k=k, bm=bm, bd=bd, m=m,
                               d=d, col_offset=col_offset,
                               col_stride=col_stride, sub_qnorm=sub_qnorm,
                               quantized=quantized)
    acc_dtype = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda qi, mi, di: (qi, 0)),
            pl.BlockSpec((bq, bd), lambda qi, mi, di: (qi, di)),
            pl.BlockSpec((bm, bd), lambda qi, mi, di: (mi, di)),
            pl.BlockSpec((bq, 1), lambda qi, mi, di: (qi, 0)),
            pl.BlockSpec((bq, 1), lambda qi, mi, di: (qi, 0)),
            pl.BlockSpec((1, bm), lambda qi, mi, di: (0, mi)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda qi, mi, di: (qi, 0)),
            pl.BlockSpec((bq, k), lambda qi, mi, di: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn_, k), jnp.float32),
            jax.ShapeDtypeStruct((qn_, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, bm), acc_dtype),    # running q·cᵀ partial
            pltpu.VMEM((1, bm), jnp.float32),   # running |c|² partial
            pltpu.VMEM((bq, k), jnp.float32),   # running top-k vals
            pltpu.VMEM((bq, k), jnp.int32),     # running top-k idx
            pltpu.VMEM((bq, k), jnp.float32),   # merge output vals
            pltpu.VMEM((bq, k), jnp.int32),     # merge output idx
        ],
        interpret=interpret,
    )(query_gids.astype(jnp.int32)[:, None], queries, corpus, qnorm[:, None],
      q_scale[:, None], c_scale[None, :])


# Kernel contracts (DESIGN.md §10.1): what tools/lint_kernels.py holds
# the pallas_call sites above to.  Grid axis 0 tails via Pallas OOB
# write masking; the corpus/D axes tail via the in-kernel masks quoted.
register(KernelContract(
    module="repro.kernels.knn_topk",
    entry="knn_topk",
    body="_kernel",
    grid_rank=2,
    tail={0: OOB_WRITE, 1: "tile_idx >= m"},
    accumulators=("float32", "float32", "int32", "float32", "int32"),
    vmem_model=_avmem.knn_topk_block_bytes,
    max_shapes={"d": 2048, "k": 300, "bq": 128, "bm": 512},
))
register(KernelContract(
    module="repro.kernels.knn_topk",
    entry="knn_topk_dtiled",
    body="_dtiled_kernel",
    grid_rank=3,
    tail={0: OOB_WRITE, 1: "tile_idx >= m", 2: "lane < d"},
    accumulators=("float32", "float32", "float32", "int32", "float32",
                  "int32"),
    vmem_model=_avmem.knn_topk_dtiled_block_bytes,
    max_shapes={"d": 1 << 20, "k": 512, "bq": 128, "bm": 512,
                "bd": 512},
))
