"""Analytic per-kernel VMEM block-residency models (DESIGN.md §10.2).

Each ``<kernel>_block_bytes`` function returns the EXACT number of bytes
of one grid step's block-resident state: every ``in_specs``/``out_specs``
block at its block shape and operand itemsize, plus every VMEM scratch
buffer.  Scalar-prefetch operands live in SMEM and operands left in HBM
(``memory_space=pl.ANY``) are reached by DMA; both are excluded.  The
property test (tests/test_vmem_model.py) pins these against the specs an
interpret-mode ``pallas_call`` actually receives, and the KC03 lint rule
evaluates each registered contract's model at its declared max shapes
with :func:`fits_vmem`.

:func:`stage_a_vmem_bytes` is the coarser *capacity-planning* model the
serving benchmark sweeps record (operand blocks + the score tile +
top-k, dropping O(bq + bm) vectors); it lives here so the kernel models
and the planning model share one module, and ``kernels.ops`` re-exports
it unchanged.
"""
from __future__ import annotations

# Scoped VMEM the chip's compiler grants a kernel by default (TPU v5e).
VMEM_BUDGET_BYTES = 16 * 2 ** 20
# The Pallas pipeline double-buffers every blocked operand, and the
# compiler adds temporaries of its own (the HIGHEST-precision f32
# contraction's operand splits among them).  Measured for the monolithic
# stage A on a described v5e (k=300, bq=128, bm=512): D=2048 compiles
# (2 × 6.43 MB of blocks), D=2560 is refused at 18.64 MiB scoped against
# 2 × 7.74 MB of blocks.  The check doubles the block model and adds
# that headroom.
PIPELINE_BUFFERS = 2
COMPILER_HEADROOM_BYTES = 3 * 2 ** 20


def fits_vmem(block_bytes: int) -> bool:
    """Whether a kernel with this block model fits the scoped VMEM."""
    return (PIPELINE_BUFFERS * block_bytes + COMPILER_HEADROOM_BYTES
            <= VMEM_BUDGET_BYTES)


def stage_a_vmem_bytes(d: int, k: int, bq: int = 128, bm: int = 512,
                       bd: int | None = None,
                       itemsize: int = 4) -> int:
    """Analytic peak VMEM residency (bytes) of one stage-A grid step.

    Monolithic (``bd=None``): the [bq, D] query and [bm, D] corpus
    blocks dominate — linear in the item count D.  With the pipeline's
    double buffering and the compiler's temporaries (:func:`fits_vmem`)
    the wall lies between D = 2048 (compiles) and D = 2560 (refused) at
    bq=128, bm=512, k=300 on a v5e.
    D-tiled: [bq, bd] + [bm, bd] operand
    blocks (``itemsize`` bytes: 4 fp32, 1 int8) + the f32 [bq, bm]
    accumulator — flat in D.  Both include the f32+i32 [bq, k] running
    top-k and its merge output.  This is the model `benchmarks/bench_serving.py --scale`
    records per sweep point (DESIGN.md §8.2's table is generated from
    it); it counts double-buffered operand blocks once, so real
    residency is ≤ 2× for the streamed inputs.
    """
    topk = 2 * bq * k * (4 + 4)        # running list + merge output
    if bd is None:
        return (bq * d + bm * d) * itemsize + bq * bm * 4 + topk
    bd = min(bd, d)
    return (bq * bd + bm * bd) * itemsize + bq * bm * 4 + topk


def knn_topk_block_bytes(d: int, k: int, bq: int = 128, bm: int = 512,
                         itemsize: int = 4) -> int:
    """Monolithic stage A: qid[bq] + q[bq,d] + c[bm,d] in, 2×[bq,k]
    out, [bq,bm] score tile + 4×[bq,k] top-k/merge scratch."""
    return (bq * 4 + (bq + bm) * d * itemsize
            + 2 * bq * k * 4 + bq * bm * 4 + 4 * bq * k * 4)


def knn_topk_dtiled_block_bytes(d: int, k: int, bq: int = 128,
                                bm: int = 512, bd: int = 512,
                                itemsize: int = 4) -> int:
    """D-tiled stage A: qid/qn/qs[bq] + cs[bm] + q[bq,bd] + c[bm,bd]
    in, 2×[bq,k] out, [bq,bm] + [bm] f32 accumulators + 4×[bq,k]
    top-k/merge scratch."""
    bd = min(bd, d)
    return (3 * bq * 4 + 2 * bm * 4 + (bq + bm) * bd * itemsize
            + 2 * bq * k * 4 + bq * bm * 4 + 4 * bq * k * 4)


def blend_topn_onehot_block_bytes(topn: int, bq: int = 128,
                                  bm: int = 512, bi: int = 512) -> int:
    """One-hot stage B: uid[bq] + counts[bq,bm] + corpus[bm,bi] in,
    2×[bq,topn] out, 2×[bq,bi] + 4×[bq,topn] scratch."""
    return (bq * 4 + bq * bm * 4 + bm * bi * 4
            + 2 * bq * topn * 4 + 2 * bq * bi * 4 + 4 * bq * topn * 4)


def blend_topn_rows_block_bytes(k: int, topn: int, bq: int = 8,
                                bi: int = 512) -> int:
    """Cross-shard stage B: q[bq,bi] + nbr[bq,k,bi] in, 2×[bq,topn]
    out, [bq,bi] prediction tile + 4×[bq,topn] scratch.  The [bq,k,bi]
    block dominates — bq defaults low accordingly."""
    return (bq * bi * 4 + bq * k * bi * 4
            + 2 * bq * topn * 4 + bq * bi * 4 + 4 * bq * topn * 4)


def blend_topn_rows_quant_block_bytes(k: int, topn: int, bq: int = 8,
                                      bi: int = 512) -> int:
    """Quantized stage B: int8 q[bq,bi] + nbr[bq,k,bi] (itemsize 1) +
    f32 scales qs[bq] + ns[bq,k] in, 2×[bq,topn] out, [bq,bi] f32
    prediction tile + 4×[bq,topn] scratch."""
    return (bq * bi + bq * k * bi + bq * 4 + bq * k * 4
            + 2 * bq * topn * 4 + bq * bi * 4 + 4 * bq * topn * 4)


def sparse_row_scatter_block_bytes(u: int, w: int, bi: int = 512) -> int:
    """Planned scatter: whole ids[u,w] + vals[u,w] in, [8,bi] f32 DMA
    accumulator (the table stays in HBM; plan arrays are
    scalar-prefetch)."""
    return 2 * u * w * 4 + 8 * bi * 4


def sparse_row_gather_block_bytes(u: int, w: int, bi: int = 512) -> int:
    """Planned gather: whole ids[u,w] in, whole [u,w] out, [8,bi] f32
    DMA landing tile (the table stays in HBM)."""
    return 2 * u * w * 4 + 8 * bi * 4


def decayed_scatter_block_bytes(b: int, bn: int = 256,
                                bi: int = 512) -> int:
    """Multi-hot scatter: ids[bn,b] + w[bn] in, [bi] out, [bi] scratch."""
    return bn * b * 4 + bn * 4 + bi * 4 + bi * 4


def flash_attention_block_bytes(d: int, bq: int = 128, bk: int = 128,
                                itemsize: int = 4) -> int:
    """Attention: q[1,bq,d] + k/v[1,bk,d] in, [1,bq,d] out, f32
    (max[bq], denom[bq], acc[bq,d]) scratch."""
    return ((bq + 2 * bk + bq) * d * itemsize
            + 2 * bq * 4 + bq * d * 4)
