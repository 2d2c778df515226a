"""Jit'd dispatch wrappers: Pallas on TPU, reference on CPU.

The public entry points the rest of the system calls; each picks the
implementation for the current backend and is guaranteed (by
tests/test_kernels.py shape/dtype sweeps) to match the ref.py oracles.
``impl="auto"`` runs the compiled Pallas kernel on a TPU and the XLA
reference elsewhere; ``"pallas"`` demands the compiled kernel and raises
off a TPU; ``"interpret"`` runs the kernel bodies in the Pallas
interpreter on any host; ``"ref"`` runs the XLA reference
(:func:`resolve_impl`).

``default_impl`` overrides the per-call default process-wide — the
benchmark's ``--backend interpret`` arm and the interpret-mode stream
equivalence tests route the *whole* update pipeline through the Pallas
kernels on CPU with it.  The override is read at trace time, so entering
or leaving the context clears jax's compilation caches.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import vmem as _analysis_vmem
from repro.kernels import ref, tile_plan
from repro.kernels.decayed_scatter import (batched_decayed_scatter,
                                           decayed_scatter)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.knn_topk import knn_topk as _knn_pallas
from repro.kernels.knn_topk import knn_topk_dtiled as _knn_dtiled_pallas
from repro.kernels.serving_topn import (blend_topn_onehot as _blend_onehot,
                                        blend_topn_rows as _blend_rows)
from repro.kernels.serving_topn import \
    blend_topn_rows_quant as _blend_rows_quant_pallas
from repro.kernels.sparse_row_gather import \
    sparse_row_gather as _sparse_gather_pallas
from repro.kernels.sparse_row_scatter import \
    sparse_row_scatter as _sparse_scatter_pallas

_DEFAULT_IMPL = "auto"


@contextlib.contextmanager
def default_impl(impl: str) -> Iterator[None]:
    """Process-wide impl override (auto | pallas | interpret | ref).

    Jitted callers (core.updates) capture the dispatch decision at trace
    time, so both transitions clear the jit caches — this is a test /
    benchmark harness knob, not a serving-path switch.
    """
    global _DEFAULT_IMPL
    prev = _DEFAULT_IMPL
    _DEFAULT_IMPL = impl
    jax.clear_caches()
    try:
        yield
    finally:
        _DEFAULT_IMPL = prev
        jax.clear_caches()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: Optional[str]) -> str:
    """The implementation a call with ``impl`` runs here.

    Returns ``"pallas"`` (compiled kernel), ``"interpret"`` (kernel body
    in the Pallas interpreter) or ``"ref"`` (XLA reference).  ``None``
    reads the :func:`default_impl` override.  Raises when the compiled
    kernel is demanded (``"pallas"``) off a TPU: interpret mode is only
    ever chosen explicitly.
    """
    impl = _DEFAULT_IMPL if impl is None else impl
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    if impl == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"impl='pallas' needs a TPU backend, found "
            f"{jax.default_backend()!r}; use impl='interpret' to run the "
            "kernel bodies in the Pallas interpreter")
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def stage_a_bd(d: int, k: int) -> Optional[int]:
    """D-tile width stage A needs for ``d`` items, or None (monolithic).

    The monolithic kernel holds [bq, D] and [bm, D] blocks; once its
    block model no longer fits VMEM (``analysis.vmem.fits_vmem``) the
    D-tiled kernel (VMEM flat in D, DESIGN.md §8.4) takes over — the
    chip's compiler refuses the monolithic kernel from D = 2560 on, far
    below the paper's 12k-item catalogues.  Chosen from the
    shape alone, so every impl serves the same regime.
    """
    if _analysis_vmem.fits_vmem(_analysis_vmem.knn_topk_block_bytes(d=d,
                                                                   k=k)):
        return None
    return 512


def knn_topk(queries: jax.Array, corpus: jax.Array, k: int,
             impl: str | None = None,
             **kw: Any) -> Tuple[jax.Array, jax.Array]:
    """Fused similarity + top-k (paper §2.2 neighbour search).

    O(Q·M·I) compute over corpus tiles with an on-chip [Q, k] running
    merge — never a [Q, M] score matrix in HBM (DESIGN.md §3.4).
    impl: auto | pallas | interpret | ref.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.knn_topk_ref(queries, corpus, k,
                                kw.get("metric", "euclidean"))
    return _knn_pallas(queries, corpus, k, interpret=impl == "interpret", **kw)


def knn_topk_dtiled(queries: jax.Array, corpus: jax.Array, k: int,
                    bd: int = 512, impl: str | None = None,
                    **kw: Any) -> Tuple[jax.Array, jax.Array]:
    """D-tiled streaming top-k (DESIGN.md §8.4): VMEM flat in D.

    Same contract as :func:`knn_topk` (euclidean only) with the item
    axis tiled at width ``bd``; int8 ``queries``/``corpus`` take
    ``q_scale``/``c_scale`` (per-row, `optim.compression
    .quantize_int8_rows`) and are bitwise the `ref.dtiled_topk_ref`
    oracle on every impl.  impl: auto | pallas | interpret | ref.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.dtiled_topk_ref(queries, corpus, k, bd=bd, **kw)
    return _knn_dtiled_pallas(queries, corpus, k, bd=bd,
                              interpret=impl == "interpret", **kw)


# ---------------------------------------------------------------------------
# Fused serving pipeline (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# ``n_items`` everywhere below is the number of REAL item columns: the
# state store pads the item axis to the lane tile (DESIGN.md §3.3), and
# columns at or past ``n_items`` are never recommended.  None means
# every column is real.

@functools.partial(jax.jit, static_argnames=("k", "topn", "metric",
                                             "n_items"))
def _fused_recommend_ref(corpus: jax.Array, user_ids: jax.Array,
                         alpha: float, k: int, topn: int, metric: str,
                         n_items: Optional[int]) -> jax.Array:
    return ref.fused_recommend_ref(corpus, user_ids, k, alpha, topn, metric,
                                   n_items=n_items)


@functools.partial(jax.jit,
                   static_argnames=("k", "alpha", "topn", "metric",
                                    "n_items", "interpret"))
def _fused_recommend_pallas(corpus: jax.Array, user_ids: jax.Array,
                            k: int, alpha: float, topn: int, metric: str,
                            n_items: Optional[int],
                            interpret: bool) -> jax.Array:
    queries = corpus[user_ids]
    _, idx = _knn_pallas(queries, corpus, k, metric=metric,
                         query_gids=user_ids, interpret=interpret)
    _, ids = _blend_onehot(corpus, user_ids, idx, alpha=alpha, topn=topn,
                           n_items=n_items, interpret=interpret)
    return ids


@functools.partial(jax.jit, static_argnames=("k", "topn", "bd", "n_items"))
def _fused_recommend_dtiled_ref(corpus: jax.Array, user_ids: jax.Array,
                                alpha: float, k: int, topn: int, bd: int,
                                n_items: Optional[int]) -> jax.Array:
    queries = corpus[user_ids]
    _, idx = ref.dtiled_topk_ref(queries, corpus, k, bd=bd,
                                 query_gids=user_ids)
    return ref.blend_topn_rows_ref(queries, corpus[idx], alpha, topn,
                                   n_items=n_items)


@functools.partial(jax.jit,
                   static_argnames=("k", "alpha", "topn", "bd", "n_items",
                                    "interpret"))
def _fused_recommend_dtiled_pallas(corpus: jax.Array, user_ids: jax.Array,
                                   k: int, alpha: float, topn: int,
                                   bd: int, n_items: Optional[int],
                                   interpret: bool) -> jax.Array:
    queries = corpus[user_ids]
    _, idx = _knn_dtiled_pallas(queries, corpus, k, bd=bd,
                                query_gids=user_ids, interpret=interpret)
    _, ids = _blend_onehot(corpus, user_ids, idx, alpha=alpha, topn=topn,
                           n_items=n_items, interpret=interpret)
    return ids


@functools.partial(jax.jit, static_argnames=("k", "topn", "bd", "n_items"))
def _fused_recommend_quant_ref(corpus_q: jax.Array, c_scale: jax.Array,
                               user_ids: jax.Array, alpha: float, k: int,
                               topn: int, bd: int,
                               n_items: Optional[int]) -> jax.Array:
    return ref.fused_recommend_quant_ref(corpus_q, c_scale, user_ids, k,
                                         alpha, topn, bd, n_items=n_items)


@functools.partial(jax.jit,
                   static_argnames=("k", "alpha", "topn", "bd", "n_items",
                                    "interpret"))
def _fused_recommend_quant_pallas(corpus_q: jax.Array, c_scale: jax.Array,
                                  user_ids: jax.Array, k: int,
                                  alpha: float, topn: int, bd: int,
                                  n_items: Optional[int],
                                  interpret: bool) -> jax.Array:
    queries_q = corpus_q[user_ids]
    q_scale = c_scale[user_ids]
    _, idx = _knn_dtiled_pallas(queries_q, corpus_q, k, bd=bd,
                                query_gids=user_ids, q_scale=q_scale,
                                c_scale=c_scale, interpret=interpret)
    # stage B fetches only the selected k rows — and fetches them int8:
    # ¼ the HBM bytes of the fp32 gather (DESIGN.md §8.4)
    _, ids = _blend_rows_quant_pallas(queries_q, q_scale, corpus_q[idx],
                                      c_scale[idx], alpha=alpha,
                                      topn=topn, n_items=n_items,
                                      interpret=interpret)
    return ids


def _check_topn(topn: int, n_cols: int, n_items: Optional[int]) -> None:
    n_real = n_cols if n_items is None else n_items
    if topn > n_real:
        raise ValueError(f"topn={topn} > n_items={n_real}")


def fused_recommend(corpus: jax.Array, user_ids: jax.Array, k: int,
                    alpha: float, topn: int, metric: str = "euclidean",
                    impl: str | None = None, bd: int | None = None,
                    n_items: int | None = None) -> jax.Array:
    """Fused serving path: corpus rows → top-n item ids, one program.

    ``corpus`` f32[M, I] (the cached serving corpus), ``user_ids``
    i32[Q] corpus rows (self-excluded from their own neighbourhood) →
    i32[Q, topn].  The TPU path is the two-stage Pallas pipeline of
    DESIGN.md §8 (streaming top-k + one-hot blend/top-n); the CPU path
    is the XLA reference of the same regime.  ``k`` is clamped to M−1
    (see the comment at the clamp); cosine serves through the reference
    (the kernels fuse the euclidean surrogate / dot only).  Stage A runs
    the D-tiled kernel of DESIGN.md §8.4 with tile width ``bd`` when
    given, else when the monolithic kernel would not fit VMEM
    (:func:`stage_a_bd`).  ``n_items`` (real item columns, see above)
    masks the store's pad columns.  impl: auto | pallas | interpret |
    ref.
    """
    impl = resolve_impl(impl)
    q_n, m = user_ids.shape[0], corpus.shape[0]
    _check_topn(topn, corpus.shape[1], n_items)
    if q_n == 0 or m == 0:
        return jnp.zeros((q_n, topn), jnp.int32)
    # clamp BELOW m: self-exclusion leaves m−1 finite candidates, and a
    # k that admits a −inf slot resolves it differently in the kernel
    # (accumulator-init index) than in the reference (the self row) —
    # keeping every selected candidate finite keeps the paths identical
    k = max(1, min(k, m - 1))
    if metric == "cosine":
        return _fused_recommend_ref(corpus, user_ids, alpha, k=k, topn=topn,
                                    metric=metric, n_items=n_items)
    if bd is None and metric == "euclidean":
        bd = stage_a_bd(corpus.shape[1], k)
    if impl == "ref":
        if bd is not None:
            return _fused_recommend_dtiled_ref(corpus, user_ids, alpha, k=k,
                                               topn=topn, bd=bd,
                                               n_items=n_items)
        return _fused_recommend_ref(corpus, user_ids, alpha, k=k, topn=topn,
                                    metric=metric, n_items=n_items)
    if bd is not None:
        return _fused_recommend_dtiled_pallas(
            corpus, user_ids, k=k, alpha=float(alpha), topn=topn, bd=bd,
            n_items=n_items, interpret=impl == "interpret")
    return _fused_recommend_pallas(
        corpus, user_ids, k=k, alpha=float(alpha), topn=topn,
        metric=metric, n_items=n_items, interpret=impl == "interpret")


def fused_recommend_quant(corpus_q: jax.Array, c_scale: jax.Array,
                          user_ids: jax.Array, k: int,
                          alpha: float, topn: int, bd: int = 512,
                          impl: str | None = None,
                          n_items: int | None = None) -> jax.Array:
    """Int8 fused serving (DESIGN.md §8.4): quantized corpus → top-n ids.

    ``corpus_q`` int8[M, I] with per-row ``c_scale`` f32[M]
    (`optim.compression.quantize_int8_rows`; cached by
    `streaming.state_store.StateStore.quantized_corpus`).  Stage A runs
    the D-tiled int8 top-k (exact int32 MXU partials, scales applied at
    score-finish — bitwise `ref.fused_recommend_quant_ref` on every
    impl); stage B gathers only the selected k rows, int8 on the wire,
    and dequantizes in VMEM.  HBM traffic per query batch is
    O(Q/bq · M·I) int8 reads + O(Q·k·I) int8 + O(Q·n) out — ¼ the
    fp32 path's bytes.  Euclidean only.  ``n_items`` as in
    :func:`fused_recommend`.  impl: auto | pallas | interpret | ref.
    """
    impl = resolve_impl(impl)
    q_n, m = user_ids.shape[0], corpus_q.shape[0]
    _check_topn(topn, corpus_q.shape[1], n_items)
    if q_n == 0 or m == 0:
        return jnp.zeros((q_n, topn), jnp.int32)
    k = max(1, min(k, m - 1))   # same −inf-slot reasoning as above
    if impl == "ref":
        return _fused_recommend_quant_ref(corpus_q, c_scale, user_ids,
                                          alpha, k=k, topn=topn, bd=bd,
                                          n_items=n_items)
    return _fused_recommend_quant_pallas(
        corpus_q, c_scale, user_ids, k=k, alpha=float(alpha), topn=topn,
        bd=bd, n_items=n_items, interpret=impl == "interpret")


@functools.partial(jax.jit, static_argnames=("k", "shard", "n_shards",
                                             "metric", "bd"))
def _shard_topk_ref(queries: jax.Array, corpus: jax.Array,
                    query_gids: jax.Array, k: int, shard: int,
                    n_shards: int, metric: str,
                    bd: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    if bd is None:
        return ref.shard_topk_ref(queries, corpus, k, shard, n_shards,
                                  query_gids, metric)
    vals, idx = ref.dtiled_topk_ref(queries, corpus, k, bd=bd,
                                    query_gids=query_gids,
                                    col_offset=shard, col_stride=n_shards,
                                    sub_qnorm=True)
    gids = idx * n_shards + shard
    return vals, jnp.where(jnp.isneginf(vals), query_gids[:, None], gids)


@functools.partial(jax.jit, static_argnames=("k", "shard", "n_shards",
                                             "metric", "bd", "interpret"))
def _shard_topk_pallas(queries: jax.Array, corpus: jax.Array,
                       query_gids: jax.Array, k: int, shard: int,
                       n_shards: int, metric: str, bd: Optional[int],
                       interpret: bool) -> Tuple[jax.Array, jax.Array]:
    if bd is None:
        vals, idx = _knn_pallas(queries, corpus, k, metric=metric,
                                query_gids=query_gids, col_offset=shard,
                                col_stride=n_shards, sub_qnorm=True,
                                interpret=interpret)
    else:
        vals, idx = _knn_dtiled_pallas(queries, corpus, k, bd=bd,
                                       query_gids=query_gids,
                                       col_offset=shard, col_stride=n_shards,
                                       sub_qnorm=True, interpret=interpret)
    gids = idx * n_shards + shard
    # k >= m_s on the owner shard admits the excluded self column as a
    # −inf candidate; the reference resolves its index to the self row
    # (the only −inf score), the kernel to the accumulator init — pin
    # the reference's answer so the cross-shard merge sees identical
    # (score, gid) lists
    return vals, jnp.where(jnp.isneginf(vals), query_gids[:, None], gids)


def shard_topk(queries: jax.Array, corpus: jax.Array, k: int, shard: int,
               n_shards: int, query_gids: jax.Array | None = None,
               metric: str = "euclidean",
               impl: str | None = None) -> Tuple[jax.Array, jax.Array]:
    """Per-shard neighbour candidates ``([Q, k'] scores, global ids)``.

    ``k' = min(k, M_s)``.  The TPU path streams corpus tiles through the
    fused top-k kernel with the shard's global-id mapping (column gid =
    ``row·n_shards + shard``) — the [Q, M_s] score matrix never reaches
    HBM; the CPU path is the XLA reference of the same regime.  Stage A
    is D-tiled where the monolithic kernel would not fit VMEM
    (:func:`stage_a_bd`).  Cosine serves through the reference.
    """
    impl = resolve_impl(impl)
    m_s = corpus.shape[0]
    q_n = queries.shape[0]
    if m_s == 0 or q_n == 0:
        kk = min(k, m_s)
        return (jnp.full((q_n, kk), -jnp.inf, jnp.float32),
                jnp.zeros((q_n, kk), jnp.int32))
    if query_gids is None:
        query_gids = jnp.full((q_n,), -1, jnp.int32)
    bd = (stage_a_bd(corpus.shape[1], min(k, m_s))
          if metric == "euclidean" else None)
    if impl == "ref" or metric == "cosine":
        return _shard_topk_ref(queries, corpus, query_gids, k=min(k, m_s),
                               shard=shard, n_shards=n_shards,
                               metric=metric, bd=bd)
    return _shard_topk_pallas(
        queries, corpus, query_gids, k=min(k, m_s), shard=shard,
        n_shards=n_shards, metric=metric, bd=bd,
        interpret=impl == "interpret")


@functools.partial(jax.jit, static_argnames=("k", "shard", "n_shards",
                                             "bd"))
def _shard_topk_quant_ref(queries_q: jax.Array, q_scale: jax.Array,
                          corpus_q: jax.Array, c_scale: jax.Array,
                          query_gids: jax.Array, k: int, shard: int,
                          n_shards: int,
                          bd: int) -> Tuple[jax.Array, jax.Array]:
    vals, idx = ref.dtiled_topk_ref(queries_q, corpus_q, k, bd=bd,
                                    query_gids=query_gids,
                                    col_offset=shard, col_stride=n_shards,
                                    sub_qnorm=True, q_scale=q_scale,
                                    c_scale=c_scale)
    gids = idx * n_shards + shard
    return vals, jnp.where(jnp.isneginf(vals), query_gids[:, None], gids)


@functools.partial(jax.jit, static_argnames=("k", "shard", "n_shards",
                                             "bd", "interpret"))
def _shard_topk_quant_pallas(queries_q: jax.Array, q_scale: jax.Array,
                             corpus_q: jax.Array, c_scale: jax.Array,
                             query_gids: jax.Array, k: int, shard: int,
                             n_shards: int, bd: int,
                             interpret: bool
                             ) -> Tuple[jax.Array, jax.Array]:
    vals, idx = _knn_dtiled_pallas(queries_q, corpus_q, k, bd=bd,
                                   query_gids=query_gids,
                                   col_offset=shard, col_stride=n_shards,
                                   sub_qnorm=True, q_scale=q_scale,
                                   c_scale=c_scale, interpret=interpret)
    gids = idx * n_shards + shard
    # same −inf → self-gid pin as _shard_topk_pallas
    return vals, jnp.where(jnp.isneginf(vals), query_gids[:, None], gids)


def shard_topk_quant(queries_q: jax.Array, q_scale: jax.Array,
                     corpus_q: jax.Array, c_scale: jax.Array, k: int,
                     shard: int, n_shards: int,
                     query_gids: jax.Array | None = None,
                     bd: int = 512, impl: str | None = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Per-shard int8 neighbour candidates ``([Q, k'] scores, gids)``.

    The quantized twin of :func:`shard_topk` — D-tiled stage A over one
    shard's int8 corpus, ``sub_qnorm`` on so the emitted scores are the
    full −|q̂−ĉ|² on DEQUANTIZED values: per-row quantization is
    corpus-partition invariant (a row's (q, scale) is the same on any
    shard), so per-pair scores across shards are exactly the
    single-corpus int8 scores and the cross-shard merge stays
    bitwise-consistent (DESIGN.md §7.3/§8.4).  Bitwise the oracle on
    every impl.  impl: auto | pallas | interpret | ref.
    """
    impl = resolve_impl(impl)
    m_s = corpus_q.shape[0]
    q_n = queries_q.shape[0]
    if m_s == 0 or q_n == 0:
        kk = min(k, m_s)
        return (jnp.full((q_n, kk), -jnp.inf, jnp.float32),
                jnp.zeros((q_n, kk), jnp.int32))
    if query_gids is None:
        query_gids = jnp.full((q_n,), -1, jnp.int32)
    fn = (_shard_topk_quant_ref if impl == "ref"
          else functools.partial(_shard_topk_quant_pallas,
                                 interpret=impl == "interpret"))
    return fn(queries_q, q_scale, corpus_q, c_scale, query_gids,
              k=min(k, m_s), shard=shard, n_shards=n_shards, bd=bd)


@functools.partial(jax.jit, static_argnames=("topn", "n_items"))
def _blend_rows_ref(queries: jax.Array, neighbor_rows: jax.Array,
                    alpha: float, topn: int,
                    n_items: Optional[int]) -> jax.Array:
    return ref.blend_topn_rows_ref(queries, neighbor_rows, alpha, topn,
                                   n_items=n_items)


@functools.partial(jax.jit, static_argnames=("alpha", "topn", "n_items",
                                             "interpret"))
def _blend_rows_pallas(queries: jax.Array, neighbor_rows: jax.Array,
                       alpha: float, topn: int, n_items: Optional[int],
                       interpret: bool) -> jax.Array:
    return _blend_rows(queries, neighbor_rows, alpha=alpha, topn=topn,
                       n_items=n_items, interpret=interpret)[1]


def blend_topn_rows(queries: jax.Array, neighbor_rows: jax.Array,
                    alpha: float, topn: int, impl: str | None = None,
                    n_items: int | None = None) -> jax.Array:
    """Cross-shard final stage: fetched rows [Q, k, I] → top-n ids.

    Mean over k + alpha blend + top-n; the TPU path fuses them per item
    tile (no [Q, I] prediction intermediate), the CPU path is the
    historical ``_combine_neighbors`` math.  ``n_items`` as in
    :func:`fused_recommend`.
    """
    impl = resolve_impl(impl)
    _check_topn(topn, queries.shape[1], n_items)
    if impl == "ref":
        return _blend_rows_ref(queries, neighbor_rows, alpha, topn=topn,
                               n_items=n_items)
    return _blend_rows_pallas(queries, neighbor_rows, alpha=float(alpha),
                              topn=topn, n_items=n_items,
                              interpret=impl == "interpret")


@functools.partial(jax.jit, static_argnames=("topn", "n_items"))
def _blend_rows_quant_ref(queries_q: jax.Array, q_scale: jax.Array,
                          neighbor_rows_q: jax.Array, n_scale: jax.Array,
                          alpha: float, topn: int,
                          n_items: Optional[int]) -> jax.Array:
    return ref.blend_topn_rows_quant_ref(queries_q, q_scale,
                                         neighbor_rows_q, n_scale, alpha,
                                         topn, n_items=n_items)


@functools.partial(jax.jit, static_argnames=("alpha", "topn", "n_items",
                                             "interpret"))
def _blend_rows_quant_pallas_ids(queries_q: jax.Array, q_scale: jax.Array,
                                 neighbor_rows_q: jax.Array,
                                 n_scale: jax.Array, alpha: float,
                                 topn: int, n_items: Optional[int],
                                 interpret: bool) -> jax.Array:
    return _blend_rows_quant_pallas(queries_q, q_scale, neighbor_rows_q,
                                    n_scale, alpha=alpha, topn=topn,
                                    n_items=n_items, interpret=interpret)[1]


def blend_topn_rows_quant(queries_q: jax.Array, q_scale: jax.Array,
                          neighbor_rows_q: jax.Array, n_scale: jax.Array,
                          alpha: float, topn: int,
                          impl: str | None = None,
                          n_items: int | None = None) -> jax.Array:
    """Quantized cross-shard final stage: int8 rows [Q, k, I] → top-n.

    The int8 twin of :func:`blend_topn_rows`: the k fetched rows cross
    the wire quantized (¼ the fp32 bytes) with per-row scales and are
    dequantized on-chip.  impl: auto | pallas | interpret | ref.
    """
    impl = resolve_impl(impl)
    _check_topn(topn, queries_q.shape[1], n_items)
    if impl == "ref":
        return _blend_rows_quant_ref(queries_q, q_scale, neighbor_rows_q,
                                     n_scale, alpha, topn=topn,
                                     n_items=n_items)
    return _blend_rows_quant_pallas_ids(
        queries_q, q_scale, neighbor_rows_q, n_scale, alpha=float(alpha),
        topn=topn, n_items=n_items, interpret=impl == "interpret")


def serving_cache_size() -> int:
    """Number of live compiled programs behind the serving entry points.

    One program per distinct (impl, request-batch bucket, corpus shape,
    static-arg) combination — the engine-side pow2 request bucketing
    (`StreamingEngine.recommend`) exists to keep this O(log Q);
    `launch/serve.py` prints it so a bucketing regression is visible
    from the CLI.
    """
    return sum(f._cache_size() for f in (
        _fused_recommend_ref, _fused_recommend_pallas,
        _fused_recommend_dtiled_ref, _fused_recommend_dtiled_pallas,
        _fused_recommend_quant_ref, _fused_recommend_quant_pallas,
        _shard_topk_ref, _shard_topk_pallas,
        _shard_topk_quant_ref, _shard_topk_quant_pallas,
        _blend_rows_ref, _blend_rows_pallas,
        _blend_rows_quant_ref, _blend_rows_quant_pallas_ids))


def stage_a_vmem_bytes(d: int, k: int, bq: int = 128, bm: int = 512,
                       bd: int | None = None,
                       itemsize: int = 4) -> int:
    """Analytic peak VMEM residency (bytes) of one stage-A grid step.

    Re-exported from :mod:`repro.analysis.vmem`, which owns this
    capacity-planning model alongside the exact per-kernel block models
    the contract linter budgets against (DESIGN.md §10.2); see
    :func:`repro.analysis.vmem.stage_a_vmem_bytes` for the full model
    notes.  Kept as a function (not an alias) so the signature stays in
    this module's API docs.
    """
    return _analysis_vmem.stage_a_vmem_bytes(d, k, bq=bq, bm=bm, bd=bd,
                                             itemsize=itemsize)


def multihot_scatter(ids: jax.Array, weights: jax.Array, n_items: int,
                     impl: str | None = None) -> jax.Array:
    """Weighted multi-hot scatter (the Eq. 1+2 from-scratch builder).

    One decayed-average user/group vector per call: O(N·B) input ids
    against an [n_items] output (DESIGN.md §3.1).
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.decayed_scatter_ref(ids, weights, n_items)
    if ids.ndim == 3:
        return batched_decayed_scatter(ids, weights, n_items,
                                       interpret=impl == "interpret")
    return decayed_scatter(ids, weights, n_items, interpret=impl == "interpret")


def plan_bi(n_items: int) -> int | None:
    """Item-tile width for the tile-planned kernels, or None.

    The largest lane-aligned tile (512/256/128) dividing ``n_items``;
    None means the kernels cannot run at this width (the state store
    pads its item axis so they always can).  Public so hint producers
    (the streaming engine's host-measured ``T_max``, DESIGN.md §3.3)
    bucket ids with the same tile width the kernels use.
    """
    for bi in (512, 256, 128):
        if n_items % bi == 0:
            return bi
    return None


def _plan_dims(n_items: int, ids: jax.Array,
               t_max_cap: int = 0) -> Tuple[int, int]:
    """(bi, t_max) for the tile-planned kernels.

    ``bi`` is the largest lane-aligned tile dividing ``n_items`` (an
    untileable width raises: the state store pads its item axis, so
    reaching one is a bug, not a reason to switch implementation);
    ``t_max`` is the static per-row touched-tile bound.  When ``ids`` is
    concrete (benchmark / direct calls outside jit) the true maximum is
    measured on host and pow2-bucketed — typical baskets touch only a
    few tiles, so the grid shrinks far below the ``min(W, I/bi)`` worst
    case that tracers must otherwise assume.  Under jit, a caller-
    supplied ``t_max_cap`` (the engine's host-measured bound, threaded
    through the batch appliers as a static arg) shrinks the tracer-side
    grid the same way; 0 means no hint.
    """
    bi = plan_bi(n_items)
    if bi is None:
        raise ValueError(
            f"the tile-planned kernels need an item width divisible by 128, "
            f"got {n_items}; StateStore pads its item axis to the lane tile "
            "(StoreConfig.n_cols)")
    w = ids.shape[1]
    cap = max(1, min(w, n_items // bi))
    if isinstance(ids, jax.core.Tracer):
        return bi, (max(1, min(cap, t_max_cap)) if t_max_cap else cap)
    from repro.core.types import _pow2_pad
    return bi, min(_pow2_pad(tile_plan.max_touched_tiles(ids, bi)), cap)


def sparse_row_scatter(table: jax.Array, rows: jax.Array, ids: jax.Array,
                       vals: jax.Array, impl: str | None = None,
                       t_max_cap: int = 0) -> jax.Array:
    """Sparse per-row scatter-add into a [M, I] table (add-path deltas).

    XLA's native scatter is already O(U·W) on CPU/GPU; the tile-planned
    Pallas kernel is the TPU path (DMAs only the dirty tiles of the
    touched rows, in place — O(U·W) HBM traffic too).  ``t_max_cap``
    (optional, static) is a host-measured upper bound on per-row touched
    tiles that shrinks the kernel grid under jit; it MUST be sound (>=
    the true maximum) — the plan truncates beyond it.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.sparse_row_scatter_ref(table, rows, ids, vals)
    bi, t_max = _plan_dims(table.shape[1], ids, t_max_cap)
    return _sparse_scatter_pallas(table, rows, ids, vals, bi=bi, t_max=t_max,
                                  interpret=impl == "interpret")


def sparse_row_gather(table: jax.Array, rows: jax.Array, ids: jax.Array,
                      impl: str | None = None,
                      t_max_cap: int = 0) -> jax.Array:
    """Sparse per-row gather from a [M, I] table (update-path supports).

    XLA's native gather is already O(U·W) on CPU/GPU; the tile-planned
    Pallas kernel is the TPU path (DMAs only the touched rows' dirty
    tiles — O(U·W) HBM traffic too).  ``t_max_cap`` as in
    :func:`sparse_row_scatter`.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.sparse_row_gather_ref(table, rows, ids)
    bi, t_max = _plan_dims(table.shape[1], ids, t_max_cap)
    return _sparse_gather_pallas(table, rows, ids, bi=bi, t_max=t_max,
                                 interpret=impl == "interpret")


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    impl: str | None = None, **kw: Any) -> jax.Array:
    """Blocked attention: [B,S,H,D] each → [B,S,H,D].

    O(S²·D) compute with O(S·D) memory (never an [S, S] score matrix in
    HBM); serves the LM stack, not the TIFU maintenance path.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal, window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                        interpret=impl == "interpret", **kw)
