"""Personalised collaborative-filtering prediction and ranking metrics.

Covers the paper's prediction step (§2.2, last part) and the evaluation
metrics (Recall@K, NDCG@K — §6.1).

Prediction:  p = alpha * u_target + (1 - alpha) * mean(top-k neighbours).

``nearest_neighbors``/``predict`` are the reference (jnp) formulation —
the semantics oracle and the building block for ad-hoc analysis.  The
SERVING entry points (`recommend_for_users`, `shard_topk_candidates`,
`sharded_recommend_for_users`) dispatch through ``kernels.ops``
(DESIGN.md §8): on TPU they run the fused Pallas pipeline
(`kernels.knn_topk` streaming top-k + `kernels.serving_topn` one-hot
blend/top-n — O(Q·k) HBM intermediates, never a [Q, M] score matrix or
[Q, k, I] gather); on CPU they run `kernels.ref` oracles that are
bitwise the historical unfused outputs, and interpret mode drives the
Pallas path on any host (tests pin all three against each other).
Distances follow TIFU-kNN: Euclidean by default, cosine optional
(cosine serves through the reference path — the kernels fuse the
euclidean surrogate and dot only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ops


def pairwise_scores(queries, corpus, metric: str = "euclidean"):
    """Similarity scores (higher = closer). [Q,I] x [M,I] → [Q,M]."""
    if metric == "euclidean":
        # -||q - c||^2 = 2 q·c - ||q||^2 - ||c||^2 (monotone in distance)
        qc = queries @ corpus.T
        qn = jnp.sum(queries * queries, axis=-1, keepdims=True)
        cn = jnp.sum(corpus * corpus, axis=-1)[None, :]
        return 2.0 * qc - qn - cn
    if metric == "cosine":
        qn = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-12)
        cn = corpus / jnp.maximum(
            jnp.linalg.norm(corpus, axis=-1, keepdims=True), 1e-12)
        return qn @ cn.T
    if metric == "dot":
        return queries @ corpus.T
    raise ValueError(f"unknown metric {metric}")


@functools.partial(jax.jit, static_argnames=("k", "metric", "exclude_self"))
def nearest_neighbors(queries, corpus, k: int, metric: str = "euclidean",
                      exclude_self: bool = False, query_ids=None):
    """Top-k neighbour indices per query. Returns (scores, indices)."""
    scores = pairwise_scores(queries, corpus, metric)
    if exclude_self:
        ids = (jnp.arange(queries.shape[0]) if query_ids is None
               else query_ids)
        scores = scores.at[jnp.arange(queries.shape[0]), ids].set(-jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "metric", "exclude_self",
                                              "mesh", "rules"))
def predict(queries, corpus, k: int, alpha: float,
            metric: str = "euclidean", exclude_self: bool = True,
            query_ids=None, mesh=None, rules=None):
    """Final TIFU-kNN prediction vector p per query user. [Q,I].

    With a mesh: corpus users sharded over (pod,data), items over model —
    scores are constrained corpus-sharded (never [Q,M]-replicated), the
    per-shard top-k merge is XLA's partitioned top-k, and the neighbour
    gather stays item-sharded.  Sharding-agnostic semantics otherwise.
    """
    if mesh is None:
        _, idx = nearest_neighbors(queries, corpus, k, metric, exclude_self,
                                   query_ids)
        neighbors = jnp.mean(corpus[idx], axis=1)        # [Q, I]
        return alpha * queries + (1.0 - alpha) * neighbors

    _, idx = streaming_topk(queries, corpus, k, metric,
                            exclude_self=exclude_self, query_ids=query_ids)
    neighbors = chunked_neighbor_mean(corpus, idx)
    return alpha * queries + (1.0 - alpha) * neighbors


def streaming_topk(queries, corpus, k: int, metric: str = "euclidean",
                   chunk: int = 65536, exclude_self: bool = False,
                   query_ids=None):
    """Top-k without materializing the [Q, M] score matrix.

    Scans corpus chunks with a running top-k merge — the pure-JAX
    rendition of kernels.knn_topk (the Pallas kernel is the on-chip TPU
    version of this schedule).
    """
    q_n, d = queries.shape
    m = corpus.shape[0]
    # Remainder rows are handled as one extra masked tail block (padding
    # only O(chunk) rows, never a full-corpus copy).  Shrinking the chunk
    # instead degenerates to chunk=1 — a scan of length M — for
    # prime-sized corpora.
    chunk = max(1, min(chunk, m))    # m == 0 → zero blocks, -inf result
    nc = m // chunk
    blocks = corpus[:nc * chunk].reshape(nc, chunk, d)
    qids = (jnp.arange(q_n) if query_ids is None else query_ids)

    def body(carry, inp):
        vals, idx = carry
        block, ci = inp
        s = pairwise_scores(queries, block, metric)       # [Q, chunk]
        tile = ci * chunk + jnp.arange(chunk)[None, :]
        s = jnp.where(tile >= m, -jnp.inf, s)             # padding rows
        if exclude_self:
            s = jnp.where(tile == qids[:, None], -jnp.inf, s)
        mv = jnp.concatenate([vals, s.astype(jnp.float32)], axis=1)
        mi = jnp.concatenate([idx, jnp.broadcast_to(tile, s.shape)], axis=1)
        tv, tp_ = jax.lax.top_k(mv, k)
        return (tv, jnp.take_along_axis(mi, tp_, axis=1)), None

    init = (jnp.full((q_n, k), -jnp.inf, jnp.float32),
            jnp.zeros((q_n, k), jnp.int32))
    carry, _ = jax.lax.scan(body, init, (blocks, jnp.arange(nc)))
    rem = m - nc * chunk
    if rem:
        tail = jnp.zeros((chunk, d), corpus.dtype).at[:rem].set(
            corpus[nc * chunk:])
        carry, _ = body(carry, (tail, jnp.asarray(nc)))
    return carry


def distributed_predict(queries, corpus, k: int, alpha: float, mesh, rules,
                        metric: str = "euclidean"):
    """Optimized distributed TIFU-kNN prediction (EXPERIMENTS.md §Perf H1).

    Sharding: corpus USERS over every mesh axis, items unsharded; queries
    replicated.  Per device: local scores + local top-k; two-stage
    hierarchical candidate merge (model axis then data axis — each an
    all-gather of only [Q, k] candidates); neighbour mean as a local
    one-hot matmul (MXU-friendly, no [Q,k,I] gather) psum'd once.

    vs the natural item-TP formulation (psum of [Q, M] partial scores +
    row gathers): measured 26 GiB → <1 GiB collectives per device.
    """
    axes = tuple(a for a in ("pod", "data", "model")
                 if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = int(np.prod([sizes[a] for a in axes]))
    m_loc = corpus.shape[0] // n_shards
    q_n = queries.shape[0]

    def body(q, c_loc):
        shard = jnp.zeros((), jnp.int32)
        for a in axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        lo = shard * m_loc
        s = pairwise_scores(q, c_loc, metric).astype(jnp.float32)
        vals, idx = jax.lax.top_k(s, k)                  # local candidates
        idx = idx + lo
        # hierarchical merge: innermost axis first
        for a in reversed(axes):
            vals_g = jax.lax.all_gather(vals, a, axis=1, tiled=True)
            idx_g = jax.lax.all_gather(idx, a, axis=1, tiled=True)
            vals, pos = jax.lax.top_k(vals_g, k)
            idx = jnp.take_along_axis(idx_g, pos, axis=1)
        # neighbour mean via one-hot matmul over the local rows
        local_id = idx - lo
        valid = (local_id >= 0) & (local_id < m_loc)
        rows = jnp.where(valid, local_id, 0)
        sel = jnp.zeros((q_n, m_loc), c_loc.dtype)
        sel = sel.at[jnp.arange(q_n)[:, None], rows].add(
            valid.astype(c_loc.dtype))
        partial = sel @ c_loc                            # [Q, I] partial sum
        nbr_sum = jax.lax.psum(partial, axes)
        return alpha * q + (1.0 - alpha) * nbr_sum / k

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axes, None)),
        out_specs=P(None, None), check_vma=False,
    )(queries, corpus)


def chunked_neighbor_mean(corpus, idx, chunk_k: int = 8):
    """mean(corpus[idx], axis=1) accumulated over neighbour chunks.

    Avoids the [Q, k, I] gather (Q=4096, k=300, I=16k ⇒ 80 GB).
    """
    q_n, k = idx.shape
    # Pad the neighbour list to a chunk multiple (index -1, masked in the
    # body) rather than shrinking chunk_k to 1 for prime k.
    chunk_k = max(1, min(chunk_k, k))
    pad = (-k) % chunk_k
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((q_n, pad), -1, idx.dtype)], axis=1)
    blocks = idx.reshape(q_n, (k + pad) // chunk_k,
                         chunk_k).transpose(1, 0, 2)

    def body(acc, ib):
        valid = (ib >= 0)[..., None].astype(corpus.dtype)
        rows = jnp.where(ib >= 0, ib, 0)
        return acc + jnp.sum(corpus[rows] * valid, axis=1), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((q_n, corpus.shape[1]), corpus.dtype), blocks)
    return acc / k


def recommend_topn(pred, n: int):
    """Indices of the top-n scored items per user. [Q, n]."""
    return jax.lax.top_k(pred, n)[1]


def recommend_for_users(corpus, user_ids, k: int, alpha: float, topn: int,
                        metric: str = "euclidean", n_items=None):
    """Fused serving path: corpus rows → top-n item ids (DESIGN.md §8).

    ``corpus`` is the (cached) materialized corpus f32[M, I]
    (``StateStore.corpus()``, DESIGN.md §3.6); ``user_ids`` i32[Q] are
    the requesting users, which are corpus rows (self-excluded from the
    neighbourhood).  ``n_items`` is the real catalogue: the store's lane
    padding columns past it are never recommended.  Dispatches through
    ``kernels.ops.fused_recommend``: one compiled program per request
    batch shape — the engine-side pow2 request bucketing
    (`StreamingEngine.recommend`) bounds how many such shapes serving
    ever compiles.  Returns i32[Q, topn] item ids.
    """
    return ops.fused_recommend(corpus, user_ids, k=k, alpha=alpha,
                               topn=topn, metric=metric, n_items=n_items)


# ---------------------------------------------------------------------------
# Cross-shard serving (user-axis sharded deployment, DESIGN.md §7)
# ---------------------------------------------------------------------------

def _placement(x):
    """A sharding that puts a new array on the devices holding ``x``."""
    sh = x.sharding
    if isinstance(sh, NamedSharding):
        return NamedSharding(sh.mesh, P())
    return sh


@jax.jit
def _take_rows(array, rows):
    return jnp.take(array, rows, axis=0)


def _gather_owned_rows(arrays, gids, n_shards: int, target):
    """Rows ``gids`` (global user ids) of per-shard ``arrays``, on ``target``.

    Shard ``s`` owns global user ``r·n_shards + s`` as its local row
    ``r``; each shard gathers its rows on its own devices and only those
    rows leave it (through the host, where they are placed in request
    order).  Row lists are padded to a power of two so the gathers
    compile once per size bucket.  Returns ``gids.shape + row shape``.
    """
    gids = np.asarray(gids, np.int64)
    flat = gids.ravel()
    tail = arrays[0].shape[1:]
    out = np.empty((flat.size,) + tail, arrays[0].dtype)
    for s, arr in enumerate(arrays):
        pos = np.nonzero(flat % n_shards == s)[0]
        if pos.size == 0:
            continue
        local = (flat[pos] // n_shards).astype(np.int32)
        local = np.resize(local, 1 << (pos.size - 1).bit_length())
        rows = _take_rows(arr, jax.device_put(local, _placement(arr)))
        out[pos] = np.asarray(rows)[:pos.size]
    return jax.device_put(out.reshape(gids.shape + tail), target)


def _merge_candidates(vals, gids, k: int) -> np.ndarray:
    """Global top-k ids from per-shard candidate lists, on host.

    Score descending, then global id ascending — `jax.lax.top_k`'s
    tie-break on a single corpus.
    """
    all_vals = np.concatenate([np.asarray(v) for v in vals], axis=1)
    all_gids = np.concatenate([np.asarray(g) for g in gids], axis=1)
    order = np.lexsort((all_gids, -all_vals), axis=-1)
    return np.take_along_axis(all_gids, order, axis=1)[:, :k]


def shard_topk_candidates(queries, corpus, k: int, shard: int,
                          n_shards: int, query_ids=None,
                          metric: str = "euclidean"):
    """Per-shard neighbour candidates: ``([Q, k] scores, global ids)``.

    ``corpus`` is one shard's local corpus (rows = users owned by
    ``shard`` under the round-robin `UserShardSpec` contract, so local
    row r is global user ``r·n_shards + shard``).  Scores are the same
    per-pair values the single-corpus path computes; self-exclusion
    compares global ids, so a query user is masked only on its owner
    shard.  Dispatches through ``kernels.ops.shard_topk`` (on TPU the
    streaming top-k kernel — the [Q, M_s] score matrix stays on chip).
    O(Q·M_s) compute, O(Q·k) output — the merge step moves candidate
    lists, never corpora.
    """
    return ops.shard_topk(queries, corpus, k=k, shard=shard,
                          n_shards=n_shards, query_gids=query_ids,
                          metric=metric)


def sharded_recommend_for_users(corpora, user_ids, k: int, alpha: float,
                                topn: int, n_shards: int,
                                metric: str = "euclidean",
                                n_items=None) -> np.ndarray:
    """Distributed TIFU-kNN serving over per-shard corpora (§7).

    Pipeline: (1) each owner shard gathers its query rows on its own
    devices; (2) every shard scores the queries against only its local
    corpus, on its own devices, and returns its top-k candidate
    ``(score, global id)`` lists; (3) a host merge takes the global
    top-k — candidates are ordered by (score desc, global id asc),
    exactly `jax.lax.top_k`'s tie-break on a single corpus; (4) only the
    k selected neighbour ROWS move, from their owner shards to shard 0's
    devices, where they are blended (``kernels.ops.blend_topn_rows`` —
    on TPU the fused mean/blend/top-n kernel).  Cross-device traffic is
    the query rows, the [Q, k] candidate lists and the selected rows;
    corpora never leave their shards.  ``n_items`` as in
    :func:`recommend_for_users`.

    Returns i32[Q, topn] item ids.  Per-pair scores do not depend on
    the partition, so any shard count gives the same answer
    (tests/test_sharded_engine.py pins it against the single-corpus
    path).
    """
    user_ids = np.asarray(user_ids, np.int64)
    target = _placement(corpora[0])
    queries = _gather_owned_rows(corpora, user_ids, n_shards, target)
    qids = user_ids.astype(np.int32)
    cands = []
    for s, corpus in enumerate(corpora):     # dispatch every shard first
        place = _placement(corpus)
        cands.append(shard_topk_candidates(
            jax.device_put(queries, place), corpus, k, s, n_shards,
            query_ids=jax.device_put(qids, place), metric=metric))
    sel = _merge_candidates([v for v, _ in cands], [g for _, g in cands], k)
    neighbor_rows = _gather_owned_rows(corpora, sel, n_shards, target)
    return np.asarray(ops.blend_topn_rows(queries, neighbor_rows, alpha,
                                          topn, n_items=n_items))


def recommend_for_users_quant(corpus_q, c_scale, user_ids, k: int,
                              alpha: float, topn: int, bd: int = 512,
                              n_items=None):
    """Int8 fused serving (DESIGN.md §8.4): quantized corpus → top-n ids.

    The million-item twin of :func:`recommend_for_users`: ``corpus_q``
    int8[M, I] with per-row power-of-two ``c_scale`` f32[M]
    (``StateStore.quantized_corpus()``).  Stage A streams the corpus in
    D-tiles of width ``bd`` (VMEM flat in I), stage B fetches only the
    selected k rows — int8 on the wire.  Bitwise-deterministic across
    cpu/interpret/tpu dispatch (exact int32 partials + exact
    power-of-two scale application).  Euclidean only.  Returns
    i32[Q, topn] item ids.
    """
    return ops.fused_recommend_quant(corpus_q, c_scale, user_ids, k=k,
                                     alpha=alpha, topn=topn, bd=bd,
                                     n_items=n_items)


def sharded_recommend_for_users_quant(quant_corpora, user_ids, k: int,
                                      alpha: float, topn: int,
                                      n_shards: int, bd: int = 512,
                                      n_items=None) -> np.ndarray:
    """Distributed int8 serving over per-shard quantized corpora (§8.4).

    Same four-stage pipeline as :func:`sharded_recommend_for_users`,
    int8 end to end: ``quant_corpora`` is a list of per-shard
    ``(corpus_q int8[M_s, I], scale f32[M_s])`` pairs
    (``StateStore.quantized_corpus()``).  Because row quantization is
    corpus-partition invariant (per-row scales — a row's (q, scale)
    does not depend on which shard holds it), every per-pair candidate
    score equals the single-corpus int8 score bitwise, so the merge
    (score desc, global id asc) selects the same neighbour set and the
    result is bitwise ``recommend_for_users_quant`` on the equivalent
    single corpus (tests/test_quantized_serving.py pins this).
    Cross-device traffic: query rows, [Q, k] candidates and the
    selected rows — int8, ¼ the fp32 path's row-fetch bytes.
    """
    user_ids = np.asarray(user_ids, np.int64)
    corpora = [q for q, _ in quant_corpora]
    scales = [sc[:, None] for _, sc in quant_corpora]
    target = _placement(corpora[0])
    queries_q = _gather_owned_rows(corpora, user_ids, n_shards, target)
    q_scale = _gather_owned_rows(scales, user_ids, n_shards, target)[:, 0]
    qids = user_ids.astype(np.int32)
    cands = []
    for s, (cq, cs) in enumerate(quant_corpora):
        place = _placement(cq)
        cands.append(ops.shard_topk_quant(
            jax.device_put(queries_q, place), jax.device_put(q_scale, place),
            cq, cs, k, shard=s, n_shards=n_shards,
            query_gids=jax.device_put(qids, place), bd=bd))
    sel = _merge_candidates([v for v, _ in cands], [g for _, g in cands], k)
    neighbor_q = _gather_owned_rows(corpora, sel, n_shards, target)
    n_scale = _gather_owned_rows(scales, sel, n_shards, target)[..., 0]
    return np.asarray(ops.blend_topn_rows_quant(
        queries_q, q_scale, neighbor_q, n_scale, alpha, topn,
        n_items=n_items))


# ---------------------------------------------------------------------------
# Ranking metrics (numpy; evaluation only)
# ---------------------------------------------------------------------------

def recall_at_k(recommended: np.ndarray, truth: list, k: int) -> float:
    """Mean Recall@k over users. ``truth``: list of item-id arrays."""
    vals = []
    for recs, t in zip(np.asarray(recommended)[:, :k], truth):
        t = set(int(x) for x in np.asarray(t).ravel() if x >= 0)
        if not t:
            continue
        hit = len(t.intersection(int(r) for r in recs))
        vals.append(hit / len(t))
    return float(np.mean(vals)) if vals else 0.0


def ndcg_at_k(recommended: np.ndarray, truth: list, k: int) -> float:
    """Mean NDCG@k over users (binary relevance)."""
    vals = []
    discounts = 1.0 / np.log2(np.arange(2, k + 2))
    for recs, t in zip(np.asarray(recommended)[:, :k], truth):
        t = set(int(x) for x in np.asarray(t).ravel() if x >= 0)
        if not t:
            continue
        rel = np.array([1.0 if int(r) in t else 0.0 for r in recs])
        dcg = float(np.sum(rel * discounts[:len(rel)]))
        idcg = float(np.sum(discounts[:min(len(t), k)]))
        vals.append(dcg / idcg if idcg > 0 else 0.0)
    return float(np.mean(vals)) if vals else 0.0
