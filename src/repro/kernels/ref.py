"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel's tests sweep shapes/dtypes and assert allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def knn_topk_ref(queries, corpus, k: int, metric: str = "euclidean"):
    """Top-k (scores, indices) of each query against the corpus.

    euclidean uses the monotone surrogate 2qc − |c|² (per-query |q|² is
    rank-irrelevant and omitted, matching the kernel).
    """
    if metric == "euclidean":
        scores = (2.0 * queries @ corpus.T
                  - jnp.sum(corpus * corpus, axis=-1)[None, :])
    elif metric == "dot":
        scores = queries @ corpus.T
    else:
        raise ValueError(metric)
    return jax.lax.top_k(scores.astype(jnp.float32), k)


def _pairwise_scores(queries, corpus, metric: str):
    """Mirror of ``core.knn.pairwise_scores`` (duplicated: kernels must
    not import core).  Bitwise parity with the core version is pinned by
    tests/test_serving_pipeline.py."""
    if metric == "euclidean":
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


def fused_recommend_ref(corpus, user_ids, k: int, alpha, topn: int,
                        metric: str = "euclidean", n_items=None):
    """Oracle for the fused serving pipeline (DESIGN.md §8).

    Computes EXACTLY what the pre-fusion `core.knn.recommend_for_users`
    computed — row gather, full-score nearest neighbours with self
    exclusion, [Q, k, I] neighbour gather + mean, alpha blend, top-n —
    in the same operation order, so the dispatcher's CPU path stays
    bitwise-identical to the historical serving output.  Only the first
    ``n_items`` columns (None: all) are candidates for the top-n.
    """
    queries = corpus[user_ids]
    scores = _pairwise_scores(queries, corpus, metric)
    scores = scores.at[jnp.arange(queries.shape[0]), user_ids].set(-jnp.inf)
    _, idx = jax.lax.top_k(scores, k)
    neighbors = jnp.mean(corpus[idx], axis=1)
    pred = alpha * queries + (1.0 - alpha) * neighbors
    return jax.lax.top_k(pred[:, :n_items], topn)[1]


def shard_topk_ref(queries, corpus, k: int, shard: int, n_shards: int,
                   query_gids=None, metric: str = "euclidean"):
    """Oracle for the per-shard candidate stage (DESIGN.md §7.3).

    One shard's local corpus scored in full; self-exclusion compares
    GLOBAL ids (``local_row · n_shards + shard``) so a query user is
    masked only on its owner shard.  Returns ``([Q, k'] scores, global
    ids)`` with ``k' = min(k, M_s)`` — the exact math the pre-fusion
    `core.knn.shard_topk_candidates` ran.
    """
    m_s = corpus.shape[0]
    scores = _pairwise_scores(queries, corpus, metric).astype(jnp.float32)
    col_gid = jnp.arange(m_s, dtype=jnp.int32) * n_shards + shard
    if query_gids is not None:
        scores = jnp.where(col_gid[None, :] == query_gids[:, None],
                           -jnp.inf, scores)
    vals, idx = jax.lax.top_k(scores, min(k, m_s))
    return vals, col_gid[idx]


def blend_topn_rows_ref(queries, neighbor_rows, alpha, topn: int,
                        n_items=None):
    """Oracle for the cross-shard blend: mean over the fetched rows,
    alpha blend, top-n over the first ``n_items`` columns (None: all) —
    the pre-fusion ``_combine_neighbors`` math."""
    neighbors = jnp.mean(neighbor_rows, axis=1)
    pred = alpha * queries + (1.0 - alpha) * neighbors
    return jax.lax.top_k(pred[:, :n_items], topn)[1]


def tiled_sqnorm_ref(x, bd: int):
    """Per-row squared norm in D-tile accumulation order (f32[M]).

    Duplicate of ``kernels.knn_topk.tiled_sqnorm`` (the oracle must not
    import kernel modules); both call sites see the same shapes, so the
    two are bitwise identical — pinned by tests/test_quantized_serving.py.
    int8 rows: exact int32 per-tile sums, f32 cross-tile accumulation.
    """
    m, d = x.shape
    bd = max(1, min(bd, d))
    nt = -(-d // bd)
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


def dtiled_topk_ref(queries, corpus, k: int, bd: int = 512,
                    query_gids=None, col_offset: int = 0,
                    col_stride: int = 1, sub_qnorm: bool = False,
                    q_scale=None, c_scale=None):
    """Oracle for the D-tiled stage A (DESIGN.md §8.4).

    Mirrors ``knn_topk_dtiled``'s accumulation schedule exactly: the
    q·cᵀ contraction is a ``lax.scan`` over ⌈D/bd⌉ D-tiles (a scan, not
    a Python loop — at I = 10⁶ an unrolled jaxpr would have ~2000 dot
    ops), each tile's partial computed in int32 (int8 inputs, exact) or
    f32, accumulated cross-tile in f32 in tile order.  On the int8 path
    this makes the oracle BITWISE the kernel's output for any (bq, bm)
    blocking — the acceptance contract of ISSUE 7.  Scores, masks and
    scale application use the identical expression tree as the kernel.
    Requires k ≤ M (callers clamp, as for ``knn_topk_ref``).
    """
    qn, d = queries.shape
    m = corpus.shape[0]
    quantized = corpus.dtype == jnp.int8
    bd = max(1, min(bd, d))
    nt = -(-d // bd)
    cn = tiled_sqnorm_ref(corpus, bd)
    qnorm = (tiled_sqnorm_ref(queries, bd) if sub_qnorm
             else jnp.zeros((qn,), jnp.float32))
    pad = nt * bd - d
    qp, cp = queries, corpus
    if pad:
        qp = jnp.pad(qp, ((0, 0), (0, pad)))
        cp = jnp.pad(cp, ((0, 0), (0, pad)))
    qt = jnp.moveaxis(qp.reshape(qn, nt, bd), 1, 0)   # [nt, Q, bd]
    ct = jnp.moveaxis(cp.reshape(m, nt, bd), 1, 0)    # [nt, M, bd]

    def step(acc, qc):
        q, c = qc
        if quantized:
            part = jax.lax.dot_general(
                q, c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            return acc + part.astype(jnp.float32), None
        return acc + jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), None

    acc, _ = jax.lax.scan(step, jnp.zeros((qn, m), jnp.float32), (qt, ct))
    if q_scale is None:
        q_scale = jnp.ones((qn,), jnp.float32)
        c_scale = jnp.ones((m,), jnp.float32)
    scores = (2.0 * (q_scale[:, None] * c_scale[None, :]) * acc
              - (c_scale * c_scale)[None, :] * cn[None, :])
    if sub_qnorm:
        scores = scores - (q_scale * q_scale * qnorm)[:, None]
    if query_gids is not None:
        col_gid = (jnp.arange(m, dtype=jnp.int32) * col_stride
                   + col_offset)
        scores = jnp.where(col_gid[None, :] == query_gids[:, None],
                           -jnp.inf, scores)
    return jax.lax.top_k(scores, k)


def blend_topn_rows_quant_ref(queries_q, q_scale, neighbor_rows_q,
                              n_scale, alpha, topn: int, n_items=None):
    """Oracle for the quantized cross-shard blend (stage B, §8.4).

    queries_q int8[Q, I] / q_scale f32[Q]; neighbor_rows_q
    int8[Q, k, I] / n_scale f32[Q, k].  Dequantizes (exact elementwise
    f32 multiplies, the kernel's in-VMEM operands bitwise), then the
    same mean + alpha blend + top-n as ``blend_topn_rows_ref``.
    """
    queries = queries_q.astype(jnp.float32) * q_scale[:, None]
    nbr = neighbor_rows_q.astype(jnp.float32) * n_scale[:, :, None]
    neighbors = jnp.mean(nbr, axis=1)
    pred = alpha * queries + (1.0 - alpha) * neighbors
    return jax.lax.top_k(pred[:, :n_items], topn)[1]


def fused_recommend_quant_ref(corpus_q, c_scale, user_ids, k: int, alpha,
                              topn: int, bd: int = 512, n_items=None):
    """Oracle for the int8 fused serving pipeline (DESIGN.md §8.4).

    The query IS the user's quantized corpus row (q_scale =
    c_scale[user]); stage A is the D-tiled int8 top-k with fused
    self-exclusion, stage B gathers only the selected k int8 rows
    (the 4×-smaller HBM fetch that motivates the path) and blends
    dequantized.  Requires k ≤ M − 1 (dispatcher clamps).
    """
    queries_q = corpus_q[user_ids]
    q_scale = c_scale[user_ids]
    _, idx = dtiled_topk_ref(queries_q, corpus_q, k, bd=bd,
                             query_gids=user_ids, q_scale=q_scale,
                             c_scale=c_scale)
    return blend_topn_rows_quant_ref(queries_q, q_scale, corpus_q[idx],
                                     c_scale[idx], alpha, topn,
                                     n_items=n_items)


def decayed_scatter_ref(ids, weights, n_items: int):
    """Weighted multi-hot scatter: out[i] = Σ_{n,b} w[n]·[ids[n,b] == i].

    ids: i32[N, B] (PAD=-1), weights: f32[N] → f32[n_items].
    This is the TIFU-kNN user-vector builder AND the EmbeddingBag-grad
    shape (one-hot-matmul on TPU).
    """
    flat = ids.reshape(-1)
    w = jnp.repeat(weights, ids.shape[1])
    valid = flat >= 0
    return jnp.zeros((n_items,), jnp.float32).at[
        jnp.where(valid, flat, 0)].add(jnp.where(valid, w, 0.0))


def sparse_row_scatter_ref(table, rows, ids, vals):
    """Sparse per-row scatter-add into a [M, I] table.

    table: f32[M, I]; rows: i32[U]; ids: i32[U, W] (PAD=-1 skipped);
    vals: f32[U, W].  Returns table with

        out[rows[r], ids[r, w]] += vals[r, w]      for ids[r, w] >= 0.

    Duplicate (row, id) pairs accumulate.  Only O(U·W) elements of the
    table are addressed — this is the batched add path's delta applier
    (DESIGN.md §3.3).
    """
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    v = jnp.where(valid, vals, 0.0)
    return table.at[rows[:, None], safe].add(v)


def sparse_row_gather_ref(table, rows, ids):
    """Sparse per-row gather from a [M, I] table.

    table: f32[M, I]; rows: i32[U]; ids: i32[U, W] (PAD=-1 → 0.0).
    Returns f32[U, W] with out[r, w] = table[rows[r], ids[r, w]].

    The read half of the sparse_row_scatter pair: the decremental paths
    gather the raw values on an event's support before computing the
    reset/delta terms (DESIGN.md §3.5).  O(U·W) elements addressed.
    """
    m = table.shape[0]
    valid = ids >= 0
    safe_rows = jnp.clip(rows, 0, m - 1)
    vals = table[safe_rows[:, None], jnp.where(valid, ids, 0)]
    return jnp.where(valid, vals, 0.0).astype(table.dtype)


def replay_scatter_plan_ref(table, ids, vals, plan, bi: int):
    """Plan-consistency oracle: replay a scatter TilePlan step-by-step
    under the TPU pipeline's semantics and return the resulting table.

    Models exactly what the hardware observes: a maximal run of
    consecutive steps mapping the same ``(row, tile)`` block loads the
    block ONCE from the pre-pass table, accumulates the valid steps'
    contributions, and flushes once at the run's end.  A plan that maps
    one block into two separate runs (the non-consecutive-revisit bug the
    (row, tile) sort exists to prevent) trips the assertion instead of
    silently losing the first run's update.  ``ids``/``vals`` must be the
    row-sorted arrays the plan was built from (numpy or jax).
    """
    import numpy as np
    tab = np.array(table, np.float32)       # HBM after all flushes
    src = tab.copy()                         # what a run's load observes
    ids = np.asarray(ids)
    vals = np.asarray(vals)
    pb, pr = np.asarray(plan.batch), np.asarray(plan.row)
    pt, pv = np.asarray(plan.tile), np.asarray(plan.valid)
    flushed = set()
    acc = None
    for s in range(pr.size):
        row, tile = int(pr[s]), int(pt[s])
        if s == 0 or (row, tile) != (int(pr[s - 1]), int(pt[s - 1])):
            assert (row, tile) not in flushed, \
                f"block {(row, tile)} revisited in a second run"
            acc = src[row, tile * bi:(tile + 1) * bi].copy()
        if pv[s]:
            b = int(pb[s])
            for i, v in zip(ids[b], vals[b]):
                if tile * bi <= i < (tile + 1) * bi:
                    acc[int(i) - tile * bi] += float(v)
        if s == pr.size - 1 or (row, tile) != (int(pr[s + 1]),
                                               int(pt[s + 1])):
            tab[row, tile * bi:(tile + 1) * bi] = acc
            flushed.add((row, tile))
    return tab


def replay_gather_plan_ref(table, ids, plan, bi: int):
    """Plan-consistency oracle for the gather: replay the plan's valid
    steps and return f32[U, W] (PAD ids → 0).  Asserts each step reads
    ids from the batch row that owns the output block (``order="batch"``
    keeps pbatch[s] == s // T_max)."""
    import numpy as np
    tab = np.asarray(table)
    ids = np.asarray(ids)
    u, w = ids.shape
    t_max = np.asarray(plan.row).size // u
    out = np.zeros((u, w), tab.dtype)
    pb, pr = np.asarray(plan.batch), np.asarray(plan.row)
    pt, pv = np.asarray(plan.tile), np.asarray(plan.valid)
    for s in range(pr.size):
        if not pv[s]:
            continue
        b, row, tile = int(pb[s]), int(pr[s]), int(pt[s])
        assert b == s // t_max, (b, s, t_max)
        for wi, i in enumerate(ids[b]):
            if tile * bi <= i < (tile + 1) * bi:
                out[b, wi] = tab[row, int(i)]
    return out


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """Plain attention oracle. q,k,v: [B,S,H,D] (H == KV heads here)."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((s, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
