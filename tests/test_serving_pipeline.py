"""Fused Pallas serving pipeline (DESIGN.md §8).

Three layers of pins:

  * kernel edge cases — masked tail blocks (prime Q/M), empty corpus,
    k > M, and duplicate-score TIE-BREAK PARITY with `jax.lax.top_k`
    for both the Pallas kernel and the pure-JAX streaming schedule;
  * dispatch parity — the ops CPU path is bitwise the historical
    (pre-fusion) serving output, and the interpret-mode Pallas pipeline
    matches it exactly, for the single-corpus, per-shard-candidate and
    cross-shard blend stages;
  * the engine-side request batcher — pow2 bucketing returns the
    unpadded answers and bounds the compiled-shape count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn
from repro.kernels import ops, ref
from repro.kernels.knn_topk import knn_topk
from repro.kernels.serving_topn import blend_topn_onehot, blend_topn_rows


# ---------------------------------------------------------------------------
# streaming_topk (pure-JAX schedule) edge cases
# ---------------------------------------------------------------------------

def test_streaming_topk_empty_corpus(rng):
    q = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    vals, idx = knn.streaming_topk(q, jnp.zeros((0, 8), jnp.float32), k=3)
    assert vals.shape == (5, 3) and idx.shape == (5, 3)
    assert np.all(np.asarray(vals) == -np.inf)


def test_streaming_topk_k_exceeds_m(rng):
    q = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(9, 8)), jnp.float32)
    vals, idx = knn.streaming_topk(q, c, k=16, chunk=4)
    rv, ri = knn.nearest_neighbors(q, c, k=9)
    np.testing.assert_allclose(np.asarray(vals)[:, :9], np.asarray(rv),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx)[:, :9], np.asarray(ri))
    assert np.all(np.asarray(vals)[:, 9:] == -np.inf)


def test_streaming_topk_duplicate_score_tiebreak(rng):
    """Duplicate corpus rows ⇒ exact-score ties; the streaming merge
    must pick the same (lowest) indices lax.top_k picks."""
    q = jnp.asarray(rng.normal(size=(7, 12)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(20, 12)), jnp.float32)
    c = jnp.concatenate([c0, c0, c0], axis=0)            # every score x3
    vals, idx = knn.streaming_topk(q, c, k=11, chunk=16)
    rv, ri = knn.nearest_neighbors(q, c, k=11)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), atol=1e-5)


# ---------------------------------------------------------------------------
# knn_topk kernel edge cases (interpret mode)
# ---------------------------------------------------------------------------

def test_knn_topk_masked_tails_prime_dims(rng):
    """Q and M prime: neither divides its block — the removed
    divisibility assert is covered by in-kernel tail masks."""
    q = jnp.asarray(rng.normal(size=(37, 24)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(641, 24)), jnp.float32)
    v, i = knn_topk(q, c, k=7, bq=16, bm=128, interpret=True)
    rv, ri = ref.knn_topk_ref(q, c, 7)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), atol=1e-3,
                               rtol=1e-4)
    assert np.all(np.asarray(i) < 641)       # tail columns never selected
    for a, b in zip(np.asarray(i), np.asarray(ri)):
        assert set(map(int, a)) == set(map(int, b))


def test_knn_topk_empty_shapes():
    v, i = knn_topk(jnp.zeros((4, 8)), jnp.zeros((0, 8)), k=3,
                    interpret=True)
    assert v.shape == (4, 3) and np.all(np.asarray(v) == -np.inf)
    v, i = knn_topk(jnp.zeros((0, 8)), jnp.zeros((5, 8)), k=3,
                    interpret=True)
    assert v.shape == (0, 3) and i.shape == (0, 3)


def test_knn_topk_k_exceeds_m(rng):
    q = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(9, 8)), jnp.float32)
    v, i = knn_topk(q, c, k=16, bq=8, bm=8, interpret=True)
    rv, ri = ref.knn_topk_ref(q, c, 9)
    np.testing.assert_allclose(np.asarray(v)[:, :9], np.asarray(rv),
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(i)[:, :9], np.asarray(ri))
    assert np.all(np.asarray(v)[:, 9:] == -np.inf)


def test_knn_topk_duplicate_score_tiebreak(rng):
    q = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(32, 12)), jnp.float32)
    c = jnp.concatenate([c0, c0, c0], axis=0)
    v, i = knn_topk(q, c, k=10, bq=8, bm=32, interpret=True)
    rv, ri = ref.knn_topk_ref(q, c, 10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_knn_topk_fused_self_exclusion(rng):
    """query_gids masking == the reference .at[r, id].set(-inf) path."""
    c = jnp.asarray(rng.normal(size=(63, 16)), jnp.float32)
    qids = jnp.asarray(rng.choice(63, 21, replace=False).astype(np.int32))
    v, i = knn_topk(c[qids], c, k=5, bq=8, bm=16, interpret=True,
                    query_gids=qids)
    rv, ri = knn.nearest_neighbors(c[qids], c, k=5, exclude_self=True,
                                   query_ids=qids)
    assert not np.any(np.asarray(i) == np.asarray(qids)[:, None])
    for a, b in zip(np.asarray(i), np.asarray(ri)):
        assert set(map(int, a)) == set(map(int, b))


def test_knn_topk_shard_gid_exclusion(rng):
    """col_offset/col_stride global ids: a query is excluded only on the
    shard owning its global id (DESIGN.md §7.1 round-robin layout)."""
    n_shards, m = 3, 60
    corpus = jnp.asarray(rng.normal(size=(m, 16)), jnp.float32)
    qids = jnp.asarray(np.arange(12, dtype=np.int32))
    queries = corpus[qids]
    for shard in range(n_shards):
        local = corpus[shard::n_shards]
        v, i = knn_topk(queries, local, k=4, bq=8, bm=8, interpret=True,
                        query_gids=qids, col_offset=shard,
                        col_stride=n_shards)
        gids = np.asarray(i) * n_shards + shard
        assert not np.any(gids == np.asarray(qids)[:, None])


# ---------------------------------------------------------------------------
# blend/top-n kernels vs the ref oracles (interpret mode)
# ---------------------------------------------------------------------------

def test_blend_topn_onehot_matches_gather_path(rng):
    m, n_items, q_n, k = 101, 67, 13, 5       # all prime-ish tails
    corpus = jnp.asarray(rng.normal(size=(m, n_items)), jnp.float32)
    uids = jnp.asarray(rng.choice(m, q_n, replace=False).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, m, (q_n, k)), jnp.int32)
    v, i = blend_topn_onehot(corpus, uids, idx, alpha=0.7, topn=6,
                             bq=8, bm=32, bi=32, interpret=True)
    pred = (0.7 * corpus[uids]
            + 0.3 * jnp.mean(corpus[idx], axis=1))
    rv, ri = jax.lax.top_k(pred, 6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), atol=1e-4)


def test_blend_topn_onehot_duplicate_item_tiebreak(rng):
    """Identical item columns ⇒ exact prediction ties; the running
    merge must keep lax.top_k's lowest-item-id order."""
    m, q_n, k = 64, 9, 4
    base = jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)
    corpus = jnp.tile(base, (1, 4))           # items repeat every 8
    uids = jnp.asarray(rng.choice(m, q_n, replace=False).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, m, (q_n, k)), jnp.int32)
    v, i = blend_topn_onehot(corpus, uids, idx, alpha=0.7, topn=10,
                             bq=4, bm=16, bi=8, interpret=True)
    pred = 0.7 * corpus[uids] + 0.3 * jnp.mean(corpus[idx], axis=1)
    np.testing.assert_array_equal(np.asarray(i),
                                  np.asarray(jax.lax.top_k(pred, 10)[1]))


def test_blend_topn_rows_matches_ref(rng):
    q_n, k, n_items = 13, 5, 67
    queries = jnp.asarray(rng.normal(size=(q_n, n_items)), jnp.float32)
    nbr = jnp.asarray(rng.normal(size=(q_n, k, n_items)), jnp.float32)
    v, i = blend_topn_rows(queries, nbr, alpha=0.3, topn=7, bq=4, bi=16,
                           interpret=True)
    ri = ref.blend_topn_rows_ref(queries, nbr, 0.3, 7)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


# ---------------------------------------------------------------------------
# ops dispatch parity: cpu == historical output == interpret Pallas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n_items,q_n,k,topn", [
    (101, 67, 23, 7, 9),       # prime tails everywhere
    (128, 64, 32, 8, 10),      # block-aligned
    (33, 41, 33, 5, 41),       # every user queries; topn == n_items
])
def test_fused_recommend_cpu_is_bitwise_historical(rng, m, n_items, q_n,
                                                   k, topn):
    corpus = jnp.asarray(rng.normal(size=(m, n_items)), jnp.float32)
    uids = jnp.asarray(rng.choice(m, q_n, replace=False).astype(np.int32))
    # the pre-fusion recommend_for_users body, verbatim
    queries = corpus[uids]
    pred = knn.predict(queries, corpus, k=k, alpha=0.7,
                       exclude_self=True, query_ids=uids)
    want = np.asarray(knn.recommend_topn(pred, topn))
    got = np.asarray(knn.recommend_for_users(corpus, uids, k=k, alpha=0.7,
                                             topn=topn))
    np.testing.assert_array_equal(got, want)
    with ops.default_impl("interpret"):
        got_i = np.asarray(knn.recommend_for_users(corpus, uids, k=k,
                                                   alpha=0.7, topn=topn))
    np.testing.assert_array_equal(got_i, want)


def test_fused_recommend_oracle_matches_predict_ulp(rng):
    """The ref.py oracle's prediction == core.knn.predict bitwise (the
    ISSUE's ≤1-ulp validation of the oracle against the predict path —
    both run the identical jnp program)."""
    corpus = jnp.asarray(rng.normal(size=(53, 29)), jnp.float32)
    uids = jnp.asarray(rng.choice(53, 11, replace=False).astype(np.int32))
    scores_core = knn.pairwise_scores(corpus[uids], corpus, "euclidean")
    scores_ref = ref._pairwise_scores(corpus[uids], corpus, "euclidean")
    np.testing.assert_array_equal(np.asarray(scores_core),
                                  np.asarray(scores_ref))
    got = np.asarray(ref.fused_recommend_ref(corpus, uids, 6, 0.7, 8))
    pred = knn.predict(corpus[uids], corpus, k=6, alpha=0.7,
                       exclude_self=True, query_ids=uids)
    np.testing.assert_array_equal(got,
                                  np.asarray(knn.recommend_topn(pred, 8)))


def test_fused_recommend_alpha_extremes(rng):
    corpus = jnp.asarray(rng.normal(size=(40, 24)), jnp.float32)
    uids = jnp.asarray(np.arange(10, dtype=np.int32))
    for alpha in (0.0, 1.0):
        want = np.asarray(knn.recommend_for_users(corpus, uids, k=4,
                                                  alpha=alpha, topn=5))
        with ops.default_impl("interpret"):
            got = np.asarray(knn.recommend_for_users(corpus, uids, k=4,
                                                     alpha=alpha, topn=5))
        np.testing.assert_array_equal(got, want, err_msg=f"alpha={alpha}")


def test_fused_recommend_empty_and_invalid():
    corpus = jnp.zeros((6, 12), jnp.float32)
    out = ops.fused_recommend(corpus, jnp.zeros((0,), jnp.int32), k=3,
                              alpha=0.7, topn=4)
    assert out.shape == (0, 4)
    out = ops.fused_recommend(jnp.zeros((0, 12), jnp.float32),
                              jnp.zeros((0,), jnp.int32), k=3, alpha=0.7,
                              topn=4)
    assert out.shape == (0, 4)
    with pytest.raises(ValueError, match="topn"):
        ops.fused_recommend(corpus, jnp.zeros((2,), jnp.int32), k=3,
                            alpha=0.7, topn=13)


def test_fused_recommend_k_clamped_below_m(rng):
    """k >= M must serve (clamped to M−1: self-exclusion leaves M−1
    finite candidates, and a −inf slot would resolve differently in the
    kernel vs the reference), not crash like the pre-fusion path —
    and the interpret path must still match the cpu path exactly."""
    corpus = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    uids = jnp.asarray(np.arange(4, dtype=np.int32))
    want = np.asarray(knn.recommend_for_users(corpus, uids, k=8,
                                              alpha=0.7, topn=5))
    for k in (9, 100):
        got = np.asarray(knn.recommend_for_users(corpus, uids, k=k,
                                                 alpha=0.7, topn=5))
        np.testing.assert_array_equal(got, want)
        with ops.default_impl("interpret"):
            got_i = np.asarray(knn.recommend_for_users(
                corpus, uids, k=k, alpha=0.7, topn=5))
        np.testing.assert_array_equal(got_i, want, err_msg=f"k={k}")


def test_shard_topk_k_exceeds_shard_interpret_matches_cpu(rng):
    """k >= m_s on the owner shard admits the excluded self column as a
    −inf candidate; its global id must resolve to the self gid in both
    impls (the cross-shard merge compares the (score, gid) lists)."""
    n_shards, m = 2, 8
    corpus = np.asarray(rng.normal(size=(m, 12)), np.float32)
    qids = jnp.asarray(np.arange(6, dtype=np.int32))
    queries = jnp.asarray(corpus[:6])
    for shard in range(n_shards):
        local = jnp.asarray(corpus[shard::n_shards])   # m_s = 4 <= k
        want_v, want_g = ops.shard_topk(queries, local, k=4, shard=shard,
                                        n_shards=n_shards,
                                        query_gids=qids, impl="ref")
        with ops.default_impl("interpret"):
            got_v, got_g = ops.shard_topk(queries, local, k=4,
                                          shard=shard, n_shards=n_shards,
                                          query_gids=qids)
        np.testing.assert_array_equal(np.asarray(got_g),
                                      np.asarray(want_g))
        np.testing.assert_allclose(np.asarray(got_v),
                                   np.asarray(want_v), atol=1e-3,
                                   rtol=1e-4)


def test_shard_topk_interpret_matches_cpu(rng):
    n_shards, m = 3, 61                        # ragged shard sizes
    corpus = np.asarray(rng.normal(size=(m, 24)), np.float32)
    qids = jnp.asarray(rng.choice(m, 14, replace=False).astype(np.int32))
    queries = jnp.asarray(corpus[np.asarray(qids)])
    for shard in range(n_shards):
        local = jnp.asarray(corpus[shard::n_shards])
        want_v, want_g = ops.shard_topk(queries, local, k=6, shard=shard,
                                        n_shards=n_shards,
                                        query_gids=qids, impl="ref")
        with ops.default_impl("interpret"):
            got_v, got_g = ops.shard_topk(queries, local, k=6,
                                          shard=shard, n_shards=n_shards,
                                          query_gids=qids)
        np.testing.assert_array_equal(np.asarray(got_g),
                                      np.asarray(want_g))
        np.testing.assert_allclose(np.asarray(got_v),
                                   np.asarray(want_v), atol=1e-3,
                                   rtol=1e-4)


def test_sharded_recommend_interpret_matches_cpu(rng):
    from repro.parallel.sharding import UserShardSpec
    m, n_items = 23, 37
    corpus = rng.normal(size=(m, n_items)).astype(np.float32)
    users = rng.choice(m, 9, replace=False)
    want = np.asarray(knn.recommend_for_users(
        jnp.asarray(corpus), jnp.asarray(users.astype(np.int32)), k=7,
        alpha=0.7, topn=6))
    for n_shards in (2, 3):
        spec = UserShardSpec(m, n_shards)
        corpora = [jnp.asarray(corpus[spec.owned_users(s)])
                   for s in range(n_shards)]
        with ops.default_impl("interpret"):
            got = knn.sharded_recommend_for_users(
                corpora, users, k=7, alpha=0.7, topn=6,
                n_shards=n_shards)
        np.testing.assert_array_equal(got, want, err_msg=f"S={n_shards}")


# ---------------------------------------------------------------------------
# Engine-side request batcher
# ---------------------------------------------------------------------------

def test_engine_recommend_pads_to_pow2_buckets(rng):
    from repro.core import TifuParams
    from repro.streaming import StateStore, StoreConfig, StreamingEngine
    p = TifuParams(n_items=41, group_size=3, k_neighbors=4, alpha=0.7)
    store = StateStore(StoreConfig(n_users=16, n_items=41, max_baskets=8,
                                   max_basket_size=6))
    eng = StreamingEngine(store, p, batch_size=16)
    for u in range(16):
        eng.add_basket(u, rng.choice(41, size=3, replace=False))
    eng.run_until_drained()
    corpus = store.corpus()
    sizes = [1, 3, 5, 6, 7, 9, 13, 16]
    for q_n in sizes:
        users = rng.choice(16, size=q_n, replace=False)
        got = eng.recommend(users, topn=5)
        assert got.shape == (q_n, 5)
        want = np.asarray(knn.recommend_for_users(
            corpus, jnp.asarray(users.astype(np.int32)), k=4, alpha=0.7,
            topn=5))
        np.testing.assert_array_equal(got, want)
    # 8 distinct request sizes, but only pow2 buckets {1,4,8,16} compile
    assert eng.metrics.serve_requests == len(sizes)
    assert eng.metrics.serve_compiled_shapes == 4
    assert eng.recommend(np.zeros((0,), np.int64)).shape == (0, 10)
