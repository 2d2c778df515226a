"""Kind-partitioned sub-batch pipeline (DESIGN.md §3.3/§4) vs the
paper-faithful RefEngine: sparse-delta adds, homogeneous deletes, scale
renormalization, and full randomized mixed streams through the engine
(including replay-after-restore)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (RefEngine, StreamState, TifuParams, AddBatch,
                        DelBasketBatch, DelItemBatch, SCALE_CEIL,
                        SCALE_FLOOR, apply_add_batch,
                        apply_del_basket_batch, apply_del_basket_batch_dense,
                        apply_del_item_batch, apply_del_item_batch_dense,
                        renormalize_users)
from repro.core.types import KIND_ADD_BASKET, KIND_DEL_BASKET, KIND_DEL_ITEM
from repro.streaming import Event, StateStore, StoreConfig, StreamingEngine

P = TifuParams(n_items=41, group_size=3, r_b=0.9, r_g=0.7)
M, N, B, K = 8, 48, 6, 48


def random_mixed_events(rng, ref: RefEngine, n_events: int,
                        n_users: int, p_add=0.6):
    """Generate a valid mixed stream, applying each event to ``ref`` as
    it is drawn (deletes need the current history)."""
    events = []
    for _ in range(n_events):
        u = int(rng.integers(0, n_users))
        st = ref.state(u)
        nb = st.n_baskets
        if nb == 0 or (rng.random() < p_add and nb < N - 2):
            items = rng.choice(P.n_items, size=int(rng.integers(1, B)),
                               replace=False).astype(np.int32)
            ref.add_basket(u, items)
            events.append(Event(KIND_ADD_BASKET, u, items=items))
        elif rng.random() < 0.5:
            pos = int(rng.integers(0, nb))
            ref.delete_basket(u, pos)
            events.append(Event(KIND_DEL_BASKET, u, pos=pos))
        else:
            pos = int(rng.integers(0, nb))
            item = int(rng.choice(st.history[pos]))
            ref.delete_item(u, pos, item)
            events.append(Event(KIND_DEL_ITEM, u, pos=pos, item=item))
    return events


def assert_matches_ref(state: StreamState, ref: RefEngine, n_users: int,
                       rtol=1e-4, atol=1e-5):
    mat = np.asarray(state.materialized_user_vecs())
    lg = np.asarray(state.materialized_last_group_vecs())
    for u in range(n_users):
        st = ref.state(u)
        np.testing.assert_allclose(mat[u], st.user_vec.astype(np.float32),
                                   rtol=rtol, atol=atol, err_msg=f"u={u}")
        np.testing.assert_allclose(lg[u],
                                   st.last_group_vec.astype(np.float32),
                                   rtol=rtol, atol=atol, err_msg=f"lgv u={u}")
        assert int(state.n_baskets[u]) == st.n_baskets
        assert int(state.n_groups[u]) == st.n_groups
        gs = list(np.asarray(state.group_sizes[u])[:st.n_groups])
        assert gs == st.group_sizes


# ---------------------------------------------------------------------------
# Direct sub-batch API
# ---------------------------------------------------------------------------

def test_add_batch_multiuser_matches_ref(rng):
    """One sparse AddBatch updating several distinct users, spanning both
    Eq. 7 (new group) and Eq. 8+9 (append) scenarios."""
    state = StreamState.zeros(M, P.n_items, N, B, K)
    ref = RefEngine(P, dtype=np.float32)
    # seed: u baskets for user u (users hit different group boundaries)
    for u in range(M):
        for _ in range(u):
            b = rng.choice(P.n_items, size=3, replace=False)
            ref.add_basket(u, b)
            state = apply_add_batch(state, AddBatch.build([u], [b], B), P)
    baskets = [rng.choice(P.n_items, size=4, replace=False)
               for _ in range(M)]
    for u, b in enumerate(baskets):
        ref.add_basket(u, b)
    state = apply_add_batch(
        state, AddBatch.build(list(range(M)), baskets, B), P)
    assert_matches_ref(state, ref, M)


def test_add_batch_padding_rows_are_noops(rng):
    state = StreamState.zeros(M, P.n_items, N, B, K)
    b = rng.choice(P.n_items, size=3, replace=False)
    state = apply_add_batch(state, AddBatch.build([1], [b], B), P)
    before = np.asarray(state.materialized_user_vecs())
    # build pads 3 -> 4 rows; the padding row aliases user 0
    batch = AddBatch.build([2, 4, 5],
                           [rng.choice(P.n_items, size=2, replace=False)
                            for _ in range(3)], B)
    assert batch.size == 4 and not bool(batch.valid[3])
    state = apply_add_batch(state, batch, P)
    after = np.asarray(state.materialized_user_vecs())
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[1], before[1])


def test_del_batches_multiuser_match_ref(rng):
    state = StreamState.zeros(M, P.n_items, N, B, K)
    ref = RefEngine(P, dtype=np.float32)
    for u in range(M):
        for _ in range(6):
            b = rng.choice(P.n_items, size=3, replace=False)
            ref.add_basket(u, b)
            state = apply_add_batch(state, AddBatch.build([u], [b], B), P)
    # basket deletions for half the users, item deletions for the rest
    del_users = list(range(0, M, 2))
    positions = [int(rng.integers(0, ref.state(u).n_baskets))
                 for u in del_users]
    for u, pos in zip(del_users, positions):
        ref.delete_basket(u, pos)
    state = apply_del_basket_batch(
        state, DelBasketBatch.build(del_users, positions), P)
    item_users = list(range(1, M, 2))
    positions, items = [], []
    for u in item_users:
        pos = int(rng.integers(0, ref.state(u).n_baskets))
        it = int(rng.choice(ref.state(u).history[pos]))
        ref.delete_item(u, pos, it)
        positions.append(pos)
        items.append(it)
    state = apply_del_item_batch(
        state, DelItemBatch.build(item_users, positions, items), P)
    assert_matches_ref(state, ref, M)


def test_delete_on_empty_history_is_noop(rng):
    state = StreamState.zeros(M, P.n_items, N, B, K)
    b = rng.choice(P.n_items, size=3, replace=False)
    state = apply_add_batch(state, AddBatch.build([1], [b], B), P)
    before = np.asarray(state.materialized_user_vecs())
    state = apply_del_basket_batch(
        state, DelBasketBatch.build([2], [0]), P)   # user 2 is empty
    state = apply_del_item_batch(
        state, DelItemBatch.build([3], [0], [5]), P)
    np.testing.assert_array_equal(
        np.asarray(state.materialized_user_vecs()), before)


def test_add_at_capacity_is_noop(rng):
    """A full history row is not all-PAD, so the sparse history write
    must not touch it: adds to a full user are no-ops (regression:
    unguarded adds wrote item ids >= n_items into occupied rows)."""
    n, b = 4, 4
    state = StreamState.zeros(2, 20, n, b, n)
    ref = RefEngine(TifuParams(n_items=20, group_size=3), dtype=np.float32)
    p20 = TifuParams(n_items=20, group_size=3)
    baskets = [rng.choice(20, size=3, replace=False) for _ in range(6)]
    for bk in baskets[:n]:
        ref.add_basket(0, bk)
    for bk in baskets:      # two adds beyond capacity
        state = apply_add_batch(state, AddBatch.build([0], [bk], b), p20)
    hist = np.asarray(state.history[0])
    assert hist.max() < 20 and int(state.n_baskets[0]) == n
    np.testing.assert_allclose(
        np.asarray(state.materialized_user_vecs()[0]),
        ref.state(0).user_vec.astype(np.float32), rtol=1e-4, atol=1e-5)
    # deleting frees a row; the next add must land normally again
    ref.delete_basket(0, 1)
    state = apply_del_basket_batch(state, DelBasketBatch.build([0], [1]),
                                   p20)
    ref.add_basket(0, baskets[4])
    state = apply_add_batch(state, AddBatch.build([0], [baskets[4]], b),
                            p20)
    np.testing.assert_allclose(
        np.asarray(state.materialized_user_vecs()[0]),
        ref.state(0).user_vec.astype(np.float32), rtol=1e-4, atol=1e-5)


def test_renormalize_preserves_values(rng):
    """Drive the scales down with many group-opening adds, renormalize,
    and check the true vectors are unchanged and scales are reset."""
    p1 = TifuParams(n_items=29, group_size=1, r_b=0.9, r_g=0.7)  # every add
    state = StreamState.zeros(2, p1.n_items, 64, 4, 64)          # opens a group
    ref = RefEngine(p1, dtype=np.float32)
    for _ in range(40):
        b = rng.choice(p1.n_items, size=3, replace=False)
        ref.add_basket(0, b)
        state = apply_add_batch(state, AddBatch.build([0], [b], 4), p1)
    assert float(state.uv_scale[0]) < 1e-3          # scales really shrank
    before = np.asarray(state.materialized_user_vecs())
    state = renormalize_users(state, jnp.asarray([0], jnp.int32))
    assert float(state.uv_scale[0]) == 1.0
    assert float(state.lgv_scale[0]) == 1.0
    np.testing.assert_allclose(np.asarray(state.materialized_user_vecs()),
                               before, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(state.materialized_user_vecs()[0]),
        ref.state(0).user_vec.astype(np.float32), rtol=1e-4, atol=1e-5)
    assert float(SCALE_FLOOR) > 0.0


def test_restore_migrates_prescale_checkpoints(rng, tmp_path):
    """Checkpoints written before the scaled representation (no
    uv_scale/lgv_scale leaves) restore with scales of 1."""
    import os
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K))
    store.checkpoint(str(tmp_path), 0)
    path = os.path.join(str(tmp_path), "state_0000000000.npz")
    old = dict(np.load(path))
    for key in ("uv_scale", "lgv_scale"):
        old.pop(key)
    with open(path, "wb") as f:
        np.savez_compressed(f, **old)
    # pre-scale-era checkpoints also predate the commit CRCs (DESIGN.md
    # §9.1): strip them so the simulation takes the legacy-accept path
    import json
    latest = os.path.join(str(tmp_path), "LATEST")
    with open(latest) as f:
        meta = json.load(f)
    for key in ("meta_crc32", "npz_crc32", "npz_bytes"):
        meta.pop(key, None)
    with open(latest, "w") as f:
        json.dump(meta, f)
    store2 = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                    max_baskets=N, max_basket_size=B,
                                    max_groups=K))
    store2.restore(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(store2.state.uv_scale),
                                  np.ones(M, np.float32))
    np.testing.assert_array_equal(np.asarray(store2.state.lgv_scale),
                                  np.ones(M, np.float32))


def test_fast_decay_hot_user_stays_finite(rng):
    """r_g=0.2, group_size=1: uv_scale shrinks ~5x per add; the probe
    interval must be derived from the decay rates or the raw rows
    overflow f32 between probes and renormalization produces NaN
    (regression)."""
    p = TifuParams(n_items=30, group_size=1, r_b=0.9, r_g=0.2)
    store = StateStore(StoreConfig(n_users=2, n_items=30, max_baskets=128,
                                   max_basket_size=4, max_groups=128))
    eng = StreamingEngine(store, p, batch_size=1)
    assert eng.renorm_check_interval < 64   # derived from min(r_b, r_g)
    ref = RefEngine(p, dtype=np.float64)
    for _ in range(70):
        b = rng.choice(30, size=3, replace=False)
        eng.add_basket(0, b)
        ref.add_basket(0, b)
    eng.run_until_drained()
    assert eng.metrics.renormalizations > 0
    mat = np.asarray(store.state.materialized_user_vecs())
    assert np.all(np.isfinite(np.asarray(store.state.user_vecs)))
    np.testing.assert_allclose(mat[0], ref.state(0).user_vec, atol=1e-6)


def test_engine_counts_dropped_adds(rng):
    store = StateStore(StoreConfig(n_users=2, n_items=P.n_items,
                                   max_baskets=3, max_basket_size=B))
    eng = StreamingEngine(store, P, batch_size=4)
    for _ in range(5):
        eng.add_basket(0, rng.choice(P.n_items, size=3, replace=False))
    eng.run_until_drained()
    assert int(store.state.n_baskets[0]) == 3
    assert eng.metrics.dropped_adds == 2


# ---------------------------------------------------------------------------
# Sparse decremental paths vs the dense baselines (DESIGN.md §3.5)
# ---------------------------------------------------------------------------

def _seeded_pair(rng, ref, n_baskets_per_user=6):
    """Two identical StreamStates (sparse/dense arms) + a seeded ref."""
    state = StreamState.zeros(M, P.n_items, N, B, K)
    for u in range(M):
        for _ in range(n_baskets_per_user):
            b = rng.choice(P.n_items, size=int(rng.integers(1, B)),
                           replace=False)
            ref.add_basket(u, b)
            state = apply_add_batch(state, AddBatch.build([u], [b], B), P)
    clone = jax.tree_util.tree_map(lambda x: x.copy(), state)
    return state, clone


def test_sparse_del_basket_matches_dense_and_ref(rng):
    """One DelBasketBatch through both arms: sparse == dense == ref,
    covering Eq. 10/11 (tau_j > 1) and Eq. 12 (tau_j == 1) positions."""
    ref = RefEngine(P, dtype=np.float32)
    sparse, dense = _seeded_pair(rng, ref, 7)   # 7 = 3+3+1: a single-
    users = list(range(M))                      # basket last group
    positions = [u % 7 for u in users]          # spans all groups
    for u, pos in zip(users, positions):
        ref.delete_basket(u, pos)
    batch = DelBasketBatch.build(users, positions)
    sparse = apply_del_basket_batch(sparse, batch, P)
    dense = apply_del_basket_batch_dense(dense, batch, P)
    assert_matches_ref(sparse, ref, M)
    np.testing.assert_allclose(
        np.asarray(sparse.materialized_user_vecs()),
        np.asarray(dense.materialized_user_vecs()), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sparse.materialized_last_group_vecs()),
        np.asarray(dense.materialized_last_group_vecs()),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sparse.history),
                                  np.asarray(dense.history))
    np.testing.assert_array_equal(np.asarray(sparse.group_sizes),
                                  np.asarray(dense.group_sizes))


def test_sparse_del_item_matches_dense_and_ref(rng):
    """DelItemBatch through both arms, including the basket-vanish
    fallback (a singleton basket) and absent-item no-ops."""
    ref = RefEngine(P, dtype=np.float32)
    sparse, dense = _seeded_pair(rng, ref, 6)
    # user 0: make basket 2 a singleton so deleting its item vanishes it
    single_item = int(np.asarray(sparse.history[0, 2].max()))
    for _ in range(int(np.sum(np.asarray(sparse.history[0, 2]) >= 0)) - 1):
        row = np.asarray(sparse.history[0, 2])
        victim = int(row[row >= 0][0])
        if victim == single_item:
            victim = int(row[row >= 0][1])
        ref.delete_item(0, 2, victim)
        b = DelItemBatch.build([0], [2], [victim])
        sparse = apply_del_item_batch(sparse, b, P)
        dense = apply_del_item_batch_dense(dense, b, P)
    users, positions, items = [], [], []
    for u in range(M):
        if u == 0:
            pos, it = 2, single_item          # vanish fallback
        else:
            pos = int(rng.integers(0, ref.state(u).n_baskets))
            it = int(rng.choice(ref.state(u).history[pos]))
        ref.delete_item(u, pos, it)
        users.append(u)
        positions.append(pos)
        items.append(it)
    batch = DelItemBatch.build(users, positions, items)
    sparse = apply_del_item_batch(sparse, batch, P)
    dense = apply_del_item_batch_dense(dense, batch, P)
    assert_matches_ref(sparse, ref, M)
    np.testing.assert_allclose(
        np.asarray(sparse.materialized_user_vecs()),
        np.asarray(dense.materialized_user_vecs()), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sparse.history),
                                  np.asarray(dense.history))


def test_sparse_delete_to_empty_and_rebuild(rng):
    """Deleting every basket empties the state (scenario 3) and
    subsequent adds rebuild it correctly on the residue-free support."""
    ref = RefEngine(P, dtype=np.float32)
    state = StreamState.zeros(M, P.n_items, N, B, K)
    baskets = [rng.choice(P.n_items, size=3, replace=False)
               for _ in range(4)]
    for b in baskets:
        ref.add_basket(0, b)
        state = apply_add_batch(state, AddBatch.build([0], [b], B), P)
    for _ in range(4):
        ref.delete_basket(0, 0)
        state = apply_del_basket_batch(state, DelBasketBatch.build([0], [0]),
                                       P)
    assert int(state.n_baskets[0]) == 0 and int(state.n_groups[0]) == 0
    np.testing.assert_allclose(
        np.asarray(state.materialized_user_vecs()[0]),
        np.zeros(P.n_items), atol=1e-5)
    b = rng.choice(P.n_items, size=4, replace=False)
    ref.add_basket(0, b)
    state = apply_add_batch(state, AddBatch.build([0], [b], B), P)
    assert_matches_ref(state, ref, 1)


def test_eq12_delete_grows_scale_and_renormalizes(rng):
    """Eq. 12 deletions fold the k/((k-1)·r_g) rescale into uv_scale
    (growth!); renormalize_users folds it back value-preservingly and
    the engine's ceiling probe keeps raw rows finite."""
    p1 = TifuParams(n_items=29, group_size=1, r_b=0.9, r_g=0.7)
    state = StreamState.zeros(2, p1.n_items, 64, 4, 64)
    ref = RefEngine(p1, dtype=np.float64)
    for _ in range(30):
        b = rng.choice(p1.n_items, size=3, replace=False)
        ref.add_basket(0, b)
        state = apply_add_batch(state, AddBatch.build([0], [b], 4), p1)
    s_after_adds = float(state.uv_scale[0])
    assert s_after_adds < 1e-3
    for _ in range(25):                   # every delete is an Eq. 12 case
        ref.delete_basket(0, 0)
        state = apply_del_basket_batch(state,
                                       DelBasketBatch.build([0], [0]), p1)
    assert float(state.uv_scale[0]) > s_after_adds * 100.0   # scale grew
    before = np.asarray(state.materialized_user_vecs())
    state = renormalize_users(state, jnp.asarray([0], jnp.int32))
    assert float(state.uv_scale[0]) == 1.0
    np.testing.assert_allclose(np.asarray(state.materialized_user_vecs()),
                               before, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(state.materialized_user_vecs()[0]),
        ref.state(0).user_vec.astype(np.float32), rtol=1e-3, atol=1e-4)
    assert SCALE_CEIL > 1.0 / SCALE_FLOOR * 1e-37   # bounds sane


def test_engine_bucket_hysteresis():
    """A kind's pow2 bucket grows immediately but shrinks only after
    bucket_hysteresis consecutive below-boundary micro-batches
    (ROADMAP: recompile churn when counts straddle a boundary)."""
    store = StateStore(StoreConfig(n_users=64, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K))
    eng = StreamingEngine(store, P, batch_size=32, bucket_hysteresis=3)
    rng = np.random.default_rng(0)

    def run_adds(n_users_in_batch, lo):
        for u in range(lo, lo + n_users_in_batch):
            eng.add_basket(u, rng.choice(P.n_items, size=3, replace=False))
        eng.step()

    run_adds(9, 0)                        # bucket -> 16
    assert eng._kind_bucket[KIND_ADD_BASKET] == 16
    for i in range(2):                    # below boundary, held at 16
        run_adds(5, 10 * (i + 1))
        assert eng._kind_bucket[KIND_ADD_BASKET] == 16
    run_adds(5, 40)                       # 3rd consecutive: shrink to 8
    assert eng._kind_bucket[KIND_ADD_BASKET] == 8
    assert eng.metrics.bucket_shrinks == 1
    run_adds(9, 50)                       # growth is immediate
    assert eng._kind_bucket[KIND_ADD_BASKET] == 16
    assert eng.metrics.bucket_grows == 1


def test_store_corpus_cache_tracks_state(rng):
    """store.corpus() == materialized_user_vecs() after every batch while
    refreshing only the rows the engine touched (threshold rebuilds
    disabled: batches here dirty half the 8-user store every step)."""
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K, corpus_rebuild_frac=1.0))
    eng = StreamingEngine(store, P, batch_size=4)
    ref = RefEngine(P, dtype=np.float32)
    events = random_mixed_events(rng, ref, 80, M)
    np.testing.assert_allclose(np.asarray(store.corpus()),
                               np.zeros((M, store.cfg.n_cols)))  # cold
    eng.submit(events)
    while eng.step():
        np.testing.assert_allclose(
            np.asarray(store.corpus()),
            np.asarray(store.state.materialized_user_vecs(padded=True)),
            rtol=1e-6, atol=1e-7)
    assert store.corpus_full_builds == 1
    # each batch dirties <= batch_size rows; far fewer refreshes than a
    # full rebuild per step would cost
    assert 0 < store.corpus_rows_refreshed <= eng.metrics.batches * 4
    # restore invalidates: the next corpus() is a fresh full build
    store.invalidate_all()
    np.testing.assert_allclose(
        np.asarray(store.corpus()),
        np.asarray(store.state.materialized_user_vecs(padded=True)),
        rtol=1e-6,
        atol=1e-7)
    assert store.corpus_full_builds == 2


def test_store_corpus_rebuild_threshold_crossover(rng):
    """Below ``corpus_rebuild_frac`` the cache refreshes rows; above it,
    one full materialize (ROADMAP: high delete rates).  Both paths are
    counted and both produce the exact corpus."""
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K, corpus_rebuild_frac=0.5))
    state = StreamState.zeros(M, P.n_items, N, B, K)
    for u in range(M):
        b = rng.choice(P.n_items, size=3, replace=False)
        state = apply_add_batch(state, AddBatch.build([u], [b], B), P)
    store.state = state
    store.corpus()                               # cold full build
    assert store.corpus_full_builds == 1

    def touch(users):
        b = [rng.choice(P.n_items, size=3, replace=False) for _ in users]
        store.state = apply_add_batch(
            store.state, AddBatch.build(list(users), b, B), P)
        store.invalidate_users(list(users))

    touch(range(3))                              # 3/8 <= 0.5: row refresh
    np.testing.assert_allclose(
        np.asarray(store.corpus()),
        np.asarray(store.state.materialized_user_vecs(padded=True)),
        rtol=1e-6,
        atol=1e-7)
    assert store.corpus_threshold_rebuilds == 0
    assert store.corpus_rows_refreshed >= 3

    rows_before = store.corpus_rows_refreshed
    touch(range(5))                              # 5/8 > 0.5: full rebuild
    np.testing.assert_allclose(
        np.asarray(store.corpus()),
        np.asarray(store.state.materialized_user_vecs(padded=True)),
        rtol=1e-6,
        atol=1e-7)
    assert store.corpus_threshold_rebuilds == 1
    assert store.corpus_full_builds == 2
    assert store.corpus_rows_refreshed == rows_before   # no scattered path


def test_engine_bucket_decay_for_absent_kinds():
    """A one-off burst of one kind must not pin its pow2 bucket forever:
    batches WITHOUT the kind advance its shrink hysteresis too, so the
    bucket decays and a later singleton pads small again (regression:
    a GDPR delete wave pinned del-basket at its burst bucket)."""
    store = StateStore(StoreConfig(n_users=64, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K))
    eng = StreamingEngine(store, P, batch_size=32, bucket_hysteresis=3)
    rng = np.random.default_rng(0)
    for u in range(64):
        eng.add_basket(u, rng.choice(P.n_items, size=3, replace=False))
    eng.run_until_drained()
    # burst: 9 basket deletions in one micro-batch -> bucket 16
    for u in range(9):
        eng.delete_basket(u, 0)
    eng.step()
    assert eng._kind_bucket[KIND_DEL_BASKET] == 16
    # add-only batches: the del-basket bucket decays after hysteresis
    for i in range(3):
        for u in range(4):
            eng.add_basket(10 + 4 * i + u,
                           rng.choice(P.n_items, size=3, replace=False))
        eng.step()
    assert eng._kind_bucket[KIND_DEL_BASKET] == 1
    assert eng.metrics.bucket_shrinks >= 1
    # a later singleton delete pads to the decayed bucket, not the burst
    eng.delete_basket(30, 0)
    eng.step()
    assert eng._kind_bucket[KIND_DEL_BASKET] == 1
    # and re-growth stays immediate
    for u in range(40, 49):
        eng.delete_basket(u, 0)
    eng.step()
    assert eng._kind_bucket[KIND_DEL_BASKET] == 16


# ---------------------------------------------------------------------------
# Randomized mixed streams through the engine (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_engine_mixed_stream_500_events_matches_ref(seed):
    """>= 500 interleaved add/delete events: the engine's (sparse-path)
    state matches BOTH the RefEngine user vectors and a dense-baseline
    shadow arm (apply_del_*_batch_dense) to <= 1e-4 relative error."""
    rng = np.random.default_rng(seed)
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K))
    eng = StreamingEngine(store, P, batch_size=16)
    ref = RefEngine(P, dtype=np.float32)
    events = random_mixed_events(rng, ref, 520, M)
    # shadow arm: the same stream through the retained dense baselines
    dense = StreamState.zeros(M, P.n_items, N, B, K)
    for ev in events:
        if ev.kind == KIND_ADD_BASKET:
            dense = apply_add_batch(
                dense, AddBatch.build([ev.user], [ev.items], B), P)
        elif ev.kind == KIND_DEL_BASKET:
            dense = apply_del_basket_batch_dense(
                dense, DelBasketBatch.build([ev.user], [ev.pos]), P)
        else:
            dense = apply_del_item_batch_dense(
                dense, DelItemBatch.build([ev.user], [ev.pos], [ev.item]),
                P)
    eng.submit(events)
    n = eng.run_until_drained()
    assert n == len(events)
    assert_matches_ref(store.state, ref, M, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(store.state.materialized_user_vecs()),
        np.asarray(dense.materialized_user_vecs()), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(store.state.materialized_last_group_vecs()),
        np.asarray(dense.materialized_last_group_vecs()),
        rtol=1e-4, atol=1e-5)


def test_engine_mixed_replay_after_restore(rng, tmp_path):
    """Mixed stream, crash mid-way, restore, at-least-once full replay:
    duplicates are skipped and the result matches the single-pass run."""
    def make():
        store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                       max_baskets=N, max_basket_size=B,
                                       max_groups=K))
        return StreamingEngine(store, P, batch_size=16), store

    ref = RefEngine(P, dtype=np.float32)
    events = random_mixed_events(rng, ref, 200, M)

    eng1, store1 = make()
    eng1.submit(events)
    eng1.run_until_drained()
    assert_matches_ref(store1.state, ref, M)

    eng2, store2 = make()
    eng2.submit(events)
    for _ in range(3):
        eng2.step()
    eng2.checkpoint(str(tmp_path), 1)
    processed = eng2.metrics.events_processed

    eng3, store3 = make()
    eng3.restore(str(tmp_path))
    replay = [dataclasses.replace(ev, seqno=i)
              for i, ev in enumerate(events)]
    eng3.submit(replay)
    assert eng3.n_pending == len(events) - processed
    eng3.run_until_drained()
    np.testing.assert_allclose(
        np.asarray(store3.state.materialized_user_vecs()),
        np.asarray(store1.state.materialized_user_vecs()),
        rtol=1e-4, atol=1e-5)


def test_hot_user_conflict_deferral_order(rng):
    """A hot user's events are applied one per batch, in order, while
    other users keep flowing (per-user pending queues)."""
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B,
                                   max_groups=K))
    eng = StreamingEngine(store, P, batch_size=4)
    ref = RefEngine(P, dtype=np.float32)
    for t in range(12):
        b = rng.choice(P.n_items, size=3, replace=False)
        eng.add_basket(5, b)
        ref.add_basket(5, b)
        if t % 3 == 0:
            b2 = rng.choice(P.n_items, size=2, replace=False)
            eng.add_basket(t % 4, b2)
            ref.add_basket(t % 4, b2)
    eng.delete_basket(5, 2)
    ref.delete_basket(5, 2)
    eng.run_until_drained()
    assert eng.n_pending == 0
    assert_matches_ref(store.state, ref, M)
