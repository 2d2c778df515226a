"""Serving under continuous update/deletion traffic (DESIGN.md §8).

The realistic serving regime (per the unlearning-benchmark literature)
interleaves recommendation requests with continuous addition AND
deletion traffic.  These tests drive a 520-event mixed stream through
the engine, serving through the request batcher after every chunk, and
pin that the corpus-cache row invalidation never goes stale:

  * the cached corpus — and therefore the fused recommendations — are
    BITWISE the fresh from-scratch rebuild of the live state at every
    serving point (the cache-staleness oracle: same arithmetic, so any
    difference can only be a stale row);
  * the served corpus stays within the established 1e-4 envelope of a
    fresh paper-faithful ``RefEngine`` rebuild of the current
    histories, at every serving point — a stale cache row is off by
    whole basket-update magnitudes (~0.1), far beyond it.  (Served item
    LISTS are pinned bitwise only between same-arithmetic paths,
    matching tests/test_sharded_engine.py: kNN neighbour selection is
    discontinuous, so an fp-level corpus difference can legitimately
    flip a near-tied neighbour and with it the blended ranking.);
  * both properties survive a mid-stream checkpoint/restore (the
    restored engine drops the cache and rebuilds it) and the restored
    engine keeps serving bitwise in step with the original under
    exactly-once replay;
  * the interpret-mode Pallas pipeline serves the same answers as the
    CPU path on the final corpus.
"""
import numpy as np

import jax.numpy as jnp

from repro.core import RefEngine, TifuParams, knn
from repro.core.types import KIND_ADD_BASKET, KIND_DEL_BASKET
from repro.kernels import ops
from repro.streaming import StateStore, StoreConfig, StreamingEngine

from test_sharded_engine import random_mixed_events

P = TifuParams(n_items=41, group_size=3, r_b=0.9, r_g=0.7)
M, N, B = 8, 48, 6
TOPN, K_NN = 5, 4


def make_engine(batch_size=16):
    store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                   max_baskets=N, max_basket_size=B))
    return StreamingEngine(store, P, batch_size=batch_size), store


def ref_corpus(replay: RefEngine) -> np.ndarray:
    """The oracle corpus: a fresh, independent RefEngine replay of the
    stream prefix (ragged numpy, per-event — the paper-faithful
    implementation).  A from-scratch ``fit_from_scratch`` regrouping
    would NOT match: the maintained group structure is path-dependent
    after deletions (the §4.3 varying-group-size relaxation), so the
    oracle must replay the same events, independently."""
    return replay.user_matrix(list(range(M))).astype(np.float32)


def serve_all(eng: StreamingEngine) -> np.ndarray:
    return eng.recommend(np.arange(M), topn=TOPN, k=K_NN)


def test_serving_under_updates_matches_ref_rebuild(tmp_path):
    rng = np.random.default_rng(11)
    ref = RefEngine(P, dtype=np.float32)
    events = random_mixed_events(rng, ref, 520, M)

    # replay the ref stream prefix-by-prefix alongside the engine
    replay = RefEngine(P, dtype=np.float32)
    eng, store = make_engine()
    chunk = 65
    ckpt_dir = str(tmp_path / "ckpt")
    restored = None
    for lo in range(0, len(events), chunk):
        part = events[lo:lo + chunk]
        eng.submit(part)
        eng.run_until_drained()
        for ev in part:
            if ev.kind == KIND_ADD_BASKET:
                replay.add_basket(ev.user, ev.items)
            elif ev.kind == KIND_DEL_BASKET:
                replay.delete_basket(ev.user, ev.pos)
            else:
                replay.delete_item(ev.user, ev.pos, ev.item)

        # (1) cache contract: the incrementally-refreshed corpus is
        # bitwise the from-scratch materialization of the live state
        cached = np.asarray(store.corpus())
        np.testing.assert_array_equal(
            cached,
            np.asarray(store.state.materialized_user_vecs(padded=True)),
            err_msg=f"stale corpus cache after {lo + len(part)} events")

        # (2a) fused recommendations == recommendations on the fresh
        # from-scratch materialization (bitwise: same state, so any
        # difference can only be a stale cache row)
        recs = serve_all(eng)
        fresh = np.asarray(knn.recommend_for_users(
            store.state.materialized_user_vecs(),
            jnp.asarray(np.arange(M, dtype=np.int32)),
            k=K_NN, alpha=P.alpha, topn=TOPN))
        np.testing.assert_array_equal(
            recs, fresh, err_msg=f"after {lo + len(part)} events")
        # (2b) independent oracle: the served corpus tracks the fresh
        # RefEngine replay (1e-4 envelope — a stale row would be off
        # by whole update magnitudes)
        np.testing.assert_allclose(
            cached[:, :P.n_items], ref_corpus(replay), atol=1e-4,
            err_msg=f"after {lo + len(part)} events")

        # mid-stream: commit, and fork a restored engine that must
        # serve identically from its rebuilt cache
        if lo // chunk == 3:
            eng.checkpoint(ckpt_dir, step=lo)
            restored, _ = make_engine()
            restored.restore(ckpt_dir)
            np.testing.assert_array_equal(serve_all(restored), recs)
            # the restored engine replays the whole prefix (exactly-once
            # dedup skips the processed part) and keeps serving in step
            restored.submit(events[:lo + chunk])
            restored.run_until_drained()
            np.testing.assert_array_equal(serve_all(restored), recs)
        elif restored is not None:
            restored.submit(part)
            restored.run_until_drained()
            np.testing.assert_array_equal(serve_all(restored), recs)

    assert eng.metrics.events_processed == len(events)
    assert restored is not None

    # (3) the interpret-mode Pallas pipeline serves the same answers
    final = serve_all(eng)
    with ops.default_impl("interpret"):
        np.testing.assert_array_equal(serve_all(eng), final)
