"""The reference's prediction is what ``StreamingEngine.recommend``
answers, on the CPU at a small size, with events still queued at each
read: the check's replay to the per-user prefixes a read saw is the
state the engine served from."""
import numpy as np
import pytest

import check
import gen
import run
from reference import blended_scores, top_n

CFG = dict(n_users=300, n_items=256, avg_baskets=6.0, avg_basket_size=6.2,
           group_size=3, r_b=0.9, r_g=0.7, k_neighbors=20, alpha=0.7,
           zipf_exponent=1.1, repeat_bias=0.6, pool_factor=3,
           baskets_per_user_cap=12, items_per_basket_cap=16, max_baskets=32,
           max_basket_size=16)
# additions arrive faster than one 16-event step a read takes in: the
# queue grows through the run; a few users delete a basket
TRAFFIC = dict(add_rate=1000, add_burst=1, adds_per_user_cap=8,
               del_user_frac=0.02, del_basket_frac=0.2, forget_interval_s=0,
               warm_s=0.0, warm_add_bursts=0, warm_dels=0, warm_del_adds=0,
               warm_forgets=0, read_rate=50)
TOPN = 10


@pytest.mark.parametrize("impl", ["auto", "interpret"])
def test_reference_prediction_is_what_recommend_answers(impl):
    from repro.core.types import KIND_ADD_BASKET, KIND_DEL_BASKET, TifuParams
    from repro.kernels import ops
    from repro.streaming import Event, StateStore, StoreConfig, StreamingEngine

    hist = gen.histories(CFG, 23)
    sched = gen.schedule(CFG, TRAFFIC, hist, 23, 2.0)
    events = run.build_events(sched, Event, (KIND_ADD_BASKET,
                                             KIND_DEL_BASKET))
    params = TifuParams(n_items=CFG["n_items"], group_size=CFG["group_size"],
                        r_b=CFG["r_b"], r_g=CFG["r_g"],
                        k_neighbors=CFG["k_neighbors"], alpha=CFG["alpha"])
    reads = []
    with ops.default_impl(impl):
        store = StateStore(StoreConfig(
            n_users=CFG["n_users"], n_items=CFG["n_items"],
            max_baskets=CFG["max_baskets"],
            max_basket_size=CFG["max_basket_size"]))
        eng = StreamingEngine(store, params, batch_size=16)
        eng.submit(run.load_events(hist, Event, KIND_ADD_BASKET))
        eng.run_until_drained()
        n = 0
        for due, user in zip(sched.read_due, sched.read_user.tolist()):
            m = int(np.searchsorted(sched.ev_due, due, "right"))
            res = eng.submit(events[n:m])
            assert res.rejected == 0
            n = m
            eng.step()
            ids = eng.recommend([user], topn=TOPN)[0]
            pending = {u: len(q) for u, q in eng._queues.items()}
            reads.append(check.Read(user, ids, n, pending, 0))
    assert eng.metrics.dead_letters == 0
    queued = [sum(r.pending.values()) for r in reads]
    assert min(queued) == 0 and max(queued) > 100
    assert (sched.ev_pos[:n] >= 0).any()
    assert check.read_errs(CFG, hist, sched, reads, TOPN) == [0.0] * len(
        reads)
    for r, corpus, sqnorm in check.read_states(CFG, hist, sched, reads):
        want = top_n(blended_scores(corpus, sqnorm, r.user,
                                    CFG["k_neighbors"], CFG["alpha"]), TOPN)
        assert np.array_equal(r.ids, want), r.user


def _bounds(corpus, k, tie):
    from reference import blended_bounds

    corpus = np.asarray(corpus, np.float64)
    return blended_bounds(corpus, np.einsum("ij,ij->i", corpus, corpus), 0,
                          k, 0.5, tie)


@pytest.mark.parametrize("near,tied", [(1e-7, True), (1e-3, False)])
def test_bounds_admit_either_neighbour_only_within_rounding(near, tied):
    # users 1 and 2 hold item 1 and item 2 at distances 1 and 1 + near
    # from user 0 (k = 1); user 3 is far
    corpus = [[1, 0, 0, 0], [1, 1, 0, 0], [1, 0, 1 + near / 2, 0],
              [0, 0, 0, 5]]
    lo, hi = _bounds(corpus, 1, check.F32_TIE)
    either = [1, 0, 0, 0], [1, 0.5, 0.5 * (1 + near / 2), 0]
    nearest = [1, 0.5, 0, 0]
    assert np.allclose(lo, either[0] if tied else nearest)
    assert np.allclose(hi, either[1] if tied else nearest)


def test_bounds_are_the_prediction_without_ties():
    rng = np.random.default_rng(5)
    corpus = rng.random((40, 12))
    sq = np.einsum("ij,ij->i", corpus, corpus)
    lo, hi = _bounds(corpus, 7, 0.0)
    want = blended_scores(corpus, sq, 0, 7, 0.5)
    assert np.allclose(lo, want) and np.allclose(hi, want)
