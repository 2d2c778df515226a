"""A forget applied as one program (DESIGN.md §11.3), single and sharded.

``forget_user`` writes the empty-history row with ``clear_users`` instead
of stepping one ``KIND_DEL_BASKET`` per basket.  These tests hold it to
the per-basket path it replaces: the same row bit for bit, every other
row untouched, a cost that does not grow with the history, the same
exactly-once log, and a checkpoint that certifies.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compliance import certify
from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                              KIND_DEL_ITEM, TifuParams)
from repro.core.updates import refresh_users
from repro.parallel.sharding import UserShardSpec
from repro.streaming import (Backpressure, Event, ShardedStreamingEngine,
                             StateStore, StoreConfig, StreamingEngine)
from repro.streaming.engine import _STATE_LEAVES

P = TifuParams(n_items=29, group_size=3, k_neighbors=4)
M, N, B = 8, 24, 6
VICTIM = 5
SHARDS = pytest.mark.parametrize("n_shards", [1, 2], ids=["single",
                                                          "sharded"])


def build(n_shards, **kw):
    """Single or sharded engine at the module-level geometry."""
    if n_shards == 1:
        store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                       max_baskets=N, max_basket_size=B))
        return StreamingEngine(store, P, batch_size=16, **kw)
    return ShardedStreamingEngine.create(
        UserShardSpec(M, n_shards), P, max_baskets=N, max_basket_size=B,
        batch_size=16, **kw)


def owner(eng, user):
    """(shard engine, local row) that holds global ``user``."""
    if isinstance(eng, StreamingEngine):
        return eng, user
    return (eng.shards[eng.spec.shard_of(user)],
            int(eng.spec.local_row(user)))


def parts(eng):
    """The engine's shards (the single engine is its own one shard)."""
    return [eng] if isinstance(eng, StreamingEngine) else eng.shards


def leaves(eng):
    """Every state leaf of every shard as host arrays of raw bits."""
    out = []
    for sh in parts(eng):
        for name in _STATE_LEAVES:
            a = np.array(getattr(sh.store.state, name))
            out.append((name, a.view(f"u{a.dtype.itemsize}")))
    return out


def dedup_skips(eng):
    return sum(sh.metrics.dedup_skips for sh in parts(eng))


def stream(seed):
    """Random traffic for every user but VICTIM, and a victim log with
    adds, basket deletions and item deletions (present and absent)."""
    rng = np.random.default_rng(seed)
    events, nb = [], [0] * M
    for _ in range(120):
        u = int(rng.integers(0, M))
        if u == VICTIM:
            continue
        if nb[u] and rng.random() < 0.25:
            pos = int(rng.integers(0, nb[u]))
            if rng.random() < 0.5:
                events.append(Event(KIND_DEL_BASKET, u, pos=pos))
                nb[u] -= 1
            else:
                events.append(Event(KIND_DEL_ITEM, u, pos=pos, item=int(
                    rng.integers(0, P.n_items))))
        else:
            events.append(Event(KIND_ADD_BASKET, u, items=rng.choice(
                P.n_items, size=int(rng.integers(1, 5)), replace=False)))
            nb[u] = min(nb[u] + 1, N)
    victim = [Event(KIND_ADD_BASKET, VICTIM, items=np.asarray(b))
              for b in ([1, 2, 3], [2, 4], [5, 6, 7], [1, 8], [3, 9, 10],
                        [11, 2], [12, 13, 1], [4, 14])]
    victim += [Event(KIND_DEL_ITEM, VICTIM, pos=0, item=2),
               Event(KIND_DEL_BASKET, VICTIM, pos=3),
               Event(KIND_DEL_ITEM, VICTIM, pos=4, item=12),
               Event(KIND_DEL_ITEM, VICTIM, pos=1, item=28),
               Event(KIND_ADD_BASKET, VICTIM, items=np.asarray([15, 16])),
               Event(KIND_DEL_BASKET, VICTIM, pos=0)]
    # interleave the victim's log with the rest, in order
    at = np.sort(rng.choice(len(events) + len(victim), len(victim),
                            replace=False))
    mixed, vi, oi = [], iter(victim), iter(events)
    for i in range(len(events) + len(victim)):
        mixed.append(next(vi) if i in at else next(oi))
    return mixed


def per_basket_forget(eng, user):
    """The per-basket path ``forget_user`` replaced: one
    ``KIND_DEL_BASKET`` per basket, last first, through the normal
    submit and step loop, then the empty-history rebuild of the row."""
    eng.run_until_drained()
    sh, row = owner(eng, user)
    nb = int(np.asarray(sh.store.state.n_baskets)[row])
    eng.submit([Event(KIND_DEL_BASKET, user, pos=p)
                for p in range(nb - 1, -1, -1)])
    eng.run_until_drained()
    sh.store.state = refresh_users(sh.store.state,
                                   jnp.asarray([row], jnp.int32), P)
    sh.store.scrub_rows([row])
    return nb


def forget_log(receipt):
    """The receipt's deletions with their explicit seqnos (a replay)."""
    return [Event(KIND_DEL_BASKET, receipt.user, pos=p, seqno=s)
            for p, s in zip(range(receipt.n_baskets_deleted - 1, -1, -1),
                            receipt.seqnos)]


@SHARDS
def test_forget_row_equals_per_basket_path(n_shards):
    events = stream(3)
    new, old = build(n_shards), build(n_shards)
    for eng in (new, old):
        eng.submit(events)
        eng.run_until_drained()
    before = leaves(new)
    receipt = new.forget_user(VICTIM)
    nb = per_basket_forget(old, VICTIM)
    assert receipt.n_baskets_deleted == nb == 7
    assert receipt.clean, receipt.residue
    shard = 0 if n_shards == 1 else new.spec.shard_of(VICTIM)
    row = owner(new, VICTIM)[1]
    per_shard = len(_STATE_LEAVES)
    for i, ((name, a), (_, b), (_, was)) in enumerate(
            zip(leaves(new), leaves(old), before)):
        # the forgotten row bit for bit as the per-basket path leaves it
        np.testing.assert_array_equal(a, b, err_msg=name)
        # every other row as it was before the forget
        keep = np.ones(a.shape[0], bool)
        if i // per_shard == shard:
            keep[row] = False
            assert not np.array_equal(a[row], was[row]) or name in (
                "err_mult", "uv_scale", "lgv_scale"), name
        np.testing.assert_array_equal(a[keep], was[keep], err_msg=name)


@SHARDS
def test_forget_cost_does_not_grow_with_history(n_shards):
    eng = build(n_shards)
    short, full = 1, 6
    eng.submit([Event(KIND_ADD_BASKET, u, items=np.asarray([u, u + 7]))
                for u, n in ((short, 2), (full, N + 4)) for _ in range(n)])
    eng.run_until_drained()
    nbs, costs = [], []
    for user in (short, full):
        sh, row = owner(eng, user)
        nbs.append(int(np.asarray(sh.store.state.n_baskets)[row]))
        eng.run_until_drained()
        m0 = [(s.metrics.batches, s.metrics.host_fetches)
              for s in parts(eng)]
        assert eng.forget_user(user).n_baskets_deleted == nbs[-1]
        m1 = [(s.metrics.batches, s.metrics.host_fetches)
              for s in parts(eng)]
        costs.append([(b1 - b0, f1 - f0)
                      for (b0, f0), (b1, f1) in zip(m0, m1)])
    assert nbs == [2, N]
    assert costs[0] == costs[1]
    assert all(batches <= 1 for batches, _ in costs[0])
    # a forget with no baskets is a clear too, but is not counted
    eng.forget_user(full)
    assert sum(s.metrics.forget_clears for s in parts(eng)) == 2


@SHARDS
def test_forget_log_is_exactly_once(n_shards):
    eng = build(n_shards)
    eng.submit(stream(5))
    eng.run_until_drained()
    sh, row = owner(eng, VICTIM)
    done = sh.metrics.events_processed
    receipt = eng.forget_user(VICTIM)
    nb = receipt.n_baskets_deleted
    assert nb == 7 and len(receipt.seqnos) == nb
    assert list(receipt.seqnos) == list(range(receipt.seqnos[0],
                                              receipt.seqnos[0] + nb))
    assert sh.watermark >= receipt.seqnos[-1]
    assert sh.metrics.events_processed == done + nb
    skips = dedup_skips(eng)
    res = eng.submit(forget_log(receipt))
    assert res.deduped == nb and res.admitted == 0
    assert dedup_skips(eng) == skips + nb
    assert eng.run_until_drained() == 0
    assert all(v == 0.0 for v in sh.store.row_residue([row]).values())
    # fresh traffic after the forget takes seqnos past the receipt's
    eng.add_basket(VICTIM, [3, 4])
    eng.run_until_drained()
    assert int(np.asarray(sh.store.state.n_baskets)[row]) == 1


@SHARDS
def test_forget_checkpoint_restore_certifies(n_shards, tmp_path):
    events = stream(7)
    eng = build(n_shards)
    eng.submit(events)
    eng.run_until_drained()
    receipt = eng.forget_user(VICTIM)
    eng.checkpoint(str(tmp_path / "ck"), 1)
    eng2 = build(n_shards)
    eng2.restore(str(tmp_path / "ck"))
    sh, row = owner(eng2, VICTIM)
    assert all(v == 0.0 for v in sh.store.row_residue([row]).values())
    # the restored log already holds the forget's seqnos
    assert eng2.submit(forget_log(receipt)).deduped == len(receipt.seqnos)
    log = events + [dataclasses.replace(ev, seqno=-1)
                    for ev in forget_log(receipt)]
    report = certify(eng2, log, forgotten_users=[VICTIM],
                     checkpoint_dir=str(tmp_path / "cert"))
    assert report.compliant, report.summary()


@SHARDS
def test_forget_refused_while_a_shed_gap_is_open(n_shards):
    eng = build(n_shards, max_pending=1)
    eng.add_basket(VICTIM, [1, 2])
    eng.run_until_drained()
    # seqno 5 fits under the high-water mark, 6 is shed: a gap opens
    # (users 1 and 3 share the victim's shard when there are two)
    res = eng.submit([Event(KIND_ADD_BASKET, 1, items=[3], seqno=5),
                      Event(KIND_ADD_BASKET, 3, items=[4], seqno=6)],
                     on_overflow="shed")
    assert res.rejected == 1
    eng.run_until_drained()
    before = leaves(eng)
    with pytest.raises(Backpressure):
        eng.forget_user(VICTIM)
    sh, row = owner(eng, VICTIM)
    assert int(np.asarray(sh.store.state.n_baskets)[row]) == 1
    assert sh.metrics.forget_clears == 0
    for (name, a), (_, b) in zip(leaves(eng), before):
        np.testing.assert_array_equal(a, b, err_msg=name)
