"""Micro-batch update latency/throughput vs vocabulary size, per backend.

Measures the kind-partitioned sparse-delta pipeline (core.updates
apply_add_batch / apply_del_*_batch via the apply_update_batch shim)
against two dense baselines for add-only, del-basket-only, del-item-only
and mixed micro-batches at n_items ∈ {1k, 10k, 100k}:

  * ``dense_seed`` — the seed's mixed path (gather [batch, n_items]
    rows, compute every update rule, select, scatter dense deltas);
  * ``dense_kind`` — the homogeneous dense decremental paths
    (apply_del_*_batch_dense): one rule per program, still O(n_items)
    row traffic.  This is the honest baseline for the sparse deletes.

Headline claims (ISSUE 1 + ISSUE 2 acceptance): add latency is flat in
n_items (O(basket) state traffic), and the sparse decremental paths beat
the dense baseline by >= 5x at 100k items because their support is the
history window (N·B ids), not the vocabulary.

``--backend`` selects which kernel path the sparse pipeline exercises
(ROADMAP: track both backends):

  * ``cpu``       — natural dispatch on a CPU host (XLA reference
                    kernels; the numbers the sparse-speedup acceptance
                    gates on);
  * ``tpu``       — natural dispatch on a TPU host (tile-planned Pallas
                    kernels; requires jax.default_backend() == "tpu");
  * ``interpret`` — the tile-planned Pallas kernels in interpret mode on
                    any host.  Orders of magnitude slower per step
                    (plumbing/equivalence numbers, not perf), so it is
                    only allowed together with ``--smoke``.

``--shards N [N ...]`` switches to the **sharded-engine arm**: instead
of the kernel-path grid it measures end-to-end add latency through a
``ShardedStreamingEngine`` at each user-shard count (DESIGN.md §7) and
records one ``arm="sharded"`` entry — the acceptance claim is that add
latency stays flat in the shard count.

``--device-resident`` switches to the **engine hot-path arm**
(DESIGN.md §12): ``step()`` add/del p50/p99, the measured
``transfers_per_step`` (contract: exactly one fused summary fetch), and
a sync-vs-async checkpointed-step comparison whose
``async_ckpt_p99_speedup_vs_sync`` ratio is the gated claim that the
background writer keeps serialize/fsync out of the hot path's p99.

Each result row records its backend, and BENCH_updates.json accumulates
one entry per (backend, mode, arm) in ``runs`` — re-running a backend
replaces only that entry, so CPU and TPU numbers are tracked
side-by-side (schema: benchmarks/README.md).  ``benchmarks/
bench_trend.py`` diffs the summary speedups of a fresh run against the
committed file (the CI bench-trend step).

    PYTHONPATH=src python benchmarks/bench_update_batch.py [--quick]
    PYTHONPATH=src python benchmarks/bench_update_batch.py --smoke  # CI
    PYTHONPATH=src python benchmarks/bench_update_batch.py --shards 1 2 4

``--smoke`` shrinks every dimension (users/batch/vocab/iters) so the CI
bench job exercises the full harness in seconds on CPU; its numbers are
for plumbing validation, not for perf tracking.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (StreamState, TifuParams, apply_add_batch,
                        apply_del_basket_batch_dense,
                        apply_del_item_batch_dense, apply_update_batch,
                        apply_update_batch_dense)
from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                              KIND_DEL_ITEM, KIND_NOOP, PAD_ID, AddBatch,
                              DelBasketBatch, DelItemBatch, UpdateBatch)
from repro.kernels import ops
from repro.launch.compile_cache import use_compile_cache


@dataclasses.dataclass(frozen=True)
class BenchConfig:
    m_users: int = 1024
    max_baskets: int = 24
    max_bsize: int = 16
    batch: int = 256
    seed_baskets: int = 6
    n_items_grid: tuple = (1_000, 10_000, 100_000)
    iters: int = 8
    dense_iters: int = 4


SMOKE = BenchConfig(m_users=128, max_baskets=12, max_bsize=8, batch=64,
                    seed_baskets=4, n_items_grid=(1_000, 4_000), iters=2,
                    dense_iters=1)
QUICK = BenchConfig(iters=4, dense_iters=2)

KINDS = ("add", "del_basket", "del_item", "mixed")

# impl override per --backend.  "cpu" pins the XLA reference path
# explicitly (NOT "auto": on a TPU host auto would silently measure the
# Pallas kernels under a 'cpu' label and poison the trend baseline);
# "tpu" uses natural dispatch on a TPU host (guarded in main()).
BACKEND_IMPL = {"cpu": "ref", "tpu": "auto", "interpret": "interpret"}


def make_params(n_items: int) -> TifuParams:
    return TifuParams(n_items=n_items, group_size=7, r_b=0.9, r_g=0.7)


def seed_state(params: TifuParams, rng, cfg: BenchConfig) -> StreamState:
    """Give every user seed_baskets baskets via the batched add path."""
    state = StreamState.zeros(cfg.m_users, params.n_items, cfg.max_baskets,
                              cfg.max_bsize, cfg.max_baskets)
    for _ in range(cfg.seed_baskets):
        for lo in range(0, cfg.m_users, cfg.batch):
            users = np.arange(lo, lo + cfg.batch, dtype=np.int32)
            state = apply_update_batch(
                state, make_batch(rng, users, "add", state, cfg), params)
    return state


def make_batch(rng, users, kind: str, state: StreamState,
               cfg: BenchConfig) -> UpdateBatch:
    """One fixed-shape batch over the given (distinct) users.

    Deterministic composition per kind: stable sub-batch sizes => the
    pow2 buckets compile once in warmup and the loop times steady state
    (add: all adds; del_basket/del_item: homogeneous; mixed: 2/1/1)."""
    u = len(users)
    kinds = np.zeros(u, np.int32)
    items = np.full((u, cfg.max_bsize), PAD_ID, np.int32)
    pos = np.zeros(u, np.int32)
    item = np.full(u, PAD_ID, np.int32)
    nb = np.asarray(state.n_baskets)
    hist = None
    for r, uu in enumerate(users):
        roll = {"add": 0.0, "del_basket": 0.6, "del_item": 0.9,
                "mixed": (0.0, 0.0, 0.6, 0.9)[r % 4]}[kind]
        if roll < 0.5 or nb[uu] == 0:
            kinds[r] = KIND_ADD_BASKET
            b = rng.choice(state.n_items,
                           size=int(rng.integers(2, cfg.max_bsize // 2)),
                           replace=False)
            items[r, :len(b)] = b
        elif roll < 0.75:
            kinds[r] = KIND_DEL_BASKET
            pos[r] = int(rng.integers(0, nb[uu]))
        else:
            kinds[r] = KIND_DEL_ITEM
            pos[r] = int(rng.integers(0, nb[uu]))
            if hist is None:
                hist = np.asarray(state.history)
            row = hist[uu, pos[r]]
            row = row[row >= 0]
            item[r] = int(row[0]) if row.size else 0
            if not row.size:
                kinds[r] = KIND_NOOP
    return UpdateBatch(kind=jnp.asarray(kinds), user=jnp.asarray(users),
                       basket_items=jnp.asarray(items),
                       basket_pos=jnp.asarray(pos), item=jnp.asarray(item))


def _dense_kind_apply(state, batch: UpdateBatch, params):
    """Route a homogeneous UpdateBatch to the dense per-kind baseline.

    Add rows (make_batch's nb==0 fallback) go through the same add path
    as the partitioned arm, so both arms evolve identical states and the
    reported delete speedup compares like against like."""
    kind = np.asarray(jax.device_get(batch.kind))
    user = np.asarray(jax.device_get(batch.user))
    cap = int(kind.shape[0])
    adds = np.nonzero(kind == KIND_ADD_BASKET)[0]
    delb = np.nonzero(kind == KIND_DEL_BASKET)[0]
    deli = np.nonzero(kind == KIND_DEL_ITEM)[0]
    if adds.size:
        items = np.asarray(jax.device_get(batch.basket_items))
        state = apply_add_batch(
            state, AddBatch.build(user[adds], items[adds], items.shape[1],
                                  pad_cap=cap), params)
    if delb.size:
        pos = np.asarray(jax.device_get(batch.basket_pos))
        state = apply_del_basket_batch_dense(
            state, DelBasketBatch.build(user[delb], pos[delb], pad_cap=cap),
            params)
    if deli.size:
        pos = np.asarray(jax.device_get(batch.basket_pos))
        it = np.asarray(jax.device_get(batch.item))
        state = apply_del_item_batch_dense(
            state, DelItemBatch.build(user[deli], pos[deli], it[deli],
                                      pad_cap=cap), params)
    return state


PATHS = {
    "partitioned": apply_update_batch,
    "dense_seed": apply_update_batch_dense,
    "dense_kind": _dense_kind_apply,
}


def bench_sharded(cfg: BenchConfig, shard_counts, backend: str) -> tuple:
    """Engine-level add latency vs user-shard count (DESIGN.md §7).

    Feeds identical per-iteration add batches (distinct users, routed by
    ``user % n_shards``) through a `ShardedStreamingEngine` and times
    `run_until_drained` — ingestion, routing, per-shard kind-partitioned
    sub-batch cuts and the batched add path, end to end.  The acceptance
    claim is that add latency stays FLAT in the shard count: sharding
    splits the same work across smaller per-shard sub-batches, so the
    per-batch wall time must not grow with n_shards (on a single test
    host the shards share one device; on a real deployment they run on
    disjoint device groups and this same number shrinks).
    """
    from repro.parallel.sharding import UserShardSpec
    from repro.streaming import ShardedStreamingEngine
    n_items = cfg.n_items_grid[min(1, len(cfg.n_items_grid) - 1)]
    params = make_params(n_items)
    # normalize: the growth metric is defined as max-vs-min shard count
    shard_counts = sorted(set(shard_counts))
    results = []
    for n_shards in shard_counts:
        spec = UserShardSpec(cfg.m_users, n_shards)
        eng = ShardedStreamingEngine.create(
            spec, params, max_baskets=cfg.max_baskets,
            max_basket_size=cfg.max_bsize, batch_size=cfg.batch)
        rng = np.random.default_rng(0)
        per_shard = cfg.batch // n_shards
        n_fed = sum(min(per_shard, spec.shard_users(s))
                    for s in range(n_shards))

        def feed():
            # shard-balanced batches (a hash-partitioned source): each
            # shard receives batch/n_shards events, so the per-shard
            # pow2 buckets sit at batch/n_shards instead of flapping
            # across the boundary on sampling noise
            for s in range(n_shards):
                owned = spec.owned_users(s)
                for u in rng.choice(owned, size=min(per_shard, len(owned)),
                                    replace=False):
                    eng.add_basket(int(u), rng.choice(
                        n_items,
                        size=int(rng.integers(2, cfg.max_bsize // 2)),
                        replace=False))

        for _ in range(3):                       # warmup/compile
            feed()
            eng.run_until_drained()
        times = []
        for _ in range(cfg.iters):
            feed()
            t0 = time.perf_counter()
            eng.run_until_drained()
            times.append(time.perf_counter() - t0)
        times = np.asarray(times)
        r = {"kind": "add", "path": "sharded_engine", "backend": backend,
             "shards": n_shards, "n_items": n_items, "batch": n_fed,
             "iters": cfg.iters, "mean_ms": float(times.mean() * 1e3),
             "p50_ms": float(np.median(times) * 1e3),
             "min_ms": float(times.min() * 1e3),
             "events_per_s": float(n_fed / times.mean())}
        results.append(r)
        print(f"sharded_engine add shards={n_shards:2d} "
              f"n_items={n_items:>6d} mean={r['mean_ms']:8.2f} ms  "
              f"({r['events_per_s']:,.0f} ev/s)")
    base = results[0]
    summary = {"shards": list(shard_counts),
               "add_mean_ms_by_shards": {str(r["shards"]): r["mean_ms"]
                                         for r in results},
               "add_latency_growth_max_vs_min_shards":
                   results[-1]["mean_ms"] / base["mean_ms"]}
    return results, summary


def bench_recovery(cfg: BenchConfig, backend: str) -> tuple:
    """Time-to-recover after an injected crash, vs history size.

    For each seeded history depth: drain a checkpointed engine, apply a
    delta window (including poison deletes that must quarantine, not
    wedge), kill the NEXT checkpoint at its commit point with an
    injected crash (``faults.FaultPlan``), then measure a fresh
    process's restore + at-least-once full-delta replay back to a
    drained engine (DESIGN.md §9).  Also exercises the bounded-ingestion
    path (a 2x-high-water burst against ``max_pending``) and reports the
    dead-letter and backpressure counters alongside the timings —
    recovery numbers are informational (bench_trend gates only speedup/
    compile-count keys).
    """
    import shutil
    import tempfile

    from repro.streaming import StateStore, StoreConfig, StreamingEngine
    from repro.streaming import Event, faults

    n_items = cfg.n_items_grid[min(1, len(cfg.n_items_grid) - 1)]
    params = make_params(n_items)
    hist_grid = [h for h in (4, 8, 16) if h + 4 <= cfg.max_baskets]
    results = []

    def make_engine():
        store = StateStore(StoreConfig(
            n_users=cfg.m_users, n_items=n_items,
            max_baskets=cfg.max_baskets, max_basket_size=cfg.max_bsize))
        return StreamingEngine(store, params, batch_size=cfg.batch)

    for h in hist_grid:
        rng = np.random.default_rng(0)
        eng = make_engine()
        seqno = 0
        for _ in range(h):
            seed = []
            for u in range(cfg.m_users):
                seed.append(Event(
                    KIND_ADD_BASKET, u, seqno=seqno,
                    items=rng.choice(n_items, size=cfg.max_bsize // 2,
                                     replace=False).astype(np.int32)))
                seqno += 1
            eng.submit(seed)
        eng.run_until_drained()
        ckpt = tempfile.mkdtemp(prefix="bench_recovery_")
        try:
            eng.checkpoint(ckpt, 1)
            # the delta a recovering engine must replay: 2 batches of
            # adds plus poison deletes (positions beyond every history)
            # that must land in the dead-letter queue at apply time
            delta = []
            for u in range(min(2 * cfg.batch, cfg.m_users)):
                delta.append(Event(
                    KIND_ADD_BASKET, u, seqno=seqno,
                    items=rng.choice(n_items, size=cfg.max_bsize // 2,
                                     replace=False).astype(np.int32)))
                seqno += 1
            for u in range(8):
                delta.append(Event(KIND_DEL_BASKET, u, seqno=seqno,
                                   pos=cfg.max_baskets - 1))
                seqno += 1
            eng.submit(delta, on_invalid="quarantine")
            eng.run_until_drained()
            with faults.inject(
                    faults.FaultPlan(crash_site="LATEST.pre_replace")):
                try:
                    eng.checkpoint(ckpt, 2)
                except faults.InjectedCrash:
                    pass            # the process died mid-commit
            restore_t, replay_t, n_replay = [], [], 0
            for _ in range(max(2, cfg.iters)):
                eng2 = make_engine()
                t0 = time.perf_counter()
                eng2.restore(ckpt)
                t1 = time.perf_counter()
                eng2.submit(delta, on_invalid="quarantine")
                n_replay = eng2.n_pending
                eng2.run_until_drained()
                t2 = time.perf_counter()
                restore_t.append(t1 - t0)
                replay_t.append(t2 - t1)
            # bounded ingestion: a 2x-high-water burst must shed
            # deterministically while the engine drains the rest
            eng2.max_pending = cfg.batch
            burst = [Event(KIND_ADD_BASKET, u % cfg.m_users,
                           items=np.arange(2, dtype=np.int32))
                     for u in range(2 * cfg.batch)]
            shed = eng2.submit(burst, on_overflow="shed")
            eng2.run_until_drained()
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        restore_t, replay_t = np.asarray(restore_t), np.asarray(replay_t)
        total = restore_t + replay_t
        r = {"kind": "recovery", "path": "engine_recovery",
             "backend": backend, "n_items": n_items, "history": h,
             "events_replayed": n_replay,
             "iters": len(total),
             "restore_ms": float(restore_t.mean() * 1e3),
             "replay_ms": float(replay_t.mean() * 1e3),
             "recover_ms": float(total.mean() * 1e3),
             "p50_recover_ms": float(np.median(total) * 1e3),
             "replay_events_per_s": float(n_replay / replay_t.mean()),
             "dead_letters": eng2.metrics.dead_letters,
             "backpressure_rejections": shed.rejected,
             "crash_site": "LATEST.pre_replace"}
        results.append(r)
        print(f"recovery    history={h:3d} n_items={n_items:>6d} "
              f"recover={r['recover_ms']:8.2f} ms "
              f"(restore {r['restore_ms']:.2f} + replay "
              f"{r['replay_ms']:.2f}; {n_replay} events, "
              f"{r['dead_letters']} dead-lettered, "
              f"{r['backpressure_rejections']} shed)")
    last = results[-1]
    summary = {"history_grid": hist_grid,
               "recover_ms_by_history": {str(r["history"]): r["recover_ms"]
                                         for r in results},
               "recover_ms_at_max_history": last["recover_ms"],
               "restore_ms_at_max_history": last["restore_ms"],
               "recovery_replay_events_per_s":
                   last["replay_events_per_s"],
               "recovery_dead_letters": last["dead_letters"],
               "recovery_backpressure_rejections":
                   last["backpressure_rejections"]}
    return results, summary


def bench_device_resident(cfg: BenchConfig, backend: str) -> tuple:
    """Engine hot-path latency under the §12 device-residency contract.

    Times ``StreamingEngine.step()`` end to end for add-only and
    delete-only micro-batches (p50/p99) and reports the measured
    ``transfers_per_step`` — the fused-step-summary contract says a
    healthy step performs exactly ONE device→host transfer, pinned by
    tests/test_transfer_budget.py and tracked here as a parity fact.
    Then times a *checkpointed* step (step + commit initiation) with
    the synchronous §9 writer vs the §12 async snapshot-then-write
    path on the SAME engine: the gated claim is that moving
    serialize/fsync off the hot path beats the inline write at p99
    (``async_ckpt_p99_speedup_vs_sync``).  The async arm's flush —
    where writer errors surface and durability is guaranteed — happens
    once, outside the timed region, exactly as a deployment would
    sync at a barrier rather than per micro-batch.
    """
    import shutil
    import tempfile

    from repro.streaming import (AsyncCheckpointer, StateStore,
                                 StoreConfig, StreamingEngine)

    n_items = cfg.n_items_grid[min(1, len(cfg.n_items_grid) - 1)]
    params = make_params(n_items)
    store = StateStore(StoreConfig(
        n_users=cfg.m_users, n_items=n_items,
        max_baskets=cfg.max_baskets, max_basket_size=cfg.max_bsize))
    eng = StreamingEngine(store, params, batch_size=cfg.batch)
    rng = np.random.default_rng(0)
    nb = np.zeros(cfg.m_users, np.int64)   # host mirror of basket counts
    user_sets = [np.arange(lo, lo + cfg.batch, dtype=np.int32)
                 for lo in range(0, cfg.m_users, cfg.batch)]

    def feed(kind: str, i: int):
        for u in user_sets[i % len(user_sets)]:
            u = int(u)
            if kind == "add" or nb[u] == 0:
                eng.add_basket(u, rng.choice(
                    n_items, size=int(rng.integers(2, cfg.max_bsize // 2)),
                    replace=False))
                nb[u] += 1
            else:
                eng.delete_basket(u, int(nb[u] - 1))
                nb[u] -= 1

    for i in range(4):                       # seed history + compile
        feed("add", i)
        eng.run_until_drained()

    results = []
    steps = max(12, cfg.iters)
    transfers, steps_timed = 0, 0
    for kind in ("add", "del"):
        for i in range(3):                   # warmup this phase's buckets
            feed(kind, i)
            eng.run_until_drained()
        times = []
        fetches0 = eng.metrics.host_fetches  # timed steps only: warmup
        for i in range(steps):               # drains pay flush fetches
            feed(kind, i)
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
            assert eng.n_pending == 0
        transfers += eng.metrics.host_fetches - fetches0
        steps_timed += steps
        times = np.asarray(times)
        r = {"kind": kind, "path": "engine_step", "backend": backend,
             "n_items": n_items, "batch": cfg.batch, "iters": steps,
             "mean_ms": float(times.mean() * 1e3),
             "p50_ms": float(np.median(times) * 1e3),
             "p99_ms": float(np.quantile(times, 0.99) * 1e3),
             "events_per_s": float(cfg.batch / times.mean())}
        results.append(r)
        print(f"engine_step {kind:10s} n_items={n_items:>6d} "
              f"p50={r['p50_ms']:8.2f} ms p99={r['p99_ms']:8.2f} ms  "
              f"({r['events_per_s']:,.0f} ev/s)")
    transfers_per_step = transfers / steps_timed

    # checkpointed step: commit initiation on the hot path, sync vs async
    ck_iters = max(6, cfg.dense_iters + 2)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_device_resident_")
    ck = AsyncCheckpointer()
    ckpt_p99 = {}
    try:
        for mode in ("sync", "async"):
            eng.checkpointer = ck if mode == "async" else None
            eng.checkpoint(os.path.join(ckpt_dir, mode), 0)  # warm path
            times = []
            for i in range(ck_iters):
                feed("add", i)
                t0 = time.perf_counter()
                eng.step()
                eng.checkpoint(os.path.join(ckpt_dir, mode), i + 1)
                times.append(time.perf_counter() - t0)
            eng.flush_checkpoints()          # durability barrier,
            times = np.asarray(times)        # outside the timed region
            ckpt_p99[mode] = float(np.quantile(times, 0.99) * 1e3)
            results.append({
                "kind": "add", "path": f"ckpt_{mode}_step",
                "backend": backend, "n_items": n_items,
                "batch": cfg.batch, "iters": ck_iters,
                "mean_ms": float(times.mean() * 1e3),
                "p50_ms": float(np.median(times) * 1e3),
                "p99_ms": ckpt_p99[mode]})
            print(f"ckpt_{mode:5s} step      n_items={n_items:>6d} "
                  f"p50={np.median(times) * 1e3:8.2f} ms "
                  f"p99={ckpt_p99[mode]:8.2f} ms")
        ck.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    add, dele = results[0], results[1]
    summary = {
        "transfers_per_step": transfers_per_step,
        "add_p50_ms": add["p50_ms"], "add_p99_ms": add["p99_ms"],
        "del_p50_ms": dele["p50_ms"], "del_p99_ms": dele["p99_ms"],
        "sync_ckpt_step_p99_ms": ckpt_p99["sync"],
        "async_ckpt_step_p99_ms": ckpt_p99["async"],
        "async_ckpt_p99_speedup_vs_sync":
            ckpt_p99["sync"] / ckpt_p99["async"],
    }
    return results, summary


def bench(path: str, params, rng, kind: str, iters: int,
          cfg: BenchConfig, backend: str) -> dict:
    apply_fn = PATHS[path]
    state = seed_state(params, rng, cfg)
    user_sets = [np.arange(lo, lo + cfg.batch, dtype=np.int32)
                 for lo in range(0, cfg.m_users, cfg.batch)]
    # warmup/compile (several batches: mixed batches flip between pow2
    # sub-batch buckets, each bucket combination compiles once)
    for _ in range(3):
        state = apply_fn(state, make_batch(rng, user_sets[0], kind, state,
                                           cfg), params)
    jax.block_until_ready(state.user_vecs)
    times = []
    for i in range(iters):
        batch = make_batch(rng, user_sets[(i + 1) % len(user_sets)], kind,
                           state, cfg)
        t0 = time.perf_counter()
        state = apply_fn(state, batch, params)
        jax.block_until_ready(state.user_vecs)
        times.append(time.perf_counter() - t0)
    times = np.asarray(times)
    return {"kind": kind, "path": path, "backend": backend,
            "n_items": params.n_items, "batch": cfg.batch, "iters": iters,
            "mean_ms": float(times.mean() * 1e3),
            "p50_ms": float(np.median(times) * 1e3),
            "min_ms": float(times.min() * 1e3),
            "events_per_s": float(cfg.batch / times.mean())}


def run_grid(cfg: BenchConfig, backend: str, quick: bool) -> list:
    results = []
    for n_items in cfg.n_items_grid:
        params = make_params(n_items)
        for kind in KINDS:
            paths = ["partitioned", "dense_seed"]
            if kind in ("del_basket", "del_item"):
                paths.insert(1, "dense_kind")
            for path in paths:
                dense = path != "partitioned"
                if (quick and dense and kind != "add"
                        and n_items == 100_000 and path == "dense_seed"):
                    continue   # the heaviest redundant configurations
                rng = np.random.default_rng(0)
                iters = cfg.dense_iters if dense else cfg.iters
                r = bench(path, params, rng, kind, iters, cfg, backend)
                results.append(r)
                print(f"{path:11s} {kind:10s} n_items={n_items:>6d} "
                      f"mean={r['mean_ms']:8.2f} ms  "
                      f"({r['events_per_s']:,.0f} ev/s)")
    return results


def summarize(results: list, cfg: BenchConfig) -> dict:
    def pick(path, kind, n):
        return next((r for r in results if r["path"] == path
                     and r["kind"] == kind and r["n_items"] == n), None)

    n_lo, n_hi = cfg.n_items_grid[0], cfg.n_items_grid[-1]
    summary = {"max_n_items": n_hi}
    add_lo, add_hi = pick("partitioned", "add", n_lo), \
        pick("partitioned", "add", n_hi)
    summary["add_latency_growth_to_max_items"] = (
        add_hi["mean_ms"] / add_lo["mean_ms"])
    dense_add = pick("dense_seed", "add", n_hi)
    if dense_add:
        summary["add_speedup_vs_dense_at_max_items"] = (
            dense_add["mean_ms"] / add_hi["mean_ms"])
    for kind in ("del_basket", "del_item"):
        sp = pick("partitioned", kind, n_hi)
        dk = pick("dense_kind", kind, n_hi)
        if sp and dk:
            summary[f"{kind}_sparse_speedup_vs_dense_at_max_items"] = (
                dk["mean_ms"] / sp["mean_ms"])
    return summary


def merge_runs(out_path: str, entry: dict) -> dict:
    """Accumulate per-(backend, mode, arm) run entries in the bench JSON.

    Re-running one backend replaces only its entry (``arm`` is None for
    the default kernel-path grid, "sharded" for the ``--shards`` engine
    sweep); a legacy single-run file (pre-ISSUE-3 format) is migrated
    into ``runs`` first.  See benchmarks/README.md for the schema."""
    payload = {"benchmark": "bench_update_batch", "runs": []}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
        except (OSError, json.JSONDecodeError):
            old = {}
        if "runs" in old:
            payload["runs"] = old["runs"]
        elif "results" in old:                 # legacy single-run layout
            payload["runs"] = [{k: old.get(k) for k in
                                ("backend", "mode", "config", "summary",
                                 "results")}]
    key = (entry["backend"], entry["mode"], entry.get("arm"))
    payload["runs"] = [r for r in payload["runs"]
                       if (r.get("backend"), r.get("mode"),
                           r.get("arm")) != key]
    payload["runs"].append(entry)
    payload["runs"].sort(key=lambda r: (str(r.get("backend")),
                                        str(r.get("mode")),
                                        str(r.get("arm"))))
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer iterations at full sizes")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + minimal iterations (CI smoke: "
                         "seconds on CPU, validates the harness only)")
    ap.add_argument("--backend", choices=sorted(BACKEND_IMPL),
                    default=None,
                    help="kernel path to exercise (default: tpu on a TPU "
                         "host, else cpu)")
    ap.add_argument("--shards", type=int, nargs="+", default=None,
                    metavar="N",
                    help="run the sharded-engine add-latency sweep over "
                         "these user-shard counts (e.g. --shards 1 2 4) "
                         "instead of the kernel-path grid; records one "
                         "arm='sharded' entry (DESIGN.md §7)")
    ap.add_argument("--recovery", action="store_true",
                    help="run the crash-recovery sweep (time-to-recover "
                         "after an injected commit-point crash vs "
                         "history size, plus dead-letter/backpressure "
                         "counters) instead of the kernel-path grid; "
                         "records one arm='recovery' entry (DESIGN.md "
                         "§9)")
    ap.add_argument("--device-resident", action="store_true",
                    help="run the engine hot-path arm: step() add/del "
                         "p50/p99, measured transfers/step, and the "
                         "sync-vs-async checkpointed-step comparison; "
                         "records one arm='device_resident' entry "
                         "(DESIGN.md §12)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_updates.json"))
    args = ap.parse_args()
    cfg = SMOKE if args.smoke else (QUICK if args.quick else BenchConfig())
    backend = args.backend or ("tpu" if jax.default_backend() == "tpu"
                               else "cpu")
    if backend == "tpu" and jax.default_backend() != "tpu":
        ap.error("--backend tpu requires a TPU host "
                 f"(jax.default_backend() == {jax.default_backend()!r})")
    if backend == "interpret" and not args.smoke:
        ap.error("--backend interpret is interpret-mode Pallas (orders of "
                 "magnitude slower): only allowed with --smoke")

    if sum(map(bool, (args.shards, args.recovery,
                      args.device_resident))) > 1:
        ap.error("--shards/--recovery/--device-resident are separate "
                 "arms; run them as distinct invocations (each records "
                 "its own entry)")
    with ops.default_impl(BACKEND_IMPL[backend]):
        if args.shards:
            results, summary = bench_sharded(cfg, args.shards, backend)
        elif args.recovery:
            results, summary = bench_recovery(cfg, backend)
        elif args.device_resident:
            results, summary = bench_device_resident(cfg, backend)
        else:
            results = run_grid(cfg, backend, args.quick)
            summary = summarize(results, cfg)
    print(f"\nsummary [{backend}]:")
    for k, v in summary.items():
        note = ""
        if k == "add_latency_growth_to_max_items":
            note = "  (acceptance: < 1.5x)"
        elif k == "add_latency_growth_max_vs_min_shards":
            note = "  (acceptance: flat, ~1x)"
        elif k == "async_ckpt_p99_speedup_vs_sync":
            note = "  (acceptance: > 1x)"
        elif k.startswith(("del_basket", "del_item")):
            note = "  (acceptance: >= 5x)"
        print(f"  {k}: {v:.2f}{note}" if isinstance(v, float)
              else f"  {k}: {v}")

    entry = {
        "backend": backend,
        "jax_backend": jax.default_backend(),
        "mode": "smoke" if args.smoke else ("quick" if args.quick
                                            else "full"),
        "config": dataclasses.asdict(cfg),
        "summary": summary,
        "results": results,
    }
    if args.shards:
        entry["arm"] = "sharded"
        entry["shards"] = summary["shards"]
    elif args.recovery:
        entry["arm"] = "recovery"
    elif args.device_resident:
        entry["arm"] = "device_resident"
    out = os.path.abspath(args.out)
    payload = merge_runs(out, entry)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out} ({len(payload['runs'])} run entries)")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
