#!/usr/bin/env python3
"""Run the streaming engine's main path once on a TPU and check it.

    python chip_smoke.py            # one chip: the TaFeng deployment
    python chip_smoke.py --chips 4  # four chips: the user-sharded engine

One chip: the paper's TaFeng deployment at Table-1 scale (13,949 users,
11,997 items padded to 12,032 columns, k = 300, synthetic data from
``--seed``) goes through the entry points a deployment calls,
``StateStore`` and ``StreamingEngine``:

1. bulk load: every user's history but its last basket, through
   ``submit`` and ``run_until_drained``;
2. mixed stream: the held-out baskets, then the paper's §6.1 deletion
   requests (1/1000 of the users delete 10% of their baskets, as many
   delete one item);
3. serving: ``recommend`` for batches of 256 users;
4. unlearning: ``forget_user`` for a few users; every receipt is clean.

It checks a seeded sample of users' ``materialized_user_vecs`` against
the paper-faithful ``RefEngine``, and the kernel path's recommendations
against the XLA reference run at HIGHEST matmul precision.

Four chips: ``ShardedStreamingEngine`` with four user shards, one per
chip, against a one-shard engine on the first chip, fed the same
events; states and recommendations must agree.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase and check passed.  The script exits non-zero
otherwise, and when JAX finds no TPU.  Times are host clock around
whole phases, compilation included: set-up information, not device
metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.ref_engine import RefEngine  # noqa: E402
from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,  # noqa: E402
                              KIND_DEL_ITEM, AddBatch, DelBasketBatch,
                              DelItemBatch)
from repro.core import updates  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.data.stream import make_stream  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_user_shard_meshes  # noqa: E402
from repro.parallel.sharding import UserShardSpec  # noqa: E402
from repro.streaming import (Event, ShardedStreamingEngine,  # noqa: E402
                             StateStore, StoreConfig, StreamingEngine)

DATASET = "tafeng"
MAX_BASKETS = 24          # longest TaFeng history is 17 baskets
MAX_BASKET_SIZE = 20      # largest TaFeng basket holds 19 items
BATCH = 256               # engine micro-batch and request batch
TOPN = 10
# RefEngine agreement, as tests/test_update_partition.py: f32 engine
# state against the float64 oracle
STATE_RTOL, STATE_ATOL = 1e-4, 1e-5
# near-tie tolerance of the serving check, relative to the magnitude of
# the compared quantity: ~170 f32 epsilons
TIE_REL = 1e-5


class CompileLog:
    """Counts backend compiles (or persistent-cache loads) and cache use."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, *args, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event: str, *args, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Check:
    """Collects named pass/fail results; any failure fails the run."""

    def __init__(self) -> None:
        self.failed: list = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def run_phase(name: str, log: CompileLog, fn):
    """Run ``fn`` and print its host-clock time split (set-up info)."""
    t0, c0, n0 = time.perf_counter(), log.seconds, log.programs
    out = fn()
    wall = time.perf_counter() - t0
    comp = log.seconds - c0
    print(f"phase {name}: {wall:.3f} s wall, {comp:.3f} s compiling or "
          f"loading {log.programs - n0} programs, {wall - comp:.3f} s "
          "rest (host clock, set-up information, not a device metric)",
          flush=True)
    return out


def submit_all(eng, events) -> None:
    """Submit ``events`` and require every one admitted."""
    res = eng.submit(events)
    if res.admitted != len(events):
        raise RuntimeError(f"submit admitted {res.admitted} of "
                           f"{len(events)} events: {res}")


def additions(histories) -> list:
    """Round-robin basket additions over users, each in its own order."""
    return [ev for ev in make_stream(histories, seed=0)
            if ev.kind == KIND_ADD_BASKET]


def deletions(histories, seed: int) -> list:
    """The paper's §6.1 deletion requests against full histories."""
    return [ev for ev in make_stream(histories, deletion_user_rate=1e-3,
                                     deletion_basket_frac=0.10,
                                     item_deletion_rate=1e-3, seed=seed)
            if ev.kind != KIND_ADD_BASKET]


def replay(ref: RefEngine, events, users: set, skip: set) -> None:
    """Apply ``users``' events to the oracle, minus quarantined seqnos."""
    for seqno, ev in enumerate(events):
        if ev.user not in users or seqno in skip:
            continue
        if ev.kind == KIND_ADD_BASKET:
            ref.add_basket(ev.user, np.unique(ev.items))
        elif ev.kind == KIND_DEL_BASKET:
            ref.delete_basket(ev.user, ev.pos)
        elif ev.kind == KIND_DEL_ITEM:
            ref.delete_item(ev.user, ev.pos, ev.item)


_rows = jax.jit(lambda st, idx: st.materialized_user_vecs()[idx])


def check_state(check: Check, state, ref: RefEngine, users) -> None:
    users = np.asarray(sorted(users))
    got = np.asarray(_rows(state, jnp.asarray(users, jnp.int32)))
    want = np.stack([ref.state(int(u)).user_vec for u in users])
    err = np.abs(got - want)
    bad = err > STATE_ATOL + STATE_RTOL * np.abs(want)
    check("state-vs-RefEngine", not bad.any(),
          f"{users.size} users, |got - want| <= {STATE_ATOL} + "
          f"{STATE_RTOL}·|want| elementwise; max abs err "
          f"{float(err.max()):.3e}, {int(bad.sum())} cells outside")


def _near_ties(exact, got, k: int, scale: float) -> tuple:
    """Whether ``got`` is a top-k of ``exact`` up to near-ties.

    ``exact`` are float64 scores (higher first), ``got`` the selected
    indices.  An index may differ from the exact top-k only when its
    exact score lies within ``TIE_REL·scale`` of the k-th score.
    Returns (ok, largest deviation in units of ``scale``).
    """
    kth = np.partition(exact, -k)[-k]
    want = set(np.argsort(-exact, kind="stable")[:k].tolist())
    diff = want.symmetric_difference(int(i) for i in got)
    dev = max((abs(exact[i] - kth) / scale for i in diff), default=0.0)
    return dev <= TIE_REL, dev


def check_serving(check: Check, corpus, n_items: int, batches, recs,
                  alpha: float, k: int) -> None:
    """Kernel recommendations against the XLA reference at HIGHEST.

    Rows must agree exactly, or differ only by near-ties that a float64
    recomputation confirms: every neighbour either path picked and the
    other did not scores within ``TIE_REL`` of the k-th neighbour (scale
    |q|² + max|c|²), and each path's top-n is the float64 top-n of its
    own neighbours' blend up to near-ties (scale max|p|).
    """
    bd = ops.stage_a_bd(corpus.shape[1], k)
    host = None
    exact_rows = tie_rows = failed_rows = 0
    worst = 0.0
    for users, got in zip(batches, recs):
        ids = jnp.asarray(users, jnp.int32)
        queries = corpus[ids]

        def stage_a(impl):
            if bd is None:
                return ops.knn_topk(queries, corpus, k, impl=impl,
                                    query_gids=ids)[1]
            return ops.knn_topk_dtiled(queries, corpus, k, bd=bd, impl=impl,
                                       query_gids=ids)[1]

        nbr_k = np.asarray(stage_a(None))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ops.fused_recommend(
                corpus, ids, k, alpha, TOPN, impl="ref", n_items=n_items))
            nbr_r = np.asarray(stage_a("ref"))
        if got.shape != want.shape or not (
                (got >= 0) & (got < n_items)).all():
            check("serving-output", False, f"shape {got.shape}, ids "
                  f"outside [0, {n_items})")
            return
        for r, u in enumerate(users):
            if np.array_equal(got[r], want[r]):
                exact_rows += 1
                continue
            if host is None:
                host = np.asarray(corpus)[:, :n_items].astype(np.float64)
                cn = np.einsum("ij,ij->i", host, host)
            q = host[u]
            s = 2.0 * host @ q - cn - q @ q
            s[u] = -np.inf
            ok_a, dev_a = _near_ties(s, nbr_k[r], k, q @ q + cn.max())
            ok_r, dev_r = _near_ties(s, nbr_r[r], k, q @ q + cn.max())
            devs = [dev_a, dev_r]
            ok = ok_a and ok_r
            for nbr, ids_row in ((nbr_k[r], got[r]), (nbr_r[r], want[r])):
                p = alpha * q + (1.0 - alpha) * host[nbr].mean(axis=0)
                ok_b, dev_b = _near_ties(p, ids_row, TOPN,
                                         float(np.abs(p).max()) or 1.0)
                ok, devs = ok and ok_b, devs + [dev_b]
            worst = max(worst, *devs)
            if ok:
                tie_rows += 1
            else:
                failed_rows += 1
    check("serving-vs-XLA-reference", failed_rows == 0,
          f"{exact_rows} rows equal, {tie_rows} rows differ only by "
          f"float64-confirmed near-ties (tolerance {TIE_REL} relative), "
          f"{failed_rows} rows wrong; worst tie deviation {worst:.2e}")


def print_dispatch(store: StateStore, params, k: int) -> None:
    """Each main-path kernel entry point and what it resolves to here."""
    impl = ops.resolve_impl(None)
    n_cols = store.cfg.n_cols
    bd = ops.stage_a_bd(n_cols, k)
    print(f"dispatch: impl={impl} on {jax.default_backend()}; item axis "
          f"{store.cfg.n_items} padded to {n_cols} columns, tile "
          f"bi={ops.plan_bi(n_cols)}; stage A "
          f"{'knn_topk_dtiled bd=%d' % bd if bd else 'knn_topk'}",
          flush=True)
    st = store.state
    u = jnp.zeros((BATCH,), jnp.int32)
    valid = jnp.zeros((BATCH,), bool)
    programs = {
        "apply_add_batch (sparse_row_gather + sparse_row_scatter)":
            updates.apply_add_batch_counted.lower(
                st, AddBatch(u, jnp.zeros((BATCH, MAX_BASKET_SIZE),
                                          jnp.int32), valid), params),
        "apply_del_basket_batch (sparse_row_gather + sparse_row_scatter)":
            updates.apply_del_basket_batch.lower(
                st, DelBasketBatch(u, u, valid), params),
        "apply_del_item_batch (sparse_row_gather + sparse_row_scatter)":
            updates.apply_del_item_batch.lower(
                st, DelItemBatch(u, u, u, valid), params),
        "fused_recommend (stage A + blend_topn_onehot)":
            jax.jit(lambda c, i: ops.fused_recommend(
                c, i, k, params.alpha, TOPN, n_items=store.cfg.n_items))
            .lower(jax.ShapeDtypeStruct((store.cfg.n_users, n_cols),
                                        jnp.float32), u),
    }
    for name, lowered in programs.items():
        n = lowered.as_text().count("tpu_custom_call")
        print(f"dispatch: {name}: {impl}, {n} tpu_custom_call op(s) "
              "in the lowered program", flush=True)
        if impl == "pallas" and n == 0:
            raise RuntimeError(f"{name} lowered without a Pallas kernel")


def run_single(check: Check, log: CompileLog, seed: int,
               scale: float = 1.0, n_request_batches: int = 4,
               n_forget: int = 4) -> None:
    """The one-chip phases and their checks."""
    rng = np.random.default_rng(seed)
    ds = run_phase("generate-data", log,
                   lambda: synthetic.generate(DATASET, seed=seed,
                                              scale=scale))
    params, hist = ds.params, ds.histories
    n_users = len(hist)
    train = {u: h[:-1] for u, h in hist.items()}
    held_out = {u: h[-1:] for u, h in hist.items()}
    print(f"deployment: {DATASET} seed={seed} scale={scale}: {n_users} "
          f"users, {params.n_items} items, "
          f"{sum(len(h) for h in hist.values())} baskets, k="
          f"{params.k_neighbors}", flush=True)

    store = StateStore(StoreConfig(
        n_users=n_users, n_items=params.n_items, max_baskets=MAX_BASKETS,
        max_basket_size=MAX_BASKET_SIZE))
    eng = StreamingEngine(store, params, batch_size=BATCH)
    print_dispatch(store, params, params.k_neighbors)

    bulk = additions(train)
    mixed = additions(held_out) + deletions(hist, seed)
    events = bulk + mixed

    def ingest(evs):
        submit_all(eng, evs)
        eng.run_until_drained()
        print(f"  {len(evs)} events; engine metrics {eng.metrics}",
              flush=True)

    run_phase("bulk-load", log, lambda: ingest(bulk))
    run_phase("mixed-stream", log, lambda: ingest(mixed))
    check("events-processed",
          eng.metrics.events_processed + eng.metrics.dead_letters
          == len(events),
          f"{eng.metrics.events_processed} applied + "
          f"{eng.metrics.dead_letters} quarantined of {len(events)}")

    batches = [rng.choice(n_users, size=min(BATCH, n_users), replace=False)
               for _ in range(n_request_batches)]
    recs = run_phase("serve", log,
                     lambda: [eng.recommend(b, topn=TOPN) for b in batches])
    check_serving(check, store.corpus(), params.n_items, batches, recs,
                  params.alpha, min(params.k_neighbors, n_users - 1))

    del_users = {ev.user for ev in mixed if ev.kind != KIND_ADD_BASKET}
    forget = [int(u) for u in rng.choice(n_users, size=n_forget,
                                         replace=False)]

    def forget_all():
        return [eng.forget_user(u) for u in forget]

    receipts = run_phase("forget", log, forget_all)
    check("forget-receipts", all(r.clean for r in receipts),
          "; ".join(f"user {r.user}: {r.n_baskets_deleted} baskets, "
                    f"clean={r.clean}" for r in receipts))

    sample = (set(int(u) for u in rng.choice(n_users, size=min(64, n_users),
                                            replace=False))
              | del_users | set(forget))
    skip = {ev.seqno for ev, _ in eng.dead_letter}
    ref = RefEngine(params, dtype=np.float64)
    replay(ref, events, sample, skip)
    for r in receipts:
        for p in range(r.n_baskets_deleted - 1, -1, -1):
            ref.delete_basket(r.user, p)
    check_state(check, store.state, ref, sample)


def _sharded_events(hist, seed: int) -> list:
    """Two baskets per user round-robin, then a deletion burst.

    Every round holds each user once, so the micro-batches stay full,
    and the engines run without tile hints (``run_sharded``): each
    device compiles one applier per update kind, and the four-chip call
    spends its time on the sharded path rather than on recompiling.
    """
    rng = np.random.default_rng(seed)
    two = {u: h[:2] for u, h in hist.items()}
    evs = additions(two)
    users = rng.choice(len(hist), size=len(hist) // 64, replace=False)
    evs += [Event(KIND_DEL_BASKET, int(u), pos=0) for u in users]
    evs += [Event(KIND_DEL_ITEM, int(u), pos=0, item=int(two[int(u)][0][0]))
            for u in rng.choice(len(hist), size=len(hist) // 64,
                                replace=False)]
    return evs


def run_sharded(check: Check, log: CompileLog, seed: int,
                scale: float = 1.0, n_request_batches: int = 2) -> None:
    """Four user shards on four devices against one shard on one."""
    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found "
                           f"{len(devices)}")
    ds = run_phase("generate-data", log,
                   lambda: synthetic.generate(DATASET, seed=seed,
                                              scale=scale))
    params, hist = ds.params, ds.histories
    n_users = len(hist)
    events = _sharded_events(hist, seed)
    print(f"deployment: {DATASET} seed={seed} scale={scale}: {n_users} "
          f"users, {params.n_items} items, {len(events)} events",
          flush=True)

    def build(n_shards):
        eng = ShardedStreamingEngine.create(
            UserShardSpec(n_users, n_shards), params, MAX_BASKETS,
            MAX_BASKET_SIZE,
            meshes=make_user_shard_meshes(n_shards,
                                          devices=devices[:n_shards]),
            batch_size=BATCH, tile_hints=False)
        submit_all(eng, events)
        eng.run_until_drained()
        return eng

    four = run_phase("ingest-4-shards", log, lambda: build(4))
    one = run_phase("ingest-1-shard", log, lambda: build(1))
    for s, sh in enumerate(four.shards):
        print(f"  shard {s}: {sh.store.cfg.n_users} users on "
              f"{sorted(d.id for d in sh.store.state.user_vecs.devices())}",
              flush=True)

    rng = np.random.default_rng(seed + 1)
    users = np.sort(rng.choice(n_users, size=min(256, n_users),
                               replace=False))
    worst = 0.0
    bitwise = True
    for u in users:
        sh = four.shards[u % 4].store.state
        a = np.asarray(_rows(sh, jnp.asarray([u // 4], jnp.int32)))[0]
        b = np.asarray(_rows(one.shards[0].store.state,
                             jnp.asarray([u], jnp.int32)))[0]
        bitwise &= bool(np.array_equal(a, b))
        worst = max(worst, float(np.abs(a - b).max()))
    check("sharded-state-vs-one-shard", worst <= STATE_ATOL,
          f"{users.size} users; bitwise equal: {bitwise}; max abs diff "
          f"{worst:.3e} (tolerance {STATE_ATOL})")

    batches = [rng.choice(n_users, size=min(64, n_users), replace=False)
               for _ in range(n_request_batches)]
    got = run_phase("serve-4-shards", log,
                    lambda: [four.recommend(b, topn=TOPN) for b in batches])
    want = run_phase("serve-1-shard", log,
                     lambda: [one.recommend(b, topn=TOPN) for b in batches])
    rows = sum(len(b) for b in batches)
    same = sum(int(np.array_equal(g[r], w[r])) for g, w in zip(got, want)
               for r in range(len(g)))
    check("sharded-recommend-vs-one-shard", same == rows,
          f"{same} of {rows} request rows identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip main path; 4: only the "
                         "user-sharded engine against one shard")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    print(f"devices: {len(devices)} x {devices[0].device_kind}",
          flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    log = CompileLog()
    check = Check()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(check, log, args.seed)
        used = devices[:4]
    else:
        run_single(check, log, args.seed)
        used = devices[:1]
    print(f"total: {time.perf_counter() - t0:.3f} s wall, "
          f"{log.seconds:.3f} s compiling or loading {log.programs} "
          f"programs; persistent cache {log.cache_hits} hits, "
          f"{log.cache_misses} misses", flush=True)
    for d in used:
        stats = d.memory_stats() or {}
        print(f"memory: device {d.id} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')} of "
              f"{stats.get('bytes_limit')}", flush=True)
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
