"""TIFU-kNN serving driver: batched next-basket recommendation requests
against a live (stream-maintained) state store.

Requests go through the ENGINE-SIDE BATCHER (`StreamingEngine.recommend`,
DESIGN.md §8): the engine reads its cached materialized corpus
(``StateStore.corpus()`` — between requests the micro-batches invalidate
only the touched rows, so each request pays an O(dirty·I) row refresh
instead of a full [M, I] densification), pads the query batch to a pow2
bucket and serves it through the fused pipeline
(``kernels.ops.fused_recommend``: the Pallas streaming-top-k + one-hot
blend/top-n kernels on TPU, the bitwise-identical XLA reference on CPU).

The trickle demo varies the request batch size on purpose: the printed
compiled-program-cache size must stay at the pow2-bucket count, not the
distinct-request-size count — if it tracks the latter, the request
bucketing has regressed (the serving bench gates this, see
benchmarks/bench_serving.py).

    PYTHONPATH=src python -m repro.launch.serve --users 2000 --requests 5
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.types import KIND_ADD_BASKET
from repro.data import synthetic
from repro.kernels import ops
from repro.streaming import Event, StateStore, StoreConfig, StreamingEngine
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="tafeng")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=256,
                    help="max request batch size (actual sizes vary per "
                         "request to exercise the pow2 bucketing)")
    ap.add_argument("--topn", type=int, default=10)
    ap.add_argument("--trickle", type=int, default=64,
                    help="streaming events applied between requests "
                         "(exercises the corpus-cache row invalidation)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bounded ingestion (DESIGN.md §9): high-water "
                         "mark on the engine's pending queues; trickle "
                         "events past it are shed (counted, resubmitted "
                         "next round) instead of growing memory "
                         "unboundedly")
    ap.add_argument("--poison", type=int, default=0,
                    help="malformed events injected per trickle round "
                         "(out-of-range items): they must land in the "
                         "dead-letter queue, not wedge serving")
    args = ap.parse_args()

    ds = synthetic.generate(args.dataset, scale=args.scale)
    p = ds.params
    n_users = len(ds.histories)
    store = StateStore(StoreConfig(
        n_users=n_users, n_items=p.n_items,
        max_baskets=max(len(h) for h in ds.histories.values()) + 8,
        max_basket_size=max((len(b) for h in ds.histories.values()
                             for b in h), default=8) + 2))
    eng = StreamingEngine(store, p, batch_size=512)
    t0 = time.perf_counter()
    for u, h in ds.histories.items():
        for b in h:
            eng.add_basket(u, b)
    n = eng.run_until_drained()
    # the high-water mark bounds the live trickle, not the bulk load
    eng.max_pending = args.max_pending
    print(f"loaded {n} baskets for {n_users} users in "
          f"{time.perf_counter()-t0:.1f}s")

    rng = np.random.default_rng(0)
    recs = None
    for r in range(args.requests):
        if r and args.trickle:
            # live updates between requests: only these users' corpus
            # rows are refreshed by the next store.corpus() call.  The
            # whole round goes through one admission-checked submit —
            # shed events just lower this round's trickle volume (a real
            # source resends them), poison quarantines, serving answers
            # regardless.
            trickle = [Event(KIND_ADD_BASKET, int(u),
                             items=rng.choice(p.n_items,
                                              size=int(rng.integers(1, 6)),
                                              replace=False).astype(
                                                  np.int32))
                       for u in rng.choice(n_users,
                                           size=min(args.trickle, n_users),
                                           replace=False)]
            trickle += [Event(KIND_ADD_BASKET, 0,
                              items=np.asarray([p.n_items + i], np.int32))
                        for i in range(args.poison)]
            adm = eng.submit(trickle, on_invalid="quarantine",
                             on_overflow="shed")
            if adm.rejected or adm.quarantined:
                print(f"  admission: {adm.admitted} admitted, "
                      f"{adm.rejected} shed (backpressure), "
                      f"{adm.quarantined} dead-lettered")
            eng.run_until_drained()
        # deliberately ragged request sizes: they must all land in a
        # handful of pow2 buckets, not one compile per size
        size = int(rng.integers(max(1, args.batch // 2), args.batch + 1))
        users = rng.choice(n_users, size=min(size, n_users), replace=False)
        t0 = time.perf_counter()
        recs = eng.recommend(users, topn=args.topn)
        dt = time.perf_counter() - t0
        print(f"request batch {r}: {len(users)} users → top-{args.topn} "
              f"in {dt*1e3:.1f} ms ({dt/len(users)*1e6:.0f} us/user)")
    print(f"corpus cache: {store.corpus_full_builds} full build(s), "
          f"{store.corpus_rows_refreshed} row refreshes")
    print(f"serving compiled-program cache: "
          f"{eng.metrics.serve_compiled_shapes} shape bucket(s) across "
          f"{eng.metrics.serve_requests} requests "
          f"({ops.serving_cache_size()} live compiled programs)")
    print(f"ingestion: {eng.metrics.events_processed} events applied, "
          f"{eng.metrics.backpressure_rejections} shed by backpressure, "
          f"{eng.metrics.dead_letters} dead-lettered")
    print("sample recommendation for user 0:", np.asarray(recs[0]))
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
