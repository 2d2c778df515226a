"""Beyond-paper: batched SPMD streaming-engine throughput (events/s) vs
the paper's one-update-at-a-time Spark latencies, plus per-event latency
of the jit'd micro-batch across batch sizes."""
from __future__ import annotations

import time

from repro.data import stream, synthetic
from repro.streaming import StateStore, StoreConfig, StreamingEngine
from repro.launch.compile_cache import use_compile_cache


def run(batch_size: int, n_events: int = 4096, scale=0.01):
    ds = synthetic.generate("tafeng", scale=scale, seed=0)
    p = ds.params
    n_users = len(ds.histories)
    store = StateStore(StoreConfig(
        n_users=n_users, n_items=p.n_items,
        max_baskets=max(len(h) for h in ds.histories.values()) + 8,
        max_basket_size=max((len(b) for h in ds.histories.values()
                             for b in h), default=8) + 2))
    eng = StreamingEngine(store, p, batch_size=batch_size)
    events = stream.make_stream(ds.histories, deletion_user_rate=0.02,
                                seed=1)[:n_events]
    eng.submit(events)
    eng.step()   # warm up / compile
    t0 = time.perf_counter()
    n = eng.run_until_drained()
    dt = time.perf_counter() - t0
    return n, dt, eng.metrics.batches


def main():
    print("batch_size,events,seconds,events_per_s,us_per_event")
    for bs in (64, 256, 1024):
        n, dt, batches = run(bs)
        print(f"{bs},{n},{dt:.2f},{n/dt:,.0f},{dt/max(n,1)*1e6:.1f}")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
