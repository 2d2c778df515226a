"""GDPR compliance arm: certification sweep, deletion SLOs, rank drift.

Three phases, one ``arm="compliance"`` entry in ``BENCH_updates.json``
(DESIGN.md §11, ISSUE 9):

* **certification sweep** — drive randomized deletion-burst streams
  (basket-level and item-level deletions interleaved with adds, plus a
  full user-level ``forget_user``) through single and sharded engines
  and run ``repro.compliance.certify`` on each; every stream must
  certify, and a deliberately tampered stream (one deletion skipped)
  must be DETECTED — the sweep validates the detector in both
  directions.
* **deletion latency under mixed serve traffic** — time individually
  submitted+drained deletions and full ``forget_user`` calls while
  ``recommend`` batches interleave, and report p50/p95/p99 plus
  normalized SLO fractions (``*_over_slo`` = measured p99 / objective;
  the trend gate enforces ``<= 1.0``).
* **ranking drift** — fit a synthetic dataset through the engine's add
  path, evaluate Recall@{10,20} / NDCG@{10,20} via the
  ``table2_predictive`` evaluation path, apply a deletion burst
  (random basket deletions for a user fraction + user-level forgets),
  and report the signed metric drift on the retained users.

Summary keys follow the EN03 convention (repro.analysis.bench_schema):
``*_ms`` percentiles, ``*qps*``, ``*drift*``, ``*certified*``,
``*swept*`` and ``*overlap*`` are parity facts; ``*_over_slo`` is
gated-slo (hard ``<= 1.0``).

    PYTHONPATH=src python benchmarks/bench_compliance.py          # full
    PYTHONPATH=src python benchmarks/bench_compliance.py --smoke  # CI

``--smoke`` shrinks the sweep (streams/events/deletions) so the CI
bench job exercises the full harness in seconds on CPU; its numbers
validate plumbing, not perf.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import jax
import numpy as np

try:
    from repro.compliance import certify
except ImportError:  # run as a plain script without PYTHONPATH=src
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "src"))
    from repro.compliance import certify

from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                              KIND_DEL_ITEM, TifuParams)
from repro.data import synthetic
from repro.kernels import ops
from repro.parallel.sharding import UserShardSpec
from repro.streaming import (Event, ShardedStreamingEngine, StateStore,
                             StoreConfig, StreamingEngine)
from repro.launch.compile_cache import use_compile_cache

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import table2_predictive                                   # noqa: E402
from bench_update_batch import BACKEND_IMPL, merge_runs    # noqa: E402


@dataclasses.dataclass(frozen=True)
class ComplianceConfig:
    """Knobs of the compliance arm (SMOKE shrinks every dimension)."""

    # phase 1: certification sweep
    n_streams: int = 100
    n_users: int = 8
    n_events: int = 120
    n_items: int = 41
    max_baskets: int = 24
    max_basket_size: int = 6
    checkpoint_every: int = 10     # round-trip check on every Nth stream
    # phase 2: deletion latency under serve traffic
    latency_users: int = 64
    latency_prefill: int = 6       # baskets per user before the burst
    latency_deletions: int = 300
    latency_forgets: int = 8
    serve_every: int = 5           # a recommend batch every N deletions
    serve_batch: int = 8
    deletion_slo_ms: float = 250.0
    forget_slo_ms: float = 2000.0
    # phase 3: ranking drift through the table2_predictive path
    drift_dataset: str = "tafeng"
    drift_scale: float = 0.05
    drift_seed: int = 0
    drift_user_frac: float = 0.25  # fraction of users hit by the burst
    drift_basket_frac: float = 0.3
    drift_forgets: int = 2


SMOKE = ComplianceConfig(
    n_streams=3, n_events=60, checkpoint_every=2, latency_users=16,
    latency_prefill=4, latency_deletions=30, latency_forgets=2,
    drift_scale=0.01, drift_forgets=1)


def _params(cfg: ComplianceConfig) -> TifuParams:
    """The sweep's TIFU hyper-parameters (small k for tiny corpora)."""
    return TifuParams(n_items=cfg.n_items, group_size=3,
                      k_neighbors=4)


def _gen_stream(rng, n_users, n_events, n_items, max_baskets,
                skip=()):
    """One randomized interleaved add/del_basket/del_item stream."""
    events, nb = [], [0] * n_users
    for _ in range(n_events):
        u = int(rng.integers(0, n_users))
        if u in skip:
            continue
        r = rng.random()
        if nb[u] > 0 and r < 0.25:
            pos = int(rng.integers(0, nb[u]))
            if r < 0.15:
                events.append(Event(KIND_DEL_BASKET, u, pos=pos))
                nb[u] -= 1
            else:
                events.append(Event(
                    KIND_DEL_ITEM, u, pos=pos,
                    item=int(rng.integers(0, n_items))))
        else:
            items = rng.choice(n_items, size=int(rng.integers(1, 5)),
                               replace=False)
            events.append(Event(KIND_ADD_BASKET, u,
                                items=items.tolist()))
            nb[u] = min(nb[u] + 1, max_baskets)
    return events


def _build_engine(cfg: ComplianceConfig, params, n_shards: int):
    """A fresh single or sharded engine at the sweep's store shapes."""
    if n_shards == 1:
        store = StateStore(StoreConfig(
            n_users=cfg.n_users, n_items=params.n_items,
            max_baskets=cfg.max_baskets,
            max_basket_size=cfg.max_basket_size))
        return StreamingEngine(store, params)
    return ShardedStreamingEngine.create(
        UserShardSpec(n_users=cfg.n_users, n_shards=n_shards), params,
        max_baskets=cfg.max_baskets,
        max_basket_size=cfg.max_basket_size)


def bench_certification(cfg: ComplianceConfig):
    """Phase 1: certify ``n_streams`` randomized deletion-burst streams.

    Alternates 1- and 2-shard engines, forgets one random user per
    stream, runs the checkpoint round-trip check on every
    ``checkpoint_every``-th stream, and ends with the tampered-stream
    canary (a skipped deletion that certify must flag).  Raises if any
    stream fails to certify or the canary goes undetected.
    """
    params = _params(cfg)
    results, passed = [], 0
    for i in range(cfg.n_streams):
        rng = np.random.default_rng(1000 + i)
        t0 = time.perf_counter()
        eng = _build_engine(cfg, params, n_shards=1 + (i % 2))
        events = _gen_stream(rng, cfg.n_users, cfg.n_events,
                             params.n_items, cfg.max_baskets)
        eng.submit(events)
        eng.run_until_drained()
        victim = int(rng.integers(0, cfg.n_users))
        receipt = eng.forget_user(victim)
        dels = [Event(KIND_DEL_BASKET, victim, pos=p)
                for p in range(receipt.n_baskets_deleted - 1, -1, -1)]
        if i % cfg.checkpoint_every == 0:
            with tempfile.TemporaryDirectory() as d:
                report = certify(eng, events + dels,
                                 forgotten_users=[victim],
                                 checkpoint_dir=d)
        else:
            report = certify(eng, events + dels,
                             forgotten_users=[victim])
        ok = report.compliant and receipt.clean
        passed += ok
        if not ok:
            raise AssertionError(
                f"stream {i} failed certification:\n{report.summary()}")
        results.append({
            "phase": "certify", "stream": i,
            "shards": 1 + (i % 2), "forgotten_user": victim,
            "compliant": bool(report.compliant),
            "receipt_clean": bool(receipt.clean),
            "envelope_slack": report.envelope_slack,
            "overlap_mean": report.overlap_mean,
            "wall_s": time.perf_counter() - t0})
    # tampered canary: drop one deletion from the delivered stream —
    # the engine state then differs from the retained-only fit and the
    # structural check MUST flag it
    rng = np.random.default_rng(7)
    events = _gen_stream(rng, cfg.n_users, cfg.n_events, params.n_items,
                         cfg.max_baskets)
    skipped = next(e for e in events if e.kind == KIND_DEL_BASKET)
    eng = _build_engine(cfg, params, n_shards=1)
    eng.submit([e for e in events if e is not skipped])
    eng.run_until_drained()
    detected = not certify(eng, events).compliant
    if not detected:
        raise AssertionError("tampered stream (skipped deletion) was "
                             "NOT detected")
    overlap = [r["overlap_mean"] for r in results]
    summary = {
        "certified_streams_swept": float(passed),
        "certified_violations_detected": 1.0,
        "certify_topn_overlap_mean": float(np.mean(overlap)),
        "certify_envelope_slack_max_over_sweep": float(
            max(r["envelope_slack"] for r in results)),
    }
    return results, summary


def _percentiles(samples_ms):
    """(p50, p95, p99) of a latency sample list, in milliseconds."""
    a = np.asarray(samples_ms, np.float64)
    return (float(np.percentile(a, 50)), float(np.percentile(a, 95)),
            float(np.percentile(a, 99)))


def bench_deletion_latency(cfg: ComplianceConfig):
    """Phase 2: deletion/forget latency percentiles under serve load.

    One engine, ``latency_users`` prefilled users; then
    ``latency_deletions`` randomized basket/item deletions are
    submitted and drained ONE AT A TIME (the per-request latency a
    deletion SLA is written against), with a ``recommend`` batch every
    ``serve_every`` deletions, and ``latency_forgets`` full user-level
    forgets at the end.
    """
    params = _params(cfg)
    store = StateStore(StoreConfig(
        n_users=cfg.latency_users, n_items=params.n_items,
        max_baskets=cfg.max_baskets,
        max_basket_size=cfg.max_basket_size))
    eng = StreamingEngine(store, params)
    rng = np.random.default_rng(42)
    nb = [0] * cfg.latency_users
    for u in range(cfg.latency_users):
        for _ in range(cfg.latency_prefill):
            items = rng.choice(params.n_items,
                               size=int(rng.integers(1, 5)),
                               replace=False)
            eng.submit([Event(KIND_ADD_BASKET, u,
                              items=items.tolist())])
            nb[u] += 1
    eng.run_until_drained()
    eng.store.corpus()                 # warm the serving cache
    eng.recommend(np.arange(cfg.serve_batch), topn=5)
    # warm the single-event deletion programs: the first del_basket /
    # del_item each trigger a jit compile that would otherwise land in
    # the timed tail and report compile cost as deletion latency
    eng.submit([Event(KIND_DEL_BASKET, 0, pos=nb[0] - 1)])
    eng.run_until_drained()
    nb[0] -= 1
    eng.submit([Event(KIND_DEL_ITEM, 0, pos=0,
                      item=int(rng.integers(0, params.n_items)))])
    eng.run_until_drained()

    del_ms, serve_s, serves = [], 0.0, 0
    for i in range(cfg.latency_deletions):
        u = int(rng.integers(0, cfg.latency_users))
        if nb[u] == 0:
            continue
        pos = int(rng.integers(0, nb[u]))
        if rng.random() < 0.5:
            ev = Event(KIND_DEL_BASKET, u, pos=pos)
            nb[u] -= 1
        else:
            ev = Event(KIND_DEL_ITEM, u, pos=pos,
                       item=int(rng.integers(0, params.n_items)))
        t0 = time.perf_counter()
        eng.submit([ev])
        eng.run_until_drained()
        del_ms.append((time.perf_counter() - t0) * 1e3)
        if i % cfg.serve_every == 0:
            users = rng.integers(0, cfg.latency_users, cfg.serve_batch)
            t0 = time.perf_counter()
            eng.recommend(users, topn=5)
            serve_s += time.perf_counter() - t0
            serves += cfg.serve_batch
    forget_ms = []
    for u in range(cfg.latency_forgets):
        receipt = eng.forget_user(u)
        assert receipt.clean, f"forget_user({u}) left residue: " \
            f"{receipt.residue}"
        forget_ms.append(receipt.latency_s * 1e3)
    d50, d95, d99 = _percentiles(del_ms)
    f50, f95, f99 = _percentiles(forget_ms)
    summary = {
        "deletion_p50_ms": d50, "deletion_p95_ms": d95,
        "deletion_p99_ms": d99,
        "forget_p50_ms": f50, "forget_p95_ms": f95,
        "forget_p99_ms": f99,
        "deletion_p99_over_slo": d99 / cfg.deletion_slo_ms,
        "forget_p99_over_slo": f99 / cfg.forget_slo_ms,
        "serve_qps_under_burst": serves / serve_s if serve_s else 0.0,
    }
    results = [{"phase": "latency", "deletions": len(del_ms),
                "forgets": len(forget_ms), "serves": serves,
                **summary}]
    return results, summary


def bench_drift(cfg: ComplianceConfig):
    """Phase 3: Recall/NDCG drift on retained users after a burst.

    Fits a synthetic dataset through the engine's add path, scores the
    held-out baskets via ``table2_predictive.evaluate``, then applies a
    deletion burst — ``drift_user_frac`` of users lose
    ``drift_basket_frac`` of their training baskets, ``drift_forgets``
    users are forgotten outright — and scores the RETAINED users again
    with the same path.  Drift is signed (after − before): deletions
    remove genuine signal, so small negative recall drift is the
    expected cost of compliance, and the numbers quantify it.
    """
    ds = synthetic.generate(cfg.drift_dataset, scale=cfg.drift_scale,
                            seed=cfg.drift_seed)
    p = ds.params
    train, test = ds.train_test_split()
    users = sorted(train)
    max_nb = max(len(b) for b in train.values())
    max_bs = max(max(len(x) for x in bs) for bs in train.values())
    store = StateStore(StoreConfig(
        n_users=len(users), n_items=p.n_items,
        max_baskets=max(max_nb + 1, 4),
        max_basket_size=max(max_bs, 2)))
    eng = StreamingEngine(store, p)
    for u in users:
        for b in train[u]:
            eng.submit([Event(KIND_ADD_BASKET, u, items=list(b))])
    eng.run_until_drained()

    rng = np.random.default_rng(cfg.drift_seed + 1)
    n_burst = max(1, int(len(users) * cfg.drift_user_frac))
    burst_users = rng.choice(len(users), size=n_burst, replace=False)
    forgotten = set(int(u) for u in burst_users[:cfg.drift_forgets])
    retained = [u for u in range(len(users)) if u not in forgotten]
    # before/after are scored on the SAME retained-user set, so the
    # drift isolates the burst's effect from population change
    before = table2_predictive.evaluate(
        np.asarray(eng.store.corpus())[retained],
        [users[u] for u in retained], test, p)
    nb = {u: len(train[users[u]]) for u in range(len(users))}
    for u in burst_users:
        u = int(u)
        if u in forgotten:
            continue
        n_del = max(1, int(nb[u] * cfg.drift_basket_frac))
        for _ in range(n_del):
            if nb[u] == 0:
                break
            eng.submit([Event(KIND_DEL_BASKET, u,
                              pos=int(rng.integers(0, nb[u])))])
            nb[u] -= 1
    eng.run_until_drained()
    for u in sorted(forgotten):
        receipt = eng.forget_user(u)
        assert receipt.clean
    after = table2_predictive.evaluate(
        np.asarray(eng.store.corpus())[retained],
        [users[u] for u in retained], test, p)
    summary = {
        "recall10_drift": after["recall@10"] - before["recall@10"],
        "recall20_drift": after["recall@20"] - before["recall@20"],
        "ndcg10_drift": after["ndcg@10"] - before["ndcg@10"],
        "ndcg20_drift": after["ndcg@20"] - before["ndcg@20"],
    }
    results = [{"phase": "drift", "n_users": len(users),
                "burst_users": int(n_burst),
                "forgotten": sorted(forgotten),
                "before": before, "after": after, **summary}]
    return results, summary


def main() -> int:
    """CLI entry: run the three phases, merge one compliance entry."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep (CI smoke: seconds on CPU)")
    ap.add_argument("--backend", choices=sorted(BACKEND_IMPL),
                    default=None,
                    help="kernel path to exercise (default: tpu on a "
                         "TPU host, else cpu)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_updates.json"))
    args = ap.parse_args()
    cfg = SMOKE if args.smoke else ComplianceConfig()
    backend = args.backend or ("tpu" if jax.default_backend() == "tpu"
                               else "cpu")
    if backend == "tpu" and jax.default_backend() != "tpu":
        ap.error("--backend tpu requires a TPU host "
                 f"(jax.default_backend() == {jax.default_backend()!r})")
    if backend == "interpret" and not args.smoke:
        ap.error("--backend interpret is interpret-mode Pallas: only "
                 "allowed with --smoke")

    results, summary = [], {}
    with ops.default_impl(BACKEND_IMPL[backend]):
        for phase in (bench_certification, bench_deletion_latency,
                      bench_drift):
            r, s = phase(cfg)
            results.extend(r)
            summary.update(s)

    print(f"\nsummary [{backend}]:")
    for k, v in summary.items():
        note = ""
        if k.endswith("_over_slo"):
            note = "  (acceptance: <= 1.0)"
        elif k == "certified_streams_swept":
            note = f"  (acceptance: == {cfg.n_streams})"
        print(f"  {k}: {v:.4f}{note}" if isinstance(v, float)
              else f"  {k}: {v}")

    entry = {
        "backend": backend,
        "jax_backend": jax.default_backend(),
        "mode": "smoke" if args.smoke else "full",
        "arm": "compliance",
        "config": dataclasses.asdict(cfg),
        "summary": summary,
        "results": results,
    }
    out = os.path.abspath(args.out)
    payload = merge_runs(out, entry)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"\nwrote {out}")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
