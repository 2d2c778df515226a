"""Serving latency/throughput vs corpus size, fused vs baseline.

The update path has been benchmarked since PR 1; this is the READ path
(ISSUE 5): batched recommendation requests against a materialized
corpus, measuring

  * ``fused``    — the live serving path (`core.knn.recommend_for_users`
                   → ``kernels.ops.fused_recommend``): on CPU the XLA
                   reference (bitwise the historical output), on TPU /
                   interpret the two-stage Pallas pipeline of
                   DESIGN.md §8 (streaming top-k + one-hot blend/top-n,
                   O(Q·k) HBM intermediates);
  * ``baseline`` — the pre-fusion unfused computation pinned in-line
                   here (full [Q, M] score materialization, [Q, k, I]
                   neighbour gather, [Q, I] prediction, separate
                   top-n), always through XLA.

On a CPU host the two arms run the same math, so the speedup sits at
~1x BY CONSTRUCTION (the fused CPU path is pinned bitwise to the
baseline); the enforceable CPU signals are the latency trend, the
queries/s / p50 / p99 numbers, and the REQUEST-BUCKETING gate: a sweep
of ragged request sizes through `StreamingEngine.recommend` must
compile only the pow2 bucket count of programs
(``serving_compiled_programs``, enforced as an upper bound by
``bench_trend.py`` — "compiled" metrics must never increase).  The
fused-vs-baseline speedup becomes meaningful on the TPU arm (ROADMAP:
needs a real-TPU run, like the update kernels' ``--backend tpu`` arm).

``--backend`` as in bench_update_batch.py: ``cpu`` pins the XLA
reference path, ``tpu`` natural dispatch on a TPU host, ``interpret``
drives the Pallas serving kernels in interpret mode (plumbing numbers;
only allowed with ``--smoke``).

``--scale`` runs the million-item sweep instead (ISSUE 7 / DESIGN.md
§8.4): corpus ITEM counts 64k → 1M through three serving paths —
monolithic fp32 (the §8 kernel, whose [bq, D] + [bm, D] VMEM blocks
grow linearly in D and blow the 16 MiB budget long before 1M),
D-tiled fp32, and D-tiled int8 over the per-row-quantized corpus.
Per sweep point it records latency, the analytic per-query-block VMEM
model (``kernels.ops.stage_a_vmem_bytes``), corpus HBM bytes, and the
int8-vs-fp32 top-n overlap, then ASSERTS the tentpole claim: D-tiled
VMEM stays flat (within 10%) across the sweep while monolithic no
longer fits VMEM at the top size.

Entries merge into BENCH_updates.json under ``arm="serving"`` (or
``arm="serving_scale"`` for ``--scale``) — schema:
benchmarks/README.md.  Scale-summary keys follow the non-gated
parity-key convention (no "compiled"/"speedup" substrings), so
``bench_trend.py`` records but never gates them.

    PYTHONPATH=src python benchmarks/bench_serving.py
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke  # CI
    PYTHONPATH=src python benchmarks/bench_serving.py --scale
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TifuParams, knn
from repro.kernels import ops
from repro.streaming import StateStore, StoreConfig, StreamingEngine
from repro.launch.compile_cache import use_compile_cache

from bench_update_batch import BACKEND_IMPL, merge_runs


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    n_items: int = 2048
    q_batch: int = 256
    k: int = 64
    topn: int = 10
    alpha: float = 0.7
    corpus_grid: tuple = (1_024, 8_192, 32_768)
    iters: int = 30
    warmup: int = 3
    # request-bucketing sweep (through a real engine)
    bucket_users: int = 512
    bucket_requests: int = 32


SMOKE = ServeConfig(n_items=192, q_batch=48, k=8, topn=5,
                    corpus_grid=(160, 320), iters=3, warmup=1,
                    bucket_users=64, bucket_requests=8)


@dataclasses.dataclass(frozen=True)
class ScaleConfig:
    """Million-item sweep (``--scale``): the D axis grows, Q/M stay
    fixed, so every trend in the output is an item-count trend."""
    m_users: int = 256
    q_batch: int = 32
    k: int = 16
    topn: int = 10
    alpha: float = 0.7
    items_grid: tuple = (65_536, 262_144, 1_048_576)
    bd: int = 1024
    iters: int = 3
    warmup: int = 1


SCALE_SMOKE = ScaleConfig(m_users=48, q_batch=8, k=4, topn=5,
                          items_grid=(768, 1_536), bd=256, iters=2,
                          warmup=1)

# v4/v5-class VMEM per core; the budget stage_a_vmem_bytes is judged
# against (DESIGN.md §8.2)
VMEM_BUDGET = 16 * 2**20


@functools.partial(jax.jit, static_argnames=("k", "topn"))
def baseline_recommend(corpus, user_ids, k, alpha, topn):
    """The pre-fusion serving computation, pinned here as the baseline:
    [Q, M] scores in HBM, [Q, k, I] neighbour gather, [Q, I] prediction,
    then top-n — compiled as ONE program, exactly like the historical
    ``recommend_for_users`` jit, so the fused-vs-baseline ratio compares
    kernel paths, not dispatch overheads."""
    queries = corpus[user_ids]
    pred = knn.predict(queries, corpus, k=k, alpha=alpha,
                       exclude_self=True, query_ids=user_ids)
    return knn.recommend_topn(pred, topn)


def bench_path(path: str, corpus, cfg: ServeConfig, rng, backend: str):
    m = corpus.shape[0]
    users = jnp.asarray(rng.choice(m, size=min(cfg.q_batch, m),
                                   replace=False).astype(np.int32))
    if path == "fused":
        def run():
            return knn.recommend_for_users(corpus, users, k=cfg.k,
                                           alpha=cfg.alpha, topn=cfg.topn)
    else:
        def run():
            return baseline_recommend(corpus, users, cfg.k, cfg.alpha,
                                      cfg.topn)
    for _ in range(cfg.warmup):
        jax.block_until_ready(run())
    times = []
    for _ in range(cfg.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    times = np.asarray(times)
    q_n = int(users.shape[0])
    return {"path": path, "backend": backend, "m_users": m,
            "n_items": cfg.n_items, "q_batch": q_n, "k": cfg.k,
            "topn": cfg.topn, "iters": cfg.iters,
            "mean_ms": float(times.mean() * 1e3),
            "p50_ms": float(np.median(times) * 1e3),
            "p99_ms": float(np.quantile(times, 0.99) * 1e3),
            "min_ms": float(times.min() * 1e3),
            "queries_per_s": float(q_n / times.mean())}


def make_corpus(m: int, n_items: int, rng) -> jnp.ndarray:
    """A dense random corpus stands in for materialized user vectors —
    serving cost depends only on shapes, not values."""
    return jnp.asarray(rng.random((m, n_items), np.float32))


def bench_bucketing(cfg: ServeConfig, rng) -> dict:
    """Ragged request sizes through the engine-side batcher: the
    compiled-shape count must track the pow2 BUCKETS, not the sizes."""
    p = TifuParams(n_items=cfg.n_items, group_size=3, k_neighbors=cfg.k,
                   alpha=cfg.alpha)
    store = StateStore(StoreConfig(n_users=cfg.bucket_users,
                                   n_items=cfg.n_items, max_baskets=4,
                                   max_basket_size=8))
    eng = StreamingEngine(store, p, batch_size=cfg.bucket_users)
    for u in range(cfg.bucket_users):
        eng.add_basket(u, rng.choice(cfg.n_items, size=4, replace=False))
    eng.run_until_drained()
    sizes = sorted(int(rng.integers(1, cfg.bucket_users + 1))
                   for _ in range(cfg.bucket_requests))
    for q_n in sizes:
        eng.recommend(rng.choice(cfg.bucket_users, size=q_n,
                                 replace=False), topn=cfg.topn)
    buckets = {1 << max(0, (s - 1).bit_length()) for s in sizes}
    return {"request_sizes": len(set(sizes)),
            "pow2_buckets": len(buckets),
            "compiled_shapes": eng.metrics.serve_compiled_shapes}


def _time_runs(run, iters: int, warmup: int) -> np.ndarray:
    for _ in range(warmup):
        jax.block_until_ready(run())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    return np.asarray(times)


def _topn_overlap(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.mean([len(set(x) & set(y)) / len(x)
                          for x, y in zip(a, b)]))


def bench_scale_point(n_items: int, cfg: ScaleConfig, backend: str) -> list:
    """One sweep point: the three serving paths over the same corpus.

    The VMEM numbers come from the analytic per-grid-step model
    (``ops.stage_a_vmem_bytes``) — the quantity the Pallas grid actually
    holds resident; latency is wall clock on whatever backend runs."""
    from repro.optim.compression import quantize_int8_rows

    rng = np.random.default_rng(0)
    corpus = make_corpus(cfg.m_users, n_items, rng)
    corpus_q, c_scale = quantize_int8_rows(corpus)
    users = jnp.asarray(rng.choice(cfg.m_users, size=cfg.q_batch,
                                   replace=False).astype(np.int32))
    paths = {
        "fp32_mono": (
            lambda: knn.recommend_for_users(corpus, users, k=cfg.k,
                                            alpha=cfg.alpha,
                                            topn=cfg.topn),
            ops.stage_a_vmem_bytes(n_items, cfg.k),
            int(corpus.size) * 4),
        "fp32_dtiled": (
            lambda: ops.fused_recommend(corpus, users, k=cfg.k,
                                        alpha=cfg.alpha, topn=cfg.topn,
                                        bd=cfg.bd),
            ops.stage_a_vmem_bytes(n_items, cfg.k, bd=cfg.bd),
            int(corpus.size) * 4),
        "int8_dtiled": (
            lambda: knn.recommend_for_users_quant(corpus_q, c_scale,
                                                  users, k=cfg.k,
                                                  alpha=cfg.alpha,
                                                  topn=cfg.topn,
                                                  bd=cfg.bd),
            ops.stage_a_vmem_bytes(n_items, cfg.k, bd=cfg.bd,
                                   itemsize=1),
            int(corpus_q.size) + int(c_scale.size) * 4),
    }
    out, recs = [], {}
    for path, (run, vmem, hbm) in paths.items():
        times = _time_runs(run, cfg.iters, cfg.warmup)
        recs[path] = np.asarray(run())
        out.append({"path": path, "backend": backend, "n_items": n_items,
                    "m_users": cfg.m_users, "q_batch": cfg.q_batch,
                    "k": cfg.k, "topn": cfg.topn, "bd": cfg.bd,
                    "iters": cfg.iters,
                    "mean_ms": float(times.mean() * 1e3),
                    "p50_ms": float(np.median(times) * 1e3),
                    "min_ms": float(times.min() * 1e3),
                    "stage_a_vmem_bytes": int(vmem),
                    "fits_vmem": bool(vmem <= VMEM_BUDGET),
                    "corpus_hbm_bytes": int(hbm)})
    overlap = _topn_overlap(recs["fp32_mono"], recs["int8_dtiled"])
    for r in out:
        r["int8_fp32_topn_overlap"] = overlap
    del corpus, corpus_q
    return out


def summarize_scale(results: list, cfg: ScaleConfig) -> dict:
    """Scale-arm summary.  Keys deliberately avoid the "compiled" and
    "speedup" substrings so ``bench_trend.py`` records but never gates
    them (CPU/interpret latencies here are plumbing numbers, and the
    VMEM claims are asserted below, not trend-gated)."""
    def pick(path, n):
        return next(r for r in results if r["path"] == path
                    and r["n_items"] == n)

    d_lo, d_hi = cfg.items_grid[0], cfg.items_grid[-1]
    dtiled = [pick("fp32_dtiled", n) for n in cfg.items_grid]
    vmems = [r["stage_a_vmem_bytes"] for r in dtiled]
    mono_lo, mono_hi = pick("fp32_mono", d_lo), pick("fp32_mono", d_hi)
    int8_hi, fp32_hi = pick("int8_dtiled", d_hi), pick("fp32_dtiled", d_hi)
    summary = {
        "scale_max_items": d_hi,
        "scale_dtiled_vmem_mib_at_max_items":
            vmems[-1] / 2**20,
        "scale_dtiled_vmem_growth_across_sweep":
            max(vmems) / min(vmems),
        "scale_mono_vmem_mib_at_max_items":
            mono_hi["stage_a_vmem_bytes"] / 2**20,
        "scale_mono_vmem_growth_across_sweep":
            mono_hi["stage_a_vmem_bytes"] / mono_lo["stage_a_vmem_bytes"],
        "scale_mono_fits_vmem_at_max_items": int(mono_hi["fits_vmem"]),
        "scale_int8_hbm_reduction_vs_fp32":
            fp32_hi["corpus_hbm_bytes"] / int8_hi["corpus_hbm_bytes"],
        "scale_int8_fp32_topn_overlap_at_max_items":
            int8_hi["int8_fp32_topn_overlap"],
        "scale_int8_p50_ms_at_max_items": int8_hi["p50_ms"],
        "scale_fp32_dtiled_p50_ms_at_max_items": fp32_hi["p50_ms"],
    }
    # The tentpole claims, enforced at bench time (ISSUE 7 acceptance):
    # per-query-block serving memory flat (within 10%) across the sweep
    # for the D-tiled paths, while the monolithic kernel's grows with D
    # and — at full scale — no longer fits VMEM at all.
    assert summary["scale_dtiled_vmem_growth_across_sweep"] <= 1.10, vmems
    assert all(r["fits_vmem"] for r in dtiled), vmems
    assert summary["scale_mono_vmem_growth_across_sweep"] > 1.10
    if d_hi >= 1_000_000:
        assert not mono_hi["fits_vmem"], mono_hi["stage_a_vmem_bytes"]
    return summary


def run_scale(cfg: ScaleConfig, backend: str) -> tuple:
    results = []
    with ops.default_impl(BACKEND_IMPL[backend]):
        for n_items in cfg.items_grid:
            for r in bench_scale_point(n_items, cfg, backend):
                results.append(r)
                fits = "fits" if r["fits_vmem"] else "EXCEEDS VMEM"
                print(f"{r['path']:12s} I={n_items:>9,d} "
                      f"mean={r['mean_ms']:9.2f} ms "
                      f"vmem={r['stage_a_vmem_bytes'] / 2**20:8.2f} MiB "
                      f"({fits}) hbm={r['corpus_hbm_bytes'] / 2**20:8.1f}"
                      f" MiB")
    return results, summarize_scale(results, cfg)


def summarize(results: list, bucketing: dict, cfg: ServeConfig,
              backend: str) -> dict:
    def pick(path, m):
        return next(r for r in results if r["path"] == path
                    and r["m_users"] == m)

    m_lo, m_hi = cfg.corpus_grid[0], cfg.corpus_grid[-1]
    fused_lo, fused_hi = pick("fused", m_lo), pick("fused", m_hi)
    base_hi = pick("baseline", m_hi)
    ratio = base_hi["mean_ms"] / fused_hi["mean_ms"]
    # On cpu the two arms run the SAME math (the fused cpu path is
    # bitwise-pinned to the baseline), so the ratio is a parity check
    # around 1x, not a speedup — name it so the trend gate (which
    # enforces "*speedup*" keys) never gates on dispatch noise.  The
    # Pallas backends keep the speedup name: there the paths differ.
    ratio_key = ("serving_fused_baseline_parity_at_max_corpus"
                 if backend == "cpu"
                 else "serving_fused_speedup_vs_baseline_at_max_corpus")
    return {
        "max_corpus_users": m_hi,
        "serving_qps_at_max_corpus": fused_hi["queries_per_s"],
        "serving_p50_ms_at_max_corpus": fused_hi["p50_ms"],
        "serving_p99_ms_at_max_corpus": fused_hi["p99_ms"],
        "serving_latency_growth_to_max_corpus":
            fused_hi["mean_ms"] / fused_lo["mean_ms"],
        ratio_key: ratio,
        "serving_compiled_programs": bucketing["compiled_shapes"],
        "serving_request_sizes_swept": bucketing["request_sizes"],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI: validates the harness, not "
                         "perf)")
    ap.add_argument("--scale", action="store_true",
                    help="million-item sweep: mono vs D-tiled vs int8 "
                         "(arm=serving_scale)")
    ap.add_argument("--backend", choices=sorted(BACKEND_IMPL),
                    default=None,
                    help="serving kernel path (default: tpu on a TPU "
                         "host, else cpu)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_updates.json"))
    args = ap.parse_args()
    cfg = SMOKE if args.smoke else ServeConfig()
    backend = args.backend or ("tpu" if jax.default_backend() == "tpu"
                               else "cpu")
    if backend == "tpu" and jax.default_backend() != "tpu":
        ap.error("--backend tpu requires a TPU host "
                 f"(jax.default_backend() == {jax.default_backend()!r})")
    if backend == "interpret" and not args.smoke:
        ap.error("--backend interpret is interpret-mode Pallas (orders "
                 "of magnitude slower): only allowed with --smoke")

    if args.scale:
        scfg = SCALE_SMOKE if args.smoke else ScaleConfig()
        results, summary = run_scale(scfg, backend)
        print(f"\nsummary [serving_scale/{backend}]:")
        for key, v in summary.items():
            print(f"  {key}: {v:.3f}" if isinstance(v, float)
                  else f"  {key}: {v}")
        entry = {
            "backend": backend,
            "jax_backend": jax.default_backend(),
            "mode": "smoke" if args.smoke else "full",
            "arm": "serving_scale",
            "config": dataclasses.asdict(scfg),
            "summary": summary,
            "results": results,
        }
        out = os.path.abspath(args.out)
        payload = merge_runs(out, entry)
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {out} ({len(payload['runs'])} run entries)")
        return 0

    results = []
    with ops.default_impl(BACKEND_IMPL[backend]):
        for m in cfg.corpus_grid:
            rng = np.random.default_rng(0)
            corpus = make_corpus(m, cfg.n_items, rng)
            for path in ("fused", "baseline"):
                r = bench_path(path, corpus, cfg, rng, backend)
                results.append(r)
                print(f"{path:9s} M={m:>7d} I={cfg.n_items} "
                      f"Q={r['q_batch']} mean={r['mean_ms']:8.2f} ms "
                      f"p99={r['p99_ms']:8.2f} ms "
                      f"({r['queries_per_s']:,.0f} q/s)")
            del corpus
        bucketing = bench_bucketing(cfg, np.random.default_rng(1))
    print(f"bucketing: {bucketing['request_sizes']} request sizes → "
          f"{bucketing['compiled_shapes']} compiled shapes "
          f"({bucketing['pow2_buckets']} pow2 buckets)")
    summary = summarize(results, bucketing, cfg, backend)
    print(f"\nsummary [{backend}]:")
    for key, v in summary.items():
        note = ""
        if key == "serving_fused_baseline_parity_at_max_corpus":
            note = ("  (~1x by construction — bitwise-pinned paths; "
                    "the TPU arm is the perf claim)")
        elif key == "serving_compiled_programs":
            note = "  (gated: must not increase)"
        print(f"  {key}: {v:.2f}{note}" if isinstance(v, float)
              else f"  {key}: {v}{note}")

    entry = {
        "backend": backend,
        "jax_backend": jax.default_backend(),
        "mode": "smoke" if args.smoke else "full",
        "arm": "serving",
        "config": dataclasses.asdict(cfg),
        "summary": summary,
        "results": results,
    }
    out = os.path.abspath(args.out)
    payload = merge_runs(out, entry)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out} ({len(payload['runs'])} run entries)")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
