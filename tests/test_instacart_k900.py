"""Serving at the paper's Instacart hyperparameters: k = 900 neighbours.

k = 900 is wider than a corpus tile (bm = 512), so stage A's running
top-k list still holds unfilled (-inf) slots after the first tile and
fills over later ones.  These tests run that regime on the CPU: the
D-tiled kernel in interpret mode against its XLA oracle, the fused
serving program against the reference path and a plain float64 blend,
and ``StreamingEngine.recommend`` (m = 3, r_b 0.9, r_g 0.7, alpha 0.9)
against the same blend built from ``core.ref_engine``'s user vectors.
The chip benchmark's ``instacart`` configuration is checked against the
paper's deployment, and its ``instacart.serve`` cell is rehearsed end to
end at a tiny size.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import astutil, kernel_rules
from repro.analysis.contracts import REGISTRY
from repro.analysis.linter import load_contracts
from repro.core.ref_engine import RefEngine
from repro.core.types import KIND_ADD_BASKET, PAPER_HYPERPARAMS, TifuParams
from repro.data.synthetic import DATASET_STATS
from repro.kernels import ops, ref
from repro.kernels.knn_topk import knn_topk_dtiled
from repro.streaming import Event, StateStore, StoreConfig, StreamingEngine

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
K, ALPHA, TOPN = 900, 0.9, 10
# a float32 score of an item lies within this of its float64 value
# (scores are at most 1; float32 keeps 2**-24 of it, and the blend sums
# k rows)
SCORE_ROUNDING = 1e-6
# users whose float64 distance lies within this share of |q|^2 + |c|^2
# of the k-th nearest's may swap with it under float32 distances
F32_TIE = 1e-5


def blend_bounds(vecs: np.ndarray, user: int, k: int,
                 alpha: float) -> tuple:
    """The lowest and highest float64 blended score of each item over
    every set of k nearest other users that float32 distances could
    pick: users whose distance lies within ``F32_TIE`` of the squared norms
    of the k-th nearest's are ties, any of which may fill the places
    the surely nearer leave.  Without ties both bounds are the exact
    blend (k nearest, their mean, the alpha-blend)."""
    sq = np.einsum("ij,ij->i", vecs, vecs)
    dist = sq - 2.0 * vecs @ vecs[user] + sq[user]
    dist[user] = np.inf
    kth = np.argsort(dist, kind="stable")[k - 1]
    slack = F32_TIE * (sq + sq[user])
    ties = np.abs(dist - dist[kth]) <= slack + slack[kth]
    sure = (dist < dist[kth]) & ~ties
    free = k - int(sure.sum())
    cand = np.sort(vecs[ties], axis=0)
    lo = vecs[sure].sum(axis=0) + cand[:free].sum(axis=0)
    hi = vecs[sure].sum(axis=0) + cand[cand.shape[0] - free:].sum(axis=0)
    own = alpha * vecs[user]
    return own + (1 - alpha) * lo / k, own + (1 - alpha) * hi / k


def assert_top_n_of_blend(ids, vecs: np.ndarray, users, k: int,
                          alpha: float, n_items: int) -> None:
    """Each row of ``ids`` is the float64 blend's top-n, up to float32
    rounding: at every rank, the returned item's highest possible score
    lies at most ``SCORE_ROUNDING`` below the rank's lowest."""
    ids = np.asarray(ids)
    for row, u in zip(ids, users):
        lo, hi = blend_bounds(vecs, int(u), k, alpha)
        lo, hi = lo[:n_items], hi[:n_items]
        assert np.unique(row).size == row.size and row.max() < n_items
        best = -np.sort(-lo)[:row.size]
        assert (best - hi[row]).max() <= SCORE_ROUNDING, (u, row)


def _exact_corpus(rng, m: int, d: int) -> np.ndarray:
    """Sparse multiples of 1/4: every score 2q.c - |c|^2 is exact in
    float32 whatever the summation order, so kernel and oracle agree bit
    for bit, and equal scores are real ties."""
    vals = rng.integers(1, 4, size=(m, d)) * 0.25
    return np.where(rng.random((m, d)) < 0.05, vals, 0.0).astype(np.float32)


@pytest.mark.parametrize("m", [1100, 901], ids=["three_tiles", "k_is_m-1"])
def test_dtiled_k900_bitwise_against_oracle(m):
    # D = 600: two D-tiles of 512, the second ragged; M = 1,100 is three
    # 512-row tiles, M = 901 leaves exactly k = M - 1 finite candidates
    # once each query excludes itself
    rng = np.random.default_rng(m)
    corpus = jnp.asarray(_exact_corpus(rng, m, 600))
    users = jnp.asarray([0, 511, 512, m - 1], jnp.int32)
    v, i = knn_topk_dtiled(corpus[users], corpus, K, interpret=True,
                           query_gids=users)
    rv, ri = ref.dtiled_topk_ref(corpus[users], corpus, K, bd=512,
                                 query_gids=users)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # and against numpy: (score desc, index asc), the query excluded
    c = np.asarray(corpus, np.float64)
    for row, u in enumerate(np.asarray(users)):
        score = 2.0 * c @ c[u] - np.einsum("ij,ij->i", c, c)
        score[u] = -np.inf
        want = np.lexsort((np.arange(m), -score))[:K]
        np.testing.assert_array_equal(np.asarray(i)[row], want)
        assert np.isfinite(np.asarray(v)[row]).all()


def _tifu_like(rng, m: int, n_items: int, n_cols: int) -> np.ndarray:
    """Nonnegative sparse rows in [0, 1], zero past ``n_items``."""
    x = rng.random((m, n_cols)) * (rng.random((m, n_cols)) < 0.02)
    x[:, n_items:] = 0.0
    return x.astype(np.float32)


def test_fused_recommend_k900_matches_ref_and_float64_blend():
    # 2,048 columns at k = 900: past the monolithic kernel's VMEM, so
    # the D-tiled program serves, as at Instacart's 8,064
    m, n_items, n_cols = 1100, 2000, 2048
    assert ops.stage_a_bd(n_cols, K) == 512
    rng = np.random.default_rng(17)
    corpus = _tifu_like(rng, m, n_items, n_cols)
    users = np.array([0, 17, 511, 512, 1099], np.int32)
    # at alpha 0.9 a user's own items lead its top-10; users with few or
    # no items of their own are served by their neighbours' mean
    corpus[0] = 0.0
    corpus[511] *= 0.01
    got = ops.fused_recommend(jnp.asarray(corpus), jnp.asarray(users), K,
                              ALPHA, TOPN, impl="interpret",
                              n_items=n_items)
    want = ops.fused_recommend(jnp.asarray(corpus), jnp.asarray(users), K,
                               ALPHA, TOPN, impl="ref", n_items=n_items)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert_top_n_of_blend(got, corpus.astype(np.float64), users, K, ALPHA,
                          n_items)


def test_engine_recommend_at_instacart_hyperparameters():
    p = PAPER_HYPERPARAMS["instacart"]
    n_users, n_items = 1000, 2000
    params = TifuParams(n_items=n_items, group_size=p.group_size,
                        r_b=p.r_b, r_g=p.r_g, k_neighbors=p.k_neighbors,
                        alpha=p.alpha)
    assert (params.group_size, params.k_neighbors, params.alpha) == (3, K,
                                                                     ALPHA)
    store = StateStore(StoreConfig(n_users=n_users, n_items=n_items,
                                   max_baskets=8, max_basket_size=9))
    eng = StreamingEngine(store, params, batch_size=256)
    oracle = RefEngine(params)
    rng = np.random.default_rng(900)
    adds = []                       # (rank in the user's history, event)
    for u in range(n_users):
        for rank in range(rng.integers(1, 9)):
            basket = np.sort(rng.choice(n_items, rng.integers(3, 10),
                                        replace=False))
            adds.append((rank, Event(KIND_ADD_BASKET, u, items=basket)))
            oracle.add_basket(u, basket)
    # users interleave, each user's baskets in order
    eng.submit([e for _, e in sorted(adds, key=lambda a: a[0])])
    eng.run_until_drained()
    vecs = np.stack([oracle.state(u).user_vec for u in range(n_users)])

    users = [3, 250, 511, 512, 998]
    with ops.default_impl("interpret"):
        got = eng.recommend(users, topn=TOPN)
    assert got.shape == (len(users), TOPN)
    assert_top_n_of_blend(got, vecs, users, K, ALPHA, n_items)


def test_dtiled_contract_covers_k900():
    # what the KC03 lint holds the kernel to: its VMEM model at the
    # declared largest shapes, which now reach Instacart's k
    load_contracts()
    c = REGISTRY[("repro.kernels.knn_topk", "knn_topk_dtiled")]
    assert c.max_shapes["k"] >= K > c.max_shapes["bm"]
    path = ROOT / "src" / "repro" / "kernels" / "knn_topk.py"
    sites = {s.entry: s for s in astutil.find_pallas_sites(
        astutil.load(path).tree)}
    assert kernel_rules._check_vmem(sites["knn_topk_dtiled"], c, path) == []
    # Instacart's 7,999 items pad to 8,064 columns: the D-tiled kernel
    assert ops.stage_a_bd(8064, K) == 512


def _bench_files(cell: str = "instacart.serve") -> tuple:
    """(config, traffic, BENCHMARK.json) of a chip benchmark cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {c["name"]: c for c in bench["workloads"]}[cell]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / (w["traffic"] + ".json")).read_text())
    return cfg, traffic, bench


def _poisson_tail(lam: float, cap: int) -> float:
    """P(X > cap) for X ~ Poisson(lam)."""
    return 1.0 - sum(math.exp(-lam) * lam ** j / math.factorial(j)
                     for j in range(cap + 1))


def test_instacart_config_is_the_papers_deployment():
    cfg, traffic, bench = _bench_files()
    p = PAPER_HYPERPARAMS["instacart"]
    assert (cfg["n_items"], cfg["group_size"], cfg["r_b"], cfg["r_g"],
            cfg["k_neighbors"], cfg["alpha"]) == (
        p.n_items, p.group_size, p.r_b, p.r_g, p.k_neighbors, p.alpha)
    assert all(cfg[k] == v for k, v in DATASET_STATS["instacart"].items())
    conf = {c["name"]: c for c in bench["configs"]}["instacart"]
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    # the cuts clip fewer than 0.05 users and baskets, as each says
    users = cfg["n_users"] * _poisson_tail(cfg["avg_baskets"],
                                           cfg["baskets_per_user_cap"])
    baskets = (cfg["n_users"] * cfg["avg_baskets"]
               * _poisson_tail(cfg["avg_basket_size"],
                               cfg["items_per_basket_cap"]))
    assert users < 0.05 and baskets < 0.05
    assert f"{users:.3f} users" in cfg["reduced"]["baskets_per_user_cap"]
    assert f"{baskets:.3f} baskets" in cfg["reduced"]["items_per_basket_cap"]
    assert cfg["max_baskets"] == (cfg["baskets_per_user_cap"]
                                  + traffic["adds_per_user_cap"])
    assert cfg["max_basket_size"] == cfg["items_per_basket_cap"]


def test_instacart_serve_cell_rehearses_correct(capsys, monkeypatch):
    # the cell's whole path on the CPU (generation, load, warm-up,
    # window of reads beside adds, check against the float64 replay) at
    # a size that runs in seconds; every rule of the traffic is kept
    monkeypatch.syspath_prepend(str(BENCH))
    import run

    cfg, traffic, bench = _bench_files()
    cfg.update(n_users=96, n_items=300, k_neighbors=8, batch_size=16,
               load_batch_size=64, baskets_per_user_cap=20, max_baskets=48)
    traffic.update(add_burst=16, adds_per_user_cap=24, warm_s=1.0,
                   warm_add_bursts=16, read_rate=40.0, read_check=24)
    rc = run.run(["--workload", "instacart.serve", "--seed", "2147490011",
                  "--seconds", "2", "--trace", "0"], require_tpu=False,
                 spec=(cfg, traffic, 1, bench))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["read_err"]["value"] == 0.0
    assert out["metrics"]["read_p50_ms"]["value"] > 0
