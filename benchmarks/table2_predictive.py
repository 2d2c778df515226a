"""Paper Table 2: Recall@{10,20} / NDCG@{10,20} for baseline (retrain
from scratch) vs incremental vs decremental maintenance, on synthetic
datasets matching TaFeng/Instacart/ValuedShopper statistics.

Claim under test: incremental == baseline EXACTLY; decremental shows no
significant regression (paper: differences ≤ ~3e-4 absolute).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import RefEngine, knn
from repro.data import synthetic
from repro.launch.compile_cache import use_compile_cache


def evaluate(user_vecs: np.ndarray, users, test, params, ks=(10, 20)):
    corpus = jnp.asarray(user_vecs, jnp.float32)
    pred = knn.predict(corpus, corpus, k=params.k_neighbors,
                       alpha=params.alpha, exclude_self=True)
    recs = np.asarray(knn.recommend_topn(pred, max(ks)))
    truth = [test[u] for u in users]
    out = {}
    for k in ks:
        out[f"recall@{k}"] = knn.recall_at_k(recs, truth, k)
        out[f"ndcg@{k}"] = knn.ndcg_at_k(recs, truth, k)
    return out


def run(dataset="tafeng", scale=0.15, seed=0, deletion_user_rate=1e-3,
        deletion_frac=0.10):
    ds = synthetic.generate(dataset, scale=scale, seed=seed)
    p = ds.params
    train, test = ds.train_test_split()
    users = sorted(train)
    rng = np.random.default_rng(seed + 1)

    # --- baseline: full from-scratch training --------------------------------
    base = RefEngine(p)
    for u in users:
        base.fit_from_scratch(u, train[u])
    m_base = evaluate(base.user_matrix(users), users, test, p)

    # --- incremental: basket-by-basket online learning -----------------------
    incr = RefEngine(p)
    for u in users:
        for b in train[u]:
            incr.add_basket(u, b)
    m_incr = evaluate(incr.user_matrix(users), users, test, p)
    max_vec_diff = max(
        float(np.max(np.abs(incr.state(u).user_vec - base.state(u).user_vec)))
        for u in users)

    # --- decremental: paper §6.1 — ~1/1000 users delete 10% of baskets ------
    decr = RefEngine(p)
    for u in users:
        decr.fit_from_scratch(u, train[u])
    n_del_users = max(1, int(len(users) * max(deletion_user_rate, 1e-3)))
    for u in rng.choice(users, size=n_del_users, replace=False):
        st = decr.state(int(u))
        n_del = max(1, int(st.n_baskets * deletion_frac))
        for _ in range(n_del):
            if st.n_baskets == 0:
                break
            decr.delete_basket(int(u), int(rng.integers(0, st.n_baskets)))
    m_decr = evaluate(decr.user_matrix(users), users, test, p)

    rows = []
    for metric in ("recall@10", "ndcg@10", "recall@20", "ndcg@20"):
        rows.append((dataset, metric, m_base[metric], m_incr[metric],
                     m_decr[metric]))
    return rows, max_vec_diff


def main(scale=0.15):
    print("dataset,metric,baseline,incremental,decremental")
    for ds in ("tafeng", "instacart", "valuedshopper"):
        sc = scale if ds != "valuedshopper" else scale / 2  # 57 b/user
        rows, vec_diff = run(ds, scale=sc)
        for r in rows:
            print(f"{r[0]},{r[1]},{r[2]:.4f},{r[3]:.4f},{r[4]:.4f}")
        assert vec_diff < 1e-10, \
            f"incremental not exact on {ds}: {vec_diff}"
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
