"""In-kernel running top-k merge shared by the serving kernels.

Stage A (``knn_topk``/``knn_topk_dtiled``) keeps a running ``[bq, k]``
neighbour list and stage B (``blend_topn_*``) a running ``[bq, n]``
item list; each grid step merges one freshly scored ``[bq, bt]`` tile
into it.  Mosaic has no ``top_k`` lowering, so the merge here is built
only from operations it lowers: lane max/min reductions, ``iota``
compares and ``where``.

Selection is by repeated extraction in the oracle's total order —
score descending, then index ascending (``jax.lax.top_k``'s lowest-index
tie-break).  Step ``j`` takes the largest ``(score, -index)`` pair that
is strictly below the pair taken at step ``j − 1``, so nothing has to be
deleted from the candidate sets between steps and equal scores resolve
to the lowest index, whichever side (running list or tile) holds it.
Slots left once every candidate has been taken hold ``(-inf, 0)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BIG = jnp.iinfo(jnp.int32).max


def init_topk(acc_vals, acc_idx) -> None:
    """Reset a running ``(acc_vals, acc_idx)`` list to all ``(-inf, 0)``."""
    acc_vals[...] = jnp.full(acc_vals.shape, -jnp.inf, jnp.float32)
    acc_idx[...] = jnp.zeros(acc_idx.shape, jnp.int32)


def merge_topk(acc_vals, acc_idx, new_vals, new_idx, tile_vals, base,
               k: int) -> None:
    """Merge a scored tile into a sorted running top-k list, in place.

    ``acc_vals``/``acc_idx`` (f32/i32 refs ``[bq, kw]``, ``kw >= k``)
    hold the running list in (score desc, index asc) order; lanes past
    ``k`` are never read as results.  ``tile_vals`` (f32 ref
    ``[bq, bt]``) holds the tile's scores, whose indices are
    ``base + lane``.  ``new_vals``/``new_idx`` are scratch refs shaped
    like the running list.  Cost: ``k`` passes over ``kw + bt`` lanes.

    The running list may be wider than a tile (``k > bt``, as at
    Instacart's k = 900 against 512-row corpus tiles): after the first
    merges its unfilled slots hold -inf scores (index 0, or a masked
    column's) and rank below every finite candidate, so a later tile's
    finite scores replace them.
    """
    bq, kw = acc_vals.shape
    bt = tile_vals.shape[1]
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (bq, kw), 1)
    t_idx = base + jax.lax.broadcasted_iota(jnp.int32, (bq, bt), 1)
    # lanes past k of the running list never compete
    a_live = lane_k < k

    def below(v, i, pv, pi):
        return (v < pv) | ((v == pv) & (i > pi))

    def step(j, carry):
        pv, pi = carry                                   # [bq, 1] each
        a_v, a_i = acc_vals[...], acc_idx[...]
        t_v = tile_vals[...]
        a_ok = a_live & below(a_v, a_i, pv, pi)
        t_ok = below(t_v, t_idx, pv, pi)
        m = jnp.maximum(
            jnp.max(jnp.where(a_ok, a_v, -jnp.inf), axis=1, keepdims=True),
            jnp.max(jnp.where(t_ok, t_v, -jnp.inf), axis=1, keepdims=True))
        sel = jnp.minimum(
            jnp.min(jnp.where(a_ok & (a_v == m), a_i, _BIG), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(t_ok & (t_v == m), t_idx, _BIG), axis=1,
                    keepdims=True))
        hit = lane_k == j
        new_vals[...] = jnp.where(hit, m, new_vals[...])
        new_idx[...] = jnp.where(hit, sel, new_idx[...])
        return m, sel

    init = (jnp.full((bq, 1), jnp.inf, jnp.float32),
            jnp.full((bq, 1), -1, jnp.int32))
    jax.lax.fori_loop(0, k, step, init)
    acc_vals[...] = new_vals[...]
    acc_idx[...] = jnp.where(new_idx[...] == _BIG, 0, new_idx[...])
