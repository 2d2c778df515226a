"""Batched JAX incremental/decremental updates (the TPU production path).

Kind-partitioned micro-batches (DESIGN.md §4): the streaming engine
splits each micro-batch into homogeneous sub-batches and each entry
point runs exactly one update rule:

  * ``apply_add_batch``        — Eq. 7-9, **sparse deltas**: O(batch·W)
    data touches the [M, I] state (W = (group_size+1)·max_basket_size),
    never an [n_items] temporary.  Matches the paper's O(1)-per-add
    asymptotic on the batched path (DESIGN.md §3.3).
  * ``apply_del_basket_batch`` — Eq. 10-12, **sparse deltas**: the
    suffix contractions expand to per-history-slot coefficients, so
    O(batch · N·B) data touches the [M, I] state (the history window),
    never an [n_items] temporary; the Eq. 12 whole-vector rescale folds
    into ``uv_scale`` (DESIGN.md §3.5).
  * ``apply_del_item_batch``   — Eq. 13 + basket-vanish fallback, same
    sparse treatment (the in-place branch touches ONE cell per table).

``apply_update_batch`` keeps the mixed-batch signature by partitioning
on the host; ``apply_update_batch_dense`` is the seed's
compute-all-kinds-and-select implementation, retained as the benchmark
baseline (benchmarks/bench_update_batch.py) and as a second oracle, and
``apply_del_*_batch_dense`` are the homogeneous dense decremental
baselines the sparse paths are validated and benchmarked against.

Design notes (DESIGN.md §3.2): the variable-length suffix contractions of
Eq. 10/12 are computed as *masked fixed-shape* weighted multi-hot
scatters using the closed-form coefficient expansion in
``decay.batched_suffix_coefficients`` — no data-dependent shapes, so one
compiled program serves every deletion position.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decay
from repro.core.tifu import (last_group_vector_padded,
                             weighted_multihot_scatter, user_vector_padded)
from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET, KIND_DEL_ITEM,
                              KIND_NOOP, PAD_ID, AddBatch, DelBasketBatch,
                              DelItemBatch, StreamState, TifuParams,
                              UpdateBatch)
from repro.kernels.ops import sparse_row_gather, sparse_row_scatter

# Adds only shrink the scales (each new group multiplies uv_scale by
# k·r_g/(k+1), each append multiplies lgv_scale by tau·r_b/(tau+1));
# sparse Eq. 12 deletions GROW uv_scale by k/((k-1)·r_g) > 1.  Fold the
# scales back into the raw rows before float32 precision suffers on
# either side: 1e-18 keeps raw magnitudes <= ~1e18 (hit only after
# hundreds of group openings per user), SCALE_CEIL bounds the growth
# symmetrically (hundreds of single-basket-group deletions).
SCALE_FLOOR = 1e-18
SCALE_CEIL = 1e18


# ---------------------------------------------------------------------------
# Helpers on padded per-user state
# ---------------------------------------------------------------------------

def _multi_hot(items, n_items):
    """Multi-hot encode a basket: i32[B] (PAD_ID padded) → f32[I].

    Set semantics (duplicate ids count once), matching
    ``tifu.multi_hot`` and the sparse add path's first-occurrence dedup.
    """
    valid = items >= 0
    ids = jnp.where(valid, items, 0)
    return jnp.zeros((n_items,), jnp.float32).at[ids].max(
        valid.astype(jnp.float32))


def _row_group_geometry(group_sizes, max_baskets):
    """Locate every history row in its group.

    Returns per-row group index g (0-based), in-group position p
    (1-based) and group size tau, for fixed ``max_baskets`` rows.
    """
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    t = jnp.arange(max_baskets)
    g = jnp.clip(jnp.searchsorted(ends, t, side="right"), 0,
                 sizes.shape[0] - 1)
    tau = sizes[g]
    p = t - starts[g] + 1
    return g, p, tau


def _locate(group_sizes, pos):
    """Locate a global basket index inside the group structure.

    Returns group index j (0-based) and in-group position i (1-based)
    of basket ``pos`` (traced).
    """
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    j = jnp.clip(jnp.searchsorted(ends, pos, side="right"), 0,
                 sizes.shape[0] - 1)
    i = pos - starts[j] + 1
    return j, i


# ---------------------------------------------------------------------------
# Single-user updates (to be vmapped)
# ---------------------------------------------------------------------------

def _add_basket(user_vec, last_group_vec, history, group_sizes, n_baskets,
                n_groups, err_mult, items, params: TifuParams):
    n_items = user_vec.shape[0]
    v_b = _multi_hot(items, n_items).astype(user_vec.dtype)
    k = n_groups
    tau = jnp.where(k > 0, group_sizes[jnp.maximum(k - 1, 0)], 0)
    new_group = (k == 0) | (tau >= params.group_size)

    # Scenario 1 (Eq. 7): new single-basket group.
    user_new_a = (k * params.r_g * user_vec + v_b) / (k + 1)
    lgv_a = v_b
    sizes_a = group_sizes.at[jnp.minimum(k, group_sizes.shape[0] - 1)].set(1)
    err_a = jnp.maximum(
        err_mult * jnp.where(k > 0, decay.error_shrink_factor(k, params.r_g),
                             0.0), 1e-30)

    # Scenario 2 (Eq. 8 + Eq. 9): append to the last group.
    safe_tau = jnp.maximum(tau, 1)
    lgv_b = (safe_tau * params.r_b * last_group_vec + v_b) / (safe_tau + 1)
    user_new_b = user_vec + (lgv_b - last_group_vec) / jnp.maximum(k, 1)
    sizes_b = group_sizes.at[jnp.maximum(k - 1, 0)].add(1)
    err_b = err_mult

    user_vec = jnp.where(new_group, user_new_a, user_new_b)
    last_group_vec = jnp.where(new_group, lgv_a, lgv_b)
    group_sizes = jnp.where(new_group, sizes_a, sizes_b)
    err_mult = jnp.where(new_group, err_a, err_b)
    history = history.at[jnp.minimum(n_baskets, history.shape[0] - 1)].set(items)
    return (user_vec, last_group_vec, history, group_sizes, n_baskets + 1,
            n_groups + new_group.astype(jnp.int32), err_mult)


def _delete_basket(user_vec, last_group_vec, history, group_sizes, n_baskets,
                   n_groups, err_mult, pos, params: TifuParams):
    n_items = user_vec.shape[0]
    max_baskets = history.shape[0]
    k = n_groups
    j, i = _locate(group_sizes, pos)
    tau_j = group_sizes[j]
    g, p, tau = _row_group_geometry(group_sizes, max_baskets)
    t = jnp.arange(max_baskets)
    valid_row = t < n_baskets
    in_group_j = valid_row & (g == j)
    f32 = user_vec.dtype

    # ---- Scenario 1 (Eq. 10 + Eq. 11): tau_j > 1 -------------------------
    safe_tau = jnp.maximum(tau_j, 2)
    # recompute v_gj from the group's rows (O(tau) real work, masked here)
    w_gj = jnp.where(in_group_j,
                     jnp.asarray(params.r_b, f32) ** (tau_j - p)
                     / jnp.maximum(tau_j, 1).astype(f32), 0.0)
    v_gj = weighted_multihot_scatter(history, w_gj, n_items).astype(f32)
    # suffix coefficients inside group j, positions p >= i
    pow_tp = jnp.asarray(params.r_b, f32) ** (tau_j - p)
    c_row = jnp.where(p == i, -pow_tp, pow_tp * (params.r_b - 1.0))
    c_row = jnp.where(in_group_j & (p >= i), c_row, 0.0)
    suffix_g = weighted_multihot_scatter(history, c_row, n_items).astype(f32)
    v_gj_new = (tau_j * v_gj + suffix_g) / ((safe_tau - 1) * params.r_b)
    user_s1 = user_vec + (jnp.asarray(params.r_g, f32) ** (k - 1 - j)
                          * (v_gj_new - v_gj) / jnp.maximum(k, 1))
    sizes_s1 = group_sizes.at[j].add(-1)
    groups_s1 = k

    # ---- Scenario 2 (Eq. 12): tau_j == 1, k > 1 ---------------------------
    # suffix over group vectors j..k-1, expanded to per-basket weights:
    # coeff per group c_g (1-based group pos = g+1), times within-group
    # decayed-average weight r_b^(tau-p)/tau.
    cg = decay.batched_suffix_coefficients(k, j + 1,
                                           jnp.asarray(params.r_g, f32),
                                           group_sizes.shape[0]).astype(f32)
    w_row_s2 = jnp.where(valid_row,
                         cg[g] * jnp.asarray(params.r_b, f32) ** (tau - p)
                         / jnp.maximum(tau, 1).astype(f32), 0.0)
    suffix_u = weighted_multihot_scatter(history, w_row_s2, n_items).astype(f32)
    safe_k = jnp.maximum(k, 2)
    user_s2 = (k * user_vec + suffix_u) / ((safe_k - 1) * params.r_g)
    sizes_s2 = _remove_entry(group_sizes, j)
    groups_s2 = k - 1
    err_s2 = err_mult * decay.error_growth_factor(safe_k.astype(f32),
                                                  params.r_g)

    # ---- Scenario 3: tau_j == 1 and k == 1 → empty state ------------------
    user_s3 = jnp.zeros_like(user_vec)
    sizes_s3 = jnp.zeros_like(group_sizes)
    groups_s3 = jnp.zeros_like(k)

    single = tau_j == 1
    last = k == 1
    user_vec = jnp.where(single, jnp.where(last, user_s3, user_s2), user_s1)
    group_sizes = jnp.where(single, jnp.where(last, sizes_s3, sizes_s2),
                            sizes_s1)
    n_groups = jnp.where(single, jnp.where(last, groups_s3, groups_s2),
                         groups_s1)
    err_mult = jnp.where(single, jnp.where(last, jnp.ones_like(err_mult),
                                           err_s2), err_mult)

    # ---- history compaction: shift rows > pos up by one --------------------
    src = jnp.where(t >= pos, jnp.minimum(t + 1, max_baskets - 1), t)
    history = history[src]
    history = history.at[jnp.maximum(n_baskets - 1, 0)].set(
        jnp.full((history.shape[1],), PAD_ID, jnp.int32))
    n_baskets = n_baskets - 1

    # last_group_vec: recompute from the new geometry (cheap, masked),
    # at the row's (lane-padded) width
    last_group_vec = last_group_vector_padded(
        history, group_sizes, n_groups,
        dataclasses.replace(params, n_items=user_vec.shape[0])).astype(f32)
    return (user_vec, last_group_vec, history, group_sizes, n_baskets,
            n_groups, err_mult)


def _remove_entry(sizes, j):
    """Remove entry j from a padded i32 vector (shift left, zero-fill)."""
    n = sizes.shape[0]
    t = jnp.arange(n)
    src = jnp.where(t >= j, jnp.minimum(t + 1, n - 1), t)
    out = sizes[src]
    return out.at[n - 1].set(jnp.where(j <= n - 1, 0, out[n - 1]))


def _delete_item(user_vec, last_group_vec, history, group_sizes, n_baskets,
                 n_groups, err_mult, pos, item, params: TifuParams):
    """Scenario 3 of §4.3 (Eq. 13 + Eq. 11) with basket-vanish fallback."""
    n_items = user_vec.shape[0]
    f32 = user_vec.dtype
    row = history[pos]
    present = jnp.any(row == item)
    blen = jnp.sum(row >= 0)
    vanish = present & (blen == 1)

    # --- Eq. 13 path: remove the item from the basket in place -------------
    j, i = _locate(group_sizes, pos)
    k = n_groups
    tau_j = jnp.maximum(group_sizes[j], 1)
    delta = -_multi_hot(jnp.array([item]), n_items).astype(f32)
    scale_g = jnp.asarray(params.r_b, f32) ** (tau_j - i) / tau_j
    dg = scale_g * delta                       # v'_gj - v_gj
    user_ip = user_vec + (jnp.asarray(params.r_g, f32) ** (k - 1 - j)
                          * dg / jnp.maximum(k, 1))
    lgv_ip = jnp.where(j == k - 1, last_group_vec + dg, last_group_vec)
    new_row = jnp.where(row == item, PAD_ID, row)
    hist_ip = history.at[pos].set(new_row)

    # --- fallback: basket vanishes → full basket deletion -------------------
    (user_db, lgv_db, hist_db, sizes_db, nb_db, ng_db, err_db) = \
        _delete_basket(user_vec, last_group_vec, history, group_sizes,
                       n_baskets, n_groups, err_mult, pos, params)

    apply_ip = present & ~vanish
    apply_db = vanish
    user_vec = jnp.where(apply_ip, user_ip,
                         jnp.where(apply_db, user_db, user_vec))
    last_group_vec = jnp.where(apply_ip, lgv_ip,
                               jnp.where(apply_db, lgv_db, last_group_vec))
    history = jnp.where(apply_ip, hist_ip,
                        jnp.where(apply_db, hist_db, history))
    group_sizes = jnp.where(apply_db, sizes_db, group_sizes)
    n_baskets = jnp.where(apply_db, nb_db, n_baskets)
    n_groups = jnp.where(apply_db, ng_db, n_groups)
    err_mult = jnp.where(apply_db, err_db, err_mult)
    return (user_vec, last_group_vec, history, group_sizes, n_baskets,
            n_groups, err_mult)


def _single_update(user_vec, last_group_vec, history, group_sizes, n_baskets,
                   n_groups, err_mult, kind, items, pos, item,
                   params: TifuParams):
    """Dispatch one update (Algorithm 1 generalised to 4 kinds)."""
    state = (user_vec, last_group_vec, history, group_sizes, n_baskets,
             n_groups, err_mult)
    add = _add_basket(*state, items, params)
    # guard delete positions for noop/add rows so gathers stay in-bounds
    safe_pos = jnp.clip(pos, 0, jnp.maximum(n_baskets - 1, 0))
    delb = _delete_basket(*state, safe_pos, params)
    deli = _delete_item(*state, safe_pos, item, params)

    def _sel(a, b, c, d):
        return jnp.where(kind == KIND_ADD_BASKET, b,
                         jnp.where(kind == KIND_DEL_BASKET, c,
                                   jnp.where(kind == KIND_DEL_ITEM, d, a)))

    # suppress deletes on empty histories (no-op)
    empty = n_baskets == 0
    kind = jnp.where(empty & ((kind == KIND_DEL_BASKET)
                              | (kind == KIND_DEL_ITEM)), KIND_NOOP, kind)
    return tuple(_sel(s, a, b, c)
                 for s, a, b, c in zip(state, add, delb, deli))


# ---------------------------------------------------------------------------
# Sparse-delta add path (Eq. 7-9, DESIGN.md §3.3)
# ---------------------------------------------------------------------------

def _capacity_mask(nb, k, tau, max_baskets, max_groups, group_size):
    """Mask adds that would overflow the padded history/group arrays.

    The single source of truth for apply_add_batch's no-op guard and
    the engine's dropped_adds metric.
    """
    new_group = (k == 0) | (tau >= group_size)
    return (nb >= max_baskets) | (new_group & (k >= max_groups))


def _first_occurrence(ids):
    """Pick one representative slot per distinct non-PAD id per row.

    Returns bool[U, W], True on exactly one slot per distinct id
    (set-semantics dedup inside the support window).  Sort-based —
    O(U·W·logW), no [U, W, W] pairwise intermediate; any representative
    slot works because every consumer scatters a value that depends only
    on the id, not the slot.
    """
    u, w = ids.shape
    order = jnp.argsort(ids, axis=1)
    sorted_ids = jnp.take_along_axis(ids, order, axis=1)
    first_sorted = jnp.concatenate(
        [jnp.ones((u, 1), bool),
         sorted_ids[:, 1:] != sorted_ids[:, :-1]], axis=1)
    first = jnp.zeros((u, w), bool).at[
        jnp.arange(u)[:, None], order].set(first_sorted)
    return (ids >= 0) & first


def _apply_add_batch(state: StreamState, batch: AddBatch,
                     params: TifuParams, t_max_cap: int = 0):
    """Apply a homogeneous basket-addition sub-batch with sparse deltas.

    The support of one addition is the new basket plus the last group's
    items (the only vectors Eq. 7-9 touch); everything else is a per-user
    *scalar*: the Eq. 7 rescale ``k·r_g/(k+1)`` and the Eq. 8 rescale
    ``tau·r_b/(tau+1)`` multiply ``uv_scale``/``lgv_scale`` instead of the
    [n_items] rows.  No [batch, n_items] gather or scatter anywhere —
    total state traffic is O(batch · (group_size+1) · max_basket_size).

    INVARIANT (streaming.engine): each user appears at most once among
    valid rows; padding rows carry zero deltas / unit factors and may
    alias any user.
    """
    u = batch.user
    n_bask, bh = state.max_baskets, state.max_basket_size
    kmax = state.max_groups
    m = params.group_size
    f32 = state.user_vecs.dtype

    # --- per-row scalars -----------------------------------------------------
    k = state.n_groups[u]                              # [U]
    nb = state.n_baskets[u]
    s = state.uv_scale[u]
    sig = state.lgv_scale[u]
    em = state.err_mult[u]
    tau = jnp.where(k > 0, state.group_sizes[u, jnp.maximum(k - 1, 0)], 0)
    new_group = (k == 0) | (tau >= m)
    # Capacity guard: a full history row is NOT all-PAD, so the sparse
    # history write below would corrupt it (and group_sizes at k == kmax).
    # Adds to full users are no-ops; the engine sizes N/K so real traffic
    # never hits this (deletions free rows) and surfaces drops via
    # apply_add_batch_counted.
    at_capacity = _capacity_mask(nb, k, tau, n_bask, kmax, m)
    valid = batch.valid & ~at_capacity
    items = jnp.where(valid[:, None], batch.items, PAD_ID)
    kf = jnp.maximum(k, 1).astype(f32)
    tauf = tau.astype(f32)
    r_b = jnp.asarray(params.r_b, f32)
    r_g = jnp.asarray(params.r_g, f32)

    # --- sparse support: last group's history rows + the new basket ----------
    start = nb - tau                                   # [U]
    row_t = jnp.arange(m)[None, :]                     # [1, m]
    rows_valid = (row_t < tau[:, None]) & (k > 0)[:, None] \
        & valid[:, None]
    grp_rows = jnp.clip(start[:, None] + row_t, 0, n_bask - 1)
    old_ids = state.history[u[:, None], grp_rows]      # [U, m, Bh]
    old_ids = jnp.where(rows_valid[:, :, None], old_ids,
                        PAD_ID).reshape(u.shape[0], m * bh)
    ids_all = jnp.concatenate([old_ids, items], axis=1)     # [U, W]
    first = _first_occurrence(ids_all)
    bfirst = _first_occurrence(items)                       # [U, Bb]
    zeros_old = jnp.zeros(old_ids.shape, f32)

    # gather the true last-group values on the support (O(U·W), sparse;
    # PAD ids read 0, which the `first` mask already zeroes downstream)
    lraw = sparse_row_gather(state.last_group_vecs, u, ids_all,
                             t_max_cap=t_max_cap)
    ltrue = lraw * sig[:, None]

    # --- scale updates (the dense part of Eq. 7/8, now scalar) ---------------
    s_ratio = jnp.where(new_group & (k > 0),
                        kf * r_g / (kf + 1.0), 1.0)    # k==0: s unchanged
    s_new = s * s_ratio
    sig_ratio = jnp.where(new_group, 1.0 / sig,        # reset sigma' = 1
                          tauf * r_b / (jnp.maximum(tauf, 1.0) + 1.0))
    sig_ratio = jnp.where(valid, sig_ratio, 1.0)
    sig_new = sig * sig_ratio

    # --- sparse deltas into the raw user rows --------------------------------
    # Scenario 2 (Eq. 8+9): u' = u + (lgv' - lgv)/k with
    # lgv' - lgv = (alpha-1)·lgv + beta·v_b, alpha = tau·r_b/(tau+1).
    alpha = tauf * r_b / (tauf + 1.0)
    beta = 1.0 / (tauf + 1.0)
    l_part = jnp.where(new_group[:, None], 0.0,
                       first * (alpha - 1.0)[:, None] * ltrue
                       / (kf * s)[:, None])
    # Scenario 1 (Eq. 7): u' = (k·r_g·u + v_b)/(k+1); the rescale lives in
    # s_new, the sparse part is v_b/((k+1)·s_new).
    b_coeff = jnp.where(new_group, 1.0 / ((kf * (k > 0) + 1.0) * s_new),
                        beta / (kf * s))
    user_vals = l_part + jnp.concatenate(
        [zeros_old, bfirst * b_coeff[:, None]], axis=1)

    # --- sparse deltas into the raw last-group rows --------------------------
    # Scenario 1 resets lgv to v_b: subtract the old raw values on their
    # support (exact zeroing) and add 1/sig_new at the basket ids.
    # Scenario 2 appends: add v_b/((tau+1)·sig_new) at the basket ids.
    lgv_reset = first * (-lraw) + jnp.concatenate(
        [zeros_old, bfirst / sig_new[:, None]], axis=1)
    lgv_append = jnp.concatenate(
        [zeros_old, bfirst / ((tauf + 1.0) * sig_new)[:, None]], axis=1)
    lgv_vals = jnp.where(new_group[:, None], lgv_reset, lgv_append)

    user_vecs = sparse_row_scatter(state.user_vecs, u, ids_all, user_vals,
                                   t_max_cap=t_max_cap)
    lg_vecs = sparse_row_scatter(state.last_group_vecs, u, ids_all, lgv_vals,
                                 t_max_cap=t_max_cap)

    # --- per-row scalar/bookkeeping scatters (no [batch, N, B] dense delta) --
    valid_i = valid.astype(jnp.int32)
    err_new = jnp.maximum(
        em * jnp.where(k > 0, decay.error_shrink_factor(kf, params.r_g),
                       0.0), 1e-30)
    err_ratio = jnp.where(valid & new_group, err_new / em, 1.0)
    gs_slot = jnp.where(new_group, jnp.minimum(k, kmax - 1),
                        jnp.maximum(k - 1, 0))
    hist_slot = jnp.minimum(nb, n_bask - 1)
    # the target history row is all PAD (-1); adding (item - PAD) writes
    # the basket without a dense [batch, N, B] delta block.
    hist_delta = jnp.where(valid[:, None], items - PAD_ID, 0)

    dropped = jnp.sum((at_capacity & batch.valid).astype(jnp.int32))
    return StreamState(
        n_real=state.n_real,
        user_vecs=user_vecs,
        last_group_vecs=lg_vecs,
        history=state.history.at[u, hist_slot].add(hist_delta),
        group_sizes=state.group_sizes.at[u, gs_slot].add(valid_i),
        n_baskets=state.n_baskets.at[u].add(valid_i),
        n_groups=state.n_groups.at[u].add(valid_i
                                          * new_group.astype(jnp.int32)),
        err_mult=state.err_mult.at[u].multiply(err_ratio),
        uv_scale=state.uv_scale.at[u].multiply(
            jnp.where(valid, s_ratio, 1.0)),
        lgv_scale=state.lgv_scale.at[u].multiply(sig_ratio),
    ), dropped


@functools.partial(jax.jit, static_argnames=("params", "t_max_cap"),
                   donate_argnums=(0,))
def apply_add_batch(state: StreamState, batch: AddBatch,
                    params: TifuParams, t_max_cap: int = 0) -> StreamState:
    """Apply a homogeneous basket-addition sub-batch with sparse deltas.

    Eq. 7–9 under the scaled representation: O(batch · W) state traffic
    (W = (group_size+1) · max_basket_size), never an [n_items]
    temporary — the paper's O(1)-per-add asymptotic on the batched path
    (see ``_apply_add_batch`` for the full derivation; the drop count is
    dead-code-eliminated here).  ``t_max_cap`` (static) is the engine's
    host-measured touched-tile bound, forwarded to the sparse kernels
    (DESIGN.md §3.3); 0 disables.
    """
    return _apply_add_batch(state, batch, params, t_max_cap)[0]


@functools.partial(jax.jit, static_argnames=("params", "t_max_cap"),
                   donate_argnums=(0,))
def apply_add_batch_counted(state: StreamState, batch: AddBatch,
                            params: TifuParams, t_max_cap: int = 0):
    """As ``apply_add_batch`` (Eq. 7–9, O(batch · W)), counting drops.

    Returns ``(state, dropped)`` where ``dropped`` is the number of
    valid rows the capacity guard masked to no-ops (i32 scalar) — one
    fused program, so the engine's dropped_adds metric costs no extra
    dispatch.
    """
    return _apply_add_batch(state, batch, params, t_max_cap)


# ---------------------------------------------------------------------------
# Dense masked decremental sub-batches (their support IS the history)
# ---------------------------------------------------------------------------

def _gather_true(state: StreamState, u):
    """Gather per-user state rows with scales folded in (true values)."""
    s = state.uv_scale[u]
    sig = state.lgv_scale[u]
    return (state.user_vecs[u] * s[:, None],
            state.last_group_vecs[u] * sig[:, None],
            state.history[u], state.group_sizes[u], state.n_baskets[u],
            state.n_groups[u], state.err_mult[u], s, sig)


def _scatter_del_deltas(state: StreamState, u, valid, old, new):
    """Write masked true-value deltas back into the scaled raw storage.

    Raw deltas are divided by the (unchanged) per-user scales; invalid
    rows carry zero deltas, so padding rows may alias any user.  The
    last-group raw row is *set* to new_true/sigma (its support after a
    deletion is recomputed from history, DESIGN.md §3.3 invariant).
    """
    uv, lgv, hist, gs, nb, ng, em, s, sig = old
    n_uv, n_lgv, n_hist, n_gs, n_nb, n_ng, n_em = new
    vf = valid[:, None]
    duv = jnp.where(vf, (n_uv - uv) / s[:, None], 0.0)
    # lgv raw' = new_true/sigma (support re-derived from history)
    dlgv = jnp.where(vf, n_lgv / sig[:, None] - state.last_group_vecs[u],
                     0.0)
    return StreamState(
        n_real=state.n_real,
        user_vecs=state.user_vecs.at[u].add(duv),
        last_group_vecs=state.last_group_vecs.at[u].add(dlgv),
        history=state.history.at[u].add(
            jnp.where(valid[:, None, None], n_hist - hist, 0)),
        group_sizes=state.group_sizes.at[u].add(
            jnp.where(valid[:, None], n_gs - gs, 0)),
        n_baskets=state.n_baskets.at[u].add(jnp.where(valid, n_nb - nb, 0)),
        n_groups=state.n_groups.at[u].add(jnp.where(valid, n_ng - ng, 0)),
        err_mult=state.err_mult.at[u].multiply(
            jnp.where(valid, n_em / em, 1.0)),
        uv_scale=state.uv_scale,
        lgv_scale=state.lgv_scale,
    )


@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def apply_del_basket_batch_dense(state: StreamState, batch: DelBasketBatch,
                                 params: TifuParams) -> StreamState:
    """Apply a homogeneous basket-deletion sub-batch (Eq. 10-12), densely.

    Dense masked per-user rows: gathers [batch, n_items] state rows and
    writes dense deltas — O(batch · n_items) state traffic.  Retained as
    the correctness baseline and the benchmark baseline for the sparse
    path (``apply_del_basket_batch``, DESIGN.md §3.5), which touches
    only the history-window support.
    """
    u = batch.user
    old = _gather_true(state, u)
    uv, lgv, hist, gs, nb, ng, em = old[:7]
    valid = batch.valid & (nb > 0)
    safe_pos = jnp.clip(batch.pos, 0, jnp.maximum(nb - 1, 0))
    new = jax.vmap(
        lambda *a: _delete_basket(*a, params))(uv, lgv, hist, gs, nb, ng,
                                               em, safe_pos)
    return _scatter_del_deltas(state, u, valid, old, new)


@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def apply_del_item_batch_dense(state: StreamState, batch: DelItemBatch,
                               params: TifuParams) -> StreamState:
    """Apply a homogeneous item-deletion sub-batch, densely.

    Eq. 13 + the basket-vanish fallback on dense [batch, n_items] rows —
    O(batch · n_items) state traffic; the correctness/benchmark baseline
    of the sparse path (``apply_del_item_batch``).
    """
    u = batch.user
    old = _gather_true(state, u)
    uv, lgv, hist, gs, nb, ng, em = old[:7]
    valid = batch.valid & (nb > 0)
    safe_pos = jnp.clip(batch.pos, 0, jnp.maximum(nb - 1, 0))
    new = jax.vmap(
        lambda *a: _delete_item(*a, params))(uv, lgv, hist, gs, nb, ng, em,
                                             safe_pos, batch.item)
    return _scatter_del_deltas(state, u, valid, old, new)


# ---------------------------------------------------------------------------
# Sparse decremental sub-batches (Eq. 10-13, DESIGN.md §3.5)
# ---------------------------------------------------------------------------
#
# The paper's decremental cost is linear in the surviving history, and the
# history's item support is at most N·B ids — orders of magnitude below
# n_items at production vocabularies.  These paths expand the Eq. 10-13
# suffix contractions into per-history-slot coefficients and apply them
# through the sparse row gather/scatter kernel pair, so (like the add
# path) no [batch, n_items] temporary ever materializes.  The Eq. 12
# whole-vector rescale k/((k-1)·r_g) folds into ``uv_scale`` — the scales
# can now also GROW; the engine renormalizes outside [SCALE_FLOOR·1e2,
# SCALE_CEIL] (see streaming.engine._maintain).


def _slots(c_row, bh):
    """Expand per-history-row coefficients to per-slot values.

    [U, N] → [U, N·B]: each valid id in row t carries weight c_row[t].
    """
    u, n = c_row.shape
    return jnp.broadcast_to(c_row[:, :, None], (u, n, bh)).reshape(u, -1)


def _del_basket_sparse_core(state: StreamState, u, hist, gs, nb, k, s, sig,
                            em, pos, valid, params: TifuParams,
                            t_max_cap: int = 0):
    """Shared sparse basket-deletion math (Eq. 10-12 on the support).

    Rows with ``valid`` False produce all-PAD support ids, zero scatter
    values and unit ratios, so padding rows may alias any user.  Returns
    ``(ids, u_vals, l_vals, s_ratio, em_ratio, new_hist, new_gs, d_nb,
    d_ng)`` — the caller assembles the StreamState (the item-deletion
    path merges these with its in-place Eq. 13 branch first).
    """
    f32 = state.user_vecs.dtype
    n_rows = u.shape[0]
    n_bask, bh = hist.shape[1], hist.shape[2]
    kmax = gs.shape[1]
    rb = jnp.asarray(params.r_b, f32)
    rg = jnp.asarray(params.r_g, f32)

    g, p, tau = jax.vmap(
        lambda sizes: _row_group_geometry(sizes, n_bask))(gs)   # [U, N]
    j, i = jax.vmap(_locate)(gs, pos)                           # [U]
    tau_j = jnp.take_along_axis(gs, j[:, None], axis=1)[:, 0]

    t = jnp.arange(n_bask)[None, :]
    valid_row = (t < nb[:, None]) & valid[:, None]
    in_gj = valid_row & (g == j[:, None])

    single = tau_j == 1
    last_g = k <= 1
    s1 = valid & ~single                  # Eq. 10+11: group j shrinks
    s2 = valid & single & ~last_g         # Eq. 12: group j vanishes
    s3 = valid & single & last_g          # last basket: state empties

    kf = jnp.maximum(k, 1).astype(f32)
    safe_k = jnp.maximum(k, 2).astype(f32)
    tjf = tau_j.astype(f32)
    safe_tau = jnp.maximum(tau_j, 2).astype(f32)
    tau_f = jnp.maximum(tau, 1).astype(f32)

    # --- support: the user's masked history window -------------------------
    ids = jnp.where(valid_row[:, :, None], hist,
                    PAD_ID).reshape(n_rows, n_bask * bh)
    first = _first_occurrence(ids).astype(f32)
    uraw = sparse_row_gather(state.user_vecs, u, ids, t_max_cap=t_max_cap)
    lraw = sparse_row_gather(state.last_group_vecs, u, ids,
                             t_max_cap=t_max_cap)

    # --- scenario 1: per-slot expansion of r_g^(k-1-j)·(v'_gj - v_gj)/k ----
    pow_tp = rb ** jnp.where(in_gj, tau_j[:, None] - p, 0)
    w_gj = jnp.where(in_gj, pow_tp / tau_f, 0.0)           # v_gj slots
    sc = jnp.where(p == i[:, None], -pow_tp, pow_tp * (rb - 1.0))
    sc = jnp.where(in_gj & (p >= i[:, None]), sc, 0.0)     # Eq. 10 suffix
    dvg = ((tjf - (tjf - 1.0) * rb)[:, None] * w_gj + sc) \
        / ((safe_tau - 1.0) * rb)[:, None]                 # (v'_gj - v_gj)
    cu1 = (rg ** jnp.maximum(k - 1 - j, 0) / kf)[:, None] * dvg

    # --- scenario 2: suffix over groups j..k-1; the k/((k-1)·r_g) rescale --
    # folds into uv_scale, leaving only the sparse suffix_u/(k·s) delta.
    cg = jax.vmap(lambda kk, jj: decay.batched_suffix_coefficients(
        kk, jj, params.r_g, kmax))(k, j + 1).astype(f32)   # [U, K]
    cu2 = jnp.where(valid_row,
                    jnp.take_along_axis(cg, g, axis=1)
                    * rb ** jnp.where(valid_row, tau - p, 0) / tau_f, 0.0)
    s_ratio = jnp.where(s2, kf / ((safe_k - 1.0) * rg), 1.0)

    # --- user-vector scatter values (raw storage) --------------------------
    u_vals = jnp.where(s1[:, None], _slots(cu1, bh) / s[:, None],
                       jnp.where(s2[:, None],
                                 _slots(cu2, bh) / (kf * s)[:, None],
                                 jnp.where(s3[:, None], -uraw * first, 0.0)))

    # --- last-group row: reset to the new true value on the support --------
    lgv_new_1 = s1 & (j == k - 1)         # last group shrank
    lgv_new_2 = s2 & (j == k - 1)         # last group removed → old k-2
    lgv_change = lgv_new_1 | lgv_new_2 | s3
    cl1 = w_gj + dvg                      # v'_gj slots
    cl2 = jnp.where(valid_row & (g == (k - 2)[:, None]),
                    rb ** jnp.where(valid_row, tau - p, 0) / tau_f, 0.0)
    cl = jnp.where(lgv_new_1[:, None], cl1,
                   jnp.where(lgv_new_2[:, None], cl2, 0.0))
    l_vals = jnp.where(lgv_change[:, None],
                       -lraw * first + _slots(cl, bh) / sig[:, None], 0.0)

    # --- history compaction + group-size bookkeeping (O(N·B), not O(I)) ----
    src = jnp.where(t >= pos[:, None], jnp.minimum(t + 1, n_bask - 1), t)
    new_hist = jnp.take_along_axis(hist, src[:, :, None], axis=1)
    new_hist = new_hist.at[jnp.arange(n_rows),
                           jnp.maximum(nb - 1, 0)].set(PAD_ID)
    gs_s1 = gs.at[jnp.arange(n_rows), j].add(-1)
    gs_s2 = jax.vmap(_remove_entry)(gs, j)
    new_gs = jnp.where(single[:, None],
                       jnp.where(last_g[:, None], jnp.zeros_like(gs), gs_s2),
                       gs_s1)

    em_ratio = jnp.where(s2, decay.error_growth_factor(safe_k, params.r_g),
                         1.0)
    em_ratio = jnp.where(s3, 1.0 / em, em_ratio)
    d_nb = jnp.where(valid, -1, 0)
    d_ng = jnp.where(valid & single, -1, 0)
    return (ids, u_vals, l_vals, s_ratio, em_ratio, new_hist, new_gs,
            d_nb, d_ng)


@functools.partial(jax.jit, static_argnames=("params", "t_max_cap"),
                   donate_argnums=(0,))
def apply_del_basket_batch(state: StreamState, batch: DelBasketBatch,
                           params: TifuParams,
                           t_max_cap: int = 0) -> StreamState:
    """Apply a homogeneous basket-deletion sub-batch with sparse deltas.

    Implements Eq. 10–12 (suffix contractions expanded to per-history-
    slot coefficients, DESIGN.md §3.5).  State traffic is O(batch · N·B)
    — the deleted user's history window — instead of the dense path's
    O(batch · n_items).  Semantics match ``apply_del_basket_batch_dense``
    and the RefEngine to ~1e-4 (tests/test_update_partition.py).
    ``t_max_cap`` as in :func:`apply_add_batch`.
    """
    u = batch.user
    hist = state.history[u]
    gs = state.group_sizes[u]
    nb = state.n_baskets[u]
    k = state.n_groups[u]
    s = state.uv_scale[u]
    sig = state.lgv_scale[u]
    em = state.err_mult[u]
    valid = batch.valid & (nb > 0)
    pos = jnp.clip(batch.pos, 0, jnp.maximum(nb - 1, 0))
    (ids, u_vals, l_vals, s_ratio, em_ratio, new_hist, new_gs, d_nb,
     d_ng) = _del_basket_sparse_core(state, u, hist, gs, nb, k, s, sig, em,
                                     pos, valid, params, t_max_cap)
    vf = valid[:, None]
    return StreamState(
        n_real=state.n_real,
        user_vecs=sparse_row_scatter(state.user_vecs, u, ids, u_vals,
                                     t_max_cap=t_max_cap),
        last_group_vecs=sparse_row_scatter(state.last_group_vecs, u, ids,
                                           l_vals, t_max_cap=t_max_cap),
        history=state.history.at[u].add(
            jnp.where(valid[:, None, None], new_hist - hist, 0)),
        group_sizes=state.group_sizes.at[u].add(
            jnp.where(vf, new_gs - gs, 0)),
        n_baskets=state.n_baskets.at[u].add(d_nb),
        n_groups=state.n_groups.at[u].add(d_ng),
        err_mult=state.err_mult.at[u].multiply(
            jnp.where(valid, em_ratio, 1.0)),
        uv_scale=state.uv_scale.at[u].multiply(
            jnp.where(valid, s_ratio, 1.0)),
        lgv_scale=state.lgv_scale,
    )


@functools.partial(jax.jit, static_argnames=("params", "t_max_cap"),
                   donate_argnums=(0,))
def apply_del_item_batch(state: StreamState, batch: DelItemBatch,
                         params: TifuParams,
                         t_max_cap: int = 0) -> StreamState:
    """Apply a homogeneous item-deletion sub-batch with sparse deltas.

    The Eq. 13 in-place branch touches a single (user, item) cell of each
    vector table — O(1) per event; the basket-vanish fallback reuses the
    sparse Eq. 10–12 core on the history window, O(N·B) per event.  One
    fused program serves both branches (the support is the window plus
    one appended item slot).  ``t_max_cap`` as in :func:`apply_add_batch`
    (the hint covers the appended item slot too).
    """
    u = batch.user
    hist = state.history[u]
    gs = state.group_sizes[u]
    nb = state.n_baskets[u]
    k = state.n_groups[u]
    s = state.uv_scale[u]
    sig = state.lgv_scale[u]
    em = state.err_mult[u]
    f32 = state.user_vecs.dtype
    n_rows = u.shape[0]
    valid = batch.valid & (nb > 0)
    pos = jnp.clip(batch.pos, 0, jnp.maximum(nb - 1, 0))

    row = hist[jnp.arange(n_rows), pos]                       # [U, B]
    present = valid & jnp.any(row == batch.item[:, None], axis=1)
    blen = jnp.sum(row >= 0, axis=1)
    apply_db = present & (blen == 1)                          # basket vanishes
    apply_ip = present & (blen > 1)                           # Eq. 13 in place

    (ids_db, u_db, l_db, s_ratio, em_ratio, hist_db, gs_db, d_nb,
     d_ng) = _del_basket_sparse_core(state, u, hist, gs, nb, k, s, sig, em,
                                     pos, apply_db, params, t_max_cap)

    # --- Eq. 13 in place: one cell per table -------------------------------
    j, i = jax.vmap(_locate)(gs, pos)
    tau_j = jnp.maximum(jnp.take_along_axis(gs, j[:, None], axis=1)[:, 0], 1)
    rb = jnp.asarray(params.r_b, f32)
    rg = jnp.asarray(params.r_g, f32)
    kf = jnp.maximum(k, 1).astype(f32)
    dg = -(rb ** jnp.maximum(tau_j - i, 0)) / tau_j.astype(f32)
    du_ip = jnp.where(apply_ip,
                      rg ** jnp.maximum(k - 1 - j, 0) * dg / (kf * s), 0.0)
    dl_ip = jnp.where(apply_ip & (j == k - 1), dg / sig, 0.0)

    ids = jnp.concatenate(
        [ids_db, jnp.where(apply_ip, batch.item, PAD_ID)[:, None]], axis=1)
    u_vals = jnp.concatenate([u_db, du_ip[:, None]], axis=1)
    l_vals = jnp.concatenate([l_db, dl_ip[:, None]], axis=1)

    # --- history/bookkeeping: in-place row edit vs fallback compaction -----
    row_ip = jnp.where(row == batch.item[:, None], PAD_ID, row)
    hist_ip = hist.at[jnp.arange(n_rows), pos].set(row_ip)
    new_hist = jnp.where(apply_db[:, None, None], hist_db,
                         jnp.where(apply_ip[:, None, None], hist_ip, hist))
    new_gs = jnp.where(apply_db[:, None], gs_db, gs)
    touched = apply_db | apply_ip
    return StreamState(
        n_real=state.n_real,
        user_vecs=sparse_row_scatter(state.user_vecs, u, ids, u_vals,
                                     t_max_cap=t_max_cap),
        last_group_vecs=sparse_row_scatter(state.last_group_vecs, u, ids,
                                           l_vals, t_max_cap=t_max_cap),
        history=state.history.at[u].add(
            jnp.where(touched[:, None, None], new_hist - hist, 0)),
        group_sizes=state.group_sizes.at[u].add(
            jnp.where(apply_db[:, None], new_gs - gs, 0)),
        n_baskets=state.n_baskets.at[u].add(d_nb),
        n_groups=state.n_groups.at[u].add(d_ng),
        err_mult=state.err_mult.at[u].multiply(
            jnp.where(apply_db, em_ratio, 1.0)),
        uv_scale=state.uv_scale.at[u].multiply(
            jnp.where(apply_db, s_ratio, 1.0)),
        lgv_scale=state.lgv_scale,
    )


# ---------------------------------------------------------------------------
# Mixed-batch entry points
# ---------------------------------------------------------------------------

def apply_update_batch(state: StreamState, batch: UpdateBatch,
                       params: TifuParams) -> StreamState:
    """Apply a mixed micro-batch through the partitioned pipeline.

    Compat shim: host-partitions the batch into homogeneous kind
    sub-batches, so each event pays its own kind's cost (adds
    O(batch·W), deletions O(batch·N·B) — Eq. 7–13 via the sparse
    appliers above).  INVARIANT (enforced by streaming.engine): within
    one batch each user appears at most once among non-noop rows; the
    sub-batches therefore touch disjoint users and can be applied in
    any order.  Requires concrete (non-traced) ``batch.kind``;
    fully-traced callers should build homogeneous sub-batches
    themselves (see configs/tifu_knn.py).
    """
    kind = np.asarray(jax.device_get(batch.kind))
    add_rows = np.nonzero(kind == KIND_ADD_BASKET)[0]
    delb_rows = np.nonzero(kind == KIND_DEL_BASKET)[0]
    deli_rows = np.nonzero(kind == KIND_DEL_ITEM)[0]
    cap = int(kind.shape[0])
    user = np.asarray(jax.device_get(batch.user))
    if add_rows.size:
        items = np.asarray(jax.device_get(batch.basket_items))
        state = apply_add_batch(
            state, AddBatch.build(user[add_rows], items[add_rows],
                                  items.shape[1], pad_cap=cap), params)
    if delb_rows.size:
        pos = np.asarray(jax.device_get(batch.basket_pos))
        state = apply_del_basket_batch(
            state, DelBasketBatch.build(user[delb_rows], pos[delb_rows],
                                        pad_cap=cap), params)
    if deli_rows.size:
        pos = np.asarray(jax.device_get(batch.basket_pos))
        item = np.asarray(jax.device_get(batch.item))
        state = apply_del_item_batch(
            state, DelItemBatch.build(user[deli_rows], pos[deli_rows],
                                      item[deli_rows], pad_cap=cap), params)
    return state


@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def apply_update_batch_dense(state: StreamState, batch: UpdateBatch,
                             params: TifuParams) -> StreamState:
    """Apply a mixed micro-batch via the seed's dense path.

    Gathers [batch, n_items] rows, computes ALL update rules (Eq. 7–13)
    per row, selects one, scatters dense deltas — ~4x redundant compute
    and O(batch · n_items) traffic regardless of kind mix.  Retained as
    the benchmark baseline (bench_update_batch.py measures the
    partitioned pipeline against it) and as a second oracle.
    """
    u = batch.user
    *gathered, s, sig = _gather_true(state, u)
    gathered = tuple(gathered)
    updated = jax.vmap(
        lambda uv, lgv, h, gs, nb, ng, em, kind, items, pos, item:
        _single_update(uv, lgv, h, gs, nb, ng, em, kind, items, pos, item,
                       params))(
        *gathered, batch.kind, batch.basket_items, batch.basket_pos,
        batch.item)
    deltas = tuple(new - old for new, old in zip(updated, gathered))
    return StreamState(
        n_real=state.n_real,
        user_vecs=state.user_vecs.at[u].add(deltas[0] / s[:, None]),
        last_group_vecs=state.last_group_vecs.at[u].add(
            deltas[1] / sig[:, None]),
        history=state.history.at[u].add(deltas[2]),
        group_sizes=state.group_sizes.at[u].add(deltas[3]),
        n_baskets=state.n_baskets.at[u].add(deltas[4]),
        n_groups=state.n_groups.at[u].add(deltas[5]),
        err_mult=state.err_mult.at[u].add(deltas[6]),
        uv_scale=state.uv_scale,
        lgv_scale=state.lgv_scale,
    )


# ---------------------------------------------------------------------------
# Maintenance passes
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def refresh_users(state: StreamState, users, params: TifuParams) -> StreamState:
    """Recompute selected users from scratch (stability tracker).

    The exact Eq. 1+2 closed-form rebuild on the padded history —
    O(|users| · (N·B + n_items)) — resetting the per-user error
    trackers and scales to 1 (the fresh rows are true values).
    """
    h = state.history[users]
    gs = state.group_sizes[users]
    ng = state.n_groups[users]
    # rebuild at the leaves' padded width: pad columns come out zero
    params = dataclasses.replace(params, n_items=state.n_cols)
    fresh = jax.vmap(lambda hh, gg, nn: user_vector_padded(hh, gg, nn, params))(
        h, gs, ng).astype(state.user_vecs.dtype)
    lgv = jax.vmap(lambda hh, gg, nn: last_group_vector_padded(
        hh, gg, nn, params))(h, gs, ng).astype(state.user_vecs.dtype)
    return StreamState(
        n_real=state.n_real,
        user_vecs=state.user_vecs.at[users].set(fresh),
        last_group_vecs=state.last_group_vecs.at[users].set(lgv),
        history=state.history,
        group_sizes=state.group_sizes,
        n_baskets=state.n_baskets,
        n_groups=state.n_groups,
        err_mult=state.err_mult.at[users].set(1.0),
        uv_scale=state.uv_scale.at[users].set(1.0),
        lgv_scale=state.lgv_scale.at[users].set(1.0),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def renormalize_users(state: StreamState, users) -> StreamState:
    """Fold the per-user scales back into the raw rows (scale -> 1).

    Dense per selected user — O(|users| · n_items) — but
    value-preserving and rare: the engine triggers it only when a scale
    approaches SCALE_FLOOR/SCALE_CEIL (hundreds of group openings or
    Eq. 12 deletions per user between triggers).
    """
    s = state.uv_scale[users]
    sig = state.lgv_scale[users]
    return StreamState(
        n_real=state.n_real,
        user_vecs=state.user_vecs.at[users].multiply(s[:, None]),
        last_group_vecs=state.last_group_vecs.at[users].multiply(
            sig[:, None]),
        history=state.history,
        group_sizes=state.group_sizes,
        n_baskets=state.n_baskets,
        n_groups=state.n_groups,
        err_mult=state.err_mult,
        uv_scale=state.uv_scale.at[users].set(1.0),
        lgv_scale=state.lgv_scale.at[users].set(1.0),
    )
