"""Tile-planned sparse kernels (kernels.tile_plan + the scatter/gather
pair) vs the ref.py oracles — interpret-mode equivalence sweep plus the
plan-level DMA accounting that pins O(U·W) TPU traffic (ISSUE 3):
dirty-tile DMAs per row must equal the touched-tile count, never I/bi."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tile_plan
from repro.kernels.sparse_row_gather import sparse_row_gather
from repro.kernels.sparse_row_scatter import sparse_row_scatter


def _touched(rows, ids, bi):
    """{(row, tile)} pairs a batch genuinely dirties (numpy oracle)."""
    rows, ids = np.asarray(rows), np.asarray(ids)
    return {(int(r), int(i) // bi)
            for r, row_ids in zip(rows, ids) for i in row_ids if i >= 0}


def _make(rng, m, items, u, w, mode):
    """(table, rows, ids, vals) with ids clustered / spread / mixed."""
    table = jnp.asarray(rng.normal(size=(m, items)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, m, u), jnp.int32)
    if mode == "clustered":          # every row's ids inside ONE tile
        base = rng.integers(0, items // 128, u) * 128
        ids = base[:, None] + rng.integers(0, min(128, items), (u, w))
        ids = np.minimum(ids, items - 1)
    elif mode == "spread":           # ids across many tiles
        ids = rng.choice(items, size=(u, w), replace=False) \
            if items >= u * w else rng.integers(0, items, (u, w))
    else:                            # mixed + PADs
        ids = rng.integers(-1, items, (u, w))
    ids = jnp.asarray(ids, jnp.int32)
    vals = jnp.asarray(rng.normal(size=(u, w)), jnp.float32)
    return table, rows, ids, vals


@pytest.mark.parametrize("m,items,u,w,bi", [
    (32, 1024, 8, 16, 128),
    (64, 2048, 16, 24, 512),
    (16, 640, 8, 8, 128),            # non-pow2 items
])
@pytest.mark.parametrize("mode", ["clustered", "spread", "mixed"])
def test_tile_planned_pair_matches_ref(rng, m, items, u, w, bi, mode):
    table, rows, ids, vals = _make(rng, m, items, u, w, mode)
    out = sparse_row_scatter(table, rows, ids, vals, bi=bi, interpret=True)
    exp = ref.sparse_row_scatter_ref(table, rows, ids, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
    got = sparse_row_gather(table, rows, ids, bi=bi, interpret=True)
    # reads are exact: every output element is a single table read
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.sparse_row_gather_ref(table, rows,
                                                              ids)))


def test_scatter_unique_support_is_bitwise_exact(rng):
    """With a duplicate-free support each cell receives exactly one add:
    the tile-planned kernel must be bit-for-bit equal to the oracle."""
    m, items, u, w, bi = 16, 1024, 6, 12, 128
    table = jnp.asarray(rng.normal(size=(m, items)), jnp.float32)
    rows = jnp.asarray(rng.permutation(m)[:u], jnp.int32)   # distinct rows
    ids = jnp.asarray(rng.choice(items, size=(u, w), replace=False),
                      jnp.int32)
    vals = jnp.asarray(rng.normal(size=(u, w)), jnp.float32)
    out = sparse_row_scatter(table, rows, ids, vals, bi=bi, interpret=True)
    exp = ref.sparse_row_scatter_ref(table, rows, ids, vals)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_duplicate_rows_with_differing_supports(rng):
    """THE case the (row, tile) work-item sort exists for: duplicate
    target rows whose ids touch different tile sets.  A rectangular
    per-row plan would revisit a block non-consecutively (undefined);
    the sorted plan accumulates every visit in one run."""
    m, items, bi = 8, 1024, 128
    table = jnp.asarray(rng.normal(size=(m, items)), jnp.float32)
    rows = jnp.asarray([5, 5, 5, 2], jnp.int32)
    ids = jnp.asarray([[0, 1, 2, 900],        # tiles {0, 7}
                       [130, 131, -1, 901],   # tiles {1, 7}
                       [0, 300, 640, -1],     # tiles {0, 2, 5}
                       [5, 6, 7, 8]], jnp.int32)
    vals = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    out = sparse_row_scatter(table, rows, ids, vals, bi=bi, interpret=True)
    exp = ref.sparse_row_scatter_ref(table, rows, ids, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)
    got = sparse_row_gather(table, rows, ids, bi=bi, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.sparse_row_gather_ref(table, rows, ids)))


def test_all_pad_rows_and_all_pad_batch(rng):
    m, items, bi = 8, 512, 128
    table = jnp.asarray(rng.normal(size=(m, items)), jnp.float32)
    # some rows all-PAD, some real
    rows = jnp.asarray([1, 3, 6], jnp.int32)
    ids = jnp.asarray([[4, 200, -1, -1],
                       [-1, -1, -1, -1],
                       [500, -1, 3, -1]], jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    out = sparse_row_scatter(table, rows, ids, vals, bi=bi, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.sparse_row_scatter_ref(table, rows, ids, vals)),
        atol=1e-5)
    got = sparse_row_gather(table, rows, ids, bi=bi, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.sparse_row_gather_ref(table, rows, ids)))
    # an ENTIRELY pad batch is an identity scatter / zero gather
    ids0 = jnp.full((3, 4), -1, jnp.int32)
    out0 = sparse_row_scatter(table, rows, ids0, vals, bi=bi,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(table))
    got0 = sparse_row_gather(table, rows, ids0, bi=bi, interpret=True)
    np.testing.assert_array_equal(np.asarray(got0), np.zeros((3, 4)))


# ---------------------------------------------------------------------------
# Plan-level properties: DMA accounting + the replay oracles
# ---------------------------------------------------------------------------

def test_plan_dma_tiles_equal_touched_not_all_tiles(rng):
    """Acceptance: DMA'd table tiles == touched tiles, NOT U · I/bi.
    (Padding steps clone the previous block, which the pipeline does not
    re-fetch, so block-index changes count the real DMAs.)"""
    m, items, u, w, bi = 64, 4096, 12, 16, 128     # 32 tiles/row dense
    table_tiles = items // bi
    rows = np.concatenate([rng.integers(0, m, u - 2), [7, 7]])  # dup rows
    ids = rng.integers(-1, items, (u, w))
    plan = tile_plan.build_plan(jnp.asarray(rows, jnp.int32),
                                jnp.asarray(ids, jnp.int32),
                                bi=bi, t_max=min(w, table_tiles),
                                order="target")
    touched = _touched(rows, ids, bi)
    assert tile_plan.plan_dma_tiles(plan) == len(touched)
    assert len(touched) < u * table_tiles          # clean tiles skipped
    # gather plan: per-batch-row touched tiles (duplicates read twice);
    # consecutive rows can share a boundary tile, hence <=
    plan_g = tile_plan.build_plan(jnp.asarray(rows, jnp.int32),
                                  jnp.asarray(ids, jnp.int32),
                                  bi=bi, t_max=min(w, table_tiles),
                                  order="batch")
    per_row = sum(len({int(i) // bi for i in row if i >= 0})
                  for row in ids)
    assert 0 < tile_plan.plan_dma_tiles(plan_g) <= per_row
    assert per_row < u * table_tiles


def test_clustered_batch_dmas_one_tile_per_row(rng):
    """Ids clustered in one tile: exactly one DMA per distinct row for
    the scatter plan — the flat latency-vs-vocabulary regime."""
    m, items, u, w, bi = 32, 8192, 8, 16, 512
    rows = rng.permutation(m)[:u]
    ids = 1024 + rng.integers(0, 512, (u, w))      # all inside tile 2
    plan = tile_plan.build_plan(jnp.asarray(rows, jnp.int32),
                                jnp.asarray(ids, jnp.int32),
                                bi=bi, t_max=4, order="target")
    assert tile_plan.plan_dma_tiles(plan) == u     # == touched, != I/bi
    assert items // bi == 16


def test_replay_oracles_match_refs(rng):
    """The ref.py plan-consistency oracle (which asserts the
    consecutive-revisit contract while replaying) reproduces the plain
    oracles on both plan orders."""
    m, items, u, w, bi = 16, 1024, 10, 12, 128
    t_max = min(w, items // bi)
    table = jnp.asarray(rng.normal(size=(m, items)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, m, u), jnp.int32)   # dups likely
    ids = jnp.asarray(rng.integers(-1, items, (u, w)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(u, w)), jnp.float32)

    order = jnp.argsort(rows, stable=True)
    rows_s, ids_s = rows[order], ids[order]
    vals_s = jnp.where(ids_s >= 0, vals[order], 0.0)
    plan = tile_plan.build_plan(rows_s, ids_s, bi=bi, t_max=t_max,
                                order="target")
    got = ref.replay_scatter_plan_ref(table, ids_s, vals_s, plan, bi)
    np.testing.assert_allclose(
        got, np.asarray(ref.sparse_row_scatter_ref(table, rows, ids,
                                                   vals)), atol=1e-5)

    plan_g = tile_plan.build_plan(rows, ids, bi=bi, t_max=t_max,
                                  order="batch")
    got_g = ref.replay_gather_plan_ref(table, ids, plan_g, bi)
    np.testing.assert_array_equal(
        got_g, np.asarray(ref.sparse_row_gather_ref(table, rows, ids)))


def test_ops_dispatch_fallback_and_t_max_selection(rng):
    """n_items % 128 != 0 has no tiling: the kernel path refuses it
    (no silent switch to the reference) while the reference serves it;
    concrete batches get a measured (pow2) T_max below the static
    bound."""
    table = jnp.asarray(rng.normal(size=(8, 130)), jnp.float32)  # 130 % 128
    rows = jnp.asarray([1, 2], jnp.int32)
    ids = jnp.asarray(rng.integers(-1, 130, (2, 6)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(2, 6)), jnp.float32)
    with pytest.raises(ValueError, match="divisible by 128"):
        ops._plan_dims(130, ids)
    with pytest.raises(ValueError, match="divisible by 128"):
        ops.sparse_row_scatter(table, rows, ids, vals, impl="interpret")
    with pytest.raises(ValueError, match="divisible by 128"):
        ops.sparse_row_gather(table, rows, ids, impl="interpret")
    np.testing.assert_allclose(
        np.asarray(ops.sparse_row_scatter(table, rows, ids, vals,
                                          impl="ref")),
        np.asarray(ref.sparse_row_scatter_ref(table, rows, ids, vals)),
        atol=1e-5)

    # concrete ids -> measured t_max; tracers -> static worst case
    ids_c = jnp.asarray([[0, 1, 2, 3], [128, 129, 130, 131]], jnp.int32)
    bi, t_max = ops._plan_dims(1024, ids_c)
    assert bi == 512 and t_max == 1                # one tile per row
    ids_sp = jnp.asarray([[0, 600, 1023, -1]], jnp.int32)
    assert ops._plan_dims(1024, ids_sp) == (512, 2)
    traced = jax.eval_shape(lambda i: jnp.asarray(
        ops._plan_dims(1024, i)[1]), ids_c)
    assert traced.shape == ()                      # static bound path runs


def test_engine_mixed_stream_tile_planned_matches_ref_and_xla():
    """>= 500 interleaved add/delete events through the FULL engine with
    every sparse kernel routed through the tile-planned Pallas pair in
    interpret mode (ops.default_impl): the final state must match both
    the XLA-reference arm of the same stream and the paper-faithful
    RefEngine (ISSUE 3 acceptance)."""
    from repro.core import RefEngine, TifuParams
    from repro.streaming import Event, StateStore, StoreConfig, \
        StreamingEngine
    from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                                  KIND_DEL_ITEM)

    p = TifuParams(n_items=640, group_size=3, r_b=0.9, r_g=0.7)  # 5 tiles
    m, n, b, k = 16, 12, 4, 12
    rng = np.random.default_rng(3)
    ref_eng = RefEngine(p, dtype=np.float32)
    events = []
    for _ in range(520):
        u = int(rng.integers(0, m))
        st = ref_eng.state(u)
        nb = st.n_baskets
        if nb == 0 or (rng.random() < 0.6 and nb < n - 2):
            items = rng.choice(p.n_items, size=int(rng.integers(1, b)),
                               replace=False).astype(np.int32)
            ref_eng.add_basket(u, items)
            events.append(Event(KIND_ADD_BASKET, u, items=items))
        elif rng.random() < 0.5:
            pos = int(rng.integers(0, nb))
            ref_eng.delete_basket(u, pos)
            events.append(Event(KIND_DEL_BASKET, u, pos=pos))
        else:
            pos = int(rng.integers(0, nb))
            item = int(rng.choice(ref_eng.state(u).history[pos]))
            ref_eng.delete_item(u, pos, item)
            events.append(Event(KIND_DEL_ITEM, u, pos=pos, item=item))

    def run(impl):
        with ops.default_impl(impl):
            store = StateStore(StoreConfig(n_users=m, n_items=p.n_items,
                                           max_baskets=n, max_basket_size=b,
                                           max_groups=k))
            eng = StreamingEngine(store, p, batch_size=16)
            eng.submit(list(events))
            assert eng.run_until_drained() == len(events)
            return np.asarray(store.state.materialized_user_vecs())

    xla = run("ref")                      # the XLA reference arm
    planned = run("interpret")            # tile-planned Pallas pair
    np.testing.assert_allclose(planned, xla, rtol=1e-4, atol=1e-5)
    for u in range(m):
        np.testing.assert_allclose(
            planned[u], ref_eng.state(u).user_vec.astype(np.float32),
            rtol=1e-4, atol=1e-5, err_msg=f"u={u}")


def test_ops_interpret_matches_ref_end_to_end(rng):
    """ops-level dispatch: impl='interpret' (tile-planned Pallas) equals
    impl='ref' for a divisible vocabulary."""
    table = jnp.asarray(rng.normal(size=(16, 1024)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, 16, 6), jnp.int32)
    ids = jnp.asarray(rng.integers(-1, 1024, (6, 10)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.sparse_row_scatter(table, rows, ids, vals,
                                          impl="interpret")),
        np.asarray(ops.sparse_row_scatter(table, rows, ids, vals,
                                          impl="ref")), atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(ops.sparse_row_gather(table, rows, ids,
                                         impl="interpret")),
        np.asarray(ops.sparse_row_gather(table, rows, ids, impl="ref")))
