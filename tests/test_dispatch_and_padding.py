"""Kernel dispatch without silent fallbacks, and the lane-padded store.

``kernels.ops`` runs the compiled Pallas kernel only on a TPU, the
interpreter only when asked for by name, and refuses a width the
tile-planned kernels cannot tile instead of switching to the reference.
The state store pads its item axis to the 128-lane tile: pad columns are
never written, never recommended, and never reach a checkpoint.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import RefEngine, TifuParams
from repro.core.types import KIND_ADD_BASKET, KIND_DEL_BASKET, KIND_DEL_ITEM
from repro.kernels import ops
from repro.streaming import Event, StateStore, StoreConfig, StreamingEngine

P = TifuParams(n_items=41, group_size=3, r_b=0.9, r_g=0.7, k_neighbors=4)
M, N, B = 8, 12, 5

_T = jnp.zeros((8, 256), jnp.float32)
_R = jnp.zeros((2,), jnp.int32)
_I = jnp.zeros((2, 4), jnp.int32)

ENTRY_POINTS = {
    "knn_topk": lambda impl: ops.knn_topk(_T, _T, 3, impl=impl),
    "knn_topk_dtiled": lambda impl: ops.knn_topk_dtiled(_T, _T, 3,
                                                        impl=impl),
    "fused_recommend": lambda impl: ops.fused_recommend(_T, _R, 3, 0.7, 2,
                                                        impl=impl),
    "shard_topk": lambda impl: ops.shard_topk(_T, _T, 3, 0, 1, impl=impl),
    "blend_topn_rows": lambda impl: ops.blend_topn_rows(
        _T[:2], jnp.zeros((2, 3, 256)), 0.7, 2, impl=impl),
    "sparse_row_scatter": lambda impl: ops.sparse_row_scatter(
        _T, _R, _I, jnp.zeros((2, 4)), impl=impl),
    "sparse_row_gather": lambda impl: ops.sparse_row_gather(_T, _R, _I,
                                                            impl=impl),
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_pallas_impl_refuses_a_host_without_tpu(entry):
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        ENTRY_POINTS[entry]("pallas")


def test_interpret_mode_only_by_name():
    assert ops.resolve_impl("auto") == "ref"        # this host has no TPU
    assert ops.resolve_impl(None) == "ref"
    assert ops.resolve_impl("interpret") == "interpret"
    assert ops.resolve_impl("ref") == "ref"
    with ops.default_impl("interpret"):
        assert ops.resolve_impl(None) == "interpret"
    with pytest.raises(ValueError, match="unknown impl"):
        ops.resolve_impl("mosaic")


def test_untileable_width_raises_on_tpu(monkeypatch, rng):
    """On a TPU an item width with no 128-multiple tile is an error, not
    a switch to the XLA reference (the store always pads)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.resolve_impl(None) == "pallas"
    table = jnp.asarray(rng.normal(size=(8, 130)), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, 130, (2, 6)), jnp.int32)
    with pytest.raises(ValueError, match="divisible by 128"):
        ops.sparse_row_scatter(table, _R, ids, jnp.zeros((2, 6)))
    with pytest.raises(ValueError, match="divisible by 128"):
        ops.sparse_row_gather(table, _R, ids)


def _mixed_events(rng, ref):
    events = []
    for _ in range(120):
        u = int(rng.integers(0, M))
        nb = ref.state(u).n_baskets
        if nb == 0 or (rng.random() < 0.6 and nb < N - 2):
            items = rng.choice(P.n_items, size=int(rng.integers(1, B)),
                               replace=False).astype(np.int32)
            ref.add_basket(u, items)
            events.append(Event(KIND_ADD_BASKET, u, items=items))
        elif rng.random() < 0.5:
            pos = int(rng.integers(0, nb))
            ref.delete_basket(u, pos)
            events.append(Event(KIND_DEL_BASKET, u, pos=pos))
        else:
            pos = int(rng.integers(0, nb))
            item = int(rng.choice(ref.state(u).history[pos]))
            ref.delete_item(u, pos, item)
            events.append(Event(KIND_DEL_ITEM, u, pos=pos, item=item))
    return events


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_padded_store_pad_columns_stay_zero(rng, impl):
    ref = RefEngine(P, dtype=np.float32)
    events = _mixed_events(rng, ref)
    with ops.default_impl(impl):
        store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                       max_baskets=N, max_basket_size=B))
        eng = StreamingEngine(store, P, batch_size=4, tile_hints=True)
        assert store.cfg.n_cols == 128
        eng.submit(events)
        assert eng.run_until_drained() == len(events)
        st = store.state
        assert st.user_vecs.shape == (M, 128) and st.n_items == P.n_items
        np.testing.assert_array_equal(np.asarray(st.user_vecs)[:, 41:], 0)
        np.testing.assert_array_equal(
            np.asarray(st.last_group_vecs)[:, 41:], 0)
        np.testing.assert_array_equal(np.asarray(store.corpus())[:, 41:], 0)
        mat = np.asarray(st.materialized_user_vecs())
    assert mat.shape == (M, P.n_items)
    for u in range(M):
        np.testing.assert_allclose(mat[u], ref.state(u).user_vec,
                                   rtol=1e-4, atol=1e-5, err_msg=f"u={u}")


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_pad_columns_never_recommended(rng, impl):
    """Real columns scoring below the pad columns' exact zero (deletion
    arithmetic can leave -1e-9 dust) still win every top-n slot."""
    corpus = -np.abs(rng.normal(size=(16, 128))).astype(np.float32)
    corpus[:, 41:] = 0.0
    corpus = jnp.asarray(corpus)
    users = jnp.arange(16, dtype=jnp.int32)
    ids = np.asarray(ops.fused_recommend(corpus, users, 4, 0.7, 10,
                                         impl=impl, n_items=41))
    assert ids.shape == (16, 10) and (ids < 41).all()
    rows = np.asarray(ops.blend_topn_rows(
        corpus, jnp.broadcast_to(corpus[:, None], (16, 3, 128)), 0.7, 10,
        impl=impl, n_items=41))
    assert (rows < 41).all()
    with pytest.raises(ValueError, match="topn=42 > n_items=41"):
        ops.fused_recommend(corpus, users, 4, 0.7, 42, impl=impl,
                            n_items=41)


def test_checkpoint_holds_real_columns(rng, tmp_path):
    """Commits carry the catalogue's columns only; restore re-pads."""
    ref = RefEngine(P, dtype=np.float32)
    events = _mixed_events(rng, ref)

    def engine():
        store = StateStore(StoreConfig(n_users=M, n_items=P.n_items,
                                       max_baskets=N, max_basket_size=B))
        return StreamingEngine(store, P, batch_size=4), store

    eng, store = engine()
    eng.submit(events)
    eng.run_until_drained()
    eng.checkpoint(str(tmp_path), step=1)
    with np.load(tmp_path / "state_0000000001.npz") as npz:
        assert npz["user_vecs"].shape == (M, P.n_items)
        assert npz["last_group_vecs"].shape == (M, P.n_items)
    back, store2 = engine()
    back.restore(str(tmp_path))
    assert store2.state.user_vecs.shape == (M, 128)
    np.testing.assert_array_equal(np.asarray(store2.state.user_vecs),
                                  np.asarray(store.state.user_vecs))
    np.testing.assert_array_equal(
        np.asarray(store2.state.materialized_user_vecs()),
        np.asarray(store.state.materialized_user_vecs()))
