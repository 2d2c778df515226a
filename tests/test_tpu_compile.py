"""The main-path kernels compile for a TPU v5e at the deployment width.

Interpret mode runs the kernel bodies but not the chip's compiler, which
refuses what the interpreter accepts: unaligned blocks, unlowered
primitives, more scoped VMEM than a kernel may use.  These tests compile
each main-path kernel for a described (not attached) ``v5e:2x2`` chip at
the shapes ``chip_smoke.py`` runs: the paper's TaFeng deployment (13,949
users, 11,997 items padded to 12,032 columns, k = 300), 256-user update
and request batches.  Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import StreamState, lane_padded
from repro.core.updates import clear_users
from repro.kernels import ops
from repro.kernels.knn_topk import knn_topk_dtiled
from repro.kernels.serving_topn import blend_topn_onehot, blend_topn_rows
from repro.kernels.sparse_row_gather import sparse_row_gather
from repro.kernels.sparse_row_scatter import sparse_row_scatter

N_USERS, N_ITEMS, K, TOPN = 13_949, 11_997, 300, 10
N_COLS = lane_padded(N_ITEMS)
BATCH = 256
# support widths of the TaFeng store (max_baskets 24, max_basket_size
# 20, group_size 7): adds see (7 + 1)·20 ids, deletes 24·20 + 1
W_ADD, W_DEL = 160, 481
T_MAX = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no chip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep the compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes, donate=()) -> str:
    return jax.jit(fn, donate_argnums=donate).lower(*shapes).compile() \
        .as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("w", [W_ADD, W_DEL])
def test_sparse_row_scatter_compiles(one_chip, w):
    bi = ops.plan_bi(N_COLS)
    text = _compiled_text(
        lambda t, r, i, v: sparse_row_scatter(t, r, i, v, bi=bi,
                                              t_max=T_MAX),
        _sds(one_chip, (N_USERS, N_COLS)),
        _sds(one_chip, (BATCH,), jnp.int32),
        _sds(one_chip, (BATCH, w), jnp.int32),
        _sds(one_chip, (BATCH, w)), donate=(0,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [W_ADD, W_DEL])
def test_sparse_row_gather_compiles(one_chip, w):
    bi = ops.plan_bi(N_COLS)
    text = _compiled_text(
        lambda t, r, i: sparse_row_gather(t, r, i, bi=bi, t_max=T_MAX),
        _sds(one_chip, (N_USERS, N_COLS)),
        _sds(one_chip, (BATCH,), jnp.int32),
        _sds(one_chip, (BATCH, w), jnp.int32))
    assert "tpu_custom_call" in text


def test_stage_a_compiles_at_deployment_width(one_chip):
    # the width decides the regime: at 12k items only the D-tiled
    # kernel fits VMEM, and that is what the engine dispatches
    bd = ops.stage_a_bd(N_COLS, K)
    assert bd is not None
    text = _compiled_text(
        lambda q, c, g: knn_topk_dtiled(q, c, K, bd=bd, query_gids=g),
        _sds(one_chip, (BATCH, N_COLS)),
        _sds(one_chip, (N_USERS, N_COLS)),
        _sds(one_chip, (BATCH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_blend_topn_onehot_compiles(one_chip):
    text = _compiled_text(
        lambda c, u, i: blend_topn_onehot(c, u, i, 0.7, TOPN,
                                          n_items=N_ITEMS),
        _sds(one_chip, (N_USERS, N_COLS)),
        _sds(one_chip, (BATCH,), jnp.int32),
        _sds(one_chip, (BATCH, K), jnp.int32))
    assert "tpu_custom_call" in text


def test_blend_topn_rows_compiles(one_chip):
    # the cross-shard stage B of the four-chip phase: 64-user requests
    text = _compiled_text(
        lambda q, r: blend_topn_rows(q, r, 0.7, TOPN, n_items=N_ITEMS),
        _sds(one_chip, (64, N_COLS)),
        _sds(one_chip, (64, K, N_COLS)))
    assert "tpu_custom_call" in text


def test_clear_users_writes_the_row_in_place(one_chip):
    # a forget's one program: the donated leaves alias its outputs, and
    # nothing larger than a row is copied (max_baskets 24, basket 20)
    n, b, k = 24, 20, 4
    st = StreamState(
        _sds(one_chip, (N_USERS, N_COLS)), _sds(one_chip, (N_USERS, N_COLS)),
        _sds(one_chip, (N_USERS, n, b), jnp.int32),
        _sds(one_chip, (N_USERS, k), jnp.int32),
        *[_sds(one_chip, (N_USERS,), jnp.int32)] * 2,
        *[_sds(one_chip, (N_USERS,))] * 3, n_real=N_ITEMS)
    text = clear_users.lower(st, _sds(one_chip, (1,), jnp.int32)) \
        .compile().as_text()
    assert "input_output_alias" in text
    copies = re.findall(r"=\s*\w+\[([\d,]*)\]\S*\s+copy(?:-start)?\(", text)
    sizes = [math.prod(int(d) for d in c.split(",") if d) for c in copies]
    assert all(size < N_COLS for size in sizes), sizes
