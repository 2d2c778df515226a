"""Pin the analytic VMEM models to real pallas_call block allocations.

The KC03 lint rule budgets each kernel contract by evaluating its
``repro.analysis.vmem`` model at the declared max shapes — which is
only meaningful if the models count exactly the bytes the kernels
allocate.  These tests intercept ``pl.pallas_call`` and recompute the
per-grid-step block residency (every in/out BlockSpec at its block
shape × operand itemsize, plus every VMEM scratch buffer) from the
specs the kernel actually passes, over sampled (d, k, bq, bm, bd)
configurations, and require EXACT equality with the model.  The
stage-A capacity-planning model (``stage_a_vmem_bytes``) is pinned to
the exact models through closed-form deltas.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import vmem as avmem
from repro.analysis.contracts import REGISTRY
from repro.analysis.linter import load_contracts
from repro.kernels import ops
from repro.kernels.knn_topk import knn_topk, knn_topk_dtiled
from repro.kernels.serving_topn import (blend_topn_rows,
                                        blend_topn_rows_quant)


def _block_bytes(kw, operands) -> int:
    """Recompute one grid step's block residency from a pallas_call."""
    total = 0
    for spec, op in zip(kw["in_specs"], operands):
        total += int(np.prod(spec.block_shape)) \
            * np.dtype(op.dtype).itemsize
    out_specs, out_shapes = kw["out_specs"], kw["out_shape"]
    if not isinstance(out_specs, (list, tuple)):
        out_specs, out_shapes = [out_specs], [out_shapes]
    for spec, osh in zip(out_specs, out_shapes):
        total += int(np.prod(spec.block_shape)) \
            * np.dtype(osh.dtype).itemsize
    for scratch in kw.get("scratch_shapes", []):
        total += int(np.prod(scratch.shape)) \
            * np.dtype(scratch.dtype).itemsize
    return total


@pytest.fixture
def captured_bytes(monkeypatch):
    """Intercept pl.pallas_call; record each site's block bytes."""
    captured: list = []
    real = pl.pallas_call

    def spy(kernel, **kw):
        inner = real(kernel, **kw)

        def wrapped(*operands):
            captured.append(_block_bytes(kw, operands))
            return inner(*operands)

        return wrapped

    monkeypatch.setattr(pl, "pallas_call", spy)
    jax.clear_caches()  # jit caches would skip the retrace (and the spy)
    yield captured
    jax.clear_caches()


@pytest.mark.parametrize("d,k,bq,bm", [(32, 4, 8, 16), (48, 8, 16, 16),
                                       (64, 4, 8, 32)])
def test_knn_topk_model_matches_blocks(captured_bytes, d, k, bq, bm):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(bq, d)).astype(np.float32)
    c = rng.normal(size=(3 * bm + 5, d)).astype(np.float32)
    knn_topk(q, c, k, bq=bq, bm=bm, interpret=True)
    assert captured_bytes == [avmem.knn_topk_block_bytes(
        d=d, k=k, bq=bq, bm=bm, itemsize=4)]


@pytest.mark.parametrize("d,k,bq,bm,bd", [(64, 4, 8, 16, 32),
                                          (96, 8, 8, 16, 48)])
def test_knn_topk_dtiled_model_matches_blocks(captured_bytes, d, k, bq,
                                              bm, bd):
    rng = np.random.default_rng(1)
    q = rng.normal(size=(bq, d)).astype(np.float32)
    c = rng.normal(size=(2 * bm + 3, d)).astype(np.float32)
    knn_topk_dtiled(q, c, k, bq=bq, bm=bm, bd=bd, interpret=True)
    assert captured_bytes == [avmem.knn_topk_dtiled_block_bytes(
        d=d, k=k, bq=bq, bm=bm, bd=bd, itemsize=4)]


def test_knn_topk_dtiled_int8_model_matches_blocks(captured_bytes):
    d, k, bq, bm, bd = 64, 4, 8, 16, 32
    rng = np.random.default_rng(2)
    q = rng.integers(-127, 128, size=(bq, d), dtype=np.int8)
    c = rng.integers(-127, 128, size=(2 * bm, d), dtype=np.int8)
    knn_topk_dtiled(q, c, k, bq=bq, bm=bm, bd=bd, interpret=True,
                    q_scale=np.ones(bq, np.float32),
                    c_scale=np.ones(2 * bm, np.float32))
    assert captured_bytes == [avmem.knn_topk_dtiled_block_bytes(
        d=d, k=k, bq=bq, bm=bm, bd=bd, itemsize=1)]


@pytest.mark.parametrize("k,topn,bq,bi", [(4, 8, 4, 32), (8, 16, 8, 64)])
def test_blend_topn_rows_model_matches_blocks(captured_bytes, k, topn,
                                              bq, bi):
    rng = np.random.default_rng(3)
    queries = rng.normal(size=(bq, 2 * bi)).astype(np.float32)
    nbrs = rng.normal(size=(bq, k, 2 * bi)).astype(np.float32)
    blend_topn_rows(queries, nbrs, 0.7, topn, bq=bq, bi=bi,
                    interpret=True)
    assert captured_bytes == [avmem.blend_topn_rows_block_bytes(
        k=k, topn=topn, bq=bq, bi=bi)]


def test_blend_topn_rows_quant_model_matches_blocks(captured_bytes):
    k, topn, bq, bi = 4, 8, 4, 32
    rng = np.random.default_rng(4)
    qq = rng.integers(-127, 128, size=(bq, 2 * bi), dtype=np.int8)
    nq = rng.integers(-127, 128, size=(bq, k, 2 * bi), dtype=np.int8)
    blend_topn_rows_quant(qq, np.ones(bq, np.float32), nq,
                          np.ones((bq, k), np.float32), 0.7, topn,
                          bq=bq, bi=bi, interpret=True)
    assert captured_bytes == [avmem.blend_topn_rows_quant_block_bytes(
        k=k, topn=topn, bq=bq, bi=bi)]


@pytest.mark.parametrize("d,k,bq,bm,bd", [(256, 16, 128, 512, 128),
                                          (1024, 64, 64, 256, 512),
                                          (4096, 300, 128, 512, 512)])
def test_stage_a_delta_identities(d, k, bq, bm, bd):
    # the planning model drops exactly the O(bq + bm) side vectors the
    # exact models count; the closed-form deltas pin that relationship
    mono_delta = (avmem.knn_topk_block_bytes(d=d, k=k, bq=bq, bm=bm)
                  - avmem.stage_a_vmem_bytes(d, k, bq=bq, bm=bm))
    assert mono_delta == bq * 4 + bq * k * 8
    dt_delta = (avmem.knn_topk_dtiled_block_bytes(d=d, k=k, bq=bq,
                                                  bm=bm, bd=bd)
                - avmem.stage_a_vmem_bytes(d, k, bq=bq, bm=bm, bd=bd))
    assert dt_delta == 3 * bq * 4 + 2 * bm * 4 + bq * k * 8


def test_ops_stage_a_delegates():
    for args in ((256, 16), (65536, 300), (1 << 20, 300)):
        for bd in (None, 512):
            assert ops.stage_a_vmem_bytes(*args, bd=bd) \
                == avmem.stage_a_vmem_bytes(*args, bd=bd)


def test_all_contracts_under_budget():
    load_contracts()
    assert len(REGISTRY) >= 9
    for (module, entry), c in REGISTRY.items():
        used = c.max_vmem_bytes()
        assert used > 0 and avmem.fits_vmem(used), (module, entry, used)
