"""The trace reduction and the trace readers, on hand-built traces."""
import importlib.util
import os
import types

import pytest

import devtrace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6  # ns


def _trace(ops, modules, host):
    ev = lambda items: [(n, a * MS, d * MS, {}) for n, a, d in items]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": ev(host)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": ev(modules)},
            {"name": "XLA Ops", "events": ev(ops)}]},
    ]


def _example():
    host = [("bench.window", 0, 100), ("bench.step", 0, 30),
            ("bench.recommend", 30, 40), ("bench.step", 70, 30)]
    modules = [("jit_apply_add_batch_counted(7)", 10, 20),
               ("jit__fused_recommend_dtiled_pallas(9)", 40, 20)]
    ops = [("sort.1", 10, 5), ("scatter.2", 14, 16),
           ("knn_topk_dtiled.1", 40, 12), ("blend_topn_onehot.1", 52, 8),
           ("late.3", 95, 10)]
    return _trace(ops, modules, host)


def test_busy_idle_programs_and_gaps():
    red = devtrace.reduce(_example())
    assert red.window_s == pytest.approx(0.1)
    # [10, 30) + [40, 60) + [95, 100): 45 ms busy
    assert red.busy_s == pytest.approx(0.045)
    assert red.idle_share == pytest.approx(0.55)
    assert red.programs["jit_apply_add_batch_counted"] == [
        pytest.approx(0.02), 1]
    assert red.op_seconds("knn_topk") == pytest.approx(0.012)
    # op times add up even where ops overlap (5 + 16 ms)
    assert red.op_seconds("apply_add_batch_counted/") == pytest.approx(0.021)
    # idle [0,10) in a step, [30,40) in a read, [60,95) mostly in a step
    gaps = [(n, round(s * 1e3)) for n, s in red.idle_gaps]
    assert gaps == [("bench.step", 35), ("bench.step", 10),
                    ("bench.recommend", 10)]
    assert red.spans["bench.step"][0] == 2
    bd = devtrace.breakdown(red)
    assert bd["device_ops"][0][0].endswith("/scatter.2")


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(red, batches=2):
    return types.SimpleNamespace(trace=red, counters={"batches": batches})


def test_applier_readers_match_programs_by_name():
    host = [("bench.window", 0, 100), ("bench.step", 0, 100)]
    modules = [("jit__add_tile_bound(3)", 10, 2),
               ("jit_apply_add_batch_counted(7)", 20, 6),
               ("jit_apply_add_batch_counted(7)", 40, 4),
               ("jit_apply_del_basket_batch(8)", 60, 3),
               ("jit_apply_del_basket_batch(8)", 70, 1),
               ("jit__refresh_corpus_rows(2)", 80, 5)]
    red = devtrace.reduce(_trace([], modules, host))
    # (2 + 6 + 4) ms of the add path over 2 steps; deletions are no part
    assert _reader("apply_add_ms").read(_ctx(red)) == pytest.approx(6.0)


def test_readers_stay_silent_without_a_device_trace():
    host = [("bench.window", 0, 100)]
    red = devtrace.reduce([{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [(n, a * MS, d * MS, {})
                                      for n, a, d in host]}]}])
    for name in ("apply_add_ms", "idle_share.ingest",
                 "idle_share.forget", "idle_share.serve", "read_device_ms",
                 "refresh_ms", "stage_a_roofline", "stage_b_roofline"):
        ctx = _ctx(red)
        ctx.counters["read_users"] = 5
        ctx.cfg = {"n_users": 4, "n_items": 8, "k_neighbors": 2}
        assert _reader(name).read(ctx) is None


def test_roofline_counts_on_a_hand_worked_shape():
    import roofline

    # 3 queries against 4 users of 8 items: 2·3·4·8 multiply-adds' flops;
    # the corpus (32 floats) and the queries (24) read once
    assert roofline.stage_a(3, 4, 8) == (192, 4 * 56)
    # k = 2: each query's own row and 2 neighbours, 3 rows of 8 floats
    assert roofline.stage_b(3, 2, 8) == (144, 4 * 72)
    peaks = {"f32_highest_flops_per_s": 96.0, "hbm_bytes_per_s": 56.0}
    assert roofline.least_seconds((192, 224), peaks) == 4.0   # bytes
    assert roofline.least_seconds((960, 224), peaks) == 10.0  # flops


def test_serve_readers_on_a_traced_window():
    host = [("bench.window", 0, 100), ("bench.recommend", 10, 20),
            ("bench.recommend", 50, 20), ("bench.step", 80, 10)]
    modules = [("jit__fused_recommend_dtiled_pallas(9)", 12, 6),
               ("jit__fused_recommend_dtiled_pallas(9)", 52, 6),
               ("jit__refresh_corpus_rows(4)", 50, 1),
               ("jit_apply_add_batch_counted(7)", 80, 5)]
    ops = [("knn_topk_dtiled.1 = (f32[1,300]) custom-call(a)", 12, 4),
           ("blend_topn_onehot.1 = (f32[1,10]) custom-call(b)", 16, 2),
           ("knn_topk_dtiled.1 = (f32[1,300]) custom-call(a)", 52, 4),
           ("blend_topn_onehot.1 = (f32[1,10]) custom-call(b)", 56, 2),
           ("fusion.2 = f32[4,8] fusion(c)", 50, 1),
           ("scatter.3 = f32[4,8] scatter(d)", 80, 5)]
    red = devtrace.reduce(_trace(ops, modules, host))
    # 3 users (one read of one, one of two) of 4 users and 8 items, k 2
    ctx = types.SimpleNamespace(
        trace=red, counters={"read_users": 3, "batches": 1},
        cfg={"n_users": 4, "n_items": 8, "k_neighbors": 2},
        peaks={"f32_highest_flops_per_s": 96.0, "hbm_bytes_per_s": 56e3})
    assert _reader("read_device_ms").read(ctx) == pytest.approx(4.0)
    assert _reader("refresh_ms").read(ctx) == pytest.approx(1 / 3)
    # stage A: 2 calls of 1.5 queries: (96 flops, 176 bytes) each, so
    # 1 s at the flops peak, over 8 ms of kernel time
    assert _reader("stage_a_roofline").read(ctx) == pytest.approx(
        100 * 2 * 1.0 / 0.008)
    # stage B: (72 flops, 144 bytes) a call: 0.75 s, over 4 ms
    assert _reader("stage_b_roofline").read(ctx) == pytest.approx(
        100 * 2 * 0.75 / 0.004)
    assert _reader("idle_share.serve").read(ctx) == pytest.approx(
        100 * (1 - 0.18))
    # no read in the window: nothing to read
    ctx.counters["read_users"] = 0
    for name in ("read_device_ms", "refresh_ms", "stage_a_roofline",
                 "stage_b_roofline"):
        assert _reader(name).read(ctx) is None
