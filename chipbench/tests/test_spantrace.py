"""Nested host spans and piecewise idle attribution (``spantrace.py``)
on hand-built traces, and on a real CPU trace."""
import importlib.util
import os
import types

import pytest

import devtrace
import spantrace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6  # ns


def _trace(host, ops, modules=()):
    ev = lambda items: [(n, a * MS, d * MS, dict(st[0]) if st else {})
                        for n, a, d, *st in items]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": ev(host)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": ev(modules)},
            {"name": "XLA Ops", "events": ev(ops)}]},
    ]


# an ingest step, a submit, a sleep under no span, then a forget of two
# baskets; times in ms
HOST = [
    ("bench.window", 0, 100),
    ("bench.step", 0, 30),
    ("engine.step", 1, 28, {"step": 0}),
    ("engine.cut", 1, 3), ("engine.fetch", 5, 7),
    ("engine.apply", 12, 8, {"kind": 1, "bucket": 8}),
    ("backend_compile_and_load", 13, 6), ("engine.log", 20, 2),
    ("bench.submit", 30, 10), ("engine.submit", 31, 8, {"n": 20}),
    ("bench.forget_user", 60, 40),
    ("engine.forget", 61, 38, {"user": 3, "baskets": 2}),
    ("engine.forget.drain", 61, 2),
    ("engine.step", 61, 1.5, {"step": 1}), ("engine.fetch", 61.5, 0.5),
    ("engine.forget.lookup", 63, 2),
    ("engine.forget.emit", 65, 1), ("engine.submit", 65.2, 0.6, {"n": 2}),
    ("engine.forget.delete", 66, 24),
    ("engine.step", 66, 12, {"step": 1}), ("engine.fetch", 70, 4),
    ("engine.step", 78, 12, {"step": 2}), ("engine.fetch", 80, 4),
    ("py.gc", 85, 3),
    ("engine.forget.scrub", 90, 4), ("engine.forget.purge", 94, 1),
    ("engine.forget.residue", 95, 3),
]
OPS = [("a", 5, 7), ("b", 20, 12), ("c", 58, 3), ("d", 70, 4),
       ("e", 80, 4), ("f", 99, 1)]


def test_spans_nest_by_time():
    t = spantrace.reduce(_trace(HOST, OPS))
    parents = t.by_parent()
    assert parents["engine.step"] == {
        "bench.step": [1, pytest.approx(0.028)],
        "engine.forget.drain": [1, pytest.approx(0.0015)],
        "engine.forget.delete": [2, pytest.approx(0.024)]}
    assert parents["backend_compile_and_load"] == {
        "engine.apply": [1, pytest.approx(0.006)]}
    assert parents["py.gc"] == {"engine.step": [1, pytest.approx(0.003)]}
    assert set(parents["engine.forget"]) == {"bench.forget_user"}


def test_idle_goes_to_the_innermost_span_or_to_no_span():
    t = spantrace.reduce(_trace(HOST, OPS))
    gaps = [(n, round(s * 1e3, 3)) for n, s in t.idle_gaps]
    # [32, 58) lies mostly in the sleep after the submit: no span, though
    # 8 ms of it lie under bench.submit
    assert gaps == [("host.other", 26.0), ("engine.forget.scrub", 15.0),
                    ("engine.step", 9.0), ("backend_compile_and_load", 8.0),
                    ("engine.step", 6.0), ("engine.cut", 5.0)]
    idle = {k: round(v * 1e3, 3) for k, v in t.idle_by_span.items()}
    assert idle == {"bench.step": 1.0, "engine.cut": 3.0, "engine.step": 15.0,
                    "engine.apply": 2.0, "backend_compile_and_load": 6.0,
                    "engine.submit": 7.6, "bench.submit": 1.0,
                    "host.other": 18.0, "engine.fetch": 0.5,
                    "engine.forget.drain": 0.5, "engine.forget.lookup": 2.0,
                    "engine.forget.emit": 0.4, "py.gc": 3.0,
                    "engine.forget.scrub": 4.0, "engine.forget.purge": 1.0,
                    "engine.forget.residue": 3.0, "engine.forget": 1.0}
    # every idle ns has one cause: the causes add up to the idle time
    busy = devtrace.reduce(_trace(HOST, OPS)).busy_s
    assert sum(t.idle_by_span.values()) == pytest.approx(0.1 - busy)


def test_nesting_queries_and_ids():
    t = spantrace.reduce(_trace(HOST, OPS))
    assert t.seconds("engine.step") == (4, pytest.approx(0.0535))
    assert t.seconds("engine.fetch", inside="engine.step") == (
        4, pytest.approx(0.0155))
    assert t.seconds("engine.step", inside="engine.forget") == (
        3, pytest.approx(0.0255))
    assert t.seconds("engine.fetch", inside="engine.forget.delete") == (
        2, pytest.approx(0.008))
    assert t.stat_sum("engine.submit", "n") == 22
    assert t.stat_sum("engine.forget", "baskets") == 2


def test_readings():
    t = spantrace.reduce(_trace(HOST, OPS))
    r = {k: f(t) for k, f in spantrace.READINGS.items()}
    assert r == {
        "step_host_ms.ingest": pytest.approx((53.5 - 15.5) / 4),
        "fetch_wait_ms.ingest": pytest.approx(15.5 / 4),
        "submit_us_per_event.ingest": pytest.approx(8.6e3 / 22),
        "forget_step_host_ms": pytest.approx((25.5 - 8.5) / 3),
        "forget_fetch_ms": pytest.approx(8.5 + 2),
        "forget_tail_ms": pytest.approx(4 + 1 + 3)}
    assert spantrace.report(t)["readings"] == r


def test_spans_that_start_outside_the_window_are_not_counted():
    host = [("bench.window", 10, 90), ("engine.forget", 0, 50),
            ("engine.step", 5, 10), ("engine.step", 20, 10),
            ("engine.forget", 60, 10), ("engine.step", 61, 8)]
    t = spantrace.reduce(_trace(host, []))
    assert t.seconds("engine.step") == (2, pytest.approx(0.018))
    # the first forget began before the window: its step is not its
    assert t.seconds("engine.step", inside="engine.forget") == (
        1, pytest.approx(0.008))


@pytest.mark.parametrize("host", [
    [("bench.window", 0, 100), ("bench.step", 0, 30),
     ("bench.submit", 30, 10), ("bench.forget_user", 60, 40)],
    [("bench.window", 0, 100)],
], ids=["harness-spans-only", "no-spans"])
def test_readings_are_none_without_their_spans(host):
    t = spantrace.reduce(_trace(host, OPS))
    assert all(f(t) is None for f in spantrace.READINGS.values())
    assert spantrace.report(t)["readings"] == {}
    assert sum(t.idle_by_span.values()) == pytest.approx(0.069)


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_engine_spans_leave_the_accepted_readings_as_they_are():
    """The device readers read the same trace the same way whether the
    host line holds the engine's spans or only the harness's."""
    modules = [("jit__add_tile_bound(3)", 5, 7),
               ("jit_apply_add_batch_counted(7)", 20, 12),
               ("jit_apply_del_basket_batch(8)", 70, 4),
               ("jit_apply_del_basket_batch(8)", 80, 4)]
    bench_only = [h for h in HOST if h[0].startswith("bench.")]
    readers = ("apply_add_ms", "apply_del_ms", "idle_share.ingest",
               "idle_share.forget", "events_per_step.ingest",
               "forget_steps")
    seen = []
    for host in (bench_only, HOST):
        red = devtrace.reduce(_trace(host, OPS, modules))
        ctx = types.SimpleNamespace(
            trace=red, counters={"batches": 3, "events_processed": 60},
            forget_steps=[3])
        seen.append(({n: _reader(n)(ctx) for n in readers},
                     red.busy_s, red.programs, red.ops))
    assert seen[0] == seen[1]
    assert seen[0][0]["idle_share.ingest"] == pytest.approx(69.0)


def test_load_keeps_the_spans_and_their_ids(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("engine.step", step=4):
                with TraceAnnotation("engine.fetch"):
                    jax.device_get(jax.numpy.ones(3) + 1)
    finally:
        jax.profiler.stop_trace()
    t = spantrace.reduce(spantrace.load(str(tmp_path)))
    names = {t.all[i].name for i in t.spans}
    assert {"engine.step", "engine.fetch"} <= names
    assert all(n.startswith(spantrace.KEEP) for n in names)
    (step,) = [t.all[i] for i in t.spans if t.all[i].name == "engine.step"]
    assert step.stats == {"step": 4}
    assert t.seconds("engine.fetch", inside="engine.step")[0] == 1
