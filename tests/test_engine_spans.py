"""Host spans of the streaming engine (DESIGN.md §12.3): their names,
nesting and ids with a recording sink, that nothing is built while no
profiler session collects, and the real profiler's trace."""
import gc
import glob
import re

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.types import KIND_ADD_BASKET, KIND_DEL_BASKET, TifuParams
from repro.parallel.sharding import UserShardSpec
from repro.streaming import (Event, ShardedStreamingEngine, StateStore,
                             StoreConfig, StreamingEngine, spans)

P = TifuParams(n_items=29, group_size=3)
FORGET = ["drain", "lookup", "clear", "scrub", "purge", "residue"]
# a step's children, in order: the kind sub-batches build and apply in turn
STEP = re.compile(r"^cut summary( fetch)?( maintain)?( build apply)*"
                  r"( invalidate log)?$")


class Node:
    def __init__(self, name, ids):
        self.name, self.ids, self.children = name, dict(ids), []
        self.parent = None

    def ancestors(self):
        node = self.parent
        while node is not None:
            yield node
            node = node.parent

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


class Recorder:
    """Stands in for ``TraceAnnotation`` with a session always on, and
    records the tree of spans."""

    roots: list = []
    stack: list = []

    def __init__(self, name, **ids):
        self.node = Node(name, ids)

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        if self.stack:
            self.node.parent = self.stack[-1]
            self.stack[-1].children.append(self.node)
        else:
            self.roots.append(self.node)
        self.stack.append(self.node)
        return self

    def __exit__(self, *exc):
        assert self.stack.pop() is self.node

    def set_metadata(self, **ids):
        self.node.ids.update(ids)


@pytest.fixture
def recorded(monkeypatch):
    """The spans the engine opens, as a list of root ``Node``s; garbage
    collection is held off so no ``py.gc`` span lands in between."""
    monkeypatch.setattr(Recorder, "roots", [])
    monkeypatch.setattr(Recorder, "stack", [])
    monkeypatch.setattr(spans, "TraceAnnotation", Recorder)
    gc.disable()
    try:
        yield Recorder.roots
    finally:
        gc.enable()
    assert Recorder.stack == []


def _single():
    store = StateStore(StoreConfig(n_users=8, n_items=P.n_items,
                                   max_baskets=24, max_basket_size=6))
    return StreamingEngine(store, P, batch_size=16)


def _sharded():
    return ShardedStreamingEngine.create(
        UserShardSpec(8, 2), P, max_baskets=24, max_basket_size=6,
        batch_size=16)


def _drive(eng):
    """Adds for six users, one basket deletion, then a forget of user 1
    (three baskets) with an add of user 4 still queued."""
    rng = np.random.default_rng(0)
    eng.submit([Event(KIND_ADD_BASKET, u,
                      items=rng.choice(P.n_items, 3, replace=False))
                for _ in range(3) for u in range(6)])
    eng.run_until_drained()
    eng.delete_basket(2, 0)
    eng.run_until_drained()
    eng.add_basket(4, [1, 2, 3])
    return eng.forget_user(1)


def _all(roots, name):
    return [n for r in roots for n in r.walk() if n.name == name]


def _short(node):
    return [c.name.removeprefix("engine.") for c in node.children]


def test_single_engine_spans_nest_with_their_ids(recorded):
    eng = _single()
    receipt = _drive(eng)
    assert receipt.n_baskets_deleted == 3
    assert {r.name for r in recorded} == {"engine.submit", "engine.step",
                                          "engine.forget"}
    # one submit span per call, never one per event
    assert [r.ids["n"] for r in recorded if r.name == "engine.submit"] \
        == [18, 1, 1]
    steps = _all(recorded, "engine.step")
    for s in steps:
        assert STEP.match(" ".join(_short(s))), _short(s)
    applied = [s for s in steps if "log" in _short(s)]
    assert [s.ids["step"] for s in applied] == list(
        range(eng.metrics.batches))
    for f in _all(recorded, "engine.fetch"):
        assert any(a.name == "engine.step" for a in f.ancestors())
    # six adds pad to 8 rows; the lone add later keeps that bucket
    # (shrink hysteresis), the lone deletion pads to 1
    kinds = {(a.ids["kind"], a.ids["bucket"])
             for a in _all(recorded, "engine.apply")}
    assert kinds == {(KIND_ADD_BASKET, 8), (KIND_DEL_BASKET, 1)}
    assert all(set(b.ids) == {"kind"} for b in _all(recorded,
                                                    "engine.build"))

    (forget,) = [r for r in recorded if r.name == "engine.forget"]
    assert forget.ids == {"user": 1, "baskets": 3}
    assert _short(forget) == ["forget." + p for p in FORGET]
    drain, _, clear = forget.children[:3]
    # the drain applies user 4's queued add, then the empty step; the
    # three baskets go in one clear, with no step, submit or fetch
    assert [len(_all([s], "engine.log")) for s in drain.children] == [1, 0]
    assert clear.ids == {"baskets": 3}
    assert clear.children == []


def test_sharded_engine_opens_one_span_per_call(recorded):
    eng = _sharded()
    _drive(eng)
    assert {r.name for r in recorded} == {"engine.submit", "engine.step",
                                          "engine.forget"}
    assert [r.ids["n"] for r in recorded if r.name == "engine.submit"] \
        == [18, 1, 1]
    for s in _all(recorded, "engine.step"):
        names = _short(s)
        # both shards cut and dispatch before either fetches
        assert names[:4] == ["cut", "summary", "cut", "summary"], names
        assert "step" not in s.ids
    (forget,) = [r for r in recorded if r.name == "engine.forget"]
    assert forget.ids == {"user": 1, "baskets": 3}
    assert _short(forget) == ["forget." + p for p in FORGET]
    assert forget.children[2].ids == {"baskets": 3}
    assert forget.children[2].children == []


def test_maintain_span_when_renormalization_triggers(recorded):
    """group_size 1 and r_g 0.2 shrink a hot user's scale ~5x per add,
    so the maintenance probe triggers renormalizations."""
    p = TifuParams(n_items=30, group_size=1, r_b=0.9, r_g=0.2)
    store = StateStore(StoreConfig(n_users=2, n_items=30, max_baskets=128,
                                   max_basket_size=4, max_groups=128))
    eng = StreamingEngine(store, p, batch_size=1)
    rng = np.random.default_rng(0)
    eng.submit([Event(KIND_ADD_BASKET, 0,
                      items=rng.choice(30, 3, replace=False))
                for _ in range(70)])
    eng.run_until_drained()
    assert eng.metrics.renormalizations > 0
    maint = _all(recorded, "engine.maintain")
    assert maint and sum(m.ids["rows"] for m in maint) == (
        eng.metrics.renormalizations + eng.metrics.refreshes)
    for m in maint:
        assert m.parent.name == "engine.step"
        assert [c.name for c in m.children] == ["engine.fetch"]


def test_gc_span_nests_in_the_open_span(recorded):
    _single()        # installs the collector hook
    with spans.span("outer"):
        gc.collect()
    (outer,) = recorded
    assert [(c.name, c.ids) for c in outer.children] == [
        ("py.gc", {"generation": 2})]


@pytest.mark.parametrize("make", [_single, _sharded],
                         ids=["single", "sharded"])
def test_no_annotation_without_a_session(monkeypatch, make):
    built = []

    class Counting(TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    assert not Counting.is_enabled()
    _drive(make())
    gc.collect()
    assert built == []


def _host_events(path):
    """Host events of the one trace under ``path``, each as (name,
    start, end, stats), per line; stats are read for engine spans."""
    (pb,) = glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats) if e.name.startswith("engine.") else {})
                    for e in line.events])
    return lines


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_real_profiler_trace(tmp_path):
    # shapes no other test uses, and no persistent cache: the first
    # step compiles its applier
    p = TifuParams(n_items=37, group_size=2)
    store = StateStore(StoreConfig(n_users=13, n_items=37, max_baskets=11,
                                   max_basket_size=5))
    eng = StreamingEngine(store, p, batch_size=8)
    eng.submit([Event(KIND_ADD_BASKET, u, items=np.array([u % 37, 36]))
                for _ in range(2) for u in range(13)])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert not spans.TraceAnnotation.is_enabled()

    lines = [ln for ln in _host_events(tmp_path)
             if any(e[0] == "engine.step" for e in ln)]
    assert len(lines) == 1
    (line,) = lines
    steps = [e for e in line if e[0] == "engine.step"]
    assert [s[3] for s in steps] == [{"step": 0}, {"step": 1}, {"step": 2}]
    fetches = [e for e in line if e[0] == "engine.fetch"]
    assert fetches and all(any(_inside(f, s) for s in steps)
                           for f in fetches)
    apply0 = [e for e in line if e[0] == "engine.apply"
              and _inside(e, steps[0])]
    assert [a[3] for a in apply0] == [{"kind": KIND_ADD_BASKET,
                                       "bucket": 8}]
    assert any(e[0].startswith("backend_compile") and _inside(e, apply0[0])
               for e in line)
