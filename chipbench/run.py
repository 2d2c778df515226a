#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 chipbench/run.py --workload tafeng.ingest --seed 7 \\
        --seconds 30 --trace 0

``--workload <config>.<traffic>`` names ``chipbench/configs/<config>.json``
(a deployment: the paper's Table-1 statistics and §6 hyperparameters)
and ``chipbench/traffic/<traffic>.json`` (the mix, its fixed rates and
the limits of the correctness check).  A run:

1. generates every user's history and the whole schedule from
   ``--seed`` (``gen.py``), before anything is timed;
2. loads the histories through ``StreamingEngine.submit``/``step`` (a
   load engine with a larger micro-batch over the same store), then
   starts the cell's engine and plays the first ``warm_s`` seconds of
   the schedule, so every shape the window uses is compiled: set-up;
3. plays ``--seconds`` more of the schedule, open loop: additions and
   deletions are submitted when due, due forgets (``forget_user``) are
   served in due order, and the engine steps while anything is
   pending.  Latencies run from due time;
4. checks what the window produced against ``reference.py``
   (``check.py``) and prints one JSON line, last on stdout.

``--trace 1`` runs the same under the JAX profiler and reports the
per-layer metrics (``metrics/<name>.py``) instead of the end-to-end
ones.  The run exits 2, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "chipbench")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")

sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(workload: str) -> tuple:
    """(config, traffic, chips, benchmark) of a ``BENCHMARK.json`` cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = load_json(ROOT, conf["file"])
    traffic = load_json(BENCH, "traffic", w["traffic"] + ".json")
    return cfg, traffic, w["chips"], bench


class CompileLog(logging.Handler):
    """Counts backend compiles (or persistent-cache loads) and cache use,
    and keeps the names of the programs compiled."""

    def __init__(self) -> None:
        import jax

        super().__init__()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        jax.config.update("jax_log_compiles", True)
        # the compile log goes to this handler only, not to stderr
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch",
                     "jax._src.compiler"):
            lg = logging.getLogger(name)
            lg.propagate = False
            lg.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ")[1])

    def _duration(self, event: str, duration: float, *args, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event: str, *args, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "seconds": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "names": len(self.names)}


COUNTERS = ("events_processed", "batches", "host_fetches", "dropped_adds",
            "dead_letters", "backpressure_rejections", "bucket_grows",
            "serve_requests", "serve_compiled_shapes")


def counters(eng) -> dict:
    return {k: getattr(eng.metrics, k) for k in COUNTERS}


class Stalls:
    """Where the loop's time went, to tell a stall in the engine from one
    in the harness: the slowest engine calls, each with the CPU time its
    thread spent and its involuntary context switches (a thread that was
    descheduled), the longest stretch of harness work between two
    calls, and the time spent in Python's garbage collector."""

    KEEP = 5

    def __init__(self):
        self.calls: list = []      # (wall, name, thread cpu, switches)
        self.harness = 0.0         # longest stretch between engine calls
        self.mark = None
        self.gc_s = [0.0, 0.0, 0.0]
        self.gc_n = [0, 0, 0]
        self.gc_max = 0.0
        self._gc_t = 0.0

    @contextlib.contextmanager
    def call(self, name: str):
        t = time.perf_counter()
        if self.mark is not None:
            self.harness = max(self.harness, t - self.mark)
        r = resource.getrusage(resource.RUSAGE_THREAD)
        try:
            yield
        finally:
            q = resource.getrusage(resource.RUSAGE_THREAD)
            self.mark = time.perf_counter()
            cpu = q.ru_utime + q.ru_stime - r.ru_utime - r.ru_stime
            rec = (self.mark - t, name, max(cpu, 0.0),
                   q.ru_nivcsw - r.ru_nivcsw)
            if len(self.calls) < self.KEEP or rec[0] > self.calls[-1][0]:
                self.calls = sorted(self.calls + [rec],
                                    reverse=True)[:self.KEEP]

    def idle(self) -> None:
        """The loop sleeps: that is no harness work."""
        self.mark = None

    def gc_callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            d = time.perf_counter() - self._gc_t
            self.gc_s[info["generation"]] += d
            self.gc_n[info["generation"]] += 1
            self.gc_max = max(self.gc_max, d)

    def report(self) -> str:
        calls = ", ".join(f"{n} {w:.6f} s wall {c:.6f} s cpu {v} invol"
                          for w, n, c, v in self.calls)
        return (f"slowest engine calls [{calls}]; longest harness stretch "
                f"{self.harness:.6f} s; gc by generation {self.gc_n} "
                f"collections {[round(x, 6) for x in self.gc_s]} s, "
                f"longest {self.gc_max:.6f} s")


class Player:
    """Plays a schedule into the engine, open loop, on fixed due times.

    ``events`` are the schedule's prebuilt ``Event`` objects, in due
    order.  Each pass of the loop submits every due event, serves a due
    forget, then steps the engine if anything is pending, and sleeps
    until the next due time otherwise.
    """

    def __init__(self, eng, sched, events, traced: bool):
        self.eng, self.s, self.events = eng, sched, events
        self.traced = traced
        self.stalls = Stalls()
        self.ie = self.jf = 0
        self.forgets: list = []    # (index, due, latency, steps, receipt)
        self.lag: list = []        # (due, seconds late when handed over)
        self.rejected = 0

    def annotate(self, name: str):
        """A host span in the trace, when traced."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def span(self, name: str):
        """An engine call: annotated, and timed for ``Stalls``."""
        with self.stalls.call(name), self.annotate(name):
            yield

    def run(self, t0: float, until: float, clock=time.perf_counter) -> None:
        s, eng = self.s, self.eng
        n_ev, n_fg = s.ev_due.size, s.forget_due.size
        while True:
            now = clock() - t0
            if now >= until:
                return
            j = int(np.searchsorted(s.ev_due, now, side="right"))
            if j > self.ie:
                self.lag.append((s.ev_due[self.ie], now - s.ev_due[self.ie]))
                with self.span("bench.submit"):
                    res = eng.submit(self.events[self.ie:j],
                                     on_invalid="quarantine",
                                     on_overflow="shed")
                self.rejected += res.rejected
                self.ie = j
            if self.jf < n_fg and s.forget_due[self.jf] <= now:
                self.forget(t0, clock)
                continue
            if eng.n_pending:
                with self.span("bench.step"):
                    eng.step()
                continue
            nxt = min(s.ev_due[self.ie] if self.ie < n_ev else until,
                      s.forget_due[self.jf] if self.jf < n_fg else until,
                      until)
            pause = nxt - (clock() - t0)
            if pause > 0:
                self.stalls.idle()
                time.sleep(pause)

    def forget(self, t0: float, clock=time.perf_counter) -> None:
        i = self.jf
        due = self.s.forget_due[i]
        self.lag.append((due, clock() - t0 - due))
        b0 = self.eng.metrics.batches
        with self.span("bench.forget_user"):
            rc = self.eng.forget_user(int(self.s.forget_user[i]))
        self.forgets.append((i, due, clock() - t0 - due,
                             self.eng.metrics.batches - b0, rc))
        self.jf += 1

    def catch_up(self, t0: float, until: float) -> None:
        """Hand over everything due before ``until``."""
        j = int(np.searchsorted(self.s.ev_due, until))
        if j > self.ie:
            res = self.eng.submit(self.events[self.ie:j],
                                  on_invalid="quarantine", on_overflow="shed")
            self.rejected += res.rejected
            self.ie = j
        self.finish_due(t0, until)

    def finish_due(self, t0: float, until: float) -> None:
        """Serve the forgets that fell due before ``until`` and were not
        reached: their latency counts the wait."""
        while self.jf < self.s.forget_due.size and \
                self.s.forget_due[self.jf] < until:
            self.forget(t0)


def build_events(sched, Event, kinds) -> list:
    add, dele = kinds
    out = []
    for u, row, pos in zip(sched.ev_user.tolist(), sched.ev_items,
                           sched.ev_pos.tolist()):
        if pos < 0:
            out.append(Event(add, u, items=row[row >= 0]))
        else:
            out.append(Event(dele, u, pos=pos))
    return out


def load_events(hist, Event, kind) -> list:
    """Every loaded basket, round-robin over users in history order."""
    nb = hist.n_baskets
    rank = np.arange(hist.owner.size) - np.repeat(hist.start[:-1], nb)
    order = np.lexsort((hist.owner, rank))
    items = hist.items
    return [Event(kind, int(hist.owner[r]), items=items[r][items[r] >= 0])
            for r in order.tolist()]


@dataclasses.dataclass
class Program:
    """What the program's run leaves for the checks and the metrics."""

    phases: dict
    seconds: float
    window_s: float
    setup_s: float
    counters: dict
    compiles: dict
    window_compiled: list
    stalls: str
    backlog: int
    player: Player
    rows: np.ndarray             # engine rows of the checked users
    checked_users: np.ndarray
    residue: list
    failed: int
    memory_peak_bytes: int


def run_program(cfg: dict, traffic: dict, seed: int, seconds: float,
                traced: bool, log: CompileLog, hist, sched,
                t_start: float) -> Program:
    """Set-up, the measured window and the program's outputs."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                                  TifuParams)
    from repro.streaming import (Event, StateStore, StoreConfig,
                                 StreamingEngine)

    phases = {}

    def phase(name, t, k):
        phases[name] = (time.perf_counter() - t, log.programs - k)

    t, k = time.perf_counter(), log.programs
    events = build_events(sched, Event, (KIND_ADD_BASKET, KIND_DEL_BASKET))
    loads = load_events(hist, Event, KIND_ADD_BASKET)
    phase("build_events", t, k)

    params = TifuParams(n_items=cfg["n_items"], group_size=cfg["group_size"],
                        r_b=cfg["r_b"], r_g=cfg["r_g"],
                        k_neighbors=cfg["k_neighbors"], alpha=cfg["alpha"])
    store = StateStore(StoreConfig(
        n_users=cfg["n_users"], n_items=cfg["n_items"],
        max_baskets=cfg["max_baskets"],
        max_basket_size=cfg["max_basket_size"]))
    t, k = time.perf_counter(), log.programs
    loader = StreamingEngine(store, params, batch_size=cfg["load_batch_size"])
    res = loader.submit(loads, on_invalid="quarantine", on_overflow="shed")
    loader.run_until_drained(max_batches=1 << 30)
    failed = (res.rejected + loader.metrics.dead_letters
              + loader.metrics.dropped_adds)
    jax.block_until_ready(store.state)
    phase("load", t, k)
    del loader, loads

    # warm-up, in two halves of the schedule's first warm_s seconds, each
    # played open loop and then applied to the end whatever compiling
    # took: the first holds the extra warm-up traffic (gen.schedule) and
    # compiles the steady shapes, the second starts from an empty queue
    # as the window does and compiles the shapes of that start
    eng = StreamingEngine(store, params, batch_size=cfg["batch_size"])
    drv = Player(eng, sched, events, traced)
    warm = traffic["warm_s"]
    t, k = time.perf_counter(), log.programs
    for lo, hi in ((0.0, warm / 2), (warm / 2, warm)):
        t0 = time.perf_counter() - lo
        drv.run(t0, hi)
        drv.catch_up(t0, hi)
        eng.run_until_drained(max_batches=1 << 30)
    jax.block_until_ready(store.state)
    phase("warm", t, k)
    t0 = time.perf_counter() - warm

    c0, k0 = counters(eng), log.snapshot()
    drv.stalls = Stalls()
    gc.callbacks.append(drv.stalls.gc_callback)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    with drv.annotate("bench.window"):
        drv.run(t0, warm + seconds)
        jax.block_until_ready(store.state)
    w1 = time.perf_counter()
    gc.callbacks.remove(drv.stalls.gc_callback)
    stalls = drv.stalls.report()
    c1, k1 = counters(eng), log.snapshot()
    window_compiled = log.names[k0["names"]:k1["names"]]
    if traced:
        jax.profiler.stop_trace()
    backlog = eng.n_pending
    drv.finish_due(t0, warm + seconds)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:1])

    # after the window: apply what is still queued, so every submitted
    # event can be checked against the reference
    eng.run_until_drained(max_batches=1 << 30)
    failed += (drv.rejected + eng.metrics.dead_letters
               + eng.metrics.dropped_adds)
    from check import checked_users
    users = checked_users(cfg, sched, drv.ie, seed)
    take = jax.jit(lambda st, idx: st.materialized_user_vecs()[idx])
    rows = np.asarray(take(store.state, jnp.asarray(users, jnp.int32)))
    residue = [f[4].residue for f in drv.forgets]
    drv.eng = drv.events = None   # the program's state goes with it
    return Program(
        phases=phases, seconds=seconds, window_s=w1 - w0, setup_s=setup_s,
        counters={k: c1[k] - c0[k] for k in c0},
        compiles={k: k1[k] - k0[k] for k in k0},
        window_compiled=window_compiled, stalls=stalls, backlog=backlog,
        player=drv, rows=rows,
        checked_users=users, residue=residue, failed=failed,
        memory_peak_bytes=int(peak))


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""

    cfg: dict
    setup_s: float
    window_s: float
    counters: dict
    forget_ms: list
    forget_steps: list
    trace: object
    peaks: dict


def cell_metrics(bench: dict, workload: str, traced: bool) -> list:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def histogram(values_ms) -> str:
    """Counts in log-spaced bins (ms), to show where the modes lie."""
    if not len(values_ms):
        return "none"
    edges = [0.5, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    counts = np.histogram(values_ms, bins=[0] + edges + [np.inf])[0]
    return " ".join(f"<{e}:{c}" for e, c in zip(edges + ["inf"], counts)
                    if c)


def run(argv=None, require_tpu: bool = True, spec=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cfg, traffic, chips, bench = spec or cell_spec(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if require_tpu:
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s). Nothing "
              "was run.", file=sys.stderr)
        return 2
    peaks = load_json(BENCH, "peaks.json")["devices"]
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks:
        print(f"chipbench: no peaks for device kind {kind!r} in "
              "peaks.json", file=sys.stderr)
        return 2
    gen = _import("gen")
    check = _import("check")
    if gen.max_history(cfg, traffic) > cfg["max_baskets"]:
        raise SystemExit(f"{args.workload}: the traffic can give a user "
                         f"{gen.max_history(cfg, traffic)} baskets; "
                         f"max_baskets is {cfg['max_baskets']}")
    log = CompileLog()
    t = time.perf_counter()
    hist = gen.histories(cfg, args.seed)
    t_end = traffic["warm_s"] + args.seconds
    sched = gen.schedule(cfg, traffic, hist, args.seed, t_end)
    t_gen = time.perf_counter() - t

    prog = run_program(cfg, traffic, args.seed, args.seconds,
                       bool(args.trace), log, hist, sched, T_START)
    prog.phases = {"generate": (t_gen, 0), **prog.phases}
    drv = prog.player
    warm = traffic["warm_s"]
    in_window = (lambda due: warm <= due < warm + args.seconds)
    forget_ms = [f[2] * 1e3 for f in drv.forgets if in_window(f[1])]
    forget_steps = [f[3] for f in drv.forgets if in_window(f[1])]
    lag = np.asarray([x[1] for x in drv.lag if in_window(x[0])] or [0.0])

    print(f"device: {len(devices)} x {kind}; compile cache {CACHE_DIR}")
    print("setup: " + ", ".join(f"{k} {v[0]:.3f} s ({v[1]} compiles)"
                                for k, v in prog.phases.items())
          + f"; setup_s {prog.setup_s:.3f} s (host clock)")
    print(f"window: {prog.window_s:.3f} s; compiles or cache loads in "
          f"window {prog.compiles['programs']} "
          f"({prog.compiles['seconds']:.3f} s), persistent-cache hits "
          f"{prog.compiles['cache_hits']}, misses "
          f"{prog.compiles['cache_misses']}; compiled "
          f"{sorted(set(prog.window_compiled))}")
    print(f"generator lag (s late when handed over): p50 "
          f"{np.percentile(lag, 50):.6f} p99 {np.percentile(lag, 99):.6f} "
          f"max {lag.max():.6f}")
    print(f"backlog at window end: {prog.backlog} events; window counters "
          f"{json.dumps(prog.counters)}")
    print(f"latency histogram ms: forgets [{histogram(forget_ms)}]")
    print(f"stalls in window: {prog.stalls}")
    sys.stdout.flush()

    trace = None
    if args.trace:
        tr = _import("devtrace")
        trace = tr.reduce(tr.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"trace: window {trace.window_s:.3f} s, device busy "
              f"{trace.busy_s:.3f} s; host spans "
              f"{json.dumps(trace.spans)}")
        top = sorted(trace.programs.items(), key=lambda kv: -kv[1][0])[:15]
        print("trace: programs by device time " + json.dumps(top))
    ctx = Ctx(cfg=cfg, setup_s=prog.setup_s, window_s=prog.window_s,
              counters=prog.counters, forget_ms=forget_ms,
              forget_steps=forget_steps, trace=trace,
              peaks=peaks.get(kind))
    metrics = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the program's state is gone once run_program returned: the
    # reference runs in its place
    gc.collect()
    numbers = check.compare(cfg, traffic, hist, sched, prog)
    correct = all(n["value"] <= n["limit"] for n in numbers.values())
    attempted = (int(np.sum((sched.ev_due >= warm)
                            & (sched.ev_due < warm + args.seconds)))
                 + len(forget_ms))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips, "memory_peak_bytes": prog.memory_peak_bytes}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": int(prog.failed), "metrics": metrics, "device": device}
    if trace is not None:
        out["breakdown"] = _import("devtrace").breakdown(trace)
    out["checks"] = numbers
    for name, n in numbers.items():
        print(f"check {name}: {n['value']!r} limit {n['limit']!r} "
              f"{'ok' if n['value'] <= n['limit'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _import(name: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(name)


if __name__ == "__main__":
    sys.exit(run())
