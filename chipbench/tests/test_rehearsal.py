"""Each cell's whole path at a tiny size on the CPU: load, window and
comparison, and the faults the comparison has to catch.

The look for a chip is skipped (``require_tpu=False``); everything else
is the run the benchmark makes.  Run with ``pytest chipbench/tests``.
"""
import json

import jax.numpy as jnp
import pytest

import run

CELLS = ["tafeng.ingest", "valuedshopper.forget"]


def _run(spec, cell, capsys, seed=3, trace=0):
    rc = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                  "2", "--trace", str(trace)], require_tpu=False,
                 spec=spec(cell))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(spec, cell, capsys):
    out = _run(spec, cell, capsys)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


def test_traced_run_reports_counters(spec, capsys):
    # on the CPU there is no device plane: the trace readers find
    # nothing and stay silent, the counter readers still report
    out = _run(spec, "tafeng.ingest", capsys, trace=1)
    assert set(out["metrics"]) == {"events_per_step.ingest"}
    assert out["device"]["window_s"] > 0


def _noop(self, *a, **k):
    return None


def _half_batch(orig):
    def cut(self):
        taken = orig(self)
        return taken[:len(taken) // 2]
    return cut


@pytest.mark.parametrize("cell,fault", [
    ("tafeng.ingest", "state_unchanged"),
    ("tafeng.ingest", "half_batch"),
    ("tafeng.ingest", "corrupt_row"),
    ("valuedshopper.forget", "state_unchanged"),
    ("valuedshopper.forget", "delete_applier_off"),
])
def test_fault_makes_run_incorrect(spec, cell, fault, capsys, monkeypatch):
    import dataclasses

    from repro.core.types import StreamState
    from repro.streaming import StreamingEngine, engine

    if fault == "state_unchanged":
        # every step returns the state it was given (after set-up's load)
        orig = StreamingEngine._apply_sub_batches
        monkeypatch.setattr(
            StreamingEngine, "_apply_sub_batches",
            lambda self, *a: orig(self, *a) if self.batch_size > 16
            else None)
    elif fault == "half_batch":
        orig = StreamingEngine._cut_batch
        half = _half_batch(orig)
        monkeypatch.setattr(
            StreamingEngine, "_cut_batch",
            lambda self: half(self) if self.batch_size == 16
            else orig(self))
    elif fault == "corrupt_row":
        orig = StreamState.materialized_user_vecs

        def corrupt(self, *a, **k):
            out = orig(self, *a, **k)
            return out.at[:, 0].add(1e-3)
        monkeypatch.setattr(StreamState, "materialized_user_vecs", corrupt)
    elif fault == "delete_applier_off":
        # the basket-deletion applier's float output is off by 0.1% in
        # every row it writes; a forgotten user's row is rebuilt from
        # the integer leaves afterwards, so only the warm-up's deletions
        # of users the check keeps can show it
        orig = engine.apply_del_basket_batch

        def off(state, batch, *a, **k):
            out = orig(state, batch, *a, **k)
            rows = jnp.where(batch.valid, batch.user, out.user_vecs.shape[0])
            return dataclasses.replace(out, user_vecs=out.user_vecs.at[
                rows].multiply(1.001, mode="drop"))
        monkeypatch.setattr(engine, "apply_del_basket_batch", off)
    out = _run(spec, cell, capsys)
    assert not out["correct"], out["checks"]
