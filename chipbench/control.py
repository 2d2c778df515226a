#!/usr/bin/env python3
"""The control of the correctness check: the reference in the program's
place, one precision step down, must come out not correct.

    python3 chipbench/control.py --workload valuedshopper.forget \\
        --seconds 30 --seeds 1 2 3

The configurations state float32 state.  The control replays the same
schedule a run plays (every event due before the window's end applied,
every forget due done) with its user vectors computed in bfloat16, and
feeds what it produced to the same comparison as a run.  It prints each
number beside its limit, per seed.  The benchmark's own runs never run
it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import gen  # noqa: E402


@dataclasses.dataclass
class _Player:
    ie: int
    forgets: list


@dataclasses.dataclass
class _Program:
    seconds: float
    player: _Player
    checked_users: np.ndarray
    rows: np.ndarray
    residue: list
    failed: int


def control_program(cfg: dict, traffic: dict, hist, sched, seed: int,
                    seconds: float) -> _Program:
    """What the reference, one precision step down, would have kept."""
    import ml_dtypes

    low = ml_dtypes.bfloat16
    t_end = traffic["warm_s"] + seconds
    n_ev = int(np.searchsorted(sched.ev_due, t_end))
    forgets = [(i, due, 0.0, 0, None) for i, due in
               enumerate(sched.forget_due) if due < t_end]
    forgotten = [int(sched.forget_user[f[0]]) for f in forgets]
    users = check.checked_users(cfg, sched, n_ev, seed)
    ref = check.replay(cfg, hist, sched, n_ev, users)
    for u in forgotten:
        ref.forget(u)
    rows = ref.matrix(users, cfg["n_items"], cfg["r_b"], cfg["r_g"],
                      dtype=low).astype(np.float32)
    residue = [{"control": 0.0} for _ in forgets]
    return _Program(seconds=seconds, player=_Player(n_ev, forgets),
                    checked_users=users, rows=rows, residue=residue,
                    failed=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run

    cfg, traffic, _, _ = run.cell_spec(args.workload)
    failed_all = True
    for seed in args.seeds:
        hist = gen.histories(cfg, seed)
        sched = gen.schedule(cfg, traffic, hist, seed,
                             traffic["warm_s"] + args.seconds)
        prog = control_program(cfg, traffic, hist, sched, seed,
                               args.seconds)
        numbers = check.compare(cfg, traffic, hist, sched, prog)
        fails = [k for k, n in numbers.items() if n["value"] > n["limit"]]
        failed_all &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": fails, "checks": numbers}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
