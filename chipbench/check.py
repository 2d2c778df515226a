"""The comparison that decides ``correct``.

Each number is computed from what the window produced and from
``reference.py`` alone, and held against its limit in the traffic
file's ``limits``:

- ``state_err``: the largest ``|engine - reference|`` over the user
  vectors of a seeded sample of the users the run touched (every user
  with a deletion and every forgotten user among them), after every
  submitted event was applied; pad columns must read 0.  TIFU vectors
  lie in [0, 1], so this is an absolute error.
- ``residue``: the sum of every forget receipt's residue (state leaves
  and serving caches of the forgotten user); exact, so its limit is 0.
- ``failed_ops``: dropped additions, dead letters and refused submits.
"""
from __future__ import annotations

import numpy as np

from reference import Users

SAMPLE = 256          # checked users, besides deleting and forgotten ones


def checked_users(cfg: dict, sched, n_submitted: int, seed: int):
    """Touched users whose rows the state check compares (sorted)."""
    rng = np.random.default_rng([seed, 2])
    users = sched.ev_user[:n_submitted]
    touched = np.unique(users)
    dels = np.unique(users[sched.ev_pos[:n_submitted] >= 0])
    pick = rng.choice(touched, size=min(SAMPLE, touched.size),
                      replace=False) if touched.size else touched
    return np.unique(np.concatenate([pick, dels, sched.forget_user]
                                    ).astype(np.int32))


def replay(cfg: dict, hist, sched, n_events: int, users=None) -> Users:
    """The reference after the load and the first ``n_events`` events,
    for ``users`` (all when None)."""
    ref = Users(cfg["group_size"])
    want = None if users is None else set(int(u) for u in users)
    for u in (range(cfg["n_users"]) if want is None else sorted(want)):
        for b in hist.baskets(u):
            ref.add(u, b)
    advance(ref, sched, 0, n_events, want)
    return ref


def advance(ref: Users, sched, lo: int, hi: int, want=None) -> list:
    """Apply schedule events ``lo:hi``; returns the users they touched."""
    touched = []
    for i in range(lo, hi):
        u = int(sched.ev_user[i])
        if want is not None and u not in want:
            continue
        if sched.ev_pos[i] < 0:
            row = sched.ev_items[i]
            ref.add(u, row[row >= 0])
        else:
            ref.delete(u, int(sched.ev_pos[i]))
        touched.append(u)
    return touched


def state_err(cfg: dict, hist, sched, n_events: int, users, rows,
              forgotten) -> float:
    ref = replay(cfg, hist, sched, n_events, users)
    for u in forgotten:
        ref.forget(int(u))
    want = ref.matrix(users, cfg["n_items"], cfg["r_b"], cfg["r_g"])
    rows = np.asarray(rows, np.float64)
    err = np.abs(rows[:, :cfg["n_items"]] - want).max(initial=0.0)
    pad = np.abs(rows[:, cfg["n_items"]:]).max(initial=0.0)
    return float(max(err, pad))


def compare(cfg: dict, traffic: dict, hist, sched, prog) -> dict:
    """Every number of the cell, each with its limit."""
    limits = traffic["limits"]
    drv = prog.player
    forgotten = [int(sched.forget_user[f[0]]) for f in drv.forgets]
    numbers = {"failed_ops": float(prog.failed)}
    numbers["state_err"] = state_err(cfg, hist, sched, drv.ie,
                                     prog.checked_users, prog.rows,
                                     forgotten)
    if forgotten:
        numbers["residue"] = float(sum(sum(r.values())
                                       for r in prog.residue))
    return {k: {"value": v, "limit": limits[k]}
            for k, v in numbers.items()}
