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
- ``read_err``: over a seeded sample of the window's reads and over the
  n ranks of each answer, the largest gap by which the reference score
  of the item returned at a rank lies below the reference's own score
  at that rank, each read against the state it saw; 0 when every
  checked read returned the reference's top-n in order (either order
  of equal scores).  The configurations state float32: neighbours whose
  distances float32 cannot tell from the k-th's (within ``F32_TIE`` of
  the squared norms) are ties, and the gap is taken from the highest
  score the returned item can have to the lowest the rank's can
  (``reference.blended_bounds``).  Scores lie in [0, 1]; an answer
  that is no list of n distinct real items reads ``MALFORMED``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from reference import Users, blended_bounds

SAMPLE = 256          # checked users, besides deleting and forgotten ones
MALFORMED = 2.0       # read_err of an answer that is no top-n list
# a distance's float32 rounding, relative to the squared norms it is
# taken from: some 170 units of the last place (2**-24), far under the
# 2**-9 of bfloat16
F32_TIE = 1e-5


@dataclasses.dataclass
class Read:
    """A checked read: its answer and the state it saw.

    The state is the load, then for each user every one of its first
    ``submitted`` schedule events but the last ``pending[user]`` (the
    engine applies a user's events in order), and the first ``forgets``
    forgets.
    """

    user: int
    ids: np.ndarray
    submitted: int
    pending: dict
    forgets: int


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


def checked_reads(sched, warm: float, seconds: float, n: int,
                  seed: int) -> np.ndarray:
    """Schedule indices of the window reads the check compares (sorted)."""
    due = sched.read_due
    idx = np.nonzero((due >= warm) & (due < warm + seconds))[0]
    rng = np.random.default_rng([seed, 4])
    return np.sort(rng.choice(idx, size=min(n, idx.size), replace=False))


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
        _apply(ref, sched, i)
        touched.append(u)
    return touched


def _apply(ref: Users, sched, i: int) -> None:
    u = int(sched.ev_user[i])
    if sched.ev_pos[i] < 0:
        row = sched.ev_items[i]
        ref.add(u, row[row >= 0])
    else:
        ref.delete(u, int(sched.ev_pos[i]))


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


def read_states(cfg: dict, hist, sched, reads: list, dtype=np.float64):
    """Yields ``(read, corpus, sqnorm)`` for ``reads`` in schedule order:
    every user's vector in ``dtype`` as the read saw it, and the squared
    norms.  The arrays are updated in place between reads."""
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    ref = replay(cfg, hist, sched, 0)
    # a lower precision's vectors are worked on in float32
    work = np.float64 if dtype == np.float64 else np.float32
    corpus = ref.matrix(range(n_users), n_items, cfg["r_b"], cfg["r_g"],
                        dtype=dtype).astype(work)
    sqnorm = np.einsum("ij,ij->i", corpus, corpus)
    n_max = max((r.submitted for r in reads), default=0)
    order = np.argsort(sched.ev_user[:n_max], kind="stable")
    bounds = np.searchsorted(sched.ev_user[:n_max][order],
                             np.arange(n_users + 1))
    applied = np.zeros(n_users, np.int64)
    last = Read(-1, None, 0, {}, 0)
    for r in reads:
        changed = set(np.unique(sched.ev_user[last.submitted:r.submitted])
                      .tolist()) | set(last.pending) | set(r.pending)
        for u in changed:
            mine = order[bounds[u]:bounds[u + 1]]
            upto = int(np.searchsorted(mine, r.submitted)) - r.pending.get(
                u, 0)
            if upto < applied[u]:
                raise ValueError(f"user {u}: {upto} events applied at a "
                                 f"read after {applied[u]}")
            for i in mine[applied[u]:upto]:
                _apply(ref, sched, int(i))
            applied[u] = upto
        for u in sched.forget_user[last.forgets:r.forgets].tolist():
            ref.forget(u)
            changed.add(u)
        for u in changed:
            corpus[u] = ref.vector(u, n_items, cfg["r_b"], cfg["r_g"], dtype)
            sqnorm[u] = corpus[u] @ corpus[u]
        last = r
        yield r, corpus, sqnorm


def read_errs(cfg: dict, hist, sched, reads: list, topn: int) -> list:
    """Each checked read's gap to the reference's top-n (see above)."""
    out = []
    for r, corpus, sqnorm in read_states(cfg, hist, sched, reads):
        lo, hi = blended_bounds(corpus, sqnorm, r.user, cfg["k_neighbors"],
                                cfg["alpha"], F32_TIE)
        ids = np.asarray(r.ids)
        if (ids.shape != (topn,) or np.unique(ids).size != topn
                or ((ids < 0) | (ids >= lo.size)).any()):
            out.append(MALFORMED)
            continue
        best = -np.sort(-lo)[:topn]
        out.append(float(max((best - hi[ids]).max(), 0.0)))
    return out


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
    if sched.read_due.size:
        reads = [drv.kept[i] for i in sorted(drv.kept)]
        errs = read_errs(cfg, hist, sched, reads, traffic["read_topn"])
        # a run that kept no read has checked nothing
        numbers["read_err"] = max(errs, default=MALFORMED)
    return {k: {"value": v, "limit": limits[k]}
            for k, v in numbers.items()}
