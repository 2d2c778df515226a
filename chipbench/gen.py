"""Seeded histories and fixed-interval schedules for the chip benchmark.

The histories follow the generator of ``repro.data.synthetic`` (Zipf
item popularity, a per-user preferred-item pool drawn by popularity,
``repeat_bias`` of each basket from the pool and the rest fresh by
popularity, Poisson basket counts and sizes around the Table-1 means),
vectorised over all baskets at once: sampling without replacement by
popularity is taken as the first ``k`` distinct draws of a
with-replacement stream, which is the same distribution.  The two
Poisson tails are cut at the configuration's caps.

A schedule is every event and forget of one run, with its due time,
built before the run from ``--seed``: the harness only replays it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Histories:
    """Every user's generated baskets, chronological, as padded rows."""

    items: np.ndarray     # i32[n_baskets, B], -1 padded
    owner: np.ndarray     # i32[n_baskets], sorted by user
    start: np.ndarray     # i64[n_users + 1]: user u's rows are start[u]:start[u+1]
    pool: np.ndarray      # i32[n_users, P], each user's preferred items

    @property
    def n_baskets(self) -> np.ndarray:
        return np.diff(self.start)

    def baskets(self, user: int) -> list:
        rows = self.items[self.start[user]:self.start[user + 1]]
        return [r[r >= 0] for r in rows]


class Popularity:
    """Zipf item popularity, sampled in O(1) per draw (Vose's alias
    method: an item slot, then the slot's item or its alias)."""

    def __init__(self, n_items: int, exponent: float):
        pop = 1.0 / np.arange(1, n_items + 1) ** exponent
        scaled = pop / pop.sum() * n_items
        self.keep = np.ones(n_items)
        self.alias = np.arange(n_items)
        small = [i for i in range(n_items) if scaled[i] < 1.0]
        large = [i for i in range(n_items) if scaled[i] >= 1.0]
        while small and large:
            s, g = small.pop(), large.pop()
            self.keep[s], self.alias[s] = scaled[s], g
            scaled[g] -= 1.0 - scaled[s]
            (small if scaled[g] < 1.0 else large).append(g)
        self.n = n_items

    def draw(self, rng, shape) -> np.ndarray:
        slot = rng.integers(0, self.n, shape)
        return np.where(rng.random(shape) < self.keep[slot], slot,
                        self.alias[slot])


def _distinct_draws(rng, pop: Popularity, want: np.ndarray) -> np.ndarray:
    """Per row, ``want[r]`` distinct items drawn by popularity, -1 padded.

    Row ``r`` keeps the first ``want[r]`` distinct values of a
    with-replacement stream (sequential sampling without replacement),
    sorted; rows whose stream holds too few distinct values draw again.
    """
    rows = want.size
    width = int(want.max(initial=0))
    out = np.full((rows, max(width, 1)), -1, np.int32)
    todo = np.nonzero(want > 0)[0]
    draws = 2 * max(width, 1) + 8
    while todo.size:
        cand = pop.draw(rng, (todo.size, draws))
        order = np.argsort(cand, axis=1, kind="stable")
        srt = np.take_along_axis(cand, order, axis=1)
        first = np.ones_like(srt, dtype=bool)
        first[:, 1:] = srt[:, 1:] != srt[:, :-1]
        is_first = np.zeros_like(first)
        np.put_along_axis(is_first, order, first, axis=1)
        rank = np.cumsum(is_first, axis=1)
        keep = is_first & (rank <= want[todo, None])
        done = keep.sum(axis=1) == want[todo]
        big = np.iinfo(np.int32).max
        vals = np.sort(np.where(keep[done], cand[done], big), axis=1)
        vals = vals[:, :out.shape[1]]
        out[todo[done]] = np.where(vals == big, -1, vals)
        todo = todo[~done]
    return out


def _pack_unique(parts: np.ndarray, width: int) -> np.ndarray:
    """Sorted distinct non-negative values of each row, -1 padded."""
    big = np.iinfo(np.int32).max
    v = np.where(parts < 0, big, parts)
    v.sort(axis=1)
    dup = np.zeros_like(v, dtype=bool)
    dup[:, 1:] = v[:, 1:] == v[:, :-1]
    v[dup] = big
    v.sort(axis=1)
    v = v[:, :width]
    return np.where(v == big, -1, v).astype(np.int32)


def make_baskets(rng, cfg: dict, pop: Popularity, pool: np.ndarray,
                 users: np.ndarray) -> np.ndarray:
    """One new basket for each entry of ``users``: i32[len(users), B]."""
    cap = cfg["items_per_basket_cap"]
    n = users.size
    size = np.clip(rng.poisson(cfg["avg_basket_size"], n), 1, cap)
    n_rep = np.minimum((size * cfg["repeat_bias"]).astype(np.int64),
                       pool.shape[1])
    # n_rep pool items without replacement: a random permutation prefix
    perm = np.argsort(rng.random((n, pool.shape[1])), axis=1)
    rep = np.take_along_axis(pool[users], perm, axis=1)
    rep = np.where(np.arange(pool.shape[1]) < n_rep[:, None], rep, -1)
    fresh = _distinct_draws(rng, pop, size - n_rep)
    return _pack_unique(np.concatenate([rep, fresh], axis=1), cap)


def histories(cfg: dict, seed: int) -> Histories:
    """Every user's history at the configuration's Table-1 statistics."""
    rng = np.random.default_rng([seed, 0])
    n_users, n_items = cfg["n_users"], cfg["n_items"]
    pop = Popularity(n_items, cfg["zipf_exponent"])
    n_b = np.clip(rng.poisson(cfg["avg_baskets"], n_users), 2,
                  cfg["baskets_per_user_cap"])
    pool_size = max(8, int(cfg["avg_basket_size"] * cfg["pool_factor"]))
    pool = _distinct_draws(rng, pop, np.full(n_users, pool_size))
    owner = np.repeat(np.arange(n_users, dtype=np.int32), n_b)
    items = make_baskets(rng, cfg, pop, pool, owner)
    start = np.concatenate([[0], np.cumsum(n_b)])
    return Histories(items=items, owner=owner, start=start, pool=pool)


@dataclasses.dataclass
class Schedule:
    """One run's traffic, every entry with its due time in seconds.

    ``ev_*`` are basket additions (``ev_pos == -1``, items in
    ``ev_items``) and basket deletions (``ev_pos >= 0``), sorted by due
    time; forgets are their own fixed-interval stream.
    """

    ev_due: np.ndarray
    ev_user: np.ndarray
    ev_items: np.ndarray
    ev_pos: np.ndarray
    forget_due: np.ndarray
    forget_user: np.ndarray


def _ticks(interval: float, t_end: float, phase: float) -> np.ndarray:
    if interval <= 0:
        return np.zeros(0)
    return np.arange(phase * interval, t_end, interval)


def max_history(cfg: dict, traffic: dict) -> int:
    """The most baskets one user can hold under ``traffic``: the
    configuration's ``max_baskets`` has to be at least this."""
    return cfg["baskets_per_user_cap"] + max(traffic["adds_per_user_cap"],
                                             traffic["warm_del_adds"])


def schedule(cfg: dict, traffic: dict, hist: Histories, seed: int,
             t_end: float) -> Schedule:
    """The fixed-interval schedule of ``traffic`` over ``[0, t_end)``.

    ``[0, warm_s)`` is the warm-up; its first half holds besides
    ``warm_add_bursts`` bursts of additions due at once,
    ``warm_forgets`` forgets and ``warm_dels`` single-basket deletions,
    alone and in pairs, half of them of users first given
    ``warm_del_adds`` baskets, so every shape the window uses compiles
    before it.
    Additions come ``add_burst`` at a time, bursts evenly spaced at
    ``add_rate`` additions per second; their users are drawn in
    proportion to their generated basket count, and no user gets more
    than ``adds_per_user_cap``.  The paper's §6.1 deletions (``del_user_frac``
    of the users each delete ``del_basket_frac`` of their loaded
    baskets) are spread evenly over the run.  Forgets take distinct
    users, stratified over the basket-count distribution so every seed
    forgets the same spread of history lengths; forgotten users get no
    other traffic.
    """
    rng = np.random.default_rng([seed, 1])
    n_users = cfg["n_users"]
    n_b = hist.n_baskets
    pop = Popularity(cfg["n_items"], cfg["zipf_exponent"])
    warm = traffic["warm_s"]
    # stratified over history length: every seed gets the same spread
    by_len = np.argsort(n_b + rng.random(n_users), kind="stable")

    def stratified(n: int, pool: np.ndarray) -> np.ndarray:
        ranked = by_len[np.isin(by_len, pool)]
        return rng.permutation(
            ranked[((np.arange(n) + 0.5) / n * ranked.size).astype(int)]
        ).astype(np.int32) if n else np.zeros(0, np.int32)

    n_wf = traffic["warm_forgets"]
    forget_due = np.concatenate([
        (np.arange(n_wf) + 0.5) * warm / 2 / max(n_wf, 1),
        warm + _ticks(traffic["forget_interval_s"], t_end - warm, 0.5)])
    forget_user = stratified(forget_due.size, np.arange(n_users))
    others = np.setdiff1d(np.arange(n_users), forget_user)
    # warm-up deletions, one basket each, alone and in pairs (the window
    # cuts both), of users spread over history length; every other one
    # first gets ``warm_del_adds`` baskets, as the window's deleting
    # users have by then: the delete path's shapes compile before it
    n_wd = traffic["warm_dels"]
    w_user = stratified(n_wd, others)
    others = np.setdiff1d(others, w_user)
    grown = np.repeat(w_user[1::2], traffic["warm_del_adds"])

    burst = int(traffic["add_burst"])
    add_due = np.repeat(_ticks(burst / traffic["add_rate"], t_end, 0.0),
                        burst) if traffic["add_rate"] > 0 else np.zeros(0)
    # warm-up bursts, due at once, so the add path's shapes compile
    add_due = np.concatenate([np.zeros(traffic["warm_add_bursts"] * burst),
                              add_due])
    n_add = add_due.size
    weight = n_b[others].astype(np.float64)
    cap = traffic["adds_per_user_cap"]
    add_user = (_capped_draw(rng, others, weight, n_add, cap) if burst == 1
                else _burst_draw(rng, others, weight, n_add, cap, burst))
    add_due = np.concatenate([np.zeros(grown.size), add_due])
    add_user = np.concatenate([grown, add_user]).astype(np.int32)
    n_add = add_user.size
    add_items = make_baskets(rng, cfg, pop, hist.pool, add_user)

    del_users = rng.choice(others, size=max(1, int(n_users
                                                   * traffic["del_user_frac"])),
                           replace=False) if traffic["del_user_frac"] else []
    del_user, del_pos = [], []
    for u in del_users:
        remaining = int(n_b[u])
        for _ in range(max(1, int(remaining * traffic["del_basket_frac"]))):
            del_user.append(u)
            del_pos.append(int(rng.integers(0, remaining)))
            remaining -= 1
    n_del = len(del_user)
    del_due = (np.arange(n_del) + 0.5) * (t_end / max(n_del, 1))
    order = rng.permutation(n_del)
    del_user = np.asarray(del_user, np.int32)[order] if n_del else \
        np.zeros(0, np.int32)
    del_pos = np.asarray(del_pos, np.int32)[order] if n_del else \
        np.zeros(0, np.int32)
    # a user's deletions stay in the order their positions were drawn
    for u in np.unique(del_user):
        idx = np.nonzero(del_user == u)[0]
        del_pos[idx] = del_pos[idx][np.argsort(order[idx])]

    w_pos = (rng.random(n_wd) * n_b[w_user]).astype(np.int32)
    group = np.arange(n_wd) // 3 * 2 + (np.arange(n_wd) % 3 > 0)
    w_due = (group + 0.5) * warm / 2 / max(int(group.max(initial=0)) + 1, 1)
    del_due = np.concatenate([w_due, del_due])
    del_user = np.concatenate([w_user, del_user]).astype(np.int32)
    del_pos = np.concatenate([w_pos, del_pos]).astype(np.int32)
    n_del = del_due.size

    due = np.concatenate([add_due, del_due])
    srt = np.argsort(due, kind="stable")
    width = add_items.shape[1]
    items = np.concatenate([add_items, np.full((n_del, width), -1, np.int32)])
    users = np.concatenate([add_user, del_user]).astype(np.int32)
    pos = np.concatenate([np.full(n_add, -1, np.int32), del_pos])

    return Schedule(ev_due=due[srt], ev_user=users[srt], ev_items=items[srt],
                    ev_pos=pos[srt], forget_due=forget_due,
                    forget_user=forget_user)


def _capped_draw(rng, users, weight, n, cap) -> np.ndarray:
    """``n`` users drawn by ``weight``, none more than ``cap`` times."""
    quota = np.zeros(users.size, np.int64)
    out = np.zeros(n, np.int32)
    filled = 0
    while filled < n:
        room = quota < cap
        if not room.any():
            raise ValueError(f"adds_per_user_cap {cap} leaves no room for "
                             f"{n} additions")
        p = np.where(room, weight, 0.0)
        draw = rng.choice(users.size, size=n - filled, p=p / p.sum())
        # the i-th draw of a user is kept while the user is under the cap
        order = np.argsort(draw, kind="stable")
        srt = draw[order]
        first = np.concatenate([[0], np.nonzero(np.diff(srt))[0] + 1])
        counts = np.diff(np.concatenate([first, [srt.size]]))
        rank = np.arange(srt.size) - np.repeat(first, counts)
        ok = np.zeros(draw.size, bool)
        ok[order] = rank < cap - quota[srt]
        kept = draw[ok]
        np.add.at(quota, kept, 1)
        out[filled:filled + kept.size] = users[kept]
        filled += kept.size
    return out


def _burst_draw(rng, users, weight, n, cap, burst) -> np.ndarray:
    """As :func:`_capped_draw`, with the users of each burst distinct:
    a burst is cut as one micro-batch, one event per user."""
    quota = np.zeros(users.size, np.int64)
    out = []
    for b in range(0, n, burst):
        room = np.nonzero(quota < cap)[0]
        if room.size < burst:
            raise ValueError(f"adds_per_user_cap {cap} leaves too few users "
                             f"for bursts of {burst}")
        p = weight[room] / weight[room].sum()
        pick = rng.choice(room, size=min(burst, n - b), replace=False, p=p)
        quota[pick] += 1
        out.append(users[pick])
    return np.concatenate(out).astype(np.int32) if out else \
        np.zeros(0, np.int32)
