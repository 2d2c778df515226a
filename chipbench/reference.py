"""Plain TIFU-kNN reference: the yardstick that decides ``correct``.

Imports nothing of the program.  User vectors are recomputed from
scratch from each user's basket list and group sizes (arXiv:2201.13313
Eq. 1-2, with the varying group size of §4.3): a basket at in-group
position ``p`` of group ``j`` (1-based, ``k`` groups, ``tau_j`` baskets
in group ``j``) weighs ``r_b^(tau_j - p) / tau_j * r_g^(k - j) / k``.
An addition opens a new group once the last holds ``group_size``
baskets; a deletion shrinks its group, and a group left empty vanishes.

A read's prediction (§2.2) blends the user's own vector with the mean
of the ``k`` nearest other users' vectors by euclidean distance.
"""
from __future__ import annotations

import numpy as np


class Users:
    """Basket lists and group sizes of the users a check looks at."""

    def __init__(self, group_size: int):
        self.m = group_size
        self.hist: dict = {}
        self.groups: dict = {}

    def add(self, user: int, basket) -> None:
        h = self.hist.setdefault(user, [])
        g = self.groups.setdefault(user, [])
        h.append(np.unique(np.asarray(basket, np.int64)))
        if not g or g[-1] >= self.m:
            g.append(1)
        else:
            g[-1] += 1

    def delete(self, user: int, pos: int) -> None:
        h, g = self.hist[user], self.groups[user]
        if not 0 <= pos < len(h):
            raise IndexError(f"user {user}: no basket at {pos} of {len(h)}")
        del h[pos]
        start = 0
        for j, tau in enumerate(g):
            if pos < start + tau:
                g[j] -= 1
                if g[j] == 0:
                    del g[j]
                return
            start += tau

    def forget(self, user: int) -> None:
        self.hist[user] = []
        self.groups[user] = []

    def weights(self, user: int, r_b: float, r_g: float, dtype=np.float64):
        """Per-basket weights of ``user``'s vector, in history order."""
        g = self.groups.get(user, [])
        k = len(g)
        out = []
        for j, tau in enumerate(g, start=1):
            for p in range(1, tau + 1):
                out.append(dtype(r_b) ** (tau - p) / dtype(tau)
                           * dtype(r_g) ** (k - j) / dtype(k))
        return out

    def vector(self, user: int, n_items: int, r_b: float, r_g: float,
               dtype=np.float64) -> np.ndarray:
        """``user``'s TIFU vector over the real catalogue."""
        v = np.zeros(n_items, dtype)
        for w, b in zip(self.weights(user, r_b, r_g, dtype),
                        self.hist.get(user, [])):
            v[b] = (v[b] + w).astype(dtype)
        return v

    def matrix(self, users, n_items: int, r_b: float, r_g: float,
               dtype=np.float64, width: int | None = None) -> np.ndarray:
        """Rows of ``users`` as one ``[len(users), width]`` array."""
        out = np.zeros((len(users), width or n_items), dtype)
        for r, u in enumerate(users):
            out[r, :n_items] = self.vector(int(u), n_items, r_b, r_g, dtype)
        return out


def _distances(corpus: np.ndarray, sqnorm: np.ndarray, user: int):
    """Squared euclidean distance of every row to ``user``'s, the user's
    own at infinity."""
    q = corpus[user]
    nz = np.nonzero(q)[0]
    dist = sqnorm - 2 * (corpus[:, nz] @ q[nz]) + sqnorm[user]
    dist[user] = np.inf
    return dist


def blended_scores(corpus: np.ndarray, sqnorm: np.ndarray, user: int,
                   k: int, alpha: float) -> np.ndarray:
    """``alpha`` times ``user``'s row plus ``1 - alpha`` times the mean of
    the rows of its ``k`` nearest other users, in ``corpus``'s dtype.

    ``corpus`` holds every user's vector, one row each, and ``sqnorm``
    their squared norms.  Distances are euclidean; among equal distances
    the lower user id is nearer.  ``k`` is at most the number of other
    users.
    """
    dist = _distances(corpus, sqnorm, user)
    k = max(1, min(k, corpus.shape[0] - 1))
    nearest = np.argsort(dist, kind="stable")[:k]
    return alpha * corpus[user] + (1 - alpha) * corpus[nearest].mean(axis=0)


def blended_bounds(corpus: np.ndarray, sqnorm: np.ndarray, user: int,
                   k: int, alpha: float, tie: float) -> tuple:
    """The lowest and the highest blended score of each item over every
    set of ``k`` nearest users that distances off by rounding could pick.

    A user whose distance lies within ``tie * (|q|^2 + |c|^2)`` of the
    k-th nearest's, that user's and the k-th's slack added, is a tie at
    the k-th place: any of the ties may fill the places that the surely
    nearer users leave.  Without such ties both bounds are
    ``blended_scores``.
    """
    dist = _distances(corpus, sqnorm, user)
    k = max(1, min(k, corpus.shape[0] - 1))
    kth = np.argsort(dist, kind="stable")[k - 1]
    slack = tie * (sqnorm + sqnorm[user])
    ties = np.abs(dist - dist[kth]) <= slack + slack[kth]
    sure = (dist < dist[kth]) & ~ties
    free = k - int(sure.sum())
    lo = corpus[sure].sum(axis=0)
    hi = lo.copy()
    cand = corpus[ties]
    cols = np.flatnonzero(cand.any(axis=0))
    cand = np.sort(cand[:, cols], axis=0)
    lo[cols] += cand[:free].sum(axis=0)
    hi[cols] += cand[cand.shape[0] - free:].sum(axis=0)
    own = alpha * corpus[user]
    return own + (1 - alpha) * lo / k, own + (1 - alpha) * hi / k


def top_n(scores: np.ndarray, n: int) -> np.ndarray:
    """The ``n`` best items, best first, lower id first among ties."""
    return np.argsort(-scores, kind="stable")[:n]
