"""Plain TIFU-kNN reference: the yardstick that decides ``correct``.

Imports nothing of the program.  User vectors are recomputed from
scratch from each user's basket list and group sizes (arXiv:2201.13313
Eq. 1-2, with the varying group size of §4.3): a basket at in-group
position ``p`` of group ``j`` (1-based, ``k`` groups, ``tau_j`` baskets
in group ``j``) weighs ``r_b^(tau_j - p) / tau_j * r_g^(k - j) / k``.
An addition opens a new group once the last holds ``group_size``
baskets; a deletion shrinks its group, and a group left empty vanishes.
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
