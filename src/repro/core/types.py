"""Core datatypes for the TIFU-kNN maintenance system.

Two state representations coexist (see DESIGN.md §3):

* ``RaggedUserState`` — per-user ragged numpy state, used by the
  paper-faithful reference engine (``core.ref_engine``).  Updates touch
  exactly the suffix the paper's algorithms touch, so latency benchmarks
  reproduce the paper's asymptotics (Fig. 2a/2b).

* ``StreamState`` — struct-of-arrays padded JAX state for ``M`` users,
  used by the batched SPMD streaming engine (``streaming.engine``).  Its
  vector tables are stored *scaled* (DESIGN.md §3.3) so basket additions
  apply sparse deltas; kind-partitioned sub-batches (``AddBatch``,
  ``DelBasketBatch``, ``DelItemBatch``, DESIGN.md §4.1) carry one
  homogeneous micro-batch each.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = -1  # padding value for item ids in basket arrays
LANE = 128   # TPU vector lane width: the dense item axis is padded to it


@dataclasses.dataclass(frozen=True)
class TifuParams:
    """TIFU-kNN hyper-parameters (Table 1 of the paper).

    Attributes:
      n_items: vocabulary size ``|I|``.
      group_size: nominal group size ``m``.
      r_b: within-group (basket) time-decay rate, ``0 < r_b <= 1``.
      r_g: across-group time-decay rate, ``0 < r_g <= 1``.
      k_neighbors: number of nearest neighbours for the CF component.
      alpha: weight of the personal component in the final prediction.
    """

    n_items: int
    group_size: int = 7
    r_b: float = 0.9
    r_g: float = 0.7
    k_neighbors: int = 300
    alpha: float = 0.7

    def __post_init__(self) -> None:
        if not (0.0 < self.r_b <= 1.0):
            raise ValueError(f"r_b must be in (0, 1], got {self.r_b}")
        if not (0.0 < self.r_g <= 1.0):
            raise ValueError(f"r_g must be in (0, 1], got {self.r_g}")
        if self.group_size < 1:
            raise ValueError("group_size must be >= 1")


# Hyper-parameters used in the paper's experiments (Table 1):
#   [m, r_b, r_g, k, alpha]
PAPER_HYPERPARAMS = {
    "tafeng": TifuParams(n_items=11997, group_size=7, r_b=0.9, r_g=0.7,
                         k_neighbors=300, alpha=0.7),
    "instacart": TifuParams(n_items=7999, group_size=3, r_b=0.9, r_g=0.7,
                            k_neighbors=900, alpha=0.9),
    "valuedshopper": TifuParams(n_items=7874, group_size=7, r_b=1.0, r_g=0.6,
                                k_neighbors=300, alpha=0.7),
}


@dataclasses.dataclass
class RaggedUserState:
    """Paper-faithful per-user state (ragged, numpy).

    ``history`` is a list of baskets, each basket a 1-D int array of item
    ids.  ``group_sizes[j]`` is the number of baskets in group ``j`` under
    the *varying group size* relaxation (paper §4.3).  ``user_vec`` and
    ``last_group_vec`` are dense ``|I|`` vectors.  ``err_mult`` tracks the
    worst-case multiplicative error factor accumulated by decremental
    updates (beyond-paper stability tracker, see core.stability).
    """

    history: List[np.ndarray]
    group_sizes: List[int]
    user_vec: np.ndarray
    last_group_vec: np.ndarray
    err_mult: float = 1.0

    @property
    def n_baskets(self) -> int:
        return len(self.history)

    @property
    def n_groups(self) -> int:
        return len(self.group_sizes)

    @staticmethod
    def empty(n_items: int) -> "RaggedUserState":
        return RaggedUserState(
            history=[],
            group_sizes=[],
            user_vec=np.zeros(n_items, dtype=np.float64),
            last_group_vec=np.zeros(n_items, dtype=np.float64),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StreamState:
    """Padded struct-of-arrays state for ``M`` users (JAX path).

    Shapes (``M`` users, ``N`` max baskets, ``B`` max basket size,
    ``K`` max groups, ``I`` items):

      user_vecs:       f32[M, I]   raw (scaled) storage, see below
      last_group_vecs: f32[M, I]   raw (scaled) storage, see below
      history:         i32[M, N, B]   (PAD_ID padded)
      group_sizes:     i32[M, K]
      n_baskets:       i32[M]
      n_groups:        i32[M]
      err_mult:        f32[M]
      uv_scale:        f32[M]
      lgv_scale:       f32[M]

    The item axis ``I`` is the catalogue padded up to the 128-lane tile
    (``n_cols``); ``n_real`` records the catalogue itself (static, not a
    leaf).  Pad columns are never written — ids are validated against
    the catalogue — so they stay exactly zero and change no distance.

    Scaled representation (DESIGN.md §3.3): the *true* TIFU vectors are

        user_vec(u)       = uv_scale[u]  * user_vecs[u]
        last_group_vec(u) = lgv_scale[u] * last_group_vecs[u]

    Basket additions (Eq. 7-9) rescale the whole user/group vector by a
    per-user scalar; storing that scalar separately turns every addition
    into a *sparse* delta whose support is only the touched items, so the
    batched add path never reads or writes an ``[n_items]`` temporary.
    Use :meth:`materialized_user_vecs` for serving / kNN / comparisons.
    Scales only shrink; ``core.updates.renormalize_users`` folds them back
    into the raw rows before they underflow (SCALE_FLOOR).
    """

    user_vecs: jax.Array
    last_group_vecs: jax.Array
    history: jax.Array
    group_sizes: jax.Array
    n_baskets: jax.Array
    n_groups: jax.Array
    err_mult: jax.Array
    uv_scale: jax.Array
    lgv_scale: jax.Array
    # catalogue size; None: every column of the dense leaves is an item
    n_real: Optional[int] = None

    def tree_flatten(self) -> Tuple[Tuple[jax.Array, ...], Optional[int]]:
        children = (self.user_vecs, self.last_group_vecs, self.history,
                    self.group_sizes, self.n_baskets, self.n_groups,
                    self.err_mult, self.uv_scale, self.lgv_scale)
        return children, self.n_real

    @classmethod
    def tree_unflatten(cls, aux: Optional[int],
                       children: Tuple[jax.Array, ...]) -> "StreamState":
        return cls(*children, n_real=aux)

    # -- true-value accessors -------------------------------------------------

    def materialized_user_vecs(self, padded: bool = False) -> jax.Array:
        """True user vectors f32[M, n_items] (raw rows × per-user scale).

        ``padded=True`` keeps the zero pad columns: f32[M, n_cols], the
        lane-aligned serving corpus (``StateStore.corpus``).
        """
        out = self.user_vecs * self.uv_scale[:, None]
        return out if padded else out[:, :self.n_items]

    def materialized_last_group_vecs(self) -> jax.Array:
        """True last-group vectors f32[M, n_items]."""
        return (self.last_group_vecs
                * self.lgv_scale[:, None])[:, :self.n_items]

    @property
    def n_users(self) -> int:
        return self.user_vecs.shape[0]

    @property
    def n_items(self) -> int:
        """Catalogue size (the real item columns)."""
        return self.n_cols if self.n_real is None else self.n_real

    @property
    def n_cols(self) -> int:
        """Width of the dense leaves: the catalogue plus lane padding."""
        return self.user_vecs.shape[1]

    @property
    def max_baskets(self) -> int:
        return self.history.shape[1]

    @property
    def max_basket_size(self) -> int:
        return self.history.shape[2]

    @property
    def max_groups(self) -> int:
        return self.group_sizes.shape[1]

    @staticmethod
    def zeros(n_users: int, n_items: int, max_baskets: int,
              max_basket_size: int, max_groups: int | None = None,
              dtype: Any = jnp.float32) -> "StreamState":
        """Empty state for a catalogue of ``n_items``, lane-padded."""
        if max_groups is None:
            max_groups = max_baskets  # worst case: all groups of size 1
        n_cols = lane_padded(n_items)
        return StreamState(
            user_vecs=jnp.zeros((n_users, n_cols), dtype),
            last_group_vecs=jnp.zeros((n_users, n_cols), dtype),
            history=jnp.full((n_users, max_baskets, max_basket_size), PAD_ID,
                             jnp.int32),
            group_sizes=jnp.zeros((n_users, max_groups), jnp.int32),
            n_baskets=jnp.zeros((n_users,), jnp.int32),
            n_groups=jnp.zeros((n_users,), jnp.int32),
            err_mult=jnp.ones((n_users,), dtype),
            uv_scale=jnp.ones((n_users,), dtype),
            lgv_scale=jnp.ones((n_users,), dtype),
            n_real=n_items,
        )


def lane_padded(n_items: int) -> int:
    """``n_items`` rounded up to the 128-lane tile of the TPU."""
    return -(-n_items // LANE) * LANE


# Update kinds for the streaming engine (Algorithm 1 generalised).
KIND_NOOP = 0
KIND_ADD_BASKET = 1
KIND_DEL_BASKET = 2
KIND_DEL_ITEM = 3


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class UpdateBatch:
    """A fixed-shape micro-batch of updates (adds and deletes mixed).

    kind:         i32[U]    one of KIND_*
    user:         i32[U]    target user row
    basket_items: i32[U, B] item ids for adds (PAD_ID padded)
    basket_pos:   i32[U]    global basket index for deletions
    item:         i32[U]    item id for item deletions
    """

    kind: jax.Array
    user: jax.Array
    basket_items: jax.Array
    basket_pos: jax.Array
    item: jax.Array

    def tree_flatten(self) -> Tuple[Tuple[jax.Array, ...], None]:
        return (self.kind, self.user, self.basket_items, self.basket_pos,
                self.item), None

    @classmethod
    def tree_unflatten(cls, aux: None,
                       children: Tuple[jax.Array, ...]) -> "UpdateBatch":
        return cls(*children)

    @property
    def size(self) -> int:
        return self.kind.shape[0]

    @staticmethod
    def noop(batch: int, max_basket_size: int) -> "UpdateBatch":
        return UpdateBatch(
            kind=jnp.zeros((batch,), jnp.int32),
            user=jnp.zeros((batch,), jnp.int32),
            basket_items=jnp.full((batch, max_basket_size), PAD_ID, jnp.int32),
            basket_pos=jnp.zeros((batch,), jnp.int32),
            item=jnp.full((batch,), PAD_ID, jnp.int32),
        )


# ---------------------------------------------------------------------------
# Kind-partitioned homogeneous sub-batches (DESIGN.md §4)
# ---------------------------------------------------------------------------
#
# A mixed UpdateBatch forces one compiled program to evaluate every update
# rule per row and select (4x redundant work).  The streaming engine instead
# partitions each micro-batch by event kind into these fixed-shape
# sub-batches, so each compiled program runs exactly one rule.  Rows beyond
# the real event count have valid=False and zero effect; they may alias any
# user because every state write is a masked delta (scatter-add / multiply
# by 1), never an unmasked set.

def _pow2_pad(n: int, cap: int = 0) -> int:
    """Pad a sub-batch length to the next power of two (bounded bucketing
    keeps the number of compiled shapes at log2(cap) per kind).  ``cap``
    (the engine batch size) bounds the padding; 0 means uncapped."""
    if n <= 0:
        return 1
    p = 1 << (n - 1).bit_length()
    return min(p, max(cap, n)) if cap else p


def _resolve_pad(n: int, pad_cap: int, pad_to: int) -> int:
    """Padded row count for a sub-batch build: an explicit ``pad_to``
    (the engine's hysteresis-held bucket) wins over the pow2 default."""
    if pad_to:
        if pad_to < n:
            raise ValueError(f"pad_to={pad_to} < sub-batch size {n}")
        return pad_to
    return _pow2_pad(n, pad_cap)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AddBatch:
    """Homogeneous basket-addition sub-batch (the paper's O(1) case).

    user:  i32[U]     target user row
    items: i32[U, B]  item ids of the new basket (PAD_ID padded)
    valid: bool[U]    False for padding rows (zero effect)
    """

    user: jax.Array
    items: jax.Array
    valid: jax.Array

    def tree_flatten(self) -> Tuple[Tuple[jax.Array, ...], None]:
        return (self.user, self.items, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux: None,
                       children: Tuple[jax.Array, ...]) -> "AddBatch":
        return cls(*children)

    @property
    def size(self) -> int:
        return self.user.shape[0]

    @staticmethod
    def build(users: Sequence[int], baskets: Sequence[Any],
              max_basket_size: int, pad_cap: int = 0,
              pad_to: int = 0) -> "AddBatch":
        """From parallel host lists of user ids and item-id sequences.

        ``pad_to`` (engine bucket hysteresis, DESIGN.md §4.1) overrides
        the pow2 bucket with an explicit row count >= len(users)."""
        n = len(users)
        u = _resolve_pad(n, pad_cap, pad_to)
        user = np.zeros(u, np.int32)
        items = np.full((u, max_basket_size), PAD_ID, np.int32)
        valid = np.zeros(u, bool)
        for r, (uu, b) in enumerate(zip(users, baskets)):
            user[r] = uu
            # baskets are item SETS: dedup + drop PADs here so duplicate
            # ids never reach history (recompute paths would double-count)
            ids = np.unique(np.asarray(b, np.int32))
            ids = ids[ids >= 0][:max_basket_size]
            items[r, :len(ids)] = ids
            valid[r] = True
        return AddBatch(user=jnp.asarray(user), items=jnp.asarray(items),
                        valid=jnp.asarray(valid))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DelBasketBatch:
    """Homogeneous basket-deletion sub-batch (linear decremental cost).

    user: i32[U]   target user row
    pos:  i32[U]   global basket index to delete
    valid: bool[U]
    """

    user: jax.Array
    pos: jax.Array
    valid: jax.Array

    def tree_flatten(self) -> Tuple[Tuple[jax.Array, ...], None]:
        return (self.user, self.pos, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux: None,
                       children: Tuple[jax.Array, ...]) -> "DelBasketBatch":
        return cls(*children)

    @property
    def size(self) -> int:
        return self.user.shape[0]

    @staticmethod
    def build(users: Sequence[int], positions: Sequence[int],
              pad_cap: int = 0, pad_to: int = 0) -> "DelBasketBatch":
        n = len(users)
        u = _resolve_pad(n, pad_cap, pad_to)
        user = np.zeros(u, np.int32)
        pos = np.zeros(u, np.int32)
        valid = np.zeros(u, bool)
        user[:n] = np.asarray(users, np.int32)
        pos[:n] = np.asarray(positions, np.int32)
        valid[:n] = True
        return DelBasketBatch(user=jnp.asarray(user), pos=jnp.asarray(pos),
                              valid=jnp.asarray(valid))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DelItemBatch:
    """Homogeneous item-deletion sub-batch (Eq. 13 with vanish fallback).

    user: i32[U]   target user row
    pos:  i32[U]   global basket index holding the item
    item: i32[U]   item id to delete
    valid: bool[U]
    """

    user: jax.Array
    pos: jax.Array
    item: jax.Array
    valid: jax.Array

    def tree_flatten(self) -> Tuple[Tuple[jax.Array, ...], None]:
        return (self.user, self.pos, self.item, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux: None,
                       children: Tuple[jax.Array, ...]) -> "DelItemBatch":
        return cls(*children)

    @property
    def size(self) -> int:
        return self.user.shape[0]

    @staticmethod
    def build(users: Sequence[int], positions: Sequence[int],
              items: Sequence[int], pad_cap: int = 0,
              pad_to: int = 0) -> "DelItemBatch":
        n = len(users)
        u = _resolve_pad(n, pad_cap, pad_to)
        user = np.zeros(u, np.int32)
        pos = np.zeros(u, np.int32)
        item = np.full(u, PAD_ID, np.int32)
        valid = np.zeros(u, bool)
        user[:n] = np.asarray(users, np.int32)
        pos[:n] = np.asarray(positions, np.int32)
        item[:n] = np.asarray(items, np.int32)
        valid[:n] = True
        return DelItemBatch(user=jnp.asarray(user), pos=jnp.asarray(pos),
                            item=jnp.asarray(item), valid=jnp.asarray(valid))
