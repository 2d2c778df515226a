"""Sharded state store for per-user TIFU-kNN state (paper §5, Fig. 1).

The Spark implementation keeps user vectors in a keyed state store; here
the store is a ``StreamState`` pytree whose user axis is sharded over the
``("pod", "data")`` mesh axes (user-level parallelism — paper: "each user
vector is calculated independently").  The item axis of ``user_vecs`` can
additionally be sharded over ``"model"`` for the kNN stage.

The store also owns the **serving corpus cache** (DESIGN.md §3.6): the
materialized ``[n_users, n_items]`` true-value corpus that kNN queries
read.  A micro-batch touches a handful of users; the engine marks those
rows dirty (``invalidate_users``) and ``corpus()`` refreshes only them —
high-QPS serving no longer pays a full scale×raw recompute per query.

Checkpointing + the idempotent update log give exactly-once semantics
across preemptions (DESIGN.md §5).  Every commit is checksummed (CRC32
of the state npz recorded in ``LATEST``, plus a self-CRC of the
metadata itself), the previous commit survives as ``LATEST.prev``, and
restore falls back to the last commit that verifies — so torn or
bit-flipped checkpoint files are *detected*, never silently installed
(DESIGN.md §9).  Store I/O retries transient failures with exponential
backoff under a bounded budget; the fault sites exercised by
``streaming.faults`` sit exactly on the commit/read path.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import zipfile
import zlib
from typing import (TYPE_CHECKING, Any, Callable, Dict, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.types import StreamState, _pow2_pad, lane_padded
from repro.optim.compression import quantize_int8_rows
from repro.streaming import faults

if TYPE_CHECKING:  # type-only: the writer runs opaque commit closures
    from repro.streaming.async_checkpoint import AsyncCheckpointer


class CorruptCheckpointError(RuntimeError):
    """A checkpoint file failed its integrity check (torn or bit-flipped).

    Raised only when NO commit in the directory verifies — a corrupt
    newest commit with an intact ``LATEST.prev`` falls back silently
    (counted in :attr:`StateStore.restore_fallbacks`).
    """


@dataclasses.dataclass
class StoreConfig:
    """Shapes, placement and cache policy of one state store.

    In a sharded deployment its user rows are ONE shard's slice
    (DESIGN.md §7).
    """

    n_users: int
    n_items: int
    max_baskets: int
    max_basket_size: int
    max_groups: Optional[int] = None
    dtype: str = "float32"
    # mesh axis names: user axis and item axis sharding
    user_axes: tuple = ("data",)
    item_axes: tuple = ("model",)
    # corpus cache: once more than this fraction of user rows is dirty,
    # one full materialize beats a huge scattered row refresh (ROADMAP:
    # very high delete rates)
    corpus_rebuild_frac: float = 0.25
    # bounded I/O retry budget for checkpoint/restore file operations:
    # transient errors back off base·2^i and then surface (DESIGN.md §9)
    io_retries: int = 4
    io_retry_base_s: float = 0.005

    @property
    def n_cols(self) -> int:
        """Width of the dense leaves: ``n_items`` padded to the lane tile.

        The tile-planned update kernels and the serving kernels run on
        lane-aligned item tiles; ``n_items`` stays the real catalogue
        (event validation, checkpoints, recommendations).
        """
        return lane_padded(self.n_items)


def _fsync_dir(path: str) -> None:
    """Make a rename in ``path`` durable.

    The file fsync orders the DATA, the directory fsync orders the
    ENTRY — both are needed for the crash-anywhere guarantee.
    """
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def with_io_retries(fn: Callable, what: str, retries: int = 4,
                    base_delay_s: float = 0.005,
                    on_retry: Optional[Callable] = None) -> Any:
    """Run ``fn`` retrying transient OSErrors with exponential backoff.

    Bounded budget: ``retries`` re-attempts (delays ``base_delay_s · 2^i``)
    and then the last error propagates — a dead disk must surface, not
    spin.  ``FileNotFoundError`` is never retried (it is a *state*, not a
    transient), and injected crashes (``faults.InjectedCrash`` is a
    BaseException) pass straight through, exactly like a real SIGKILL.
    ``on_retry`` is called once per re-attempt (metrics hook).
    """
    for attempt in range(retries + 1):
        try:
            return fn()
        except FileNotFoundError:
            raise
        except OSError as e:
            if attempt == retries:
                raise OSError(
                    f"{what}: I/O retry budget exhausted "
                    f"({retries} retries): {e}") from e
            if on_retry is not None:
                on_retry()
            time.sleep(base_delay_s * (2 ** attempt))


def _meta_crc(payload: dict) -> int:
    """Self-CRC of a metadata payload (over canonical json, crc excluded)."""
    probe = {k: v for k, v in payload.items() if k != "meta_crc32"}
    return zlib.crc32(json.dumps(probe, sort_keys=True).encode())


def _file_crc(path: str) -> Tuple[int, int]:
    """``(crc32, n_bytes)`` of a file, read in chunks."""
    crc, n = 0, 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc, n
            crc = zlib.crc32(chunk, crc)
            n += len(chunk)


def atomic_write_json(path: str, payload: dict, retries: int = 4,
                      base_delay_s: float = 0.005,
                      on_retry: Optional[Callable] = None) -> None:
    """Write json atomically and durably (the commit-point primitive).

    Tmp-file + fsync + ``os.replace`` + directory fsync, so a crash —
    process OR system — leaves either the previous intact file or
    nothing, never a truncated one (the same contract as the state npz
    writes).  A self-CRC (``meta_crc32``) is stamped into the payload so
    *silent* corruption of the committed file (bit rot — a fault the
    rename protocol cannot prevent) is detected on read
    (:func:`load_json_checked`).  Transient I/O errors are retried under
    a bounded budget.
    """
    payload = dict(payload)
    payload["meta_crc32"] = _meta_crc(payload)
    base = os.path.basename(path)

    def write() -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        faults.trip(f"{base}.pre_replace")
        os.replace(tmp, path)
        _fsync_dir(os.path.dirname(path) or ".")
        faults.trip(f"{base}.post_replace")

    with_io_retries(write, f"write {path}", retries, base_delay_s,
                    on_retry)


def load_json_checked(path: str, retries: int = 4,
                      base_delay_s: float = 0.005,
                      on_retry: Optional[Callable] = None) -> dict:
    """Read a json commit file, verifying its self-CRC when present.

    Raises :class:`CorruptCheckpointError` on undecodable json or a
    CRC mismatch (torn pre-atomic writers, bit flips); propagates
    ``FileNotFoundError`` untouched (absence is layout information, not
    corruption — the restore paths branch on it).  Legacy files without
    ``meta_crc32`` are accepted unverified.
    """
    base = os.path.basename(path)

    def read() -> bytes:
        faults.trip(f"{base}.read")
        # bytes, decoded below: a bit flip can produce invalid UTF-8,
        # which is corruption, not an I/O error to retry
        with open(path, "rb") as f:
            return f.read()

    raw = with_io_retries(read, f"read {path}", retries, base_delay_s,
                          on_retry)
    try:
        meta = json.loads(raw.decode("utf-8"))
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CorruptCheckpointError(
            f"{path} is not valid json (torn write or bit flip?): "
            f"{e}") from e
    if not isinstance(meta, dict):
        raise CorruptCheckpointError(f"{path}: expected a json object")
    want = meta.get("meta_crc32")
    if want is not None and _meta_crc(meta) != want:
        raise CorruptCheckpointError(
            f"{path} failed its integrity check "
            f"(meta_crc32={want}, computed={_meta_crc(meta)}): "
            "bit-flipped or hand-edited")
    return meta


def _load_commit(directory: str, meta: dict) -> Dict[str, np.ndarray]:
    """Load + verify the state npz a commit's metadata names.

    Raises :class:`CorruptCheckpointError` when the npz misses the CRC
    recorded at commit time or cannot be parsed; legacy commits without
    ``npz_crc32`` skip the CRC check (their zip structure still has to
    parse).
    """
    step = meta["step"]
    path = os.path.join(directory, f"state_{step:010d}.npz")
    want = meta.get("npz_crc32")
    if want is not None:
        crc, n = with_io_retries(lambda: _file_crc(path), f"crc {path}")
        if crc != want:
            raise CorruptCheckpointError(
                f"{path} failed its CRC check (recorded {want}, computed "
                f"{crc} over {n} bytes): torn or bit-flipped")

    def read() -> Dict[str, np.ndarray]:
        faults.trip("npz.read")
        with np.load(path) as data:
            return {k: np.asarray(data[k]) for k in data.files}

    try:
        leaves = with_io_retries(read, f"read {path}")
    except FileNotFoundError:
        raise
    except (OSError, ValueError, KeyError, zipfile.BadZipFile) as e:
        raise CorruptCheckpointError(f"{path} unreadable: {e}") from e
    for scale in ("uv_scale", "lgv_scale"):
        if scale not in leaves:
            leaves[scale] = np.ones(leaves["err_mult"].shape,
                                    leaves["err_mult"].dtype)
    return leaves


def load_checkpoint_arrays(
        directory: str) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Read the newest VERIFIED commit as host arrays: ``(meta, leaves)``.

    Reads the ``LATEST`` metadata (the atomic commit point), verifies
    its self-CRC and the recorded CRC of the state npz it names, and
    falls back to the previous commit (``LATEST.prev``, kept by
    :meth:`StateStore.checkpoint`) when the newest one is corrupt — the
    state and its exactly-once log always fall back *together*, so a
    replay re-applies exactly what the surviving commit has not seen
    (never a double-apply).  Pre-scaled-representation checkpoints (no
    ``uv_scale``/``lgv_scale`` leaves) migrate to scales of 1.  Shared
    by :meth:`StateStore.restore` and the resharding restore path
    (``streaming.engine.ShardedStreamingEngine.restore``, DESIGN.md §7).
    The chosen commit and any corruption skipped on the way are recorded
    under ``meta["_recovery"]``.  Cost: one O(state) read, no device
    work.
    """
    errors = []
    tried = False
    for name in ("LATEST", "LATEST.prev"):
        path = os.path.join(directory, name)
        if not os.path.exists(path):
            continue
        tried = True
        try:
            meta = load_json_checked(path)
            leaves = _load_commit(directory, meta)
        except (CorruptCheckpointError, OSError) as e:
            errors.append(f"{name}: {e}")
            continue
        meta["_recovery"] = {"source": name, "skipped": list(errors)}
        return meta, leaves
    if not tried:
        raise FileNotFoundError(
            f"no LATEST (or LATEST.prev) commit in {directory}")
    raise CorruptCheckpointError(
        f"no commit in {directory} passes its integrity checks: "
        + "; ".join(errors))


_DENSE_LEAVES = ("user_vecs", "last_group_vecs")


def pad_item_leaves(leaves: Dict[str, np.ndarray],
                    n_cols: int) -> Dict[str, np.ndarray]:
    """Zero-pad checkpointed dense leaves to the store's ``n_cols``.

    Checkpoints hold the real catalogue columns only (the pad columns
    are zero by construction), so a commit restores into any padding.
    """
    out = dict(leaves)
    for name in _DENSE_LEAVES:
        v = out[name]
        if v.shape[1] < n_cols:
            out[name] = np.pad(v, ((0, 0), (0, n_cols - v.shape[1])))
    return out


def state_shardings(cfg: StoreConfig, mesh: Any) -> StreamState:
    """PartitionSpecs for every leaf of the state pytree."""
    u = P(cfg.user_axes)
    ui = P(cfg.user_axes, cfg.item_axes)
    return StreamState(
        user_vecs=NamedSharding(mesh, ui),
        last_group_vecs=NamedSharding(mesh, ui),
        history=NamedSharding(mesh, P(cfg.user_axes, None, None)),
        group_sizes=NamedSharding(mesh, P(cfg.user_axes, None)),
        n_baskets=NamedSharding(mesh, u),
        n_groups=NamedSharding(mesh, u),
        err_mult=NamedSharding(mesh, u),
        uv_scale=NamedSharding(mesh, u),
        lgv_scale=NamedSharding(mesh, u),
        n_real=cfg.n_items,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _refresh_corpus_rows(corpus: jax.Array, user_vecs: jax.Array,
                         uv_scale: jax.Array,
                         rows: jax.Array) -> jax.Array:
    """Refresh ``corpus[rows] = uv_scale[rows] * user_vecs[rows]`` in place.

    ``rows`` may contain duplicates (pow2 padding repeats the first dirty
    row); duplicate writes carry identical values.
    """
    return corpus.at[rows].set(user_vecs[rows] * uv_scale[rows, None])


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _requantize_rows(corpus_q: jax.Array, scales: jax.Array,
                     corpus: jax.Array,
                     rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Re-quantize exactly the touched rows of the int8 serving corpus.

    ``corpus_q`` int8[M, I] / ``scales`` f32[M] are updated in place
    (donation — the refresh is O(dirty·I), not O(M·I)); per-row scaling
    means a row's quantization depends only on its own values, so
    touched rows re-quantize independently of the rest of the corpus.
    ``rows`` may contain pow2-padding duplicates (identical writes).
    """
    sub_q, sub_s = quantize_int8_rows(corpus[rows])
    return corpus_q.at[rows].set(sub_q), scales.at[rows].set(sub_s)


class StateStore:
    """Owns the StreamState, the serving corpus cache and persistence.

    On a real cluster the store's arrays are device-sharded via the
    shardings above; on the CPU test runner they are single-device.
    """

    def __init__(self, cfg: StoreConfig, mesh: Any = None) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.state = StreamState.zeros(
            cfg.n_users, cfg.n_items, cfg.max_baskets, cfg.max_basket_size,
            cfg.max_groups)
        if mesh is not None:
            sh = state_shardings(cfg, mesh)
            self.state = jax.tree.map(jax.device_put, self.state,
                                      sh, is_leaf=lambda x: x is None)
        self._corpus: Optional[jax.Array] = None
        self._dirty: Set[int] = set()
        # int8 serving corpus cache (DESIGN.md §8.4): derived from the
        # fp32 cache, with its OWN dirty set — the two caches refresh on
        # independent schedules (a deployment may serve only one)
        self._corpus_q: Optional[jax.Array] = None
        self._corpus_qscale: Optional[jax.Array] = None
        self._q_dirty: Set[int] = set()
        # degraded-serving freeze (DESIGN.md §9): while frozen, corpus()
        # keeps answering from this snapshot and performs no refreshes
        self._frozen_corpus: Optional[jax.Array] = None
        self._frozen_quant: Optional[tuple] = None
        self.corpus_full_builds = 0
        self.corpus_rows_refreshed = 0
        self.corpus_threshold_rebuilds = 0
        self.quant_full_builds = 0
        self.quant_rows_refreshed = 0
        self.quant_threshold_rebuilds = 0
        # robustness counters (observability only)
        self.io_retries = 0
        self.restore_fallbacks = 0
        self.corruption_detected = 0
        self.last_restored_meta: dict = {}

    def _on_io_retry(self) -> None:
        self.io_retries += 1

    # -- serving corpus cache (DESIGN.md §3.6) --------------------------------

    def invalidate_users(self, users: Any) -> None:
        """Mark user rows of the serving corpus stale.

        The engine calls this after every micro-batch / stability
        refresh with the touched users; O(|users|) set inserts.
        """
        if self._corpus is None and self._corpus_q is None:
            return            # no cache yet: the first corpus() builds it
        rows = [int(x) for x in np.asarray(users).ravel()]
        if self._corpus is not None:
            self._dirty.update(rows)
        if self._corpus_q is not None:
            self._q_dirty.update(rows)

    def invalidate_all(self) -> None:
        """Drop the caches entirely (restore, out-of-band state edits)."""
        self._corpus = None
        self._dirty.clear()
        self._corpus_q = None
        self._corpus_qscale = None
        self._q_dirty.clear()

    def freeze_serving(self) -> None:
        """Enter degraded serving: pin the current corpus snapshot.

        While frozen, :meth:`corpus` answers from the pinned snapshot
        and performs NO refreshes or rebuilds — so ``recommend`` keeps
        working (on admittedly stale values) while this store's state is
        being recovered underneath it (restore, resharding).  If no
        corpus is cached yet, one is materialized first.  Idempotent.
        """
        if self._frozen_corpus is None:
            self._frozen_corpus = self.corpus()

    def thaw_serving(self) -> None:
        """Leave degraded serving: un-pin the snapshots.

        The next :meth:`corpus` / :meth:`quantized_corpus` call serves
        the live state again (restore paths invalidate the caches, so
        they rebuild fresh).
        """
        self._frozen_corpus = None
        self._frozen_quant = None

    @property
    def serving_degraded(self) -> bool:
        """True while :meth:`freeze_serving` is in effect."""
        return self._frozen_corpus is not None

    def corpus(self) -> jax.Array:
        """The materialized true-value corpus f32[n_users, n_cols].

        The lane padding is kept (its columns are zero): the serving
        kernels read lane-aligned tiles, and callers pass the real
        ``n_items`` so pad columns are never recommended.

        First call (or after ``invalidate_all``) densifies everything;
        subsequent calls refresh only rows dirtied since the last call.
        The row list is padded to a pow2 bucket (duplicating one dirty
        row) so the refresh program compiles O(log n_users) times.

        LIFETIME: the refresh updates the cached buffer IN PLACE
        (donation keeps it O(dirty·I)), so the returned array is valid
        only until the next ``corpus()`` call that follows an
        invalidation.  Finish (or copy) a request batch before applying
        the next micro-batch's refresh — the serving loop here is
        synchronous, matching launch/serve.py.

        DEGRADED MODE: while :meth:`freeze_serving` is in effect the
        pinned snapshot is returned as-is (no refresh, no rebuild) —
        dirty rows keep accumulating and are reconciled at thaw.
        """
        if self._frozen_corpus is not None:
            return self._frozen_corpus
        if self._corpus is None:
            self._corpus = self.state.materialized_user_vecs(padded=True)
            self._dirty.clear()
            self.corpus_full_builds += 1
        elif len(self._dirty) > self.cfg.corpus_rebuild_frac \
                * self.cfg.n_users:
            # past the crossover one full rebuild is cheaper than a
            # scattered refresh of most rows (and compiles exactly once)
            self._corpus = self.state.materialized_user_vecs(padded=True)
            self._dirty.clear()
            self.corpus_full_builds += 1
            self.corpus_threshold_rebuilds += 1
        elif self._dirty:
            rows = np.fromiter(self._dirty, np.int32, len(self._dirty))
            self.corpus_rows_refreshed += rows.size
            pad = _pow2_pad(rows.size, self.cfg.n_users) - rows.size
            if pad:
                rows = np.concatenate([rows, np.full(pad, rows[0],
                                                     np.int32)])
            self._corpus = _refresh_corpus_rows(
                self._corpus, self.state.user_vecs, self.state.uv_scale,
                jnp.asarray(rows))
            self._dirty.clear()
        return self._corpus

    def quantized_corpus(self) -> tuple:
        """The int8 serving corpus: ``(q int8[M, I], scale f32[M])``.

        The cache entry behind `core.knn.recommend_for_users_quant`
        (DESIGN.md §8.4): per-row power-of-two-scale quantization
        (`optim.compression.quantize_int8_rows`) of the fp32 serving
        corpus.  Derived from :meth:`corpus` — the call refreshes the
        fp32 cache first, then re-quantizes ONLY the rows dirtied since
        the last ``quantized_corpus()`` call (its own dirty set: the
        two caches refresh on independent schedules).  Row-wise scaling
        is what makes this O(dirty·I): a touched row re-quantizes
        without looking at any other row.  Past
        ``corpus_rebuild_frac·n_users`` dirty rows one full re-quantize
        is cheaper (and compiles once), mirroring the fp32 policy.

        Same LIFETIME contract as :meth:`corpus` (in-place donated
        refresh), and the same DEGRADED MODE: while frozen, a pinned
        snapshot is served (quantized from the pinned fp32 snapshot on
        first use).
        """
        if self._frozen_corpus is not None:
            if self._frozen_quant is None:
                self._frozen_quant = quantize_int8_rows(self._frozen_corpus)
            return self._frozen_quant
        corpus = self.corpus()
        if self._corpus_q is None:
            self._corpus_q, self._corpus_qscale = quantize_int8_rows(corpus)
            self._q_dirty.clear()
            self.quant_full_builds += 1
        elif len(self._q_dirty) > self.cfg.corpus_rebuild_frac \
                * self.cfg.n_users:
            self._corpus_q, self._corpus_qscale = quantize_int8_rows(corpus)
            self._q_dirty.clear()
            self.quant_full_builds += 1
            self.quant_threshold_rebuilds += 1
        elif self._q_dirty:
            rows = np.fromiter(self._q_dirty, np.int32, len(self._q_dirty))
            self.quant_rows_refreshed += rows.size
            pad = _pow2_pad(rows.size, self.cfg.n_users) - rows.size
            if pad:
                rows = np.concatenate([rows, np.full(pad, rows[0],
                                                     np.int32)])
            self._corpus_q, self._corpus_qscale = _requantize_rows(
                self._corpus_q, self._corpus_qscale, corpus,
                jnp.asarray(rows))
            self._q_dirty.clear()
        return self._corpus_q, self._corpus_qscale

    # -- unlearning surface (DESIGN.md §11) -----------------------------------

    def scrub_rows(self, users: Sequence[int]) -> None:
        """Force the serving caches to drop residue for ``users`` now.

        The GDPR unlearning path: after the engine zeroes a forgotten
        user's state rows, the fp32/int8 cache rows still hold the
        pre-deletion values until the next natural refresh.  This marks
        the rows dirty and refreshes whichever caches exist, so the
        forgotten values are gone from every live serving buffer when
        the call returns.  Frozen degraded-serving snapshots are NOT
        touched — a forget while frozen shows up as residue in
        :meth:`row_residue` until ``thaw_serving`` (the honest answer:
        the pinned snapshot still serves the old values).  Cost: one
        O(|users| · n_items) row refresh per existing cache.
        """
        rows = np.asarray(list(users), np.int64)
        if rows.size == 0:
            return
        self.invalidate_users(rows)
        if self._frozen_corpus is not None:
            return
        if self._corpus_q is not None:
            self.quantized_corpus()   # refreshes the fp32 cache first
        elif self._corpus is not None:
            self.corpus()

    def row_residue(self, users: Sequence[int]) -> Dict[str, float]:
        """Residue of ``users`` rows in every live artifact, by name.

        Returns max-abs (or count) values over the given rows for the
        state leaves, the fp32/int8 serving caches, and any frozen
        degraded-serving snapshot — cache/snapshot keys appear only when
        that artifact exists.  A fully forgotten user reports 0.0
        everywhere: this is the machine-checkable no-trace predicate
        behind ``compliance.certify`` and ``forget_user`` receipts.
        Cost: O(|users| · n_items) host reads; no cache refresh.
        """
        rows = np.asarray(list(users), np.int64)
        st = self.state

        def take(leaf) -> np.ndarray:
            # index on device: only the requested rows cross to the host
            return np.asarray(leaf[jnp.asarray(rows, jnp.int32)])

        out: Dict[str, float] = {
            "user_vec_absmax": float(
                np.abs(take(st.user_vecs)).max(initial=0.0)),
            "last_group_absmax": float(
                np.abs(take(st.last_group_vecs)).max(initial=0.0)),
            "history_ids": float((take(st.history) >= 0).sum()),
            "n_baskets": float(take(st.n_baskets).sum(initial=0)),
            "n_groups": float(take(st.n_groups).sum(initial=0)),
        }
        if self._corpus is not None:
            out["corpus_absmax"] = float(
                np.abs(take(self._corpus)).max(initial=0.0))
        if self._corpus_q is not None:
            out["quant_nonzero"] = float((take(self._corpus_q) != 0).sum())
        if self._frozen_corpus is not None:
            out["frozen_absmax"] = float(
                np.abs(take(self._frozen_corpus)).max(initial=0.0))
        return out

    # -- persistence (exactly-once recovery substrate) -----------------------

    def _snapshot_leaves(self) -> Dict[str, np.ndarray]:
        """Copy every state leaf to host memory, owned by the caller.

        ``np.array(..., copy=True)`` rather than ``np.asarray`` on
        purpose: on the CPU backend a jax→numpy conversion can be a
        zero-copy *view* of the device buffer, and the engine's donated
        appliers invalidate that buffer on the very next micro-batch —
        a background writer holding a view would serialize garbage
        (read-after-free).  The deep copy is the "snapshot" half of
        snapshot-then-write (DESIGN.md §12) and the only O(state) cost
        that stays on the caller's hot path.  The dense leaves are saved
        without their lane padding (see :func:`pad_item_leaves`).
        """
        st = self.state
        n = self.cfg.n_items
        return {
            "user_vecs": np.array(np.asarray(st.user_vecs)[:, :n]),
            "last_group_vecs": np.array(
                np.asarray(st.last_group_vecs)[:, :n]),
            "history": np.array(st.history, copy=True),
            "group_sizes": np.array(st.group_sizes, copy=True),
            "n_baskets": np.array(st.n_baskets, copy=True),
            "n_groups": np.array(st.n_groups, copy=True),
            "err_mult": np.array(st.err_mult, copy=True),
            "uv_scale": np.array(st.uv_scale, copy=True),
            "lgv_scale": np.array(st.lgv_scale, copy=True),
        }

    def checkpoint(self, directory: str, step: int,
                   extra_meta: Optional[dict] = None) -> str:
        """Write one atomic checkpoint commit; returns the npz path.

        Synchronous snapshot-then-write: :meth:`_snapshot_leaves` now,
        :meth:`_write_commit` inline.  The state npz is made durable
        FIRST; the ``LATEST`` metadata write (which carries
        ``extra_meta``, e.g. the engine's exactly-once log, plus the
        npz's CRC32) is the single atomic commit point.  The previous
        ``LATEST`` survives as ``LATEST.prev`` (byte-for-byte, its
        self-CRC stays valid), giving restore a verified fallback commit
        when the newest one is later found corrupted (DESIGN.md §9).
        Transient I/O errors retry under the config's bounded budget.
        Cost: one O(state) device fetch + compressed write.
        """
        return self._write_commit(directory, step, self._snapshot_leaves(),
                                  extra_meta)

    def checkpoint_async(self, checkpointer: "AsyncCheckpointer",
                         directory: str, step: int,
                         extra_meta: Optional[dict] = None) -> str:
        """Snapshot now, commit on the background writer; returns npz path.

        The caller-thread cost is one :meth:`_snapshot_leaves` copy; the
        serialize/fsync/atomic-replace sequence (identical bytes and
        identical fault sites to :meth:`checkpoint`) runs as a FIFO job
        on ``checkpointer``'s worker thread.  Exactly-once is preserved
        because the job *ends in* the atomic ``LATEST`` replace: until
        that replace lands, restore sees the previous commit, never a
        torn one.  A writer-thread failure (including an injected
        crash) surfaces at the checkpointer's next ``submit``/``flush``
        — callers must flush before trusting the returned path exists.
        """
        leaves = self._snapshot_leaves()
        path = os.path.join(directory, f"state_{step:010d}.npz")
        checkpointer.submit(
            lambda: self._write_commit(directory, step, leaves, extra_meta),
            label=f"{directory}@{step}")
        return path

    def _write_commit(self, directory: str, step: int,
                      leaves: Dict[str, np.ndarray],
                      extra_meta: Optional[dict] = None) -> str:
        """Serialize ``leaves`` and land the atomic ``LATEST`` commit.

        The write half of snapshot-then-write: runs inline for
        :meth:`checkpoint`, or as the background writer's job for
        :meth:`checkpoint_async`.  ``leaves`` must be host-owned copies
        (see :meth:`_snapshot_leaves`) — this function never touches
        ``self.state``, so the engine may keep donating buffers while
        it writes.
        """
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"state_{step:010d}.npz")
        tmp = path + ".tmp"

        def write_npz() -> Tuple[int, int]:
            faults.trip("npz.pre_write")
            with open(tmp, "wb") as f:
                np.savez_compressed(f, **leaves)
                f.flush()
                os.fsync(f.fileno())
            # CRC over the durable tmp bytes: recorded in LATEST, checked
            # on every restore — a tear or bit flip between now and then
            # cannot be installed silently
            crc, n = _file_crc(tmp)
            faults.trip("npz.pre_replace")
            os.replace(tmp, path)
            _fsync_dir(directory)
            faults.trip("npz.post_replace")
            return crc, n

        crc, n_bytes = with_io_retries(
            write_npz, f"write {path}", self.cfg.io_retries,
            self.cfg.io_retry_base_s, self._on_io_retry)
        self._retain_previous_commit(directory)
        meta = dict(step=step, **dataclasses.asdict(self.cfg))
        meta["user_axes"] = list(meta["user_axes"])
        meta["item_axes"] = list(meta["item_axes"])
        meta["npz_crc32"] = crc
        meta["npz_bytes"] = n_bytes
        if extra_meta:
            meta.update(extra_meta)
        # LATEST is the single commit point: the npz above is durable
        # before this replace lands, and any co-checkpointed metadata
        # (the engine's exactly-once log) rides in the SAME atomic write
        # — a crash anywhere leaves the previous checkpoint fully
        # consistent, never a new state with an old log.
        atomic_write_json(os.path.join(directory, "LATEST"), meta,
                          self.cfg.io_retries, self.cfg.io_retry_base_s,
                          self._on_io_retry)
        return path

    def _retain_previous_commit(self, directory: str) -> None:
        """Copy the current ``LATEST`` to ``LATEST.prev`` (atomically).

        Byte-for-byte, so the copied file's self-CRC stays valid; a
        crash between the copy and the new ``LATEST`` replace leaves
        ``LATEST == LATEST.prev`` — consistent.  The fallback depth is
        deliberately one: state and exactly-once log always travel
        together, and a two-commits-old state converges by replay.
        """
        cur = os.path.join(directory, "LATEST")
        if not os.path.exists(cur):
            return

        def copy() -> None:
            with open(cur, "rb") as f:
                raw = f.read()
            tmp = cur + ".prev.tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, cur + ".prev")
            _fsync_dir(directory)

        with_io_retries(copy, f"retain {cur}.prev", self.cfg.io_retries,
                        self.cfg.io_retry_base_s, self._on_io_retry)

    def _validate_meta(self, meta: dict) -> None:
        """Reject checkpoints written under different shape dimensions.

        Silently installing wrong-shaped state either fails later (shape
        error far from the cause) or — worse — runs with aliased
        user/item indices.
        """
        mismatches = []
        for field in ("n_users", "n_items", "max_baskets",
                      "max_basket_size"):
            want = getattr(self.cfg, field)
            got = meta.get(field)
            if got is not None and got != want:
                mismatches.append(f"{field}: checkpoint={got} store={want}")
        k_ckpt = meta.get("max_groups") or meta.get("max_baskets")
        k_cfg = self.cfg.max_groups or self.cfg.max_baskets
        if meta.get("max_baskets") is not None and k_ckpt != k_cfg:
            mismatches.append(
                f"max_groups (effective): checkpoint={k_ckpt} store={k_cfg}")
        if mismatches:
            raise ValueError(
                "checkpoint/store shape mismatch — refusing to restore: "
                + "; ".join(mismatches))

    def install_state(self, state: StreamState) -> None:
        """Replace the owned state out-of-band (resharding restore).

        Applies the store's device/mesh placement and drops the serving
        corpus cache — every row may have changed.  Callers are
        responsible for shape-validating ``state`` against the config
        (the resharding path does, via the checkpoint metadata).
        """
        if self.mesh is not None:
            sh = state_shardings(self.cfg, self.mesh)
            state = jax.tree.map(jax.device_put, state, sh)
        self.state = state
        self.invalidate_all()

    def restore(self, directory: str) -> int:
        """Install the checkpoint in ``directory``; returns its step.

        Reads the atomic ``LATEST`` commit, validates its shape metadata
        against this store's config (refusing mismatches loudly), keeps
        the parsed metadata in :attr:`last_restored_meta` for
        co-checkpointed payloads (the engine's exactly-once log rides in
        ``meta["engine"]`` — one reader, one parse), and drops the
        serving-corpus cache.  Cost: one O(state) read + device upload.
        """
        meta, leaves = load_checkpoint_arrays(directory)
        self._validate_meta(meta)
        rec = meta.get("_recovery", {})
        if rec.get("source") not in (None, "LATEST"):
            self.restore_fallbacks += 1
        self.corruption_detected += len(rec.get("skipped", ()))
        self.last_restored_meta = meta
        step = meta["step"]
        leaves = pad_item_leaves(leaves, self.cfg.n_cols)
        self.install_state(StreamState(
            **{k: jax.numpy.asarray(v) for k, v in leaves.items()},
            n_real=self.cfg.n_items))
        return step
