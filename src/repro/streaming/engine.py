"""Micro-batch streaming engine — the Spark Structured Streaming analog.

Implements Algorithm 1 of the paper (joint incremental/decremental state
updates) as a batched SPMD program:

  * incoming events (basket additions, basket/item deletion requests)
    are buffered in per-user pending queues and cut into micro-batches
    of at most one event per user (conflicting events for the same user
    wait for the next batch — this preserves per-user sequential
    semantics while letting independent users update in parallel,
    exactly the paper's user-level parallelism);

  * each micro-batch is **partitioned by event kind** into homogeneous
    ``AddBatch`` / ``DelBasketBatch`` / ``DelItemBatch`` sub-batches
    (DESIGN.md §4), so each compiled program runs exactly one update
    rule — the add path applies sparse deltas (O(basket) state traffic),
    the decremental paths pay their paper-given linear cost;

  * an idempotent update log (sequence numbers + processed watermark)
    makes recovery exactly-once: after restoring a checkpoint, events
    with seqno <= watermark are skipped on replay;

  * users whose numerical-error bound crossed the stability threshold
    are refreshed from scratch after the batch (core.stability), and
    users whose representation scale approaches SCALE_FLOOR are
    renormalized in place (core.updates.renormalize_users).
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import json
import os
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knn, stability
from repro.core.types import (KIND_ADD_BASKET, KIND_DEL_BASKET,
                              KIND_DEL_ITEM, PAD_ID, AddBatch,
                              DelBasketBatch, DelItemBatch, StreamState,
                              TifuParams, _pow2_pad)
from repro.core.updates import (SCALE_CEIL, SCALE_FLOOR,
                                apply_add_batch_counted,
                                apply_del_basket_batch, apply_del_item_batch,
                                clear_users, refresh_users,
                                renormalize_users)
from repro.kernels import tile_plan
from repro.parallel.sharding import UserShardSpec
from repro.streaming.async_checkpoint import AsyncCheckpointer
from repro.streaming.spans import span, trace_gc
from repro.streaming.state_store import (CorruptCheckpointError, StateStore,
                                         StoreConfig, atomic_write_json,
                                         load_checkpoint_arrays,
                                         load_json_checked, pad_item_leaves)


# -- device-side step-summary programs (DESIGN.md §12) ----------------------
#
# Everything the host decides per micro-batch — maintenance triggers,
# poison checks, tile-plan bounds — is computed on device by these small
# programs and fetched together in ONE transfer per step (`_fetch`), so
# the hot path never round-trips whole state leaves.

@jax.jit
def _maintenance_probe(err_mult, uv_scale, lgv_scale):
    """Fused maintenance reduction: (err_max, scale_min, scale_max).

    One pass over the three O(n_users) maintenance leaves; the scalars
    ride the step's single transfer, replacing the full ``err_mult``
    fetch every batch and the separate min/max scale probe.
    """
    return (err_mult.max(),
            jnp.minimum(uv_scale.min(), lgv_scale.min()),
            jnp.maximum(uv_scale.max(), lgv_scale.max()))


@functools.partial(jax.jit, static_argnames="bi")
def _add_tile_bound(history, group_sizes, n_baskets, n_groups, idx,
                    new_ids, valid, *, bi: int):
    """Device touched-tile bound for an add sub-batch's support rows."""
    return tile_plan.add_support_tile_bound(
        history[idx], group_sizes[idx], n_baskets[idx], n_groups[idx],
        new_ids, valid, bi=bi)


@functools.partial(jax.jit, static_argnames="bi")
def _hist_tile_bound(history, n_baskets, idx, extra, valid, *, bi: int):
    """Device touched-tile bound for a delete sub-batch's history rows."""
    return tile_plan.history_support_tile_bound(
        history[idx], n_baskets[idx], extra, valid, bi=bi)


class InvalidEventError(ValueError):
    """A malformed event was rejected eagerly at submit time.

    Carries the offending event and a human-readable reason — raised
    instead of failing deep inside ``_apply_events`` with a shape or
    index error far from the cause (DESIGN.md §9).  ``submit(...,
    on_invalid="quarantine")`` routes these to the dead-letter queue
    instead of raising.
    """

    def __init__(self, event, reason: str):
        super().__init__(f"invalid event {event!r}: {reason}")
        self.event = event
        self.reason = reason


class Backpressure(RuntimeError):
    """Submit crossed the pending-queue high-water mark.

    The engine admitted a PREFIX of the call's events (``admitted``) and
    rejected the rest (``rejected``); rejected events were never
    assigned seqnos and count as **not delivered** — a contract-abiding
    at-least-once source resends from ``first_rejected_seqno`` (or the
    first rejected payload) once the queues drain.  Admitted events stay
    admitted.
    """

    def __init__(self, admitted: int, rejected: int,
                 first_rejected_seqno: Optional[int] = None,
                 pending: int = 0):
        super().__init__(
            f"pending queues at high-water mark ({pending} buffered): "
            f"admitted {admitted}, rejected {rejected} event(s)"
            + (f" from seqno {first_rejected_seqno}"
               if first_rejected_seqno is not None else ""))
        self.admitted = admitted
        self.rejected = rejected
        self.first_rejected_seqno = first_rejected_seqno
        self.pending = pending


@dataclasses.dataclass
class AdmissionResult:
    """What one ``submit`` call did with its events (DESIGN.md §9).

    ``admitted`` entered the pending queues; ``deduped`` were
    at-least-once redeliveries skipped by the exactly-once log;
    ``quarantined`` were malformed and moved to the dead-letter queue;
    ``rejected`` were shed by backpressure (never delivered — resend
    them).  ``first_rejected_seqno`` is the resume point for an
    explicit-seqno source.
    """

    admitted: int = 0
    deduped: int = 0
    quarantined: int = 0
    rejected: int = 0
    first_rejected_seqno: Optional[int] = None

    def merge(self, other: "AdmissionResult") -> "AdmissionResult":
        """Fold another result in (sharded router aggregation)."""
        self.admitted += other.admitted
        self.deduped += other.deduped
        self.quarantined += other.quarantined
        self.rejected += other.rejected
        if other.first_rejected_seqno is not None and (
                self.first_rejected_seqno is None
                or other.first_rejected_seqno < self.first_rejected_seqno):
            self.first_rejected_seqno = other.first_rejected_seqno
        return self

    @property
    def handed_in(self) -> int:
        """Events handed to ``submit``: each lands in one count."""
        return self.admitted + self.deduped + self.quarantined + self.rejected


def _pad_request(user_ids) -> tuple:
    """Pad a serving request to its pow2 bucket (DESIGN.md §8.3).

    Returns ``(padded_ids i64[bucket], q_n, bucket)``; padding repeats
    the first user id (computed and sliced off by the caller).  Shared
    by the single-engine and sharded request batchers so the bucketing
    contract cannot drift between them.
    """
    ids = np.asarray(user_ids, np.int64).ravel()
    q_n = ids.size
    if q_n == 0:
        return ids, 0, 0
    bucket = _pow2_pad(q_n)
    if bucket > q_n:
        ids = np.concatenate([ids, np.full(bucket - q_n, ids[0],
                                           ids.dtype)])
    return ids, q_n, bucket


@dataclasses.dataclass(frozen=True)
class Event:
    """One streaming event. ``seqno`` is assigned by the engine."""
    kind: int
    user: int
    items: Optional[np.ndarray] = None   # for adds
    pos: int = 0                         # for deletes
    item: int = PAD_ID                   # for item deletes
    seqno: int = -1


@dataclasses.dataclass(frozen=True)
class ForgetReceipt:
    """Receipt of one ``forget_user`` call (the GDPR front door).

    ``seqnos`` are the exactly-once log entries the forget consumed, one
    per deleted basket (the audit trail tying the forget to the log),
    ``purged_dead_letters`` the quarantined events of theirs that were
    dropped, and ``residue`` the post-scrub :meth:`StateStore.row_residue`
    measurement — ``clean`` is True iff every artifact reads zero.  The
    receipt is the per-call half of the compliance story; the full
    certificate is ``repro.compliance.certify`` over the event log.
    """

    user: int
    n_baskets_deleted: int
    seqnos: tuple
    purged_dead_letters: int
    latency_s: float
    residue: dict

    @property
    def clean(self) -> bool:
        """True iff no live artifact still holds the user's data."""
        return all(v == 0.0 for v in self.residue.values())


@dataclasses.dataclass
class EngineMetrics:
    """Counters one engine accumulates over its lifetime.

    Observability only — never read back by the update logic.
    """

    events_processed: int = 0
    batches: int = 0
    refreshes: int = 0
    renormalizations: int = 0
    # adds masked to no-ops by apply_add_batch's capacity guard
    dropped_adds: int = 0
    # explicit-seqno submissions skipped by the exactly-once dedup.
    # Under the documented contract these are redeliveries; a number
    # far above the source's redelivery rate means the contract is
    # being violated (out-of-order FIRST deliveries are
    # indistinguishable from duplicates and are dropped — watch this).
    dedup_skips: int = 0
    # pow2 sub-batch bucket transitions (each is a fresh compile unless
    # that bucket was seen before); shrinks are hysteresis-gated
    bucket_grows: int = 0
    bucket_shrinks: int = 0
    # serving request batches answered via `recommend`, and the number
    # of distinct (pow2 query bucket, topn, k, metric) shapes they
    # compiled — bounded at O(log max_batch) per parameter set by the
    # request bucketing (DESIGN.md §8); a count tracking the raw
    # request-size spread means the bucketing regressed
    serve_requests: int = 0
    serve_compiled_shapes: int = 0
    # host transfers performed by the step path (`_fetch` calls): the
    # fused step summary counts one per micro-batch; maintenance slow
    # paths (triggered refresh/renorm row lookups) count one more each.
    # The device-residency contract — <= 1 per healthy add-path step —
    # is pinned by the transfer-budget regression test and reported as
    # ``transfers_per_step`` by the device_resident bench arm.
    host_fetches: int = 0
    # malformed/poison events moved to the dead-letter queue (submit-time
    # validation + apply-time impossible-delete checks, DESIGN.md §9)
    dead_letters: int = 0
    # events shed by the pending-queue high-water mark (never delivered;
    # the source resends them once the queues drain)
    backpressure_rejections: int = 0
    # forgets of a user with baskets, each applied as one `clear_users`
    # program (DESIGN.md §11.3) rather than a step per deleted basket
    forget_clears: int = 0


class StreamingEngine:
    """Joint incremental/decremental state maintenance (Algorithm 1)."""

    def __init__(self, store: StateStore, params: TifuParams,
                 batch_size: int = 256,
                 stability_target_rel_err: Optional[float] = 1e-2,
                 renorm_check_interval: int = 64,
                 bucket_hysteresis: int = 8,
                 tile_hints: Optional[bool] = None,
                 max_pending: Optional[int] = None,
                 dead_letter_cap: int = 1024,
                 checkpointer: Optional[AsyncCheckpointer] = None):
        self.store = store
        self.params = params
        self.batch_size = batch_size
        # Optional background checkpoint writer (DESIGN.md §12): with a
        # checkpointer installed, `checkpoint` snapshots synchronously
        # and hands serialization to the writer thread; `restore` and
        # `flush_checkpoints` are the synchronization points where
        # writer failures surface.  None keeps the fully synchronous
        # §9 commit path.
        self.checkpointer = checkpointer
        # Bounded ingestion (DESIGN.md §9): with ``max_pending`` set,
        # `submit` admits events only while the buffered count is below
        # the high-water mark and sheds (or raises Backpressure on) the
        # rest — memory stays bounded under a slow-consumer scenario.
        self.max_pending = max_pending
        # Dead-letter queue: (event, reason) pairs for malformed/poison
        # events, ring-buffered so a poison flood cannot grow unbounded.
        self.dead_letter: deque = deque(maxlen=max(1, dead_letter_cap))
        # First-rejected explicit seqno not yet readmitted: while set,
        # first deliveries ABOVE it keep being shed — admitting them
        # would open a permanent gap below the watermark and turn the
        # rejected event's redelivery into a dropped "duplicate".
        self._shed_from: Optional[int] = None
        # Device-measured touched-tile bounds (T_max) threaded into the
        # jitted appliers as static args (DESIGN.md §3.3): shrinks the
        # tile-planned TPU kernel grids below the static min(W, I/bi)
        # worst case.  The bounds are computed on device from the
        # touched rows' history metadata and ride the step's single
        # fused transfer (§12) — no extra fetch — but each distinct
        # bound still selects a compiled applier shape, so it defaults
        # on only where it pays (the Pallas path); tests force it on
        # under interpret mode.
        if tile_hints is None:
            tile_hints = jax.default_backend() == "tpu"
        self.tile_hints = tile_hints
        # pow2 sub-batch bucket hysteresis (DESIGN.md §4.1): a kind's
        # bucket grows immediately (the rows exist, there is no choice)
        # but only shrinks after this many CONSECUTIVE micro-batches
        # whose sub-batch would fit the smaller bucket — kind counts that
        # straddle a pow2 boundary no longer flip-flop compiled shapes.
        self.bucket_hysteresis = max(1, bucket_hysteresis)
        self._kind_bucket: Dict[int, int] = {}
        self._below_bucket: Dict[int, int] = {}
        # The renormalization probe must fire before a scale that passed
        # the last probe can underflow f32 (raw rows scale as 1/scale).
        # A user gets at most one event per batch; the worst per-add
        # shrink factor is min(r_b, r_g)/2 (k=1 group opening / tau=1
        # append) and the worst per-delete growth factor is its inverse
        # 2/min(r_b, r_g) (Eq. 12 fold, k=2), so cap the interval I at
        # f^I >= 1e-14: a scale inside the probe bounds then stays
        # within a further 1e14 factor — raw magnitudes <= ~1e30/1e-30,
        # safely inside f32 range in both directions.
        f = min(params.r_b, params.r_g) / 2.0
        sound = int(np.floor(np.log(1e-14) / np.log(f))) if f < 1.0 else 64
        self.renorm_check_interval = max(1, min(renorm_check_interval,
                                                sound))
        # Deferred step summary (DESIGN.md §12): maintenance for batch N
        # runs at the START of step N+1, where its probe scalars ride
        # the same single transfer as batch N+1's poison/tile metadata —
        # identical state trajectory (apply_N -> maintain -> apply_N+1),
        # zero extra syncs.  The probe now rides EVERY step's fetch
        # (strictly more often than any interval, so the soundness cap
        # above is trivially met; the attribute is kept as the
        # documented knob/observable).  `_flush_deferred` settles the
        # pending probe at drain/checkpoint boundaries.
        self._maintenance_due = False
        # dropped-add counts accumulate ON DEVICE and ride the next
        # step's fetch — `int(dropped)` per batch was a hidden transfer
        self._dropped_dev: Optional[jax.Array] = None
        # Per-user pending queues + a min-heap of (head seqno, user):
        # cutting a batch pops at most one event per user in seqno order
        # and costs O(taken·log users) — a hot user with a deep queue no
        # longer forces a rescan of the whole buffer every step.
        self._queues: Dict[int, deque] = {}
        self._heap: List[tuple] = []   # a user is in the heap iff its
        self._n_pending = 0            # queue exists in _queues
        # Exactly-once bookkeeping (DESIGN.md §5/§7).  Conflict deferral
        # (one event per user per micro-batch) processes events OUT of
        # seqno order, so a plain high-watermark would drop
        # deferred-but-unprocessed events on replay.  We track a frontier
        # + the sparse set of processed seqnos above it, PLUS the seqnos
        # currently sitting in the pending queues: an at-least-once
        # source may redeliver an event before its first copy was ever
        # processed, and without the pending set that duplicate would be
        # enqueued (and applied) twice.
        #
        # SUBSEQUENCE SEMANTICS: this engine may be one shard of a
        # user-partitioned deployment, in which case it sees only the
        # subsequence of global seqnos routed to it.  The watermark
        # therefore means "every seqno <= watermark that was DELIVERED to
        # this engine has been processed", and it advances past gaps
        # (seqnos owned by other shards) up to `_max_delivered` — but
        # never past a pending (delivered, unprocessed) seqno and never
        # past `_max_delivered` itself.  The contract this relies on:
        # FIRST deliveries arrive in increasing seqno order (standard log
        # semantics; duplicates may arrive in any order).  The dense
        # single-engine stream is the gap-free special case.
        self.watermark = -1                 # all delivered <= this: done
        self._processed_above: set = set()
        self._pending_seqnos: set = set()
        self._max_delivered = -1
        self._next_seqno = 0
        # distinct (bucket, topn, k, metric) serving shapes compiled —
        # the host-side view of `kernels.ops.serving_cache_size`
        self._serve_shapes: set = set()
        self.metrics = EngineMetrics()
        trace_gc()
        if stability_target_rel_err is not None:
            self.err_threshold = stability.refresh_threshold(
                stability_target_rel_err, np.finfo(np.float32).eps)
        else:
            self.err_threshold = None

    # -- ingestion ------------------------------------------------------------

    @property
    def n_pending(self) -> int:
        """Number of buffered (not yet applied) events."""
        return self._n_pending

    def _enqueue(self, ev: Event) -> None:
        q = self._queues.get(ev.user)
        if q is None:
            q = self._queues[ev.user] = deque()
            heapq.heappush(self._heap, (ev.seqno, ev.user))
        q.append(ev)
        self._pending_seqnos.add(ev.seqno)
        self._n_pending += 1

    def _invalid_reason(self, ev: Event) -> Optional[str]:
        """Why ``ev`` is statically malformed, or None if well-formed.

        Static checks only (shape-config bounds); the position-vs-actual
        -history check is dynamic and happens at apply time
        (`_apply_events`), because the history length may legitimately
        change between submit and apply.
        """
        cfg = self.store.cfg
        if ev.kind not in (KIND_ADD_BASKET, KIND_DEL_BASKET,
                           KIND_DEL_ITEM):
            return f"unknown event kind {ev.kind}"
        if not 0 <= ev.user < cfg.n_users:
            return f"user {ev.user} outside [0, {cfg.n_users})"
        if ev.kind == KIND_ADD_BASKET:
            items = np.asarray(
                [] if ev.items is None else ev.items, np.int64).ravel()
            if items.size == 0:
                return "add-basket event with no items"
            if items.size > cfg.max_basket_size:
                return (f"basket of {items.size} items exceeds "
                        f"max_basket_size {cfg.max_basket_size}")
            bad = items[(items < 0) | (items >= cfg.n_items)]
            if bad.size:
                return f"item id {int(bad[0])} outside [0, {cfg.n_items})"
            return None
        if not 0 <= ev.pos < cfg.max_baskets:
            return (f"delete position {ev.pos} outside "
                    f"[0, {cfg.max_baskets})")
        if ev.kind == KIND_DEL_ITEM and not 0 <= ev.item < cfg.n_items:
            return f"item id {ev.item} outside [0, {cfg.n_items})"
        return None

    def _quarantine(self, ev: Event, reason: str) -> None:
        """Move a malformed/poison event to the dead-letter queue."""
        self.dead_letter.append((ev, reason))
        self.metrics.dead_letters += 1

    def _would_shed(self, seqno: Optional[int] = None) -> bool:
        """Would an event (with optional explicit seqno) be shed now?

        A rejected explicit seqno is an OPEN GAP: everything above it —
        including any seqno that would be freshly assigned — must keep
        shedding until that seqno's own redelivery is readmitted.
        Otherwise the watermark rolls past the gap (it looks like an
        other-shard seqno) and the redelivery is dropped as a
        "duplicate": a lost event.
        """
        if self._shed_from is not None and (seqno is None
                                            or seqno > self._shed_from):
            return True
        return (self.max_pending is not None
                and self._n_pending >= self.max_pending)

    def submit(self, events: Iterable[Event], *,
               on_invalid: str = "raise",
               on_overflow: str = "raise") -> AdmissionResult:
        """Enqueue events: dedup, validate, admit under backpressure.

        Per event, in order: (1) explicit-seqno redeliveries already
        processed (``<= watermark`` under the subsequence semantics, or
        in the sparse processed set above it) or still buffered are
        skipped — at-least-once becomes exactly-once.  CONTRACT: first
        deliveries arrive in increasing seqno order; a late out-of-order
        first delivery is indistinguishable from a redelivery and is
        dropped (counted in ``metrics.dedup_skips``).  (2) Statically
        malformed events raise :class:`InvalidEventError`
        (``on_invalid="raise"``) or move to the dead-letter queue
        (``"quarantine"``); a quarantined event CONSUMES its seqno and
        is marked processed, so replays skip it instead of
        re-quarantining forever.  (3) With ``max_pending`` set, events
        past the high-water mark are shed: never assigned a seqno, no
        log state touched — the source resends them.  Once an explicit
        seqno is shed, everything above it keeps shedding until its
        redelivery is admitted (`_would_shed`).  ``on_overflow="raise"``
        raises :class:`Backpressure` AFTER the admitted prefix is safely
        enqueued; ``"shed"`` only counts.  Cost: O(1) per event
        (amortized heap push).
        """
        if on_invalid not in ("raise", "quarantine"):
            raise ValueError(f"on_invalid={on_invalid!r}")
        if on_overflow not in ("raise", "shed"):
            raise ValueError(f"on_overflow={on_overflow!r}")
        with span("engine.submit") as sp:
            res = self._admit(events, on_invalid)
            sp.set_metadata(n=res.handed_in)
        if res.rejected and on_overflow == "raise":
            raise Backpressure(res.admitted, res.rejected,
                               res.first_rejected_seqno, self._n_pending)
        return res

    def _admit(self, events: Iterable[Event],
               on_invalid: str) -> AdmissionResult:
        """`submit`'s dedup, validation and admission, overflow shed and
        counted (the sharded router routes each event here)."""
        res = AdmissionResult()
        for ev in events:
            explicit = ev.seqno >= 0
            if explicit and (ev.seqno <= self.watermark
                             or ev.seqno in self._processed_above
                             or ev.seqno in self._pending_seqnos):
                # replay of an event that was already processed OR is
                # still buffered: skip (at-least-once -> exactly-once)
                self.metrics.dedup_skips += 1
                res.deduped += 1
                continue
            reason = self._invalid_reason(ev)
            if reason is not None:
                if on_invalid == "raise":
                    raise InvalidEventError(ev, reason)
                if not explicit:
                    ev = dataclasses.replace(ev, seqno=self._next_seqno)
                    self._next_seqno += 1
                else:
                    self._next_seqno = max(self._next_seqno, ev.seqno + 1)
                self._max_delivered = max(self._max_delivered, ev.seqno)
                self._processed_above.add(ev.seqno)
                self._advance_watermark()
                self._quarantine(ev, reason)
                res.quarantined += 1
                continue
            if self._would_shed(ev.seqno if explicit else None):
                self.metrics.backpressure_rejections += 1
                res.rejected += 1
                if explicit:
                    if (res.first_rejected_seqno is None
                            or ev.seqno < res.first_rejected_seqno):
                        res.first_rejected_seqno = ev.seqno
                    if (self._shed_from is None
                            or ev.seqno < self._shed_from):
                        self._shed_from = ev.seqno
                continue
            if not explicit:
                ev = dataclasses.replace(ev, seqno=self._next_seqno)
                self._next_seqno += 1
            else:
                self._next_seqno = max(self._next_seqno, ev.seqno + 1)
                if ev.seqno == self._shed_from:
                    self._shed_from = None    # gap closed: admissions resume
            self._max_delivered = max(self._max_delivered, ev.seqno)
            self._enqueue(ev)
            res.admitted += 1
        return res

    def add_basket(self, user: int, items: Sequence[int]) -> None:
        """Enqueue one basket addition (Eq. 7–9) for ``user``."""
        self.submit([Event(KIND_ADD_BASKET, user,
                           items=np.asarray(items, np.int32))])

    def delete_basket(self, user: int, pos: int) -> None:
        """Enqueue deletion of basket ``pos`` (Eq. 10–12) for ``user``."""
        self.submit([Event(KIND_DEL_BASKET, user, pos=pos)])

    def delete_item(self, user: int, pos: int, item: int) -> None:
        """Enqueue deletion of ``item`` from basket ``pos`` (Eq. 13)."""
        self.submit([Event(KIND_DEL_ITEM, user, pos=pos, item=item)])

    # -- unlearning front door (DESIGN.md §11) ----------------------------------

    def forget_user(self, user: int) -> ForgetReceipt:
        """Erase ``user``'s entire history and every live trace of it.

        The GDPR right-to-be-forgotten front door: drains the pending
        queues (so the user's in-flight events land first), reads the
        user's basket count, then applies the whole forget as ONE device
        program (`_clear_user`): the row becomes the empty-history row
        and the ``n_baskets`` seqnos of the deletions it stands for are
        logged processed.  Then it refreshes the serving caches
        (`StateStore.scrub_rows`) and purges the user's dead-letter
        entries (quarantined events carry payloads — residue too).
        Synchronous: returns only after the state is clean, with a
        :class:`ForgetReceipt` tying the consumed seqnos to the measured
        residue.  Cost: one drain, one scalar fetch, one O(N·B +
        n_items) row write and one O(n_items) cache refresh, whatever
        the history length.  Idempotent.
        """
        t0 = time.perf_counter()
        with span("engine.forget", user=user) as sp:
            with span("engine.forget.drain"):
                self.run_until_drained()
            # index on device, fetch one scalar —
            # np.asarray(n_baskets)[user] would pull the whole
            # O(n_users) leaf to read one element
            with span("engine.forget.lookup"):
                nb = int(jax.device_get(self.store.state.n_baskets[user]))
            sp.set_metadata(baskets=nb)
            seqnos = range(self._next_seqno, self._next_seqno + nb)
            with span("engine.forget.clear", baskets=nb):
                self._clear_user(user, seqnos)
            with span("engine.forget.scrub"):
                self.store.scrub_rows([user])
            with span("engine.forget.purge"):
                purged = self._purge_dead_letters(user)
            latency_s = time.perf_counter() - t0
            with span("engine.forget.residue"):
                residue = self.store.row_residue([user])
        return ForgetReceipt(
            user=user, n_baskets_deleted=nb, seqnos=tuple(seqnos),
            purged_dead_letters=purged, latency_s=latency_s,
            residue=residue)

    def _clear_user(self, user: int, seqnos: range) -> None:
        """Apply a forget of local row ``user`` as one device program.

        ``clear_users`` writes the row that deleting every basket and
        rebuilding from the emptied history gives (exact zeros, history
        PAD, scales and error tracker at 1; DESIGN.md §11.3), which
        also removes any f32 dust earlier item deletes left outside the
        support.  The ``seqnos``, one per deleted basket, are logged as
        delivered and processed, as the per-basket deletions were, so
        replays of them dedup and checkpoints carry the same log.  The
        forget buffers nothing, so the high-water mark does not apply;
        an open shed gap refuses it before anything changes, as it
        refuses any fresh seqno (`_would_shed`).
        """
        if seqnos and self._shed_from is not None:
            raise Backpressure(0, len(seqnos), None, self._n_pending)
        self.store.state = clear_users(self.store.state,
                                       jnp.asarray([user], jnp.int32))
        if not seqnos:
            return
        self._next_seqno = max(self._next_seqno, seqnos.stop)
        self._max_delivered = max(self._max_delivered, seqnos.stop - 1)
        self._processed_above.update(seqnos)
        self._advance_watermark()
        self.metrics.events_processed += len(seqnos)
        self.metrics.forget_clears += 1

    def _purge_dead_letters(self, user: int) -> int:
        """Drop the user's quarantined events (they carry payloads)."""
        kept = [(ev, why) for ev, why in self.dead_letter
                if ev.user != user]
        purged = len(self.dead_letter) - len(kept)
        if purged:
            self.dead_letter.clear()
            self.dead_letter.extend(kept)
        return purged

    # -- micro-batch processing -------------------------------------------------

    def _cut_batch(self) -> List[Event]:
        """Take up to batch_size events in seqno order, one per user.

        A user's later events stay queued for the next batch; cost is
        O(taken · log users) heap work.
        """
        taken: List[Event] = []
        requeue = []
        while self._heap and len(taken) < self.batch_size:
            _, user = heapq.heappop(self._heap)
            q = self._queues[user]
            taken.append(q.popleft())
            if q:
                requeue.append((q[0].seqno, user))
            else:
                del self._queues[user]
        for entry in requeue:
            heapq.heappush(self._heap, entry)
        for ev in taken:
            self._pending_seqnos.discard(ev.seqno)
        self._n_pending -= len(taken)
        return taken

    def _bucket(self, kind: int, n: int) -> int:
        """Pick the padded sub-batch size for ``n`` rows of ``kind``.

        Shrink hysteresis (DESIGN.md §4.1): growth is immediate, shrink
        waits for ``bucket_hysteresis`` consecutive under-boundary
        micro-batches.
        """
        want = _pow2_pad(n, self.batch_size)
        cur = self._kind_bucket.get(kind, 0)
        if want >= cur:
            if want > cur and cur:
                self.metrics.bucket_grows += 1
            self._kind_bucket[kind] = want
            self._below_bucket[kind] = 0
            return want
        self._below_bucket[kind] = self._below_bucket.get(kind, 0) + 1
        if self._below_bucket[kind] >= self.bucket_hysteresis:
            self._kind_bucket[kind] = want
            self._below_bucket[kind] = 0
            self.metrics.bucket_shrinks += 1
            return want
        return cur

    def _decay_absent_buckets(self, present) -> None:
        """Advance the shrink hysteresis of kinds ABSENT from a batch.

        Without this, a one-off burst (e.g. a GDPR delete wave) pins its
        large pow2 bucket forever: the kind never appears again,
        `_bucket` is never consulted, and the next singleton of that
        kind pads to the stale burst-sized bucket.  An absent batch
        counts as a zero-row batch, so after ``bucket_hysteresis``
        consecutive batches without the kind its bucket decays to the
        minimum (re-growth stays immediate, and previously compiled
        buckets are still cached).
        """
        for kind in list(self._kind_bucket):
            if kind not in present and self._kind_bucket[kind] > 1:
                self._bucket(kind, 0)

    def _fetch(self, tree):
        """The step path's host transfer: one counted ``device_get``.

        Every device→host read in the step loop goes through here, so
        ``metrics.host_fetches`` is exactly the number of transfers the
        transfer-budget regression test and the ``device_resident``
        bench arm observe.  The §12 contract: the fused step summary is
        ONE call per micro-batch; only triggered maintenance slow paths
        add more.
        """
        self.metrics.host_fetches += 1
        with span("engine.fetch"):
            return jax.device_get(tree)

    def _dispatch_tile_bounds(self, adds, delb, deli) -> Dict[int, jax.Array]:
        """Dispatch per-kind device touched-tile-bound programs (§3.3).

        For each kind sub-batch, a small jitted program over the
        touched rows' history metadata computes the maximum number of
        item tiles any row's support ids touch — the add support is the
        new basket plus the last group's history window, the delete
        supports the whole live history (plus the deleted item id).
        Returns ``{kind: i32[] device scalar}`` so the bounds ride the
        step's single fused transfer instead of forcing their own
        O(batch·N·B) host fetch.  Rows are padded to the pow2 event
        bucket (validity-masked, so padding contributes count 1) to
        bound compiled shapes.  Sound because distinct tiles <= distinct
        ids and the supports are supersets of what the appliers
        construct.
        """
        from repro.kernels import ops
        bi = ops.plan_bi(self.store.cfg.n_cols)
        st = self.store.state
        w = self.store.cfg.max_basket_size

        def pad_users(evs):
            n = len(evs)
            m = _pow2_pad(n, self.batch_size)
            users = np.zeros(m, np.int32)
            users[:n] = [ev.user for ev in evs]
            valid = np.zeros(m, bool)
            valid[:n] = True
            return jnp.asarray(users), jnp.asarray(valid)

        bounds: Dict[int, jax.Array] = {}
        if adds:
            idx, valid = pad_users(adds)
            new_ids = np.full((idx.shape[0], w), -1, np.int32)
            for r, ev in enumerate(adds):
                ids = np.asarray(ev.items, np.int32).ravel()[:w]
                new_ids[r, :ids.size] = ids
            bounds[KIND_ADD_BASKET] = _add_tile_bound(
                st.history, st.group_sizes, st.n_baskets, st.n_groups,
                idx, jnp.asarray(new_ids), valid, bi=bi)
        for kind, evs in ((KIND_DEL_BASKET, delb), (KIND_DEL_ITEM, deli)):
            if not evs:
                continue
            idx, valid = pad_users(evs)
            extra = np.full(idx.shape[0], -1, np.int32)
            if kind == KIND_DEL_ITEM:
                extra[:len(evs)] = [ev.item for ev in evs]
            bounds[kind] = _hist_tile_bound(
                st.history, st.n_baskets, idx, jnp.asarray(extra),
                valid, bi=bi)
        return bounds

    def _tile_hints(self, adds, delb, deli) -> Dict[int, int]:
        """Per-kind pow2 touched-tile bounds, fetched eagerly.

        Compatibility wrapper over `_dispatch_tile_bounds` + one
        transfer; the step loop instead folds the device scalars into
        its fused summary fetch (`_prepare_step`/`_complete_step`).
        """
        bounds = self._dispatch_tile_bounds(adds, delb, deli)
        if not bounds:
            return {}
        return {kind: _pow2_pad(max(int(v), 1))
                for kind, v in self._fetch(bounds).items()}

    def _poison_filter(self, delb, deli, nb):
        """Quarantine deletes whose position exceeds the CURRENT history.

        Dynamic poison check (DESIGN.md §9): a delete position at or
        beyond the user's current history length would be clipped by
        the applier's safe_pos guard and silently delete the WRONG
        basket — quarantine it instead.  The event still counts as
        processed (its seqno advances the log via `_finish_step`), so a
        replay skips it rather than re-poisoning.  ``nb`` is the
        per-delete-row basket-count gather that rode the fused step
        summary — no extra transfer.
        """
        keep_b: List[Event] = []
        keep_i: List[Event] = []
        for ev, n in zip(delb + deli, np.asarray(nb)):
            if ev.pos >= int(n):
                self._quarantine(
                    ev, f"delete position {ev.pos} beyond user "
                        f"{ev.user}'s history of {int(n)} basket(s)")
            elif ev.kind == KIND_DEL_BASKET:
                keep_b.append(ev)
            else:
                keep_i.append(ev)
        return keep_b, keep_i

    def _apply_sub_batches(self, adds, delb, deli,
                           hints: Dict[int, int]) -> None:
        """Apply one micro-batch's kind-partitioned sub-batches.

        One homogeneous compiled program per kind present (users are
        disjoint across the sub-batches, so application order is
        irrelevant): adds pay O(batch·W), deletions O(batch·N·B)
        (DESIGN.md §3.3/§3.5).  ``hints`` are the pow2 touched-tile
        bounds from the step summary (empty → static worst case).
        """
        self._decay_absent_buckets({kind for kind, evs in
                                    ((KIND_ADD_BASKET, adds),
                                     (KIND_DEL_BASKET, delb),
                                     (KIND_DEL_ITEM, deli)) if evs})
        b = self.store.cfg.max_basket_size
        if adds:
            pad = self._bucket(KIND_ADD_BASKET, len(adds))
            with span("engine.build", kind=KIND_ADD_BASKET):
                batch = AddBatch.build(
                    [ev.user for ev in adds], [ev.items for ev in adds], b,
                    pad_to=pad)
            # the counted variant surfaces capacity drops (masked to
            # no-ops by the guard) from the same fused program; the
            # count ACCUMULATES on device and rides the next step's
            # summary fetch — int(dropped) here would be a second
            # per-batch transfer
            with span("engine.apply", kind=KIND_ADD_BASKET, bucket=pad):
                self.store.state, dropped = apply_add_batch_counted(
                    self.store.state, batch, self.params,
                    t_max_cap=hints.get(KIND_ADD_BASKET, 0))
            self._dropped_dev = (dropped if self._dropped_dev is None
                                 else self._dropped_dev + dropped)
        if delb:
            pad = self._bucket(KIND_DEL_BASKET, len(delb))
            with span("engine.build", kind=KIND_DEL_BASKET):
                batch = DelBasketBatch.build(
                    [ev.user for ev in delb], [ev.pos for ev in delb],
                    pad_to=pad)
            with span("engine.apply", kind=KIND_DEL_BASKET, bucket=pad):
                self.store.state = apply_del_basket_batch(
                    self.store.state, batch, self.params,
                    t_max_cap=hints.get(KIND_DEL_BASKET, 0))
        if deli:
            pad = self._bucket(KIND_DEL_ITEM, len(deli))
            with span("engine.build", kind=KIND_DEL_ITEM):
                batch = DelItemBatch.build(
                    [ev.user for ev in deli], [ev.pos for ev in deli],
                    [ev.item for ev in deli], pad_to=pad)
            with span("engine.apply", kind=KIND_DEL_ITEM, bucket=pad):
                self.store.state = apply_del_item_batch(
                    self.store.state, batch, self.params,
                    t_max_cap=hints.get(KIND_DEL_ITEM, 0))
        # serving-corpus cache: only the APPLIED rows changed (§3.6) —
        # quarantined events touched nothing
        with span("engine.invalidate"):
            self.store.invalidate_users(
                [ev.user for ev in adds + delb + deli])

    def _apply_maintenance(self, err_max, lo, hi) -> None:
        """Stability refreshes + scale renorm from the probe scalars.

        The fast path — healthy error bounds, in-range scales — costs
        nothing beyond the three scalars that already rode the step
        summary.  Each TRIGGERED path pays one extra explicit fetch to
        locate the offending rows; both are rare by construction
        (stability §3.3; the scale drift analysis in ``__init__``).
        """
        floor = SCALE_FLOOR * 1e2   # renormalize well before the bounds
        ceil = SCALE_CEIL * 1e-2
        refresh = (self.err_threshold is not None
                   and err_max > self.err_threshold)
        if not (refresh or lo < floor or hi > ceil):
            return
        with span("engine.maintain") as sp:
            rows = 0
            if refresh:
                err = np.asarray(self._fetch(self.store.state.err_mult))
                bad = np.nonzero(err > self.err_threshold)[0]
                if bad.size:
                    self.store.state = refresh_users(
                        self.store.state, jnp.asarray(bad, jnp.int32),
                        self.params)
                    self.metrics.refreshes += int(bad.size)
                    # a refresh changes the served values (it resets the
                    # accumulated fp error), so those rows are stale too
                    self.store.invalidate_users(bad)
                rows += int(bad.size)
            if lo < floor or hi > ceil:
                uv_h, lgv_h = self._fetch((self.store.state.uv_scale,
                                           self.store.state.lgv_scale))
                uv_h, lgv_h = np.asarray(uv_h), np.asarray(lgv_h)
                out = np.nonzero((uv_h < floor) | (lgv_h < floor)
                                 | (uv_h > ceil) | (lgv_h > ceil))[0]
                self.store.state = renormalize_users(
                    self.store.state, jnp.asarray(out, jnp.int32))
                self.metrics.renormalizations += int(out.size)
                rows += int(out.size)
            sp.set_metadata(rows=rows)

    def _consume_summary(self, host: dict) -> None:
        """Apply the deferred halves of a fetched step summary."""
        if "dropped" in host:
            self.metrics.dropped_adds += int(host["dropped"])
            self._dropped_dev = None
        if "probe" in host:
            self._apply_maintenance(*host["probe"])
            self._maintenance_due = False

    def _flush_deferred(self) -> None:
        """Settle deferred maintenance/counters now (drain boundary).

        Deferral moves batch N's maintenance probe into step N+1's
        fused fetch; the LAST batch before a drain, checkpoint or
        forget has no next batch, so these boundaries flush explicitly
        — `run_until_drained` always ends on the empty step that pays
        this one fetch.
        """
        fetch: dict = {}
        st = self.store.state
        if self._maintenance_due:
            fetch["probe"] = _maintenance_probe(st.err_mult, st.uv_scale,
                                                st.lgv_scale)
        if self._dropped_dev is not None:
            fetch["dropped"] = self._dropped_dev
        if fetch:
            self._consume_summary(self._fetch(fetch))

    def _prepare_step(self):
        """Cut a micro-batch and dispatch its device-side step summary.

        Everything the host must learn from the device this step — the
        previous batch's deferred maintenance probe and dropped-add
        count, the delete rows' basket counts (poison check), the
        per-kind touched-tile bounds — is dispatched here as one dict
        of device values, so `_complete_step` fetches them in a SINGLE
        transfer.  Split from `_complete_step` so a sharded deployment
        dispatches every shard's programs before any shard blocks on
        its fetch (`ShardedStreamingEngine.step`).
        """
        with span("engine.cut"):
            events = self._cut_batch()
            adds = [ev for ev in events if ev.kind == KIND_ADD_BASKET]
            delb = [ev for ev in events if ev.kind == KIND_DEL_BASKET]
            deli = [ev for ev in events if ev.kind == KIND_DEL_ITEM]
        with span("engine.summary"):
            st = self.store.state
            fetch: dict = {}
            if self._maintenance_due:
                fetch["probe"] = _maintenance_probe(
                    st.err_mult, st.uv_scale, st.lgv_scale)
            if self._dropped_dev is not None:
                fetch["dropped"] = self._dropped_dev
            if delb or deli:
                idx = jnp.asarray(np.asarray(
                    [ev.user for ev in delb + deli], np.int32))
                fetch["del_nb"] = st.n_baskets[idx]
            if events and self.tile_hints:
                bounds = self._dispatch_tile_bounds(adds, delb, deli)
                if bounds:
                    fetch["tiles"] = bounds
        return events, adds, delb, deli, fetch

    def _complete_step(self, prep) -> List[Event]:
        """Fetch the step summary (ONE transfer) and apply the batch.

        Order matters: the summary was computed from the pre-
        maintenance state, which is sound — refresh/renorm touch only
        the float leaves, never ``history``/``n_baskets``/group
        metadata — and running maintenance before the appliers
        reproduces the legacy trajectory (apply_N → maintain →
        apply_N+1) exactly.
        """
        events, adds, delb, deli, fetch = prep
        host = self._fetch(fetch) if fetch else {}
        self._consume_summary(host)
        if not events:
            return events
        if "del_nb" in host:
            delb, deli = self._poison_filter(delb, deli, host["del_nb"])
        hints = {kind: _pow2_pad(max(int(v), 1))
                 for kind, v in host.get("tiles", {}).items()}
        self._apply_sub_batches(adds, delb, deli, hints)
        self._maintenance_due = True
        return events

    def _begin_step(self) -> List[Event]:
        """Cut one micro-batch and apply it (one fused summary fetch)."""
        return self._complete_step(self._prepare_step())

    def _finish_step(self, events: List[Event]) -> int:
        """Exactly-once log advance + counters for one micro-batch."""
        with span("engine.log"):
            for ev in events:
                self._processed_above.add(ev.seqno)
            self._advance_watermark()
            self.metrics.events_processed += len(events)
            self.metrics.batches += 1
            return len(events)

    def _advance_watermark(self) -> None:
        """Advance the frontier under the subsequence semantics.

        A seqno can be passed when it was processed here, OR when it was
        never delivered here (another shard owns it — in-order first
        delivery guarantees it never will be).  Pending seqnos
        (delivered, unprocessed) and anything beyond ``_max_delivered``
        block.
        """
        nxt = self.watermark + 1
        while nxt <= self._max_delivered and nxt not in self._pending_seqnos:
            self._processed_above.discard(nxt)
            self.watermark = nxt
            nxt += 1

    def step(self) -> int:
        """Process one micro-batch. Returns number of events applied."""
        with span("engine.step", step=self.metrics.batches):
            events = self._begin_step()
            if not events:
                return 0
            return self._finish_step(events)

    def run_until_drained(self, max_batches: int = 10_000) -> int:
        """Step until the pending queues empty; returns events applied."""
        total = 0
        for _ in range(max_batches):
            n = self.step()
            if n == 0:
                break
            total += n
        return total

    # -- serving (DESIGN.md §8) -------------------------------------------------

    def recommend(self, user_ids, topn: int = 10, k: Optional[int] = None,
                  alpha: Optional[float] = None,
                  metric: str = "euclidean",
                  quantized: bool = False) -> np.ndarray:
        """Top-n recommendations for ``user_ids`` — the request batcher.

        Reads the cached serving corpus (``StateStore.corpus()`` —
        micro-batches between requests invalidated only the touched
        rows) and serves through the fused pipeline
        (`core.knn.recommend_for_users` → `kernels.ops`).  The query
        batch is padded to a pow2 BUCKET (repeating the first user; the
        padding rows are computed and discarded), so serving compiles
        O(log max_batch) programs per (topn, k, metric) instead of one
        per distinct request-batch size — the compiled-shape count is
        tracked in ``metrics.serve_compiled_shapes``.  Cost: one fused
        device program per request batch, O(topn) host output per user.

        ``quantized=True`` serves the D-tiled int8 path instead
        (DESIGN.md §8.4): the ``StateStore.quantized_corpus()`` cache
        (row-invalidated alongside the fp32 one) through
        `core.knn.recommend_for_users_quant` — VMEM flat in n_items,
        ¼ the HBM bytes, euclidean only.
        """
        ids, q_n, bucket = _pad_request(user_ids)
        if q_n == 0:
            return np.zeros((0, topn), np.int32)
        k = self.params.k_neighbors if k is None else k
        alpha = self.params.alpha if alpha is None else alpha
        if quantized:
            if metric != "euclidean":
                raise ValueError("quantized serving is euclidean-only")
            corpus_q, c_scale = self.store.quantized_corpus()
            recs = knn.recommend_for_users_quant(
                corpus_q, c_scale, jnp.asarray(ids.astype(np.int32)),
                k=k, alpha=alpha, topn=topn, n_items=self.store.cfg.n_items)
        else:
            recs = knn.recommend_for_users(
                self.store.corpus(), jnp.asarray(ids.astype(np.int32)),
                k=k, alpha=alpha, topn=topn, metric=metric,
                n_items=self.store.cfg.n_items)
        self.metrics.serve_requests += 1
        # alpha included: it is a static (compile-triggering) arg of
        # the Pallas serving path, so per-request alphas must show up
        # in the gated compiled-shape count
        self._serve_shapes.add((bucket, topn, k, float(alpha), metric,
                                quantized))
        self.metrics.serve_compiled_shapes = len(self._serve_shapes)
        return np.asarray(recs)[:q_n]

    def freeze_serving(self) -> None:
        """Enter degraded serving: pin the current corpus snapshot.

        ``recommend`` keeps answering from the pinned snapshot while the
        live state is being rebuilt/restored (DESIGN.md §9); answers are
        stale but well-formed.  Idempotent.
        """
        self.store.freeze_serving()

    def thaw_serving(self) -> None:
        """Leave degraded serving; `recommend` reads live state again."""
        self.store.thaw_serving()

    @property
    def serving_degraded(self) -> bool:
        """True while `recommend` answers from a pinned stale snapshot."""
        return self.store.serving_degraded

    # -- recovery ---------------------------------------------------------------

    def checkpoint(self, directory: str, step: int) -> None:
        """Commit state + exactly-once log atomically (DESIGN.md §5).

        The log rides inside the store's LATEST metadata, which is the
        checkpoint's single atomic commit point (fsync'd tmp +
        os.replace): a crash anywhere — even between files — can never
        pair a new state npz with an old/truncated log (a torn pair
        would replay below the old watermark onto the new state:
        double-apply).  Deferred maintenance is flushed first so the
        committed state matches the drained trajectory.

        With an async ``checkpointer`` installed (§12) the caller-thread
        cost is one host snapshot copy; serialization and the atomic
        commit run on the writer thread in submission order, and writer
        failures surface at the next `checkpoint`/`flush_checkpoints`/
        `restore`.  Without one: one O(state) snapshot + inline write.
        """
        self._flush_deferred()
        extra = {"engine": {
            "watermark": self.watermark,
            "processed_above": sorted(self._processed_above),
            "delivered": self._max_delivered,
            "next_seqno": self._next_seqno}}
        if self.checkpointer is not None:
            self.store.checkpoint_async(self.checkpointer, directory,
                                        step, extra_meta=extra)
        else:
            self.store.checkpoint(directory, step, extra_meta=extra)

    def flush_checkpoints(self) -> None:
        """Block until every async commit landed (no-op when sync).

        Re-raises the writer thread's first error — the synchronization
        point a caller must cross before trusting that a `checkpoint`
        call's commit exists on disk.
        """
        if self.checkpointer is not None:
            self.checkpointer.flush()

    def restore(self, directory: str) -> None:
        """Install a checkpoint: state, serving cache, exactly-once log.

        Pending queues are dropped (they were never part of the commit);
        an at-least-once source replays the stream WITH THE ORIGINAL
        seqnos and `submit` skips everything at or below the restored
        log (a replay without seqnos is indistinguishable from new
        traffic and will re-apply).  Pending async commits are flushed
        FIRST (deterministic LATEST: restore must never race its own
        writer; a recorded writer crash re-raises here instead of being
        silently absorbed).  Cost: one O(state) read + device upload.
        """
        self.flush_checkpoints()
        self.store.restore(directory)
        meta = self.store.last_restored_meta.get("engine")
        if meta is None:
            # legacy checkpoint layout: separate ENGINE file
            with open(os.path.join(directory, "ENGINE")) as f:
                meta = json.load(f)
        self._load_log(meta)
        self._queues.clear()
        self._heap.clear()
        self._pending_seqnos.clear()
        self._n_pending = 0
        # dropped queues also drop any open backpressure gap: the source
        # replays from the restored log, so there is no seqno to readmit
        self._shed_from = None
        # the restored state has no batch behind it: nothing deferred
        self._maintenance_due = False
        self._dropped_dev = None

    def _load_log(self, meta: dict) -> None:
        """Install a persisted exactly-once log (see `checkpoint`)."""
        self.watermark = meta["watermark"]
        self._processed_above = set(meta.get("processed_above", []))
        self._next_seqno = meta["next_seqno"]
        # legacy (pre-sharding) checkpoints lack `delivered`; they were
        # written by dense single engines, where every seqno below
        # next_seqno was delivered
        self._max_delivered = meta.get("delivered", meta["next_seqno"] - 1)

    def _reset_log(self) -> None:
        """Fresh empty log (resharding restore starts a new shard log)."""
        self.watermark = -1
        self._processed_above = set()
        self._pending_seqnos = set()
        self._max_delivered = -1
        self._next_seqno = 0
        self._queues.clear()
        self._heap.clear()
        self._n_pending = 0
        self._shed_from = None
        self._maintenance_due = False
        self._dropped_dev = None


# ---------------------------------------------------------------------------
# User-axis sharded deployment (DESIGN.md §7)
# ---------------------------------------------------------------------------

_SHARD_MANIFEST = "SHARDS"

# every StreamState leaf, derived so a new field cannot silently be
# dropped by the resharding assembler
_STATE_LEAVES = tuple(f.name for f in dataclasses.fields(StreamState)
                      if f.name != "n_real")


class ShardedStreamingEngine:
    """User-axis sharded streaming maintenance (DESIGN.md §7).

    The paper's Spark deployment partitions the keyed state store by
    user; this is the jax analog: ``n_shards`` fully independent
    :class:`StreamingEngine` instances, each owning its own
    :class:`StateStore` (optionally on its own device mesh, see
    ``launch.mesh.make_user_shard_meshes``), its own exactly-once log,
    its own pow2 sub-batch buckets and its own atomic ``LATEST`` commit.
    This router only (a) assigns global seqnos, (b) routes events to
    shard ``user % n_shards`` translated to local row ``user //
    n_shards`` (:class:`repro.parallel.sharding.UserShardSpec`), and
    (c) orchestrates cross-shard checkpoint/restore and serving — no
    per-event cross-shard communication exists, matching the paper's
    "each user vector is calculated independently".

    Exactly-once across shards: each shard's log stores its watermark
    under SUBSEQUENCE semantics (see :class:`StreamingEngine`), so a
    crash that lands between two shard commits restores shards at
    different steps and a full-stream replay re-applies exactly the
    events each shard lost — never a double-apply (the failure table in
    DESIGN.md §7).  Resharding (restore an N-shard checkpoint into M
    shards) reassembles global rows by the spec bijection and carries
    the N old logs as **legacy logs**: redelivered events are checked
    against the log of their OLD owner shard (`user % N` is computable
    at submit time), which is exact, bounded, and survives further
    checkpoints.
    """

    def __init__(self, stores: Sequence[StateStore], params: TifuParams,
                 spec: UserShardSpec, **engine_kw):
        if len(stores) != spec.n_shards:
            raise ValueError(f"{len(stores)} stores for {spec.n_shards} "
                             "shards")
        for s, st in enumerate(stores):
            want = spec.shard_users(s)
            if st.cfg.n_users != want:
                raise ValueError(
                    f"shard {s}: store has {st.cfg.n_users} user rows, "
                    f"spec owns {want} (n_users={spec.n_users})")
        self.spec = spec
        self.params = params
        self.shards = [StreamingEngine(st, params, **engine_kw)
                       for st in stores]
        # One shared background writer for the whole deployment (§12):
        # FIFO submission order means the SHARDS manifest job queued
        # after the shard commits can never land before them.
        self.checkpointer: Optional[AsyncCheckpointer] = \
            engine_kw.get("checkpointer")
        self._next_seqno = 0
        # Legacy exactly-once logs from resharding restores:
        # [{"n_shards": N_old, "logs": [{"watermark", "processed_above"}]}]
        self._legacy: List[dict] = []
        # Router-level dead letters: events with no owner shard (global
        # user id out of range) — per-shard queues hold the rest.
        self.dead_letter: deque = deque(maxlen=1024)
        self.router_dead_letters = 0

    @classmethod
    def create(cls, spec: UserShardSpec, params: TifuParams,
               max_baskets: int, max_basket_size: int,
               max_groups: Optional[int] = None, meshes=None,
               **engine_kw) -> "ShardedStreamingEngine":
        """Build the per-shard stores from the spec and store shapes.

        ``meshes`` (optional) is one device mesh per shard
        (``launch.mesh.make_user_shard_meshes``); None keeps every
        shard's arrays on the default device.
        """
        stores = []
        for s in range(spec.n_shards):
            cfg = StoreConfig(n_users=spec.shard_users(s),
                              n_items=params.n_items,
                              max_baskets=max_baskets,
                              max_basket_size=max_basket_size,
                              max_groups=max_groups)
            stores.append(StateStore(
                cfg, mesh=None if meshes is None else meshes[s]))
        return cls(stores, params, spec, **engine_kw)

    # -- ingestion ------------------------------------------------------------

    @property
    def n_pending(self) -> int:
        """Buffered (not yet applied) events across all shards."""
        return sum(sh.n_pending for sh in self.shards)

    @property
    def events_processed(self) -> int:
        """Total events applied across all shards."""
        return sum(sh.metrics.events_processed for sh in self.shards)

    @property
    def dead_letters(self) -> int:
        """Total quarantined events (router-level plus every shard)."""
        return (self.router_dead_letters
                + sum(sh.metrics.dead_letters for sh in self.shards))

    @property
    def backpressure_rejections(self) -> int:
        """Total backpressure-shed events across all shards."""
        return sum(sh.metrics.backpressure_rejections
                   for sh in self.shards)

    def _legacy_processed(self, user: int, seqno: int) -> bool:
        """True when a pre-reshard deployment already processed seqno.

        The old owner shard of ``user`` is computable from the legacy
        partition count, so the check is an exact per-event lookup into
        that shard's persisted log — O(#reshards) per event.
        """
        for entry in self._legacy:
            log = entry["logs"][user % entry["n_shards"]]
            if seqno <= log["watermark"] \
                    or seqno in log["processed_above"]:
                return True
        return False

    def submit(self, events: Iterable[Event], *,
               on_invalid: str = "raise",
               on_overflow: str = "raise") -> AdmissionResult:
        """Assign global seqnos and route events to their owner shards.

        Explicit-seqno events (at-least-once redelivery) are first
        checked against the legacy logs of any previous shard layout,
        then against the owner shard's live log (inside the shard's own
        ``submit``).  Events whose GLOBAL user id has no owner shard
        raise/quarantine at the router (``self.dead_letter``); all other
        validation/backpressure happens in the owner shard and is
        aggregated into one :class:`AdmissionResult` (or one
        :class:`Backpressure`, raised after the call's admissible events
        are enqueued).  A seqno-less event probes the owner shard
        BEFORE a global seqno is assigned: a shed event must stay
        seqno-less (it was never delivered), or its burned seqno becomes
        a permanent gap in the shard's log.  Cost: O(1) per event plus
        O(#reshards) dedup.
        """
        if on_invalid not in ("raise", "quarantine"):
            raise ValueError(f"on_invalid={on_invalid!r}")
        if on_overflow not in ("raise", "shed"):
            raise ValueError(f"on_overflow={on_overflow!r}")
        with span("engine.submit") as sp:
            res = self._route(events, on_invalid)
            sp.set_metadata(n=res.handed_in)
        if res.rejected and on_overflow == "raise":
            raise Backpressure(res.admitted, res.rejected,
                               res.first_rejected_seqno, self.n_pending)
        return res

    def _route(self, events: Iterable[Event],
               on_invalid: str) -> AdmissionResult:
        """`submit`'s legacy dedup, global seqnos and routing; each
        event goes to its owner shard's `StreamingEngine._admit`."""
        res = AdmissionResult()
        for ev in events:
            explicit = ev.seqno >= 0
            if explicit:
                self._next_seqno = max(self._next_seqno, ev.seqno + 1)
                if self._legacy and self._legacy_processed(ev.user,
                                                           ev.seqno):
                    res.deduped += 1
                    continue
            if not 0 <= ev.user < self.spec.n_users:
                reason = (f"user {ev.user} outside the deployment's "
                          f"[0, {self.spec.n_users}) global range")
                if on_invalid == "raise":
                    raise InvalidEventError(ev, reason)
                self.dead_letter.append((ev, reason))
                self.router_dead_letters += 1
                res.quarantined += 1
                continue
            sh = self.shards[self.spec.shard_of(ev.user)]
            if not explicit:
                if sh._would_shed(None):
                    sh.metrics.backpressure_rejections += 1
                    res.rejected += 1
                    continue
                ev = dataclasses.replace(ev, seqno=self._next_seqno)
                self._next_seqno += 1
            res.merge(sh._admit(
                [dataclasses.replace(
                    ev, user=int(self.spec.local_row(ev.user)))],
                on_invalid))
        return res

    def add_basket(self, user: int, items: Sequence[int]) -> None:
        """Enqueue one basket addition (Eq. 7–9) for global ``user``."""
        self.submit([Event(KIND_ADD_BASKET, user,
                           items=np.asarray(items, np.int32))])

    def delete_basket(self, user: int, pos: int) -> None:
        """Enqueue deletion of basket ``pos`` (Eq. 10–12) for ``user``."""
        self.submit([Event(KIND_DEL_BASKET, user, pos=pos)])

    def delete_item(self, user: int, pos: int, item: int) -> None:
        """Enqueue deletion of ``item`` from basket ``pos`` (Eq. 13)."""
        self.submit([Event(KIND_DEL_ITEM, user, pos=pos, item=item)])

    # -- unlearning front door (DESIGN.md §11) ----------------------------------

    def forget_user(self, user: int) -> ForgetReceipt:
        """Erase global ``user``'s history and every live trace of it.

        Same contract as :meth:`StreamingEngine.forget_user`, with the
        consumed seqnos taken from THE ROUTER's counter: the router owns
        the global seqno counter, and a shard-local assignment would
        collide with future router-assigned ones — silently deduping
        later legitimate traffic.  Clears the row at the owner shard
        and purges both the router's dead letters (global ids) and the
        shard's (local rows).
        """
        if not 0 <= user < self.spec.n_users:
            raise InvalidEventError(
                Event(KIND_DEL_BASKET, user),
                f"user {user} outside the deployment's "
                f"[0, {self.spec.n_users}) global range")
        t0 = time.perf_counter()
        with span("engine.forget", user=user) as sp:
            with span("engine.forget.drain"):
                self.run_until_drained()
            sh = self.shards[self.spec.shard_of(user)]
            local = int(self.spec.local_row(user))
            # device-side scalar read (see StreamingEngine.forget_user)
            with span("engine.forget.lookup"):
                nb = int(jax.device_get(sh.store.state.n_baskets[local]))
            sp.set_metadata(baskets=nb)
            seqnos = range(self._next_seqno, self._next_seqno + nb)
            with span("engine.forget.clear", baskets=nb):
                sh._clear_user(local, seqnos)
                self._next_seqno = seqnos.stop
            with span("engine.forget.scrub"):
                sh.store.scrub_rows([local])
            with span("engine.forget.purge"):
                purged = sh._purge_dead_letters(local)
                kept = [(ev, why) for ev, why in self.dead_letter
                        if ev.user != user]
                purged += len(self.dead_letter) - len(kept)
                self.dead_letter.clear()
                self.dead_letter.extend(kept)
            latency_s = time.perf_counter() - t0
            with span("engine.forget.residue"):
                residue = sh.store.row_residue([local])
        return ForgetReceipt(
            user=user, n_baskets_deleted=nb, seqnos=tuple(seqnos),
            purged_dead_letters=purged, latency_s=latency_s,
            residue=residue)

    # -- micro-batch processing -----------------------------------------------

    def step(self) -> int:
        """Process one micro-batch per shard; returns events applied.

        Kind partitioning happens locally, so pow2 sub-batch bucket
        sizes stay shard-local.  Three phases: every shard first cuts
        its batch and dispatches its device step-summary programs
        (`_prepare_step`, async), then every shard blocks on its ONE
        summary fetch and applies (`_complete_step`), then the log
        advances — so no shard's transfer delays another shard's
        dispatch.  One ``engine.step`` span covers all three phases.
        """
        with span("engine.step"):
            prepped = [(sh, sh._prepare_step()) for sh in self.shards]
            begun = [(sh, sh._complete_step(prep)) for sh, prep in prepped]
            return sum(sh._finish_step(evs) for sh, evs in begun if evs)

    def run_until_drained(self, max_batches: int = 10_000) -> int:
        """Step all shards until no shard has pending events."""
        total = 0
        for _ in range(max_batches):
            n = self.step()
            if n == 0:
                break
            total += n
        return total

    # -- serving ---------------------------------------------------------------

    def corpora(self) -> List[jax.Array]:
        """Per-shard cached serving corpora (each shard-local, §3.6)."""
        return [sh.store.corpus() for sh in self.shards]

    def quantized_corpora(self) -> List[tuple]:
        """Per-shard int8 corpora ``[(q, scale), ...]`` (§8.4 cache)."""
        return [sh.store.quantized_corpus() for sh in self.shards]

    def recommend(self, user_ids, topn: int = 10, k: Optional[int] = None,
                  alpha: Optional[float] = None,
                  metric: str = "euclidean",
                  quantized: bool = False) -> np.ndarray:
        """Cross-shard top-n recommendations for global ``user_ids``.

        Delegates to ``core.knn.sharded_recommend_for_users`` (per-shard
        candidate top-k, streaming merge of [Q, k] score lists — never a
        corpus gather; DESIGN.md §7).  Query batches are padded to pow2
        buckets exactly like the single-engine batcher
        (`StreamingEngine.recommend`): every shard's candidate program
        sees the bucketed Q, so the per-shard compiled-shape count stays
        O(log max_batch) too.  ``quantized=True`` runs the int8 D-tiled
        pipeline over the per-shard quantized caches instead
        (`core.knn.sharded_recommend_for_users_quant`, DESIGN.md §8.4;
        euclidean only) — row-wise quantization makes the cross-shard
        merge bitwise the single-engine quantized path.
        """
        ids, q_n, _ = _pad_request(user_ids)
        if q_n == 0:
            return np.zeros((0, topn), np.int32)
        k = self.params.k_neighbors if k is None else k
        alpha = self.params.alpha if alpha is None else alpha
        if quantized:
            if metric != "euclidean":
                raise ValueError("quantized serving is euclidean-only")
            recs = knn.sharded_recommend_for_users_quant(
                self.quantized_corpora(), ids, k=k, alpha=alpha,
                topn=topn, n_shards=self.spec.n_shards,
                n_items=self.params.n_items)
        else:
            recs = knn.sharded_recommend_for_users(
                self.corpora(), ids, k=k, alpha=alpha,
                topn=topn, n_shards=self.spec.n_shards, metric=metric,
                n_items=self.params.n_items)
        return np.asarray(recs)[:q_n]

    # -- recovery ---------------------------------------------------------------

    def _shard_dir(self, directory: str, shard: int) -> str:
        return os.path.join(directory, f"shard_{shard:03d}")

    def _serialized_legacy(self) -> list:
        return [{"n_shards": e["n_shards"],
                 "logs": [{"watermark": lg["watermark"],
                           "processed_above": sorted(lg["processed_above"])}
                          for lg in e["logs"]]} for e in self._legacy]

    @staticmethod
    def _parse_legacy(raw: list) -> list:
        return [{"n_shards": e["n_shards"],
                 "logs": [{"watermark": lg["watermark"],
                           "processed_above":
                               set(lg.get("processed_above", []))}
                          for lg in e["logs"]]} for e in raw]

    def checkpoint(self, directory: str, step: int) -> None:
        """Commit every shard, then the cross-shard manifest.

        Each shard commits independently and atomically (its own
        fsync'd ``LATEST``, carrying its own exactly-once log); the
        ``SHARDS`` manifest (atomic too) only records the layout, the
        router seqno counter and the legacy logs.  A crash anywhere
        leaves shards at possibly different steps — recoverable by
        replay (DESIGN.md §7 failure table).  A directory written under
        a DIFFERENT layout is refused: re-partitioned shard files would
        tear the old manifest's view.
        """
        os.makedirs(directory, exist_ok=True)
        man_path = os.path.join(directory, _SHARD_MANIFEST)
        if os.path.exists(man_path):
            try:
                man = load_json_checked(man_path)
            except CorruptCheckpointError as e:
                raise CorruptCheckpointError(
                    f"existing manifest {man_path} is torn/corrupt "
                    f"({e}); refusing to commit over a directory whose "
                    "layout cannot be verified — use a fresh directory "
                    "or restore first") from e
            if man["n_shards"] != self.spec.n_shards \
                    or man["n_users"] != self.spec.n_users:
                raise ValueError(
                    f"checkpoint directory holds a "
                    f"{man['n_shards']}-shard/{man['n_users']}-user "
                    f"layout; refusing to overwrite with "
                    f"{self.spec.n_shards}/{self.spec.n_users} — use a "
                    "fresh directory after resharding")
        for s, sh in enumerate(self.shards):
            sh.checkpoint(self._shard_dir(directory, s), step)
        payload = {
            "version": 1,
            "n_shards": self.spec.n_shards,
            "n_users": self.spec.n_users,
            "step": step,
            "next_seqno": self._next_seqno,
            "legacy_logs": self._serialized_legacy(),
        }
        if self.checkpointer is not None:
            # FIFO: queued AFTER every shard's commit job, so the
            # manifest can never describe shards that have not landed
            self.checkpointer.submit(
                functools.partial(atomic_write_json, man_path, payload),
                label=f"{man_path}@{step}")
        else:
            atomic_write_json(man_path, payload)

    def flush_checkpoints(self) -> None:
        """Block until every shard commit + manifest landed (see §12)."""
        if self.checkpointer is not None:
            self.checkpointer.flush()

    def restore(self, directory: str) -> None:
        """Install a sharded checkpoint, resharding when layouts differ.

        Same shard count: each shard restores its own commit (states may
        sit at different steps after a torn crash; replay converges
        them).  Different shard count (N→M): global user rows are
        reassembled through the spec bijection and the N old logs become
        legacy logs (`_legacy_processed`).  A flat single-engine
        checkpoint (no manifest, root ``LATEST``) restores as N=1.
        Pending async commits are flushed first (deterministic LATEST
        + manifest; a recorded writer crash re-raises here).
        """
        self.flush_checkpoints()
        man_path = os.path.join(directory, _SHARD_MANIFEST)
        man = None
        if os.path.exists(man_path):
            try:
                man = load_json_checked(man_path)
            except CorruptCheckpointError as e:
                raise CorruptCheckpointError(
                    f"sharded checkpoint manifest {man_path} is "
                    f"torn/corrupt ({e}); the per-shard commits may "
                    "still be intact — restore shard directories "
                    "individually or rebuild the manifest") from e
            n_old = man["n_shards"]
            if man["n_users"] != self.spec.n_users:
                raise ValueError(
                    f"checkpoint n_users={man['n_users']} != spec "
                    f"n_users={self.spec.n_users}")
            dirs = [self._shard_dir(directory, s) for s in range(n_old)]
        elif os.path.exists(os.path.join(directory, "LATEST")):
            n_old, dirs = 1, [directory]      # flat single-engine layout
        else:
            raise FileNotFoundError(
                f"no {_SHARD_MANIFEST} manifest or LATEST in {directory}")
        # every shard directory must hold a restorable commit before ANY
        # shard is touched: failing fast with the offending path beats a
        # bare traceback after half the fleet was already overwritten
        missing = [d for d in dirs
                   if not (os.path.exists(os.path.join(d, "LATEST"))
                           or os.path.exists(os.path.join(d,
                                                          "LATEST.prev")))]
        if missing:
            raise FileNotFoundError(
                f"sharded checkpoint {directory} declares {n_old} "
                f"shard(s) but is missing commit(s) in: "
                f"{', '.join(missing)} — expected shard_000 … "
                f"shard_{n_old - 1:03d}, each holding a LATEST (or "
                "LATEST.prev) commit")
        self._legacy = self._parse_legacy(man.get("legacy_logs", [])
                                          if man else [])
        if n_old == self.spec.n_shards:
            for s, sh in enumerate(self.shards):
                sh.restore(dirs[s])
            self._next_seqno = max(
                [sh._next_seqno for sh in self.shards]
                + ([man["next_seqno"]] if man else []))
        else:
            self._restore_resharded(dirs, n_old)

    def recover_shard(self, shard: int, directory: str) -> dict:
        """Re-restore ONE shard's commit with its serving kept degraded.

        Freezes the shard's serving corpus first, so cross-shard
        ``recommend`` keeps answering from the pinned snapshot (stale
        but well-formed) while the shard's state store restores from its
        last good commit — the other shards are untouched.  On success
        serving thaws to the recovered state and the shard's recovery
        info (``{"source", "skipped"}``, see
        ``state_store.load_checkpoint_arrays``) is returned; on failure
        the shard STAYS frozen, still answering from the snapshot, and
        the error propagates.
        """
        sh = self.shards[shard]
        sh.freeze_serving()
        sh.restore(self._shard_dir(directory, shard))
        info = dict(sh.store.last_restored_meta.get(
            "_recovery", {"source": "LATEST", "skipped": []}))
        sh.thaw_serving()
        return info

    def _restore_resharded(self, dirs: List[str], n_old: int) -> None:
        """N→M restore: re-partition states, demote old logs to legacy."""
        spec = self.spec
        metas, leaves, old_logs = [], [], []
        for d in dirs:
            meta, lv = load_checkpoint_arrays(d)
            # shape validation minus the per-shard user count (which
            # legitimately differs across layouts)
            probe = dict(meta)
            probe.pop("n_users", None)
            self.shards[0].store._validate_meta(probe)
            log = meta.get("engine")
            if log is None:
                path = os.path.join(d, "ENGINE")
                if os.path.exists(path):       # legacy flat layout
                    with open(path) as f:
                        log = json.load(f)
            if log is None:
                raise ValueError(
                    f"shard checkpoint {d} carries no exactly-once log; "
                    "refusing to reshard (replay could double-apply)")
            metas.append(meta)
            leaves.append(pad_item_leaves(
                lv, self.shards[0].store.cfg.n_cols))
            old_logs.append(log)
        n_total = sum(lv["n_baskets"].shape[0] for lv in leaves)
        if n_total != spec.n_users:
            raise ValueError(f"checkpoint holds {n_total} user rows, spec "
                             f"n_users={spec.n_users}")
        # assemble per-new-shard host buffers; the spec bijection covers
        # every row, so no initialization value survives
        out = []
        for s in range(spec.n_shards):
            cfg = self.shards[s].store.cfg
            zero = StreamState.zeros(cfg.n_users, cfg.n_items,
                                     cfg.max_baskets, cfg.max_basket_size,
                                     cfg.max_groups)
            out.append({name: np.asarray(getattr(zero, name)).copy()
                        for name in _STATE_LEAVES})
        for so, lv in enumerate(leaves):
            rows = lv["n_baskets"].shape[0]
            u_glob = np.arange(rows, dtype=np.int64) * n_old + so
            keep = u_glob < spec.n_users
            u_glob = u_glob[keep]
            ns, nr = u_glob % spec.n_shards, u_glob // spec.n_shards
            for name in _STATE_LEAVES:
                src = lv[name][keep]
                for s in range(spec.n_shards):
                    m = ns == s
                    out[s][name][nr[m]] = src[m]
        for s, sh in enumerate(self.shards):
            sh.store.install_state(StreamState(
                **{k: jnp.asarray(v) for k, v in out[s].items()},
                n_real=sh.store.cfg.n_items))
            sh._reset_log()
        self._legacy.append({"n_shards": n_old, "logs": [
            {"watermark": lg["watermark"],
             "processed_above": set(lg.get("processed_above", []))}
            for lg in old_logs]})
        self._next_seqno = max(max(lg["next_seqno"] for lg in old_logs),
                               self._next_seqno)
