"""Elastic session pools: pre-compiled capacity tiers with live migration.

A ``SessionPool``'s capacity is baked into its compiled batched hop step, so
a hot pool hard-fails with ``PoolFullError`` instead of growing. The paper's
fix for a fixed compute envelope is to *pick* the envelope, not to stretch
it: TinyLSTMs and the sparsity-tradeoff literature both serve edge speech
enhancement from a small menu of pre-sized models. ``ElasticSessionPool`` is
the serving-side analogue — a small **ladder of capacity tiers** (default
4/16/64), each a legal batch shape of ONE shared jit hop step, with live
sessions migrated **bit-exactly** between tiers through the existing
``SessionTicket`` export/import seam:

- **One step function, one compilation per tier** — all tiers share a single
  ``make_stream_hop`` callable; jax.jit specializes it per batch shape, so
  tier capacity N compiles exactly once (the first step at that tier, or
  eagerly in ``prewarm()``). Resizing swaps the *state*, never the code.
- **Grow on attach-would-overflow** — ``attach()`` on a full pool climbs to
  the next tier instead of raising; ``PoolFullError`` only at the top tier.
- **Shrink on sustained low occupancy** — every ``pump()``/``step()`` ticks
  a watermark check: when occupancy has sat at or below
  ``shrink_fraction * lower_tier`` for ``shrink_patience`` consecutive
  checks, the pool drops one tier. The fraction (not just "fits") plus the
  patience counter are the hysteresis that keeps a pool oscillating around a
  tier boundary from thrashing: growth is instant, shrinking is lazy, and a
  freshly shrunk pool has at least ``1 - shrink_fraction`` headroom.
- **Resizes compose with the PR-3 machinery** — a resize first ``collect()``s
  the in-flight dispatch pipeline (``inflight=2`` double buffering), so no
  pending step's output is orphaned; tickets carry ring buffers, unread
  output, and per-session stats, and the pool-wide ``step_seconds`` latency
  record is carried across (same list object), so accounting is continuous.
- **Stable handles** — clients hold ``ElasticSession`` handles that survive
  resizes (the inner per-tier ``Session`` is swapped underneath), exactly as
  ``ShardedSession`` survives shard migration.

Observability: ``grow_count``/``shrink_count``/``resize_seconds`` (the pause
each migration cost) and the ``(from, to)`` ``resize_log`` feed the ramp
benchmark (``benchmarks/server_throughput.py --ramp``) and ``shard_stats()``.

Invariants are property-tested under randomized churn in
``tests/test_elastic_pool.py`` (bit-identity to a fixed-capacity reference
pool) and checked op-by-op by ``tests/soak.py``.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.quant import QuantSpec
from repro.models import tftnn as tft_mod
from repro.serve.faults import FaultPlan
from repro.serve.scheduler import SchedulerDecision, SchedulerObservation
from repro.serve.session_server import (
    STEP_COUNTERS,
    PoolFullError,
    QuarantineRecord,
    Session,
    SessionError,
    SessionPoisonedError,
    SessionPool,
    SessionTicket,
)

Pytree = dict


@dataclasses.dataclass
class ElasticSession:
    """Client handle returned by ``ElasticSessionPool.attach``.

    Stable across resizes: ``inner`` is the live per-tier ``Session`` and is
    swapped when the pool migrates to another tier; ``sid`` never changes.
    """

    sid: int
    inner: Session
    detached: bool = False

    @property
    def stats(self):
        """Per-session accounting (``SessionStats``) — survives resizes."""
        return self.inner.stats

    @property
    def slot(self) -> int:
        """The session's slot in the CURRENT tier (changes on resize)."""
        return self.inner.slot


class ElasticSessionPool:
    """A ``SessionPool`` that resizes itself along a ladder of capacity tiers.

    Same client surface as ``SessionPool`` (attach/feed/read/detach/pump plus
    the dispatch/collect seam and export/import migration), so it drops into
    ``ShardedSessionPool`` as an elastic shard. Capacity changes are live
    migrations: every session's recurrent state, ring buffer, unread output,
    and stats move bit-exactly (``SessionTicket``), and the stream's audio is
    bit-identical to one served by a fixed pool at the top tier.

    Args:
        params: TFTNN parameter pytree (placed on ``device`` once, here).
        cfg: model/front-end config shared by every tier.
        tiers: strictly increasing capacity ladder, e.g. ``(4, 16, 64)``.
            The pool starts at ``tiers[0]`` and never exceeds ``tiers[-1]``.
        quant / sample_rate / donate / device / backend / prune_keep /
            prune_axis / prune_granularity / prune_block / inflight /
            max_unread_hops / on_unparked /
            hops_per_step: forwarded to every tier's ``SessionPool`` (see
            there). The compiled step is built ONCE from these and shared by
            all tiers (``hops_per_step=K`` serves every tier through the
            multi-hop fused dispatch path; tier migration carries any
            staged ring backlog bit-exactly through ``SessionTicket``).
        shrink_fraction: occupancy watermark for shrinking, relative to the
            NEXT LOWER tier: the pool is shrink-eligible only while
            ``num_active <= shrink_fraction * lower_tier`` (default 0.5 — a
            freshly shrunk pool is at most half full). Must be in (0, 1].
        shrink_patience: consecutive eligible ``pump()``/``step()`` checks
            required before a shrink actually happens (default 8). Growth
            has no patience — an attach must not fail while capacity exists.
        step_fn: pre-built hop step shared with other pools (see
            ``SessionPool``); seeds the default lane-count entry of the
            shared step cache when given.
        step_fns: shared compiled-step cache forwarded to every tier's
            ``SessionPool`` (ONE dict for the whole ladder — and, via the
            router, for a whole fleet): each ``(max_hops, ingest_ring)``
            lane count the adaptive scheduler explores compiles once per
            batch shape, ever.
        ingest_ring: device-resident ingestion ring depth forwarded to every
            tier (see ``SessionPool``); ring backlogs migrate bit-exactly
            across tiers through the same ``SessionTicket`` seam.
        durability: optional ``DurabilityManager`` (see ``SessionPool``) —
            held at THIS layer, keyed by the resize-stable handle, and
            deliberately NOT forwarded to the per-tier inner pools: a tier
            migration must look like one continuous stream on disk, not a
            detach + fresh attach.
        finite_guard / faults / fault_tag: fault-containment knobs forwarded
            to every tier's ``SessionPool``. Quarantine records are
            harvested back to THIS layer after every collect and re-keyed
            by the resize-stable handle sid (inner per-tier sids restart at
            0 on every resize, so inner records must never outlive their
            pool); ``take_quarantined``/``quarantined``/``clear_quarantined``
            mirror the ``SessionPool`` surface with elastic handles.

    Raises:
        ValueError: empty/non-increasing ``tiers``, bad ``shrink_fraction``.
    """

    def __init__(
        self,
        params: Pytree,
        cfg: tft_mod.TFTConfig,
        tiers: Sequence[int] = (4, 16, 64),
        *,
        quant: Optional[QuantSpec] = None,
        sample_rate: int = 8000,
        donate: bool = True,
        device: Optional[jax.Device] = None,
        backend: str = "xla",
        prune_keep: Optional[float] = None,
        prune_axis: Optional[int] = None,
        prune_granularity: Optional[str] = None,
        prune_block: Tuple[int, int] = (8, 8),
        inflight: int = 1,
        max_unread_hops: Optional[int] = None,
        on_unparked=None,
        hops_per_step: int = 1,
        shrink_fraction: float = 0.5,
        shrink_patience: int = 8,
        step_fn=None,
        step_fns: Optional[Dict[Any, Any]] = None,
        ingest_ring: Optional[int] = None,
        durability: Optional[Any] = None,
        finite_guard: bool = False,
        faults: Optional[FaultPlan] = None,
        fault_tag: str = "elastic",
    ) -> None:
        tiers = tuple(int(t) for t in tiers)
        if not tiers:
            raise ValueError("tiers must be a non-empty capacity ladder")
        # >= 2, not >= 1: XLA specializes batch-1 reductions (matvec vs
        # matmul), which breaks the cross-tier bit-identity this pool
        # promises; every capacity >= 2 lowers identically per slot.
        if any(t < 2 for t in tiers) or any(
            b <= a for a, b in zip(tiers, tiers[1:])
        ):
            raise ValueError(
                f"tiers must be strictly increasing capacities >= 2, got {tiers} "
                f"(capacity-1 tiers are rejected: XLA's batch-1 specialization "
                f"would break bit-exact migration between tiers)"
            )
        if not 0.0 < shrink_fraction <= 1.0:
            raise ValueError("shrink_fraction must be in (0, 1]")
        if shrink_patience < 1:
            raise ValueError("shrink_patience must be >= 1")
        self.tiers = tiers
        self.cfg = cfg
        self.quant = quant
        self.backend = backend
        self.device = device
        self._sample_rate = sample_rate
        self._donate = donate
        self._inflight = inflight
        self._max_unread_hops = max_unread_hops
        # the inner pool wakes up with its per-tier Session; clients hold the
        # resize-stable ElasticSession — translate before calling out
        self._on_unparked = (
            None if on_unparked is None
            else lambda inner: self._wake(on_unparked, inner)
        )
        self.hops_per_step = hops_per_step
        self._shrink_fraction = shrink_fraction
        self._shrink_patience = shrink_patience
        if device is not None:
            params = jax.device_put(params, device)
        self._params = params
        self._prune_keep = prune_keep
        self._prune_axis = prune_axis
        self._prune_granularity = prune_granularity
        self._prune_block = prune_block
        self._ingest_ring = ingest_ring
        # ONE step cache for every tier: jit specializes per (capacity,)
        # batch shape and pools fill one entry per lane count on demand, so
        # each (lane count, tier shape) costs one compilation, ever.
        self._step_fns: Dict[Any, Any] = step_fns if step_fns is not None else {}
        self._step_fn_seed = step_fn
        # durability lives at the elastic layer (keyed by the stable handle
        # sid) so tier migrations never look like detach+attach on disk; the
        # inner per-tier pools are built WITHOUT a manager
        self._durability = durability
        self._durable_ids: Dict[int, str] = {}
        self._finite_guard = finite_guard
        self._faults = faults
        self._fault_tag = fault_tag
        # quarantine bookkeeping lives at THIS layer, keyed by the stable
        # handle sid: inner per-tier pools are rebuilt on every resize and
        # restart their sid counters at 0, so an inner QuarantineRecord kept
        # across a resize would collide with an innocent new session
        self._quarantined: Dict[int, QuarantineRecord] = {}
        self._fresh_quarantined: List[QuarantineRecord] = []
        self.quarantined_count = 0
        self._brownout_hops_base = 0  # hops from pools retired by resizes
        self._brownout_level = 0
        self._pool = self._make_pool(tiers[0])
        self._handles: Dict[int, ElasticSession] = {}
        self._sid_counter = itertools.count()
        self._low_streak = 0
        self.grow_count = 0
        self.shrink_count = 0
        self.resize_seconds: List[float] = []  # pause per resize (migration)
        self.resize_log: List[Tuple[int, int]] = []  # (from_cap, to_cap)

    def _wake(self, on_unparked, inner: Session) -> None:
        for handle in self._handles.values():
            if handle.inner is inner:
                on_unparked(handle)
                return

    def _make_pool(self, capacity: int) -> SessionPool:
        return SessionPool(
            self._params,
            self.cfg,
            capacity,
            quant=self.quant,
            sample_rate=self._sample_rate,
            donate=self._donate,
            device=self.device,
            backend=self.backend,
            inflight=self._inflight,
            max_unread_hops=self._max_unread_hops,
            on_unparked=self._on_unparked,
            hops_per_step=self.hops_per_step,
            prune_keep=self._prune_keep,
            prune_axis=self._prune_axis,
            prune_granularity=self._prune_granularity,
            prune_block=self._prune_block,
            step_fn=self._step_fn_seed,
            step_fns=self._step_fns,
            ingest_ring=self._ingest_ring,
            finite_guard=self._finite_guard,
            faults=self._faults,
            fault_tag=self._fault_tag,
        )

    def prewarm(self, lane_counts: Optional[Sequence[int]] = None) -> None:
        """Compile every tier's programs now (``SessionPool.prewarm`` on a
        pool of each tier's capacity; ``lane_counts`` default to
        ``(hops_per_step,)``), so neither a serving-path dispatch nor a
        resize stalls on jit."""
        for cap in self.tiers:
            pool = self._pool if cap == self._pool.capacity else self._make_pool(cap)
            pool.prewarm(lane_counts)

    # -- capacity / introspection -------------------------------------------

    @property
    def capacity(self) -> int:
        """The CURRENT tier's capacity (changes on resize)."""
        return self._pool.capacity

    @property
    def max_capacity(self) -> int:
        """The top tier — the hard ``PoolFullError`` bound."""
        return self.tiers[-1]

    @property
    def tier_index(self) -> int:
        return self.tiers.index(self._pool.capacity)

    @property
    def num_active(self) -> int:
        return len(self._handles)

    @property
    def sample_rate(self) -> int:
        return self._sample_rate

    @property
    def step_seconds(self) -> List[float]:
        """Pool-wide per-step latency record — the SAME list across resizes
        (latency accounting continuity; see ``_resize``)."""
        return self._pool.step_seconds

    # -- resizing ------------------------------------------------------------

    def resize_to(self, capacity: int) -> None:
        """Migrate the pool to an explicit tier (mostly for tests/benchmarks).

        Args:
            capacity: a value from ``tiers`` with room for every live session.

        Raises:
            ValueError: ``capacity`` is not on the ladder or is smaller than
                the current occupancy. The pool is unchanged on failure.
        """
        if capacity not in self.tiers:
            raise ValueError(f"capacity {capacity} is not on the ladder {self.tiers}")
        if capacity < self.num_active:
            raise ValueError(
                f"cannot resize to {capacity}: {self.num_active} sessions are live"
            )
        if capacity != self._pool.capacity:
            self._resize(capacity)

    def try_shrink(self, force: bool = False) -> bool:
        """One watermark-gated shrink check (called from ``pump``/``step``).

        Args:
            force: shrink NOW, and keep dropping tiers while the sessions
                fit in the lower tier with at least one free slot — no
                patience, and the plain fits-with-headroom bound instead of
                the ``shrink_fraction`` watermark. Used by
                ``ShardedSessionPool.rebalance`` to slim donor shards
                immediately after sessions migrate away.

        Returns:
            True if the pool shrank at least one tier.
        """
        shrank = False
        while True:
            i = self.tier_index
            if i == 0:
                break
            lower = self.tiers[i - 1]
            if force:
                if self.num_active >= lower:
                    break
            elif self.num_active > self._shrink_fraction * lower:
                self._low_streak = 0
                break
            if not force:
                self._low_streak += 1
                if self._low_streak < self._shrink_patience:
                    break
            self._resize(lower)
            self._low_streak = 0
            shrank = True
            if not force:
                break  # at most one lazy shrink per check
        return shrank

    def _grow(self) -> bool:
        """Climb one tier; False when already at the top."""
        i = self.tier_index
        if i + 1 >= len(self.tiers):
            return False
        self._resize(self.tiers[i + 1])
        return True

    def _resize(self, new_capacity: int) -> None:
        """Live-migrate every session to a pool of ``new_capacity`` slots.

        Bit-exact by construction: drain the in-flight dispatch pipeline
        (``collect`` — mandatory under ``inflight>1`` so no pending step's
        output is orphaned), snapshot every session through the same
        ``SessionTicket`` seam shard migration uses, then resume each one in
        the new pool. The old pool's ``step_seconds`` list moves to the new
        pool (same object), so latency percentiles span the resize.
        """
        t0 = time.perf_counter()
        old = self._pool
        old.collect()  # drain the pending pipeline before swapping tiers
        # a session the drain just poisoned must be harvested NOW: it moves
        # to this layer's quarantine instead of being exported to the new
        # tier (its state is non-finite by construction)
        self._harvest_quarantined()
        tickets = [
            (handle, old.export_session(handle.inner))
            for handle in list(self._handles.values())
        ]
        new = self._make_pool(new_capacity)
        new.step_seconds = old.step_seconds  # latency continuity (same list)
        for k in STEP_COUNTERS:  # so are the step counters
            setattr(new, k, getattr(old, k))
        new.set_brownout(self._brownout_level)
        self._brownout_hops_base += old.brownout_hops
        for handle, ticket in tickets:
            handle.inner = new.import_session(ticket)
        grew = new_capacity > old.capacity
        self._pool = new
        self.grow_count += grew
        self.shrink_count += not grew
        # any resize restarts the shrink hysteresis: a streak accumulated at
        # the OLD tier must not count toward shrinking the new one
        self._low_streak = 0
        self.resize_log.append((old.capacity, new_capacity))
        self.resize_seconds.append(time.perf_counter() - t0)

    # -- session lifecycle ---------------------------------------------------

    def attach(self, durable_id: Optional[str] = None) -> ElasticSession:
        """Claim a slot, growing to the next tier when the current one is full.

        Args:
            durable_id: on-disk identity for the stream's crash journal when
                the pool has a ``durability`` manager (default
                ``esess-<sid>``); stale state under this id is wiped.
                Ignored without a manager.

        Returns:
            A resize-stable ``ElasticSession`` handle.

        Raises:
            PoolFullError: the TOP tier is full — the message reports the
                ladder, so callers can tell "configure a bigger ladder" from
                a plain fixed pool's "make a bigger pool".
        """
        if self._pool.num_active >= self._pool.capacity and not self._grow():
            raise PoolFullError(
                f"elastic pool is full at the top tier (capacity="
                f"{self.max_capacity}, active={self.num_active}, "
                f"tiers={self.tiers}); detach a session or widen the ladder"
            )
        handle = ElasticSession(sid=next(self._sid_counter), inner=self._pool.attach())
        self._handles[handle.sid] = handle
        if self._durability is not None:
            did = durable_id if durable_id is not None else f"esess-{handle.sid}"
            self._durable_ids[handle.sid] = did
            self._durability.begin(did)
        return handle

    def _check(self, handle: ElasticSession) -> None:
        rec = self._quarantined.get(handle.sid)
        if rec is not None and rec.session is handle:
            raise SessionPoisonedError(
                f"session {handle.sid} is quarantined: {rec.message}",
                session_id=handle.sid,
                good_hops=rec.good_hops,
                good_samples_in=rec.good_samples_in,
            )
        if handle.detached or self._handles.get(handle.sid) is not handle:
            raise SessionError(
                f"session {handle.sid} is not attached to this elastic pool"
            )

    def detach(self, handle: ElasticSession) -> np.ndarray:
        """Release the session; returns unread audio (see ``SessionPool``).

        Shrinking is NOT triggered here — occupancy watermarks are evaluated
        on the serving heartbeat (``pump``/``step``), where the patience
        counter gives churn a chance to settle.
        """
        self._check(handle)
        tail = self._pool.detach(handle.inner)
        handle.detached = True
        del self._handles[handle.sid]
        did = self._durable_ids.pop(handle.sid, None)
        if did is not None and self._durability is not None:
            self._durability.forget(did)
        return tail

    # -- audio I/O -----------------------------------------------------------

    def feed(self, handle: ElasticSession, samples) -> None:
        """Queue raw audio (any chunk length) for a session."""
        self._check(handle)
        did = self._durable_ids.get(handle.sid) if self._durability is not None else None
        if did is not None:
            # journal the exact bytes before the pool sees them (write-ahead)
            samples = np.array(samples, np.float32, copy=True).reshape(-1)
            due = self._durability.record_feed(did, samples, self.cfg.hop)
            self._pool.feed(handle.inner, samples)
            if due:
                self._durability.snapshot(
                    did, self._pool.snapshot_session(handle.inner)
                )
            return
        self._pool.feed(handle.inner, samples)

    def read(self, handle: ElasticSession) -> np.ndarray:
        """Pop all enhanced audio produced for this session so far."""
        self._check(handle)
        out = self._pool.read(handle.inner)
        if out.size and self._durability is not None:
            did = self._durable_ids.get(handle.sid)
            if did is not None:
                self._durability.record_read(did, handle.stats.samples_out)
        return out

    def backlog_hops(self, handle: ElasticSession) -> int:
        """Whole hops fed and not yet stepped (see ``SessionPool``)."""
        self._check(handle)
        return self._pool.backlog_hops(handle.inner)

    def read_degraded(self, handle: ElasticSession) -> Tuple[np.ndarray, bool]:
        """``read`` plus the brownout passthrough flag (see ``SessionPool``)."""
        self._check(handle)
        out, degraded = self._pool.read_degraded(handle.inner)
        if out.size and self._durability is not None:
            did = self._durable_ids.get(handle.sid)
            if did is not None:
                self._durability.record_read(did, handle.stats.samples_out)
        return out, degraded

    # -- fault containment ---------------------------------------------------

    def _harvest_quarantined(self) -> None:
        """Re-key inner-pool quarantine records by the resize-stable handle.

        Inner per-tier sids restart at 0 in every new pool, so a record left
        at the inner layer would outlive its pool and collide with an
        innocent session after a resize; the elastic layer owns them. The
        elastic-level durable id is RELEASED (files kept), which is what
        makes the pre-poison state recoverable through
        ``durability.recover_session(..., max_feed_samples=...)``.
        """
        for rec in self._pool.take_quarantined():
            handle = None
            for h in self._handles.values():
                if h.inner is rec.session:
                    handle = h
                    break
            if handle is None:
                continue
            del self._handles[handle.sid]
            did = self._durable_ids.pop(handle.sid, None)
            if did is not None and self._durability is not None:
                self._durability.release(did)  # keep files: recovery seam
            rec = dataclasses.replace(
                rec, sid=handle.sid, session=handle, durable_id=did
            )
            self._quarantined[handle.sid] = rec
            self._fresh_quarantined.append(rec)
            self.quarantined_count += 1

    @property
    def quarantined(self) -> Dict[int, QuarantineRecord]:
        """Quarantined sessions by handle sid (a copy)."""
        return dict(self._quarantined)

    def take_quarantined(self) -> List[QuarantineRecord]:
        """Drain quarantine records not yet handed to a caller (router seam)."""
        self._harvest_quarantined()
        fresh, self._fresh_quarantined = self._fresh_quarantined, []
        return fresh

    def clear_quarantined(self, sid: Optional[int] = None) -> None:
        """Forget quarantine record(s) — after recovery or deliberate drop."""
        if sid is None:
            self._quarantined.clear()
            self._fresh_quarantined = []
        else:
            self._quarantined.pop(sid, None)
            self._fresh_quarantined = [
                r for r in self._fresh_quarantined if r.sid != sid
            ]

    def set_brownout(self, level: int) -> None:
        """Set the degradation-ladder level; survives resizes (re-applied)."""
        self._brownout_level = max(0, min(3, int(level)))
        self._pool.set_brownout(self._brownout_level)

    @property
    def brownout(self) -> int:
        return self._brownout_level

    # -- the batched hop loop ------------------------------------------------

    def observation(self) -> SchedulerObservation:
        """The inner pool's snapshot plus the elastic tier context.

        Adds what the scheduler's grow/shrink policy needs: the tier ladder
        position, the next-lower tier's capacity, and the measured mean
        migration pause (the cost side of the shrink cost model) — all pure
        data, so recorded traces replay deterministically.
        """
        obs = self._pool.observation()
        i = self.tier_index
        pause_ms = (
            float(np.mean(self.resize_seconds)) * 1e3
            if self.resize_seconds else 0.0
        )
        return dataclasses.replace(
            obs,
            tier_index=i,
            n_tiers=len(self.tiers),
            lower_capacity=self.tiers[i - 1] if i > 0 else 0,
            mean_pause_ms=pause_ms,
        )

    def apply_decision(self, decision: SchedulerDecision) -> bool:
        """Act on the grow/shrink component of a scheduler decision.

        Grow climbs one tier immediately — the EWMA slope trigger fires
        BEFORE attach-overflow would have forced it. Shrink drops one tier
        only when every live session still fits in it (the scheduler's cost
        model already gated on occupancy, slope, patience, and the measured
        migration pause vs freed slots). Returns True iff a resize happened.
        """
        if decision.grow:
            return self._grow()
        if decision.shrink:
            i = self.tier_index
            if i > 0 and self.num_active <= self.tiers[i - 1]:
                self._resize(self.tiers[i - 1])
                return True
        return False

    def dispatch(self, max_hops: Optional[int] = None) -> int:
        """Non-blocking batched step launch (see ``SessionPool.dispatch``).

        No resize can happen between a ``dispatch()`` and its ``collect()``
        from inside the pool — resizes only trigger on attach (grow) and on
        ``pump``/``step``/``try_shrink`` (shrink), and ``_resize`` drains the
        pipeline first regardless.
        """
        return self._pool.dispatch(max_hops=max_hops)

    def wait_ready(self) -> None:
        self._pool.wait_ready()

    def collect(self, proc_share: Optional[float] = None) -> int:
        n = self._pool.collect(proc_share)
        self._harvest_quarantined()
        return n

    def step(self) -> int:
        n = self._pool.step()
        self._harvest_quarantined()
        self.try_shrink()
        return n

    def pump(self, scheduler=None) -> int:
        """Drain every eligible hop; optionally under adaptive control.

        Without a scheduler this is the legacy heartbeat: full-K dispatches
        plus the watermark/patience shrink check. With an
        ``AdaptiveScheduler`` every iteration observes, decides, applies the
        grow/shrink component (``apply_decision``), and dispatches at the
        decided lane count — the watermark check is NOT run, because the
        decision trace replaces it (and must stay replayable).
        """
        if scheduler is None:
            steps = self._pool.pump()
            self._harvest_quarantined()
            self.try_shrink()
            return steps
        steps = 0
        while True:
            decision = scheduler.observe(self.observation())
            self.apply_decision(decision)
            self.set_brownout(decision.brownout)
            k = min(decision.k, self.hops_per_step)
            if not self._pool.dispatch(max_hops=k):
                break
            steps += 1
        self._pool.collect()
        self._harvest_quarantined()
        return steps

    # -- migration seam (elastic shards) --------------------------------------

    def export_session(self, handle: ElasticSession) -> SessionTicket:
        """Snapshot + release one session (the shard-migration source)."""
        self._check(handle)
        ticket = self._pool.export_session(handle.inner)
        handle.detached = True
        del self._handles[handle.sid]
        did = self._durable_ids.pop(handle.sid, None)
        if did is not None and self._durability is not None:
            self._durability.release(did)  # keep the files: it lives on
        return ticket

    def import_session(
        self, ticket: SessionTicket, durable_id: Optional[str] = None
    ) -> ElasticSession:
        """Resume an exported session here, growing a full pool if needed.

        ``durable_id`` resumes journaling under an EXISTING durable identity
        (migration continuity); ``None`` imports without durability.
        """
        if self._pool.num_active >= self._pool.capacity and not self._grow():
            raise PoolFullError(
                f"elastic pool is full at the top tier (capacity="
                f"{self.max_capacity}, active={self.num_active}, "
                f"tiers={self.tiers}); cannot import the session"
            )
        handle = ElasticSession(
            sid=next(self._sid_counter), inner=self._pool.import_session(ticket)
        )
        self._handles[handle.sid] = handle
        if durable_id is not None and self._durability is not None:
            self.bind_durable(handle, durable_id)
        return handle

    def snapshot_session(self, handle: ElasticSession) -> SessionTicket:
        """Non-destructive snapshot (see ``SessionPool.snapshot_session``)."""
        self._check(handle)
        return self._pool.snapshot_session(handle.inner)

    def discard_output(self, handle: ElasticSession, n: int) -> int:
        """Drop up to ``n`` unread samples from the front (recovery seam)."""
        self._check(handle)
        return self._pool.discard_output(handle.inner, n)

    def bind_durable(self, handle: ElasticSession, durable_id: str) -> None:
        """Adopt existing durable state for a live session (recovery seam)."""
        if self._durability is None:
            raise SessionError("elastic pool has no durability manager")
        self._check(handle)
        self._durable_ids[handle.sid] = durable_id
        self._durability.resume(durable_id)

    # -- reporting -----------------------------------------------------------

    def latency_percentiles(self, qs=(50, 95, 99)) -> Dict[int, float]:
        return self._pool.latency_percentiles(qs)

    def shard_stats(self) -> Dict[str, object]:
        """``SessionPool.shard_stats`` plus the elastic counters."""
        stats = self._pool.shard_stats()
        stats.update(
            tier=self._pool.capacity,
            tiers=self.tiers,
            max_capacity=self.max_capacity,
            grows=self.grow_count,
            shrinks=self.shrink_count,
            # containment counters span resizes (inner pools are rebuilt)
            quarantined=self.quarantined_count,
            brownout=self._brownout_level,
            brownout_hops=self._brownout_hops_base + self._pool.brownout_hops,
        )
        return stats

    def report(self) -> str:
        lines = [
            f"ElasticSessionPool(tiers={self.tiers}, tier={self.capacity}, "
            f"active={self.num_active}, grows={self.grow_count}, "
            f"shrinks={self.shrink_count})"
        ]
        lines.append(self._pool.report())
        if self.resize_seconds:
            pauses = np.asarray(self.resize_seconds) * 1e3
            lines.append(
                f"  resize pause ms: mean={pauses.mean():.2f} max={pauses.max():.2f} "
                f"({len(pauses)} resizes: {self.resize_log})"
            )
        return "\n".join(lines)
