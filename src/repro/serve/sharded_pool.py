"""Sharded session pools: one ``SessionPool`` per device behind a router.

The ROADMAP's first scaling step: a single ``SessionPool`` is one compiled
batched hop step on one device; capacity beyond that comes from running N
pools ("shards"), each pinned to its own ``jax.Device``, behind a
**consistent-hash router** keyed on session id.

Why consistent hashing instead of round-robin or least-loaded:

- **Stickiness for free** — a streaming session's recurrent state lives on
  exactly one shard; the hash makes every ``attach``/``feed``/``read`` for a
  given session id land on that shard with no routing table to replicate
  (any front-end holding the same ring routes identically).
- **Minimal reshuffle** — growing N→N+1 shards remaps only ~1/(N+1) of the
  key space (each shard contributes ``vnodes`` points to the ring), so a
  fleet resize migrates few sessions instead of all of them.

The router deliberately does NOT spill a session to a neighbouring shard
when its home shard is full — that would silently break stickiness. It
raises ``ShardFullError`` (home shard full, fleet has room: rebalance or
retry) vs ``PoolFullError`` (every shard full: the fleet is at capacity).
``rebalance()`` restores balance explicitly by migrating sessions through
``SessionPool.export_session``/``import_session`` — migrated streams resume
bit-for-bit on the new shard.

With ``tiers=(4, 16, 64)`` every shard becomes an **elastic** pool
(``repro.serve.elastic_pool.ElasticSessionPool``): a hot shard grows to its
next pre-compiled capacity tier instead of raising ``ShardFullError`` (which
then fires only when the shard's top tier is full), and ``rebalance()``
shrinks donor shards back down the ladder after draining them.

``pump_all()`` is the scaling hot path: it dispatches every shard's batched
hop step (asynchronous JAX enqueue, non-blocking) before collecting any
shard's output, so N devices compute concurrently instead of serially.

Capacity therefore scales linearly with device count as long as the host can
keep the rings fed — measured by ``benchmarks/server_throughput.py
--shards`` (fake multiple CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

**Shard health (the cross-process fabric seam):** a distributed fleet loses
shards. ``kill_shard``/``restart_shard`` are the fault-injection levers (the
chaos harness in ``tests/chaos.py`` drives them), ``check_shards`` is the
heartbeat a gateway ticks, and ``pump_all`` skips — never raises on — a
shard that dies mid-pump, recording ``pump_failures`` in ``shard_stats()``.
Failover re-homes a dead shard's sessions onto live shards through the ring
itself (``HashRing.route(..., dead=...)`` walks around dead vnodes, so only
the dead shard's keys remap), shipping each recoverable session as WIRE
BYTES (``repro.serve.wire``) so the same path works across process
boundaries; streams whose host-side state survived the fault continue
bit-exactly, the rest are bounded loss (``sessions_lost`` /
``lost_session_ids``).

See ``docs/serving.md`` for the full architecture.
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
import itertools
import logging
import time
from collections import deque
from typing import Container, Deque, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.quant import QuantSpec
from repro.serve import obs
from repro.serve.durability import DurabilityError, recover_session
from repro.serve.elastic_pool import ElasticSessionPool
from repro.serve.faults import FaultPlan
from repro.serve.scheduler import (
    AdaptiveScheduler,
    SchedulerConfig,
    scheduler_for_pool,
)
from repro.serve.session_server import (
    PoolFullError,
    QuarantineRecord,
    Session,
    SessionError,
    SessionPoisonedError,
    SessionPool,
)
from repro.serve.streaming_se import ModelConfig

Pytree = dict

# lost_session_ids is diagnostics for clients, not an unbounded ledger: the
# deque keeps the MOST RECENT losses and evicts the oldest beyond this bound
MAX_LOST_IDS_TRACKED = 1024

log = logging.getLogger(__name__)


def _max_capacity(pool) -> int:
    """A shard's hard capacity bound: the top tier for elastic shards, the
    compiled capacity for fixed ones."""
    return getattr(pool, "max_capacity", pool.capacity)


def _shard_full(pool) -> bool:
    """True when a shard cannot take one more session EVEN by growing."""
    return pool.num_active >= _max_capacity(pool)


class ShardFullError(PoolFullError):
    """``attach()`` routed to a shard with no free slot while other shards
    still have room.

    Consistent hashing pins a session id to one shard, so the router refuses
    to place it elsewhere (stickiness would silently break). Callers can
    ``rebalance()`` and retry, or construct the pool with larger per-shard
    capacity. When *every* shard is full the router raises plain
    ``PoolFullError`` instead.
    """


class ShardDownError(SessionError):
    """An operation reached a shard that has failed (``kill_shard`` fault
    injection, or a shard that died mid-pump).

    Client-visible only in the narrow window before the next health check /
    ``pump_all`` re-homes the dead shard's sessions onto live shards; the
    router's own entry points run that failover transparently, so callers
    normally see either a live session (migrated bit-exactly) or a
    ``SessionError`` naming the session as lost (state died with the shard).
    """


class _DownShard:
    """Poisoned stand-in for a failed shard's pool: every op raises.

    Installed by ``kill_shard``/``_pump_failure`` so any stray path that
    reaches a dead shard fails loudly instead of silently touching stale
    state. Router code never touches it — every iteration over the shard
    list skips indices in ``_dead``.
    """

    def __init__(self, index: int) -> None:
        object.__setattr__(self, "_index", int(index))

    def __getattr__(self, name: str):
        raise ShardDownError(
            f"shard {object.__getattribute__(self, '_index')} is down"
        )


def _hash64(data: bytes) -> int:
    """Stable 64-bit hash (blake2b) — identical across processes and runs,
    unlike Python's seeded ``hash()``."""
    return int.from_bytes(hashlib.blake2b(data, digest_size=8).digest(), "big")


class HashRing:
    """Consistent-hash ring mapping session ids to shard indices.

    Each shard owns ``vnodes`` points on a 64-bit ring; a key routes to the
    first shard point clockwise from its hash. Routing is deterministic
    (blake2b, not Python's per-process ``hash``), so two ``HashRing(n)``
    instances — in different processes — agree on every key.
    """

    def __init__(self, n_shards: int, vnodes: int = 64) -> None:
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        points = sorted(
            (_hash64(f"shard{s}:vnode{v}".encode()), s)
            for s in range(n_shards)
            for v in range(vnodes)
        )
        self.n_shards = n_shards
        self._keys = [p[0] for p in points]
        self._shards = [p[1] for p in points]

    def route(self, session_id: Hashable, dead: Container = ()) -> int:
        """Map a session id to its home shard index (pure, deterministic).

        Args:
            session_id: any hashable key.
            dead: shard indices to route AROUND — the walk clockwise from the
                key's ring point skips their vnodes, so only keys homed on a
                dead shard remap (to the next live point), and they all come
                back home the moment the shard is restarted. This is the
                failover remapping the health-check machinery uses.

        Raises:
            ShardDownError: every shard is in ``dead``.
        """
        h = _hash64(str(session_id).encode())
        start = bisect.bisect_right(self._keys, h)
        n = len(self._keys)
        for off in range(n):
            shard = self._shards[(start + off) % n]
            if shard not in dead:
                return shard
        raise ShardDownError("no live shard on the ring: all shards are down")


@dataclasses.dataclass
class ShardedSession:
    """Client handle returned by ``ShardedSessionPool.attach``.

    ``shard`` is the session's *current* home (it changes on ``rebalance()``,
    while ``HashRing.route(session_id)`` keeps returning the original hash
    home); ``inner`` is the live per-shard ``Session`` handle.
    """

    session_id: Hashable
    shard: int
    inner: Session

    @property
    def stats(self):
        """Per-session accounting (``SessionStats``) — survives migration."""
        return self.inner.stats


class ShardedSessionPool:
    """N per-device ``SessionPool`` shards behind a consistent-hash router.

    Same client surface as ``SessionPool`` (attach/feed/read/detach), plus
    ``pump_all()`` (overlapped dispatch across shards), ``rebalance()``
    (session migration off overloaded shards), and ``shard_stats()``.

    Args:
        params: TFTNN parameter pytree; replicated onto every shard's device.
        cfg: model/front-end config shared by all shards.
        capacity: slots PER SHARD (total capacity = ``capacity * shards``).
        shards: number of shards. Defaults to one per local device. May
            exceed the device count — shards then round-robin over devices,
            which is how CPU tests exercise multi-shard routing on one core.
        devices: explicit device list; defaults to ``jax.local_devices()``.
        quant / donate: forwarded to every ``SessionPool``.
        backend: hop-step implementation forwarded to every shard — ``"xla"``
            or ``"pallas"`` (the deploy-compiled fused path, see
            ``repro.serve.deploy``). One compiled step per device either way.
        prune_keep / prune_axis / prune_granularity / prune_block:
            deploy-time zero-skipping masks (weight/block/unit granular),
            forwarded to every shard's compiled step on either backend (see
            ``SessionPool``). Lossy by design; ``None`` serves unpruned.
        inflight / max_unread_hops / on_unparked: per-shard ingestion
            pipelining depth, output backpressure bound, and parked-session
            wake-up callback (see ``SessionPool``; the router translates the
            shard-internal handle, so the callback receives the client's
            ``ShardedSession``). ``pump_all`` drains
            every shard each round, so the cross-shard overlap comes from
            the round structure; ``inflight=2`` additionally overlaps each
            shard's own host drain with its device step when the pool is
            driven via per-shard ``dispatch()``/``pump()``.
        hops_per_step: multi-hop fused dispatch depth forwarded to every
            shard (see ``SessionPool``): each ``pump_all`` round drains up
            to K hops per session per shard in ONE device call per shard —
            the per-round fixed dispatch cost is amortized over K hops on
            every device at once. Bit-identical to ``hops_per_step=1``.
        tiers: when given (e.g. ``(4, 16, 64)``), every shard is an
            **elastic** ``ElasticSessionPool`` on this capacity ladder
            instead of a fixed ``SessionPool``: a hot shard grows to its
            next tier on attach instead of raising ``ShardFullError``
            (which then only fires when the shard's TOP tier is full), and
            ``rebalance()`` shrinks donor shards back down the ladder after
            migrating sessions away. ``capacity`` is ignored — the ladder
            defines each shard's sizes (total fleet capacity =
            ``tiers[-1] * shards``).
        shrink_fraction / shrink_patience: elastic-shard hysteresis knobs,
            forwarded to every ``ElasticSessionPool`` (ignored for fixed
            shards; see there).
        vnodes: virtual nodes per shard on the hash ring (more = smoother
            key-space balance at slightly larger ring).
        step_cache: optional mutable dict mapping device -> (device-resident
            params, per-lane-count step cache). Co-located shards always
            share one entry; pass the same dict to several
            ``ShardedSessionPool`` instances with identical
            params/cfg/quant/donate/capacity/hops_per_step (e.g. a benchmark
            sweeping shard counts) to also share compilations ACROSS pools.
        adaptive: closed-loop scheduling. ``True`` gives every shard its own
            ``AdaptiveScheduler`` sized to ``hops_per_step``
            (``scheduler_for_pool``); a ``SchedulerConfig`` uses that
            configuration instead. Each ``pump_all`` round then observes
            each shard, picks its lane count from measured backlog, and (on
            elastic shards) applies slope-triggered grow / cost-modeled
            shrink decisions — replacing the legacy per-pump watermark
            check. Per-shard decision traces are replayable
            (``scheduler_stats()`` / ``shard_stats()``).
        ingest_ring: device-resident ingestion ring depth forwarded to every
            shard (see ``SessionPool``).
        durability: optional ``repro.serve.durability.DurabilityManager``.
            Held at the ROUTER (keyed by the client's session id, which is
            stable across migration and failover) and deliberately NOT
            forwarded to the per-shard pools — exactly one layer journals a
            stream. With a manager: every ``feed``/``read`` is journaled,
            snapshots land on the manager's cadence, ``attach`` of an id
            with durable state on disk RECOVERS it instead of starting
            fresh, ``restart_shard`` drains ``lost_session_ids`` through
            recovery, and ``recover_sessions()`` rebuilds every orphan after
            a full process restart (the gateway calls it on start).
        finite_guard: forwarded to every shard's pool — one jitted
            ``isfinite`` all-reduce per stepped slot riding the existing
            output readback; a non-finite slot is QUARANTINED at collect
            (never emitted) and harvested to the router, where its record
            (``quarantined``) carries the last-good hop count. ``attach`` of
            a quarantined id with durable state recovers the stream up to
            the pre-poison feed (``max_feed_samples``); other router ops on
            it raise ``SessionPoisonedError``.
        faults: optional ``repro.serve.faults.FaultPlan`` threaded into
            every shard (per-shard tag ``"shard{i}"``) and used here for
            injected shard stalls — the deterministic chaos lever.
        breaker_threshold: per-shard circuit breaker. ``None`` (default)
            keeps the legacy fail-fast fabric: ANY mid-pump failure kills
            the shard and fails its sessions over immediately. With a
            threshold N, a dispatch-time failure (admission-time, so no
            input was consumed — injected step errors raise before touching
            anything) only marks the shard *suspect* for the rest of the
            pump; N CONSECUTIVE failures open the breaker (kill + failover).
            ``restart_shard`` re-arms it **half-open**: the next successful
            probe/collect closes it, the next failure re-opens it at once.
            Failures after the step launched (wait/collect) always trip
            immediately — in-flight state cannot be proven untouched.
        watchdog_seconds: wall-clock bound on each pump round's
            dispatch→ready wait, per shard. A shard exceeding it is failed
            over exactly like a mid-pump death (``watchdog_failovers``) —
            the step DID complete by then (``wait_ready`` returned), so the
            export/failover path stays bit-exact; the watchdog exists to
            stop a wedged device queue (injected ``stall_rate``) from
            capping the whole fleet's round rate.

    Raises:
        ValueError: ``shards < 1`` or empty ``devices``.
    """

    def __init__(
        self,
        params: Pytree,
        cfg: ModelConfig,
        capacity: int,
        *,
        shards: Optional[int] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        quant: Optional[QuantSpec] = None,
        donate: bool = True,
        backend: str = "xla",
        prune_keep: Optional[float] = None,
        prune_axis: Optional[int] = None,
        prune_granularity: Optional[str] = None,
        prune_block: Tuple[int, int] = (8, 8),
        inflight: int = 1,
        max_unread_hops: Optional[int] = None,
        on_unparked=None,
        hops_per_step: int = 1,
        tiers: Optional[Sequence[int]] = None,
        shrink_fraction: float = 0.5,
        shrink_patience: int = 8,
        vnodes: int = 64,
        step_cache: Optional[dict] = None,
        adaptive=None,
        ingest_ring: Optional[int] = None,
        durability=None,
        finite_guard: bool = False,
        faults: Optional[FaultPlan] = None,
        breaker_threshold: Optional[int] = None,
        watchdog_seconds: Optional[float] = None,
    ) -> None:
        if devices is None:
            devices = jax.local_devices()
        if not devices:
            raise ValueError("need at least one device")
        if shards is None:
            shards = len(devices)
        if shards < 1:
            raise ValueError("shards must be >= 1")
        self.cfg = cfg
        self.n_shards = shards
        # shards wake up with their pool-internal handles; clients hold
        # ShardedSessions — translate before calling out (elastic shards
        # already translate Session -> ElasticSession one level down)
        if on_unparked is not None:
            client_cb = on_unparked
            on_unparked = lambda inner: self._wake(client_cb, inner)  # noqa: E731
        # Shards co-located on one device (shards > len(devices), e.g. CPU
        # tests) share ONE device-resident params copy and ONE compiled hop
        # step instead of paying per-shard duplicates.
        self._shared = step_cache if step_cache is not None else {}
        self.elastic = tiers is not None
        self._devices = list(devices)
        self._params = params
        if breaker_threshold is not None and breaker_threshold < 1:
            raise ValueError("breaker_threshold must be >= 1 (or None)")
        if watchdog_seconds is not None and watchdog_seconds <= 0:
            raise ValueError("watchdog_seconds must be > 0 (or None)")
        self._faults = faults
        self._breaker_threshold = breaker_threshold
        self._watchdog = watchdog_seconds
        self._mk = dict(
            quant=quant, donate=donate, backend=backend,
            prune_keep=prune_keep, prune_axis=prune_axis,
            prune_granularity=prune_granularity, prune_block=prune_block,
            hops_per_step=hops_per_step, capacity=capacity, tiers=tiers,
            shrink_fraction=shrink_fraction, shrink_patience=shrink_patience,
            inflight=inflight,
            max_unread_hops=max_unread_hops, on_unparked=on_unparked,
            ingest_ring=ingest_ring, finite_guard=finite_guard,
        )
        self._adaptive = adaptive if adaptive is not None else False
        self._pools: List = [self._make_pool(i) for i in range(shards)]
        # one controller per shard: each shard's backlog/tier trajectory is
        # its own control problem, and each trace replays independently
        self._scheds: List[Optional[AdaptiveScheduler]] = [
            self._make_sched() for _ in range(shards)
        ]
        self._ring = HashRing(shards, vnodes=vnodes)
        self._sessions: Dict[Hashable, ShardedSession] = {}
        self._auto_sid = itertools.count()
        # -- fabric health state (kill_shard / check_shards / failover) -----
        self._dead: set = set()  # shard indices currently down
        # dead shard -> its surviving host-side pool (exportable tickets), or
        # None when the failure lost host state too (sessions unrecoverable)
        self._corpses: Dict[int, object] = {}
        self._pending_failover: set = set()  # dead shards not yet re-homed
        self._pump_failures = [0] * shards  # mid-pump deaths per shard index
        self._failover_counts = [0] * shards  # completed failovers per index
        self.shard_generations = [0] * shards  # bumped by every restart
        # -- circuit breakers / watchdog / quarantine ------------------------
        self._breaker = ["closed"] * shards  # closed | half_open | open
        self._breaker_streak = [0] * shards  # consecutive failures
        self._suspect: set = set()  # transient failures: skip this pump only
        self.breaker_opens = 0  # breaker trips (incl. legacy fail-fast kills)
        self.watchdog_failovers = 0  # shards failed over for exceeding bound
        # quarantined sessions harvested from shard pools, by client id
        self.quarantined: Dict[Hashable, QuarantineRecord] = {}
        self.sessions_quarantined = 0
        self.sessions_failed_over = 0  # re-homed bit-exactly via the wire
        self.sessions_lost = 0  # state died with the shard
        # recent losses, for client notification: bounded (oldest evicted),
        # and drained by successful recovery or re-attach of the same id
        self.lost_session_ids: Deque[Hashable] = deque(maxlen=MAX_LOST_IDS_TRACKED)
        self.failover_log: List[Dict[str, object]] = []
        # -- durable recovery (snapshot + journal + replay) ------------------
        self._durability = durability  # router-level: NOT in _mk / per shard
        self.sessions_recovered = 0  # rebuilt bit-exactly from disk
        self.recovery_errors: List[Tuple[Hashable, str]] = []

    def _make_sched(self) -> Optional[AdaptiveScheduler]:
        """A fresh per-shard controller (None when not adaptive)."""
        if not self._adaptive:
            return None
        if isinstance(self._adaptive, SchedulerConfig):
            return AdaptiveScheduler(self._adaptive)
        return scheduler_for_pool(self._mk["hops_per_step"])

    def _make_pool(self, index: int):
        """Build (or rebuild, for ``restart_shard``) the pool at one index."""
        m = self._mk
        dev = self._devices[index % len(self._devices)]
        if dev not in self._shared:
            # one params copy + ONE per-lane-count step cache per device:
            # co-located shards (and every tier of an elastic shard) fill
            # and share it instead of paying per-shard compilations
            self._shared[dev] = (jax.device_put(self._params, dev), {})
        placed, step_fns = self._shared[dev]
        kw = dict(
            quant=m["quant"], donate=m["donate"],
            device=dev, backend=m["backend"], inflight=m["inflight"],
            max_unread_hops=m["max_unread_hops"],
            on_unparked=m["on_unparked"], hops_per_step=m["hops_per_step"],
            prune_keep=m["prune_keep"], prune_axis=m["prune_axis"],
            prune_granularity=m["prune_granularity"],
            prune_block=m["prune_block"],
            step_fns=step_fns, ingest_ring=m["ingest_ring"],
            finite_guard=m["finite_guard"], faults=self._faults,
            fault_tag=f"shard{index}",
        )
        if self.elastic:
            return ElasticSessionPool(
                placed, self.cfg, m["tiers"],
                shrink_fraction=m["shrink_fraction"],
                shrink_patience=m["shrink_patience"], **kw,
            )
        return SessionPool(placed, self.cfg, m["capacity"], **kw)

    def prewarm(self) -> None:
        """Compile every step shape the fleet can dispatch, now.

        Each live shard runs its steps once on a masked-out dummy state
        (``SessionPool.prewarm``): every lane count of its adaptive
        controller's K ladder, or ``hops_per_step`` alone, at every elastic
        tier. A compile error raises here, before any session exists,
        instead of reaching ``pump_all``'s shard-failure handlers as a dead
        shard.
        """
        for i, pool in self._live():
            sched = self._scheds[i]
            ks = (
                sched.config.k_ladder if sched is not None
                else (self._mk["hops_per_step"],)
            )
            pool.prewarm(ks)

    def _live(self) -> List[Tuple[int, object]]:
        """(index, pool) for every shard that is up."""
        return [(i, p) for i, p in enumerate(self._pools) if i not in self._dead]

    # -- capacity / introspection -------------------------------------------

    @property
    def capacity(self) -> int:
        """Total CURRENT slots across all LIVE shards (elastic shards count
        their current tier; see ``max_capacity`` for the hard bound)."""
        return sum(p.capacity for _, p in self._live())

    @property
    def max_capacity(self) -> int:
        """Total live-shard slots at top tier (== ``capacity`` for fixed
        shards) — the bound ``PoolFullError`` reports."""
        return sum(_max_capacity(p) for _, p in self._live())

    @property
    def num_active(self) -> int:
        return len(self._sessions)

    @property
    def sample_rate(self) -> int:
        return self.cfg.sample_rate

    @property
    def dead_shards(self) -> List[int]:
        """Indices of shards currently down (killed or failed mid-pump)."""
        return sorted(self._dead)

    def route(self, session_id: Hashable) -> int:
        """The hash home for a session id among LIVE shards (before any
        rebalancing; equals the pure hash home while every shard is up)."""
        return self._ring.route(session_id, dead=self._dead)

    # -- session lifecycle --------------------------------------------------

    def attach(
        self, session_id: Optional[Hashable] = None, *, rebalance_on_full: bool = False
    ) -> ShardedSession:
        """Route a new session to its hash home and claim a slot there.

        Args:
            session_id: any hashable id (caller's connection/user id). The
                same id always routes to the same shard. Defaults to a
                generated ``"auto-N"`` id, skipping any already-attached ids.
            rebalance_on_full: when the home shard is full but the fleet has
                room, migrate one session off the home shard to the shard
                with the most headroom and retry, instead of raising.

        Returns:
            A ``ShardedSession`` handle (also resolvable later by raw id).

        Raises:
            SessionError: ``session_id`` is already attached, or it has
                durable state on disk that could not be recovered (loud
                failure over a silently restarted stream).
            ShardFullError: home shard full, other shards have room (and
                ``rebalance_on_full`` is off or rebalancing freed nothing).
            PoolFullError: every shard is full.
        """
        if session_id is None:
            session_id = f"auto-{next(self._auto_sid)}"
            # skip ids already attached AND ids with durable state on disk:
            # a generated id must never silently wipe an orphan's journal
            while session_id in self._sessions or (
                self._durability is not None and self._durability.has(session_id)
            ):
                session_id = f"auto-{next(self._auto_sid)}"
        if session_id in self._sessions:
            raise SessionError(f"session id {session_id!r} is already attached")
        self._failover_pending()  # re-home any dead shard's sessions first
        rec = self.quarantined.pop(session_id, None)
        if rec is not None and self._durability is not None and self._durability.has(
            session_id
        ):
            # re-attach of a poisoned stream: recover it from disk, but ONLY
            # up to the last feed proven finite — the journal tail past
            # good_samples_in is the poison that got it quarantined
            try:
                return self._recover_one(
                    session_id, max_feed_samples=rec.good_samples_in
                )
            except DurabilityError as exc:
                raise SessionError(
                    f"quarantined session {session_id!r} could not be "
                    f"recovered to its pre-poison state: {exc}"
                ) from exc
        # rec set, no durability: the quarantine record is dropped and the
        # id starts a FRESH stream (nothing on disk to roll back to)
        if self._durability is not None and self._durability.has(session_id):
            # durable state exists: this attach is a reconnect after a crash
            # or loss — recover the stream instead of starting a fresh one
            try:
                return self._recover_one(session_id)
            except DurabilityError as exc:
                raise SessionError(
                    f"session {session_id!r} has durable state that could "
                    f"not be recovered: {exc}"
                ) from exc
        shard = self._ring.route(session_id, dead=self._dead)
        pool = self._pools[shard]
        # elastic shards grow themselves inside attach(); only a shard whose
        # TOP tier is occupied counts as full here
        if _shard_full(pool):
            if all(_shard_full(p) for _, p in self._live()):
                raise PoolFullError(
                    f"all {len(self._live())} live shards are full (capacity="
                    f"{self.max_capacity}, active={self.num_active}"
                    + (f", tiers/shard={self._mk['tiers']}" if self.elastic else "")
                    + "); detach a session first"
                )
            if rebalance_on_full:
                self._drain_one(shard)
            if _shard_full(pool):
                raise ShardFullError(
                    f"shard {shard} is full (capacity={_max_capacity(pool)}, "
                    f"active={pool.num_active}"
                    + (f", tiers={pool.tiers}" if self.elastic else "")
                    + ") though other shards have room; rebalance() or retry later"
                )
        handle = ShardedSession(session_id=session_id, shard=shard, inner=pool.attach())
        self._sessions[session_id] = handle
        if self._durability is not None:
            self._durability.begin(str(session_id))
        try:  # a re-attached id is no longer "lost"
            self.lost_session_ids.remove(session_id)
        except ValueError:
            pass
        return handle

    def _wake(self, on_unparked, inner) -> None:
        for handle in self._sessions.values():
            if handle.inner is inner:
                on_unparked(handle)
                return

    def _resolve(self, sess) -> ShardedSession:
        """Accept a ``ShardedSession`` handle or a raw session id.

        A session still homed on a dead shard is failed over here first, so
        client calls transparently land on the session's new live shard; if
        the failover lost it (the shard's host state died too), the lookup
        below fails with a ``SessionError`` naming the loss.
        """
        sid = sess.session_id if isinstance(sess, ShardedSession) else sess
        rec = self.quarantined.get(sid)
        if rec is not None:
            raise SessionPoisonedError(
                f"session {sid!r} is quarantined: {rec.message}",
                session_id=sid,
                good_hops=rec.good_hops,
                good_samples_in=rec.good_samples_in,
            )
        handle = self._sessions.get(sid)
        if handle is not None and handle.shard in self._dead:
            self._failover_pending()
            handle = self._sessions.get(sid)
        if isinstance(sess, ShardedSession):
            if handle is not sess:
                raise SessionError(
                    f"session {sid!r} is not attached to this router"
                    + (
                        " (lost when its shard went down)"
                        if sid in self.lost_session_ids else ""
                    )
                )
            return sess
        if handle is None:
            raise SessionError(
                f"unknown session id {sess!r}"
                + (
                    " (lost when its shard went down)"
                    if sid in self.lost_session_ids else ""
                )
            )
        return handle

    def detach(self, sess) -> np.ndarray:
        """Release a session's slot on its shard; returns unread audio.

        Raises:
            SessionError: unknown/already-detached session.
        """
        handle = self._resolve(sess)
        tail = self._pools[handle.shard].detach(handle.inner)
        del self._sessions[handle.session_id]
        if self._durability is not None:
            self._durability.forget(str(handle.session_id))
        return tail

    def lookup(self, session_id: Hashable) -> Optional[ShardedSession]:
        """The CURRENT live handle for a session id, or ``None``.

        Handles are replaced by loss+recovery cycles; a front-end holding a
        stale handle re-binds through this (the gateway's retry path)."""
        return self._sessions.get(session_id)

    # -- audio I/O ----------------------------------------------------------

    @obs.spanned("feed")
    def feed(self, sess, samples) -> None:
        """Queue raw audio on the session's shard (any chunk length)."""
        handle = self._resolve(sess)
        mgr = self._durability
        if mgr is not None:
            # journal the exact bytes write-ahead of the shard seeing them
            samples = np.array(samples, np.float32, copy=True).reshape(-1)
            due = mgr.record_feed(str(handle.session_id), samples, self.cfg.hop)
            self._pools[handle.shard].feed(handle.inner, samples)
            if due:
                mgr.snapshot(
                    str(handle.session_id),
                    self._pools[handle.shard].snapshot_session(handle.inner),
                )
            return
        self._pools[handle.shard].feed(handle.inner, samples)

    @obs.spanned("read")
    def read(self, sess) -> np.ndarray:
        """Pop all enhanced audio produced for this session so far."""
        handle = self._resolve(sess)
        out = self._pools[handle.shard].read(handle.inner)
        if out.size and self._durability is not None:
            # durable read cursor: recovery will not re-deliver these bytes
            self._durability.record_read(
                str(handle.session_id), handle.inner.stats.samples_out
            )
        return out

    def backlog_hops(self, sess) -> int:
        """Whole hops fed to the session and not yet stepped."""
        handle = self._resolve(sess)
        return self._pools[handle.shard].backlog_hops(handle.inner)

    # -- the overlapped hop loop --------------------------------------------

    @obs.spanned("pump_all")
    def pump_all(self) -> int:
        """Pump every shard until no session anywhere has a full hop queued.

        Each round dispatches every shard's batched hop step FIRST (JAX
        enqueues asynchronously, so all devices start computing), waits for
        every shard's output (``wait_ready`` — each shard records its own
        dispatch→ready latency), and only then drains the readbacks — device
        work overlaps instead of serializing, which is where the linear
        capacity scaling comes from.

        Accounting: each round charges ``round_wall / hops_stepped`` per hop
        to every stepped session, so summed ``proc_seconds`` across all
        shards equals the overlapped wall-clock (concurrent device work is
        not double-counted into session RTFs); with ``hops_per_step=K`` a
        round covers up to K hops per session.

        Elastic shards take their lazy shrink heartbeat here too — once per
        ``pump_all`` after the rounds drain, mirroring the cadence of a
        standalone ``ElasticSessionPool.pump()`` (``dispatch``/``collect``
        never resize mid-pipeline). Under ``adaptive=`` each round instead
        observes every shard, dispatches it at its controller's lane count,
        and applies grow/shrink decisions per shard — the watermark
        heartbeat is replaced by the replayable decision trace.

        Fault tolerance: a shard that raises mid-pump — from ``dispatch``,
        ``wait_ready``, or ``collect`` — is marked down and SKIPPED for the
        rest of the pump instead of taking down the whole loop; the failure
        is recorded in ``shard_stats()`` (``pump_failures``) and its sessions
        are immediately failed over to live shards (exported tickets where
        the host-side state survived, counted lost otherwise). Shards already
        known dead (``kill_shard``) are never dispatched; their pending
        failover runs before the first round so re-homed sessions drain their
        backlogs in this very pump.

        Returns:
            Number of dispatch rounds in which at least one shard stepped.
        """
        self._failover_pending()
        self._suspect.clear()  # transient skips last at most one pump
        rounds = 0
        while True:
            t0 = time.perf_counter()
            stepped = 0
            launched = []
            for i, pool in self._live():
                if i in self._suspect:
                    continue  # failed this pump below breaker threshold
                try:
                    sched = self._scheds[i]
                    if sched is None:
                        stepped += pool.dispatch()
                    else:
                        # adaptive: observe this shard, act on grow/shrink
                        # (elastic shards only), dispatch at the decided K;
                        # the fleet's open-breaker count rides along so the
                        # controller can walk the brownout ladder
                        obs = dataclasses.replace(
                            pool.observation(),
                            open_breakers=self.open_breakers,
                        )
                        decision = sched.observe(obs)
                        if self.elastic:
                            pool.apply_decision(decision)
                        set_brownout = getattr(pool, "set_brownout", None)
                        if set_brownout is not None:
                            set_brownout(decision.brownout)
                        k = min(decision.k, self._mk["hops_per_step"])
                        stepped += pool.dispatch(max_hops=k)
                    launched.append((i, pool))
                except Exception:
                    # dispatch is admission-time: nothing was consumed, so
                    # a breaker below threshold may retry next pump
                    self._pump_failure(i)
            if stepped == 0:
                break
            ready = []
            for i, pool in launched:
                tw = time.perf_counter()  # per-shard wait clock: one wedged
                # shard must not condemn the shards waited on after it
                try:
                    if self._faults is not None:
                        stall = self._faults.stall(f"shard{i}")
                        if stall:
                            time.sleep(stall)  # injected wedged device queue
                    pool.wait_ready()
                except Exception:
                    self._pump_failure(i, force=True)
                    continue
                if (
                    self._watchdog is not None
                    and time.perf_counter() - tw > self._watchdog
                ):
                    # the step finished (wait_ready returned) but blew the
                    # round budget: fail the shard over bit-exactly rather
                    # than let one wedged queue cap the fleet's round rate
                    self.watchdog_failovers += 1
                    self._pump_failure(i, force=True)
                    continue
                ready.append((i, pool))
            share = (time.perf_counter() - t0) / stepped
            for i, pool in ready:
                try:
                    pool.collect(proc_share=share)
                    self._breaker_success(i)
                except Exception:
                    self._pump_failure(i, force=True)
            rounds += 1
        if self.elastic and not self._adaptive:
            # legacy watermark heartbeat; adaptive fleets shrink through the
            # scheduler's cost-modeled decisions instead
            for _, pool in self._live():
                pool.try_shrink()
        self._harvest_quarantined()
        return rounds

    # -- shard health: fault injection, heartbeats, failover ----------------

    def kill_shard(self, shard: int, *, lose_state: bool = False) -> None:
        """Fault injection: take one shard down (the chaos harness's lever).

        Models the two real failure classes a fabric sees:

        - ``lose_state=False`` (default) — the device/process serving the
          shard died but its host-side state survived (device reset, worker
          drained). The next health check / router op exports every resident
          session as a wire ticket and re-imports it on a live shard:
          streams continue **bit-exactly**.
        - ``lose_state=True`` — the whole shard is gone, memory included.
          Resident sessions are unrecoverable; failover records them in
          ``lost_session_ids`` / ``sessions_lost`` and their handles die
          (bounded loss: exactly the dead shard's residents, never more).

        Idempotent; killing a dead shard is a no-op. The shard stops
        receiving routes immediately (the ring walks around its vnodes);
        failover of its residents runs on the next ``check_shards()``,
        ``pump_all()``, ``attach()``, or any call touching a resident.

        Raises:
            ValueError: ``shard`` out of range.
        """
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} out of range [0, {self.n_shards})")
        if shard in self._dead:
            return
        corpse = self._pools[shard]
        self._pools[shard] = _DownShard(shard)
        self._dead.add(shard)
        self._corpses[shard] = None if lose_state else corpse
        self._pending_failover.add(shard)
        self._breaker[shard] = "open"  # admin kill: open, but not a trip

    def restart_shard(self, shard: int) -> None:
        """Bring a dead shard back with a FRESH pool (empty, zeroed state).

        New sessions whose hash home is this index route here again the
        moment it is live (the ring walk no longer skips its vnodes);
        sessions failed over while it was down stay where they landed —
        ``rebalance()`` drifts load back over time.

        Raises:
            SessionError: the shard is not down.
        """
        if shard not in self._dead:
            raise SessionError(f"shard {shard} is not down; nothing to restart")
        self._failover_pending()  # never strand residents of OTHER dead shards
        self._pools[shard] = self._make_pool(shard)
        # a fresh pool gets a fresh controller: the new generation's decision
        # trace must replay from SchedulerState() like any cold start
        self._scheds[shard] = self._make_sched()
        self._dead.discard(shard)
        self._pending_failover.discard(shard)
        self._corpses.pop(shard, None)
        self.shard_generations[shard] += 1
        # re-arm the breaker: HALF-OPEN, so the restarted generation must
        # pass one probe/collect before it is trusted again (a breaker-less
        # fabric just goes straight back to closed)
        self._breaker_streak[shard] = 0
        self._suspect.discard(shard)
        self._breaker[shard] = (
            "half_open" if self._breaker_threshold is not None else "closed"
        )
        if self._durability is not None:
            # the fresh shard brings capacity back: drain every lost session
            # with durable state through snapshot+journal recovery — the
            # streams resume bit-exactly where their last feed left off
            self.recover_sessions(
                [sid for sid in list(self.lost_session_ids)
                 if self._durability.has(sid)]
            )

    def check_shards(self) -> List[int]:
        """Health-check heartbeat: probe every live shard, fail over the dead.

        Probes each live shard with a cheap stats read; a shard that raises
        is marked down exactly like ``kill_shard`` (its host-side pool is
        kept as the export source, so sessions migrate bit-exactly whenever
        the wrapper still works). Then every dead shard with residents is
        failed over. The gateway's pump loop calls this once per tick.

        Returns:
            Indices of shards NEWLY detected dead by this probe (shards
            already known dead are not re-reported).
        """
        failed = []
        for i, pool in self._live():
            try:
                pool.shard_stats()
                self._breaker_success(i)  # half-open probe passed: close
            except Exception:
                # a probe failure means the shard WRAPPER is broken — no
                # transient grace, regardless of breaker threshold
                if self._shard_failure(i, force=True):
                    failed.append(i)
        self._failover_pending()
        self._harvest_quarantined()
        return failed

    @property
    def open_breakers(self) -> int:
        """Shards whose circuit breaker is currently open."""
        return sum(1 for s in self._breaker if s == "open")

    def _breaker_success(self, shard: int) -> None:
        """A successful collect/probe: reset the streak, close a half-open
        breaker (the probe it was waiting for)."""
        self._breaker_streak[shard] = 0
        if self._breaker[shard] == "half_open":
            self._breaker[shard] = "closed"

    def _shard_failure(self, shard: int, *, force: bool = False) -> bool:
        """One failure against shard ``shard``: trip the breaker or not.

        Returns True when the shard was taken down (breaker opened — caller
        runs failover); False when the failure stays transient (below the
        closed breaker's threshold): the shard is only marked *suspect*,
        which skips it for the remainder of the current pump. Transient
        treatment is safe exactly because it is only applied to
        admission-time failures (dispatch raises before consuming input).
        """
        if self._breaker_threshold is None:
            force = True  # legacy fail-fast fabric: first failure kills
        self._breaker_streak[shard] += 1
        if (
            not force
            and self._breaker[shard] == "closed"
            and self._breaker_streak[shard] < self._breaker_threshold
        ):
            self._suspect.add(shard)
            return False
        # threshold reached, half-open probe failed, or forced: open + kill
        self._breaker[shard] = "open"
        self.breaker_opens += 1
        corpse = self._pools[shard]
        self._pools[shard] = _DownShard(shard)
        self._dead.add(shard)
        # host wrapper survived the device fault — per-session export in
        # failover decides what is still recoverable
        self._corpses[shard] = corpse
        self._pending_failover.add(shard)
        return True

    def _pump_failure(self, shard: int, *, force: bool = False) -> None:
        """A live shard raised mid-pump: record; kill + re-home when the
        breaker trips (always, with no ``breaker_threshold``)."""
        log.warning("shard %d failed mid-pump", shard, exc_info=True)
        self._pump_failures[shard] += 1
        if self._shard_failure(shard, force=force):
            self._failover(shard)

    def _failover_pending(self) -> None:
        """Re-home the residents of every dead shard not yet failed over."""
        for shard in sorted(self._pending_failover):
            self._failover(shard)

    def _failover(self, shard: int) -> None:
        """Move every session resident on a dead shard to a live shard.

        Each recoverable session travels as WIRE BYTES (``serve.wire``
        encode → decode around the ticket), exactly as it would between
        gateway processes — the wire format is load-bearing on this path,
        not just a test artifact. Destination is the ring's remapped home
        (walk around dead vnodes), falling back to the live shard with the
        most headroom when that home is full; a session with no exportable
        state, or no live slot anywhere, is lost and recorded.
        """
        from repro.serve.wire import decode_ticket, encode_ticket

        corpse = self._corpses.pop(shard, None)
        residents = [h for h in self._sessions.values() if h.shard == shard]
        moved = lost = 0
        for handle in residents:
            # quarantined in the same pump the shard died: the poison
            # verdict outlives the shard — adopt the record instead of
            # counting the session lost (checked again after a failed
            # export, because export's collect-in-flight is itself a
            # finite-guard site and may quarantine this very session)
            if corpse is not None and self._adopt_poisoned(handle, corpse):
                continue
            blob = None
            if corpse is not None:
                try:
                    blob = encode_ticket(corpse.export_session(handle.inner))
                except Exception:
                    blob = None  # this session's state died with the fault
                if blob is None and self._adopt_poisoned(handle, corpse):
                    continue
            dst = self._failover_destination(handle.session_id) if blob else None
            if blob is None or dst is None:
                lost += 1
                handle.inner.detached = True
                del self._sessions[handle.session_id]
                self.lost_session_ids.append(handle.session_id)
                if self._durability is not None:
                    # close journal handles but KEEP the files: the durable
                    # state is exactly what recovery will rebuild from
                    self._durability.release(str(handle.session_id))
                continue
            handle.inner = self._pools[dst].import_session(decode_ticket(blob))
            handle.shard = dst
            moved += 1
        self._pending_failover.discard(shard)
        self._failover_counts[shard] += 1
        self.sessions_failed_over += moved
        self.sessions_lost += lost
        self.failover_log.append({"shard": shard, "moved": moved, "lost": lost})

    def _failover_destination(self, session_id: Hashable) -> Optional[int]:
        """Live shard to re-home one session on: ring remap, else headroom."""
        live = self._live()
        if not live:
            return None
        dst = self._ring.route(session_id, dead=self._dead)
        if not _shard_full(self._pools[dst]):
            return dst
        frees = [(_max_capacity(p) - p.num_active, i) for i, p in live]
        free, dst = max(frees)
        return dst if free > 0 else None

    # -- fault containment: quarantine harvest, brownout ---------------------

    def _adopt_poisoned(self, handle: "ShardedSession", corpse) -> bool:
        """Adopt a dead shard's quarantine record for ``handle``, if any.

        Mirrors ``_harvest_quarantined`` for the corpse of a shard that
        died in the same pump that poisoned the session: re-key by client
        id, release the durable journal (files kept), record the session
        as quarantined rather than lost. Returns True when adopted.
        """
        rec = getattr(corpse, "quarantined", {}).get(handle.inner.sid)
        if rec is None or rec.session is not handle.inner:
            return False
        del self._sessions[handle.session_id]
        did = None
        if self._durability is not None:
            did = str(handle.session_id)
            self._durability.release(did)  # keep files: recovery
        self.quarantined[handle.session_id] = dataclasses.replace(
            rec, session=handle, durable_id=did
        )
        self.sessions_quarantined += 1
        return True

    def _harvest_quarantined(self) -> None:
        """Pull fresh pool-level quarantine records up to the router.

        The shard pool already detached the poisoned session and suppressed
        its non-finite output; here the router re-keys the record by the
        CLIENT's session id, drops the live handle, and releases the durable
        journal (files kept) so ``attach`` of the same id can roll the
        stream back to its last finite state.
        """
        for i, pool in self._live():
            take = getattr(pool, "take_quarantined", None)
            if take is None:
                continue
            for rec in take():
                handle = None
                for h in self._sessions.values():
                    if h.shard == i and h.inner is rec.session:
                        handle = h
                        break
                if handle is None:
                    continue
                del self._sessions[handle.session_id]
                did = None
                if self._durability is not None:
                    did = str(handle.session_id)
                    self._durability.release(did)  # keep files: recovery
                self.quarantined[handle.session_id] = dataclasses.replace(
                    rec, session=handle, durable_id=did
                )
                self.sessions_quarantined += 1

    def clear_quarantined(self, session_id: Optional[Hashable] = None) -> None:
        """Forget quarantine record(s) without recovering them."""
        if session_id is None:
            self.quarantined.clear()
        else:
            self.quarantined.pop(session_id, None)

    def set_brownout(self, level: int) -> None:
        """Force every live shard onto one degradation-ladder rung (see
        ``SessionPool.set_brownout``; adaptive fleets walk the ladder
        per-shard through their controllers instead)."""
        for _, pool in self._live():
            setter = getattr(pool, "set_brownout", None)
            if setter is not None:
                setter(level)

    @obs.spanned("read")
    def read_degraded(self, sess) -> Tuple[np.ndarray, bool]:
        """``read`` plus the brownout passthrough flag for the popped audio
        (True only when brownout level 3 produced any of it)."""
        handle = self._resolve(sess)
        pool = self._pools[handle.shard]
        reader = getattr(pool, "read_degraded", None)
        if reader is None:
            return self.read(handle), False
        out, degraded = reader(handle.inner)
        if out.size and self._durability is not None:
            self._durability.record_read(
                str(handle.session_id), handle.inner.stats.samples_out
            )
        return out, degraded

    # -- durable recovery (snapshot + journal + replay) ----------------------

    def _recover_one(
        self,
        session_id: Hashable,
        max_feed_samples: Optional[int] = None,
    ) -> ShardedSession:
        """Rebuild one durable session on a live shard, bit-exactly.

        Destination is the ring home (walking around dead shards), falling
        back to the most-headroom live shard — the same placement rule as
        failover. The heavy lifting (snapshot decode, journal replay,
        read-cursor fast-forward, fresh finalizing snapshot) is
        ``repro.serve.durability.recover_session``.

        Raises:
            DurabilityError: the on-disk state is unrecoverable.
            PoolFullError: no live shard has a slot for the session.
        """
        dst = self._failover_destination(session_id)
        if dst is None:
            raise PoolFullError(
                f"cannot recover session {session_id!r}: no live shard has "
                f"a free slot (active={self.num_active}, "
                f"capacity={self.max_capacity})"
            )
        inner = recover_session(
            self._pools[dst],
            self._durability,
            str(session_id),
            max_feed_samples=max_feed_samples,
        )
        handle = ShardedSession(session_id=session_id, shard=dst, inner=inner)
        self._sessions[session_id] = handle
        try:
            self.lost_session_ids.remove(session_id)
        except ValueError:
            pass
        self.sessions_recovered += 1
        return handle

    def recover_sessions(
        self, session_ids: Optional[Sequence[Hashable]] = None
    ) -> List[ShardedSession]:
        """Recover every durable session that is not currently attached.

        The cold-restart entry point: after a full process kill, a fresh
        router pointed at the same durability directory rebuilds every
        orphaned stream from its newest snapshot + journal chain (the
        gateway calls this in ``start()``). Per-session failures (corrupt
        chain, full fleet) are recorded in ``recovery_errors`` and do NOT
        abort the sweep — one bad session must not block the rest.

        Args:
            session_ids: explicit ids to recover; default = every id with
                durable state on disk (``DurabilityManager.list_sessions``).

        Returns:
            Live handles for the sessions recovered by THIS call.
        """
        if self._durability is None:
            return []
        self._failover_pending()
        if session_ids is None:
            session_ids = self._durability.list_sessions()
        recovered: List[ShardedSession] = []
        for sid in session_ids:
            # a quarantined id is deliberately NOT swept back in: its journal
            # tail is the poison — only an explicit attach() rolls it back
            if (
                sid in self._sessions
                or sid in self.quarantined
                or not self._durability.has(sid)
            ):
                continue
            try:
                recovered.append(self._recover_one(sid))
            except (DurabilityError, PoolFullError) as exc:
                self.recovery_errors.append((sid, str(exc)))
        return recovered

    # -- balance ------------------------------------------------------------

    def shard_stats(self) -> List[Dict[str, object]]:
        """Per-shard load counters (see ``SessionPool.shard_stats``), plus
        the fabric's health/failover metrics on every entry:

        - ``alive`` — False while the shard is down (its load counters then
          read as zeros and ``device`` as ``"down"``),
        - ``pump_failures`` — times this index died MID-pump (the
          ``pump_all`` skip-don't-raise path),
        - ``shard_failovers`` — completed failovers of this index,
        - ``sessions_failed_over`` / ``sessions_lost`` — fleet totals
          (repeated on each entry for one-stop scraping),
        - ``breaker`` / ``breaker_streak`` — this shard's circuit-breaker
          state and consecutive-failure count,
        - ``breaker_opens`` / ``watchdog_failovers`` /
          ``sessions_quarantined`` — fleet containment totals (repeated on
          each entry).
        """
        out = []
        for i, p in enumerate(self._pools):
            if i in self._dead:
                s = {
                    "capacity": 0, "active": 0, "free": 0, "hops": 0,
                    "backlog_hops": 0, "p50_ms": 0.0, "p99_ms": 0.0,
                    "device": "down",
                    "backend": self._mk["backend"],
                    "hops_per_step": self._mk["hops_per_step"],
                    "alive": False,
                    "quarantined": 0, "brownout": 0, "brownout_hops": 0,
                }
            else:
                s = dict(p.shard_stats())
                s["alive"] = True
            s["pump_failures"] = self._pump_failures[i]
            s["shard_failovers"] = self._failover_counts[i]
            s["breaker"] = self._breaker[i]
            s["breaker_streak"] = self._breaker_streak[i]
            s["sessions_failed_over"] = self.sessions_failed_over
            s["sessions_lost"] = self.sessions_lost
            s["sessions_recovered"] = self.sessions_recovered
            s["lost_ids_tracked"] = len(self.lost_session_ids)
            s["breaker_opens"] = self.breaker_opens
            s["watchdog_failovers"] = self.watchdog_failovers
            s["sessions_quarantined"] = self.sessions_quarantined
            if self._scheds[i] is not None:
                s["scheduler"] = self._scheds[i].stats()
            out.append(s)
        return out

    def scheduler_stats(self) -> Optional[List[Dict[str, object]]]:
        """Per-shard adaptive-controller counters (None when not adaptive)."""
        if not self._adaptive:
            return None
        return [
            sched.stats() if sched is not None else {}
            for sched in self._scheds
        ]

    def _migrate(self, handle: ShardedSession, dst: int) -> None:
        """Move one live session to shard ``dst`` (resumes bit-for-bit)."""
        ticket = self._pools[handle.shard].export_session(handle.inner)
        handle.inner = self._pools[dst].import_session(ticket)
        handle.shard = dst

    def _drain_one(self, shard: int) -> None:
        """Migrate one session off ``shard`` to the shard with most headroom.

        Headroom counts growable tiers: an elastic destination at its current
        capacity still has room — ``import_session`` grows it."""
        frees = [
            _max_capacity(p) - p.num_active if i not in self._dead else -1
            for i, p in enumerate(self._pools)
        ]
        frees[shard] = -1  # never pick the shard being drained
        dst = max(range(self.n_shards), key=lambda i: frees[i])
        if frees[dst] <= 0:
            return
        handle = next(
            (h for h in self._sessions.values() if h.shard == shard), None
        )
        if handle is not None:
            self._migrate(handle, dst)

    def rebalance(self, tolerance: int = 1) -> int:
        """Migrate sessions until shard loads differ by at most ``tolerance``.

        Repeatedly moves one session from the most- to the least-loaded shard
        via ``export_session``/``import_session``; a migrated stream resumes
        bit-for-bit (state, queued input, unread output, stats all travel).
        Migration overrides the hash placement — the handle's ``shard`` field
        tracks the session's current home, so routing by handle/id still
        works. Elastic donor shards are shrunk back down their tier ladder
        afterwards (``try_shrink(force=True)``), so a drained shard returns
        its over-provisioned envelope immediately instead of waiting out the
        lazy watermark patience.

        Returns:
            Number of sessions moved.
        """
        tolerance = max(1, tolerance)  # 0 would oscillate a session forever
        self._failover_pending()  # dead-shard residents re-home first
        moved = 0
        while True:
            live = self._live()
            if len(live) < 2:
                break
            loads = {i: p.num_active for i, p in live}
            src = max(loads, key=lambda i: loads[i])
            dst = min(loads, key=lambda i: loads[i])
            if loads[src] - loads[dst] <= tolerance:
                break
            if _shard_full(self._pools[dst]):
                break  # least-loaded shard has no slot headroom
            handle = next(
                h for h in self._sessions.values() if h.shard == src
            )
            self._migrate(handle, dst)
            moved += 1
        if moved and self.elastic:
            for _, pool in self._live():
                pool.try_shrink(force=True)
        return moved

    # -- reporting ----------------------------------------------------------

    def report(self) -> str:
        lines = [
            f"ShardedSessionPool(shards={self.n_shards}, "
            f"capacity={self.capacity}, active={self.num_active}"
            + (f", dead={self.dead_shards}" if self._dead else "")
            + ")"
        ]
        for i, stats in enumerate(self.shard_stats()):
            if not stats["alive"]:
                lines.append(
                    f"  shard {i} [down]: {stats['shard_failovers']} "
                    f"failovers, {stats['pump_failures']} pump failures"
                )
                continue
            lines.append(
                f"  shard {i} [{stats['device']}]: "
                f"{stats['active']}/{stats['capacity']} active, "
                f"{stats['hops']} hops, backlog={stats['backlog_hops']}, "
                f"p50={stats['p50_ms']:.2f}ms"
            )
        if self.sessions_failed_over or self.sessions_lost:
            lines.append(
                f"  failover: {self.sessions_failed_over} sessions re-homed, "
                f"{self.sessions_lost} lost"
            )
        return "\n".join(lines)
