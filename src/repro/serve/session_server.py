"""Multi-session streaming enhancement server (the ROADMAP's serving tier).

The paper's deployment story is one ASIC per stream; the serving twin is one
accelerator per *batch* of streams. This module multiplexes many concurrent
client sessions onto a single jit-compiled batched hop step
(``repro.serve.streaming_se.make_stream_hop``):

- **Fixed-capacity ``SessionPool``** — one batched ``StreamState`` whose
  leading axis is the slot index. Capacity is chosen once; attach/detach only
  flips per-slot active masks and zeroes slot state (``reset_slots``), so
  client churn never changes array shapes and never triggers recompilation.
- **Chunk-size-agnostic ingestion** — each session owns a ring buffer;
  clients may feed 37-sample dribbles or 10-second blobs. ``pump()`` drains
  whole hops (16 ms at 8 kHz) across all sessions per batched step. With
  ``inflight=2`` the drain is **double-buffered**: the host fills hop k+1's
  input buffer while the device computes hop k (the ROADMAP async item), and
  ``max_unread_hops`` bounds per-session output growth under slow readers
  (backpressure parks the stream in its own ring instead; an
  ``on_unparked`` callback wakes the driver when the reader catches up).
- **Multi-hop fused dispatch** — ``hops_per_step=K`` amortizes the fixed
  host→device→host + Python dispatch cost over up to K hops per session per
  device call: one packed (capacity, K, hop) staging transfer in, one
  scan-batched jit step, one readback of up to K enhanced hops per slot,
  with a per-slot ``hop_counts`` vector so ragged backlogs drain unevenly
  in the same call. Bit-identical to K=1 (tests/test_fused_hops.py).
- **Donated state** — the batched recurrent state is donated to the jit step,
  so steady-state serving updates it in place (constant memory traffic, the
  software analogue of the ASIC's all-on-chip state).
- **Isolation** — inactive/starved slots are masked inside the jit step:
  their state is kept bit-for-bit and they emit nothing, so a slot's output
  depends only on its own history. A session served next to churning
  neighbours produces the same audio as a solo run (tests/test_session_server.py).
- **Accounting** — per-session hops/samples processed, processing-time share,
  and real-time factor (RTF = compute time / audio time); pool-wide step
  latency percentiles for the 16 ms budget check.
- **Sharding seams** — a pool can be pinned to one device (``device=``), its
  batched step can be split into a non-blocking ``dispatch()`` and a blocking
  ``collect()`` so a router can overlap many shards' device work
  (``repro.serve.sharded_pool.ShardedSessionPool.pump_all``), live sessions
  can be snapshotted/restored across pools (``export_session`` /
  ``import_session`` — the unit of shard rebalancing), and ``shard_stats()``
  exports the load counters the router balances on.

Quantized serving: pass ``quant=repro.core.quant.FP10`` (or FXP8 — the
"int8-class" fixed-point grid) to run the pool on the paper's deployment
number formats via the same shared hop step.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import QuantSpec
from repro.models import tftnn as tft_mod
from repro.serve import obs
from repro.serve.faults import FaultPlan, InjectedFaultError
from repro.serve.scheduler import SchedulerObservation
from repro.serve.streaming_se import (
    StreamState,
    init_stream,
    make_stream_hop,
    reset_slots,
)

Pytree = dict
# per-pool step counters: steps launched, hops they carried, and the lanes
# they computed (capacity x lane count, whether live or masked)
STEP_COUNTERS = ("steps", "hops_stepped", "lanes_offered")


@jax.jit
def _finite_slots(state, out) -> jax.Array:
    """(B,) bool — True where EVERY float leaf of (state, out) is finite.

    The post-collect finite guard: one jitted all-reduce per slot over the
    new carried state and the step's output, launched right after the step
    so its (tiny) result rides the readback the collect already pays for.
    Per-slot, because the batched hop math is row-independent: one slot
    going NaN proves nothing about its batch neighbours, and the guard's
    verdict is what quarantines exactly the poisoned slot.
    """
    leaves = jax.tree_util.tree_leaves((state, out))
    batch = leaves[0].shape[0]
    ok = jnp.ones((batch,), bool)
    for leaf in leaves:
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            ok = ok & jnp.all(jnp.isfinite(leaf.reshape(batch, -1)), axis=1)
    return ok


@jax.jit
def _nan_slots(tree, slot_mask):
    """Overwrite every float leaf of ``tree`` with NaN where ``slot_mask``
    is True (fault injection's poison writer — the software stand-in for a
    corrupt frame blowing up a slot's recurrent accumulators)."""

    def poison(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf
        m = slot_mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.full_like(leaf, jnp.nan), leaf)

    return jax.tree_util.tree_map(poison, tree)


@jax.jit
def _ring_write(ring, slot, start, block, n):
    """Write ``block[:n]`` whole hops into one slot's device ingestion ring
    at positions ``(start + i) % R``.

    Fixed shapes by construction — ``block`` is always (R, hop) with lanes
    >= ``n`` masked out, and the scalars are traced (not static) — so every
    ``feed()`` hits ONE compilation regardless of chunk size or position.
    The ring is NOT donated: an in-flight pipelined step may still be
    reading the superseded array (functional update keeps it alive).
    """
    R = ring.shape[1]
    idx = (start + jnp.arange(R)) % R
    live = (jnp.arange(R) < n)[:, None]
    cur = ring[slot][idx]
    return ring.at[slot, idx].set(jnp.where(live, block, cur))


def warm_inputs(cfg: tft_mod.TFTConfig, capacity: int, k: int,
                ring_depth: Optional[int]) -> Tuple[np.ndarray, ...]:
    """Host inputs (after the state) for one all-masked-out dispatch of the
    ``max_hops=k`` step at ``capacity`` slots: what ``prewarm`` runs so jit
    compiles before a session needs the step.

    The shapes and dtypes are exactly those ``SessionPool.dispatch`` ships:
    the device-ring form takes ``(ring, starts, lanes)``, the staged form
    ``(hops, lanes)``; lanes are a bool mask at k=1, int hop counts above.
    """
    lanes = (
        np.zeros((capacity,), bool) if k == 1
        else np.zeros((capacity,), np.int32)
    )
    if ring_depth is not None:
        return (
            np.zeros((capacity, ring_depth, cfg.hop), np.float32),
            np.zeros((capacity,), np.int32),
            lanes,
        )
    shape = (capacity, cfg.hop) if k == 1 else (capacity, k, cfg.hop)
    return np.zeros(shape, np.float32), lanes


class SessionError(RuntimeError):
    """Invalid session operation.

    Raised when a call references a session that is not live on this pool:
    a handle that was already detached, a handle belonging to a different
    pool, or (on the sharded router) an unknown session id. The pool's own
    state is never modified by a failing call.
    """


class PoolFullError(SessionError):
    """``attach()`` on a pool whose every slot is occupied.

    Capacity is fixed at construction (it is baked into the compiled batched
    step), so the only remedies are detaching a session, creating a pool
    with a larger capacity, or serving through
    ``repro.serve.elastic_pool.ElasticSessionPool`` (which grows along a
    pre-compiled tier ladder and only raises this at its top tier). The
    sharded router raises the subclass
    ``repro.serve.sharded_pool.ShardFullError`` instead when only the routed
    shard — not the whole fleet — is out of slots.
    """


class SessionPoisonedError(SessionError):
    """The session was quarantined by the finite guard.

    A batched step produced a non-finite output or carried state for this
    session's slot (a poison input chunk blowing up the recurrent
    accumulators, or an injected fault). The pool detached the session
    before any non-finite sample could be read — a quarantined session
    NEVER emits poisoned audio — and released (not deleted) its durable
    state, so ``repro.serve.durability.recover_session`` with
    ``max_feed_samples=<good_samples_in>`` rebuilds the stream at its
    last-good pre-poison point. Other slots in the same batched step are
    untouched: the hop math is row-independent and the guard's verdict is
    per-slot.
    """

    def __init__(
        self,
        message: str,
        *,
        session_id: Optional[int] = None,
        good_hops: Optional[int] = None,
        good_samples_in: Optional[int] = None,
    ) -> None:
        super().__init__(message)
        self.session_id = session_id
        self.good_hops = good_hops
        self.good_samples_in = good_samples_in


@dataclasses.dataclass
class QuarantineRecord:
    """What the pool remembers about one quarantined session.

    ``good_hops`` / ``good_samples_in`` mark the last state PROVEN finite:
    the poisoning step's own hops are excluded (its output was suppressed),
    so durability replay truncated at ``good_samples_in`` fed samples
    reconstructs the stream exactly as it was before the poison entered.
    """

    sid: int
    session: "Session"  # the dead handle (identity for router translation)
    durable_id: Optional[str]
    good_hops: int
    good_samples_in: int
    stats: "SessionStats"
    message: str = ""


@dataclasses.dataclass
class SessionStats:
    """Per-session serving accounting."""

    hops: int = 0  # hops actually enhanced
    samples_in: int = 0  # raw samples accepted by feed()
    samples_out: int = 0  # enhanced samples emitted
    proc_seconds: float = 0.0  # this session's share of batched step time

    def audio_seconds(self, sample_rate: int, hop: int) -> float:
        return self.hops * hop / sample_rate

    def rtf(self, sample_rate: int, hop: int) -> float:
        """Real-time factor: compute seconds per audio second (<1 = real time)."""
        audio = self.audio_seconds(sample_rate, hop)
        return self.proc_seconds / audio if audio > 0 else 0.0


@dataclasses.dataclass
class Session:
    """Client handle returned by ``SessionPool.attach``."""

    sid: int
    slot: int
    stats: SessionStats = dataclasses.field(default_factory=SessionStats)
    detached: bool = False


@dataclasses.dataclass
class _Pending:
    """One in-flight batched step (between dispatch() and collect())."""

    out: jax.Array  # (B, hop) at hops_per_step=1, (B, K, hop) otherwise
    counts: np.ndarray  # (B,) int — hops consumed per slot by this step
    t0: float
    dt: Optional[float] = None  # dispatch->ready, set by wait_ready()
    ready: float = 0.0  # when the output was ready, set with dt
    finite: Optional[jax.Array] = None  # (B,) bool finite-guard verdict
    degraded: bool = False  # produced in brownout passthrough mode


@dataclasses.dataclass
class SessionTicket:
    """Portable snapshot of one live session — the unit of migration.

    Produced by ``SessionPool.export_session`` and consumed by
    ``SessionPool.import_session`` (possibly on a pool pinned to a different
    device): the session's slice of the batched recurrent state (as host
    numpy arrays, so re-import places them wherever the target pool lives),
    its queued-but-unprocessed input, its enhanced-but-unread output, and its
    accounting. Importing a ticket resumes the stream bit-for-bit where the
    export left off.
    """

    state: Any  # per-slot StreamState leaves (numpy, no leading batch axis)
    pending_in: np.ndarray  # raw samples fed but not yet hopped
    unread_out: np.ndarray  # enhanced samples produced but not yet read
    stats: SessionStats
    parked: bool = False  # backpressure-parked at export (wake-up continuity)


class _RingBuffer:
    """Per-session ingestion buffer: accepts arbitrary-length float chunks,
    yields fixed hop-sized blocks."""

    def __init__(self) -> None:
        self._chunks: List[np.ndarray] = []
        self._size = 0

    def push(self, samples: np.ndarray) -> None:
        if samples.size:
            self._chunks.append(samples)
            self._size += samples.size

    def __len__(self) -> int:
        return self._size

    def pop(self, n: int) -> np.ndarray:
        """Pop exactly n samples (caller checks len() first)."""
        out = np.empty((n,), np.float32)
        filled = 0
        while filled < n:
            head = self._chunks[0]
            take = min(n - filled, head.size)
            out[filled : filled + take] = head[:take]
            if take == head.size:
                self._chunks.pop(0)
            else:
                self._chunks[0] = head[take:]
            filled += take
        self._size -= n
        return out

    def peek(self) -> np.ndarray:
        """Copy the full buffered contents WITHOUT consuming them — the
        non-destructive twin of ``pop(len(self))`` for snapshotting."""
        if not self._size:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(c, np.float32) for c in self._chunks])


class SessionPool:
    """Fixed-capacity multi-session streaming enhancement server.

    One instance = one compiled batched hop step + one batched recurrent
    state. Typical driver loop::

        pool = SessionPool(params, cfg, capacity=8)
        s = pool.attach()
        pool.feed(s, chunk)          # any chunk size, any time
        pool.pump()                  # run batched hop steps while audio waits
        audio = pool.read(s)         # enhanced samples ready so far
        pool.detach(s)

    Args:
        params: TFTNN parameter pytree (weights are quantized once here when
            ``quant`` is set).
        cfg: model/front-end config; ``cfg.hop`` fixes the step granularity.
        capacity: number of slots. Baked into the compiled step — churn never
            changes it, only a new pool can.
        quant: optional ``repro.core.quant`` grid (FP10/FXP8) for the paper's
            deployment number formats.
        sample_rate: audio sample rate for RTF accounting (paper: 8 kHz).
        donate: donate the recurrent state to the jit step (in-place update).
        device: pin params, state, and per-hop inputs to this ``jax.Device``.
            ``None`` (default) uses JAX's default placement. This is the
            shard-placement seam: ``ShardedSessionPool`` builds one pool per
            device so each shard's state lives (and stays) on its own chip.
        backend: hop-step implementation — ``"xla"`` (the training graph) or
            ``"pallas"`` (the deploy-compiled graph: BN folded, Pallas
            kernels; see ``repro.serve.deploy``). Ignored when ``step_fn``
            is supplied.
        prune_keep / prune_axis / prune_granularity / prune_block:
            deploy-time pruning (``deploy.build_deploy_plan``): keep-fraction
            for the dense zero-skipping masks on the matmul weights, either
            legacy unstructured/axis-structured (``prune_axis``) or
            weight/block/unit granular (``prune_granularity`` with
            ``prune_block`` tiles — arXiv 2111.02351). Works on both
            backends: a pruned ``"xla"`` pool serves the folded plan through
            the reference kernels. Lossy by design — the paper's
            93.9 %-pruned serving point, not a parity mode. ``None``
            (default) serves unpruned. ``shard_stats()`` reports the exact
            realized sparsity and kernel skip rate under ``"prune"``.
        inflight: depth of the dispatch pipeline (>= 1). 1 (default) is the
            classic loop: each ``dispatch()`` first waits out the previous
            step. 2 is **double-buffered ingestion** (the ROADMAP async
            item): while the device runs step k, the host drains the ring
            buffers for step k+1 into a second hop buffer and enqueues it —
            host I/O and device compute overlap inside ONE shard. The pool
            keeps ``inflight`` host-side hop buffers and reuses one only
            after its step has been collected, so pipelining never aliases
            an in-flight step's input.
        max_unread_hops: backpressure bound on the per-session output queue
            (``None`` = unbounded, the previous behaviour). A session whose
            enhanced-but-unread output (queued plus in-flight) reaches this
            many hops is *parked*: ``dispatch()`` stops popping its ring, so
            ``_out`` growth is bounded at ``max_unread_hops`` hops per slot
            and a slow reader backs pressure up into its own ring buffer
            instead of growing the pool's output memory without bound. The
            stream resumes as soon as the client ``read()``s.
        on_unparked: wake-up callback ``on_unparked(session)`` fired from
            ``read()`` when a parked session's unread output drains back
            below ``max_unread_hops`` — the signal for an async driver to
            resume pumping a stream it stopped scheduling. Called
            synchronously inside ``read()`` (including the final drain
            inside ``detach()``), at most once per park/unpark cycle.
            Requires ``max_unread_hops`` (nothing ever parks otherwise).
        hops_per_step: maximum hops drained per session per ``dispatch()``
            (default 1 = the classic one-hop step). With K > 1 the pool
            serves through the **multi-hop fused dispatch** path
            (``make_stream_hop(..., max_hops_per_step=K)``): each dispatch
            pops up to K hops per backlogged session into one packed
            (capacity, K, hop) staging buffer, ships it in ONE transfer,
            runs the scan-batched step in ONE device call, and ``collect()``
            reads back up to K enhanced hops per slot in one readback.
            Sessions with different backlogs drain different hop counts in
            the same call (per-slot ``hop_counts``), and outputs are
            bit-identical to ``hops_per_step=1``. The tradeoff is output
            granularity: a backlogged stream's audio arrives K hops at a
            time (throughput up, per-hop readback latency amortized).
        step_fn: a pre-built hop step (from ``make_stream_hop(params, cfg,
            quant=quant, donate=donate, backend=backend,
            max_hops_per_step=hops_per_step)``) to use instead of compiling
            a fresh one. Pools that share a device, params, config, quant,
            backend, capacity, and ``hops_per_step`` can share ONE compiled
            step this way — the router uses it so co-located shards don't
            pay N identical XLA compilations. The caller is responsible for
            the match.
        step_fns: a dict to cache compiled steps in, keyed by
            ``(max_hops, ingest_ring)``. The pool builds steps lazily per
            lane count (``dispatch(max_hops=k)``, the adaptive scheduler's
            seam) and looks them up here first; pass ONE shared dict to
            pools that share device/params/config/quant/backend so a
            scheduler exploring K values compiles each lane count once per
            fleet, not once per pool. ``step_fn`` (if also given) seeds the
            ``(hops_per_step, ingest_ring)`` entry.
        ingest_ring: depth (in hops) of the **device-resident ingestion
            ring**, or ``None`` (default) for the classic host staging
            path. With a ring, every whole hop a ``feed()`` completes is
            shipped to the device immediately (one fixed-shape jitted
            scatter per feed) and ``dispatch()`` gathers up to K consecutive
            ring lanes in place (``make_stream_hop(..., from_ring=R)``) —
            sub-hop dribbles stop round-tripping through host numpy at
            dispatch time, which is what makes per-pump K re-tuning cheap.
            Must be >= ``hops_per_step``; outputs are bit-identical to the
            staged path.
        durability: optional ``repro.serve.durability.DurabilityManager``.
            When set, every ``attach`` registers a durable id (override via
            ``attach(durable_id=...)``), every ``feed`` appends the fed
            bytes to that session's crash journal (and snapshots the
            session on the manager's cadence via ``snapshot_session``),
            every non-empty ``read`` records the client's cumulative read
            cursor, and ``detach`` deletes the durable state. After a
            process crash ``repro.serve.durability.recover_session``
            rebuilds the stream bit-exactly in a fresh pool. Exactly ONE
            layer should journal a given stream: hand the manager to the
            outermost pool a client feeds (the sharded router journals at
            the router, not per shard).
        finite_guard: opt-in poison containment (default off = zero
            overhead). Every dispatch additionally launches one jitted
            per-slot ``isfinite`` all-reduce over the step's output AND new
            carried state (``_finite_slots``); ``collect()`` reads the tiny
            verdict back alongside the output it already fetches. A slot
            that fails the check is **quarantined**: its output for that
            step is suppressed (a quarantined session never emits
            non-finite audio), the session is detached into
            ``self.quarantined``, further calls on its handle raise the
            typed ``SessionPoisonedError``, and its durable files (if any)
            are released intact so the pre-poison state is recoverable via
            ``durability.recover_session(..., max_feed_samples=
            record.good_samples_in)``. Slots sharing the batched step are
            untouched — the hop math is row-independent.
        faults: optional ``repro.serve.faults.FaultPlan``. Deterministic
            fault injection: each ``dispatch()`` first asks the plan
            whether to raise ``InjectedFaultError`` (before consuming ANY
            input — the failed call is side-effect-free) and, after
            launching the step, whether to overwrite stepped slots' output
            or carried state with NaN (what the finite guard exists to
            catch). Production pools pass ``None``.
        fault_tag: name of this pool in the fault plan's schedule (the
            router tags each shard so a plan targets shards independently).

    Raises:
        ValueError: ``capacity < 1``, ``inflight < 1``, ``hops_per_step <
            1``, ``ingest_ring < hops_per_step``, ``on_unparked`` without
            ``max_unread_hops``, bad ``backend``.
    """

    def __init__(
        self,
        params: Pytree,
        cfg: tft_mod.TFTConfig,
        capacity: int,
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
        step_fn=None,
        step_fns: Optional[Dict[Any, Any]] = None,
        ingest_ring: Optional[int] = None,
        durability: Optional[Any] = None,
        finite_guard: bool = False,
        faults: Optional[FaultPlan] = None,
        fault_tag: str = "pool",
    ) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if inflight < 1:
            raise ValueError("inflight must be >= 1")
        if max_unread_hops is not None and max_unread_hops < 1:
            raise ValueError("max_unread_hops must be >= 1 (or None)")
        if on_unparked is not None and max_unread_hops is None:
            raise ValueError(
                "on_unparked requires max_unread_hops: without the "
                "backpressure bound no session ever parks, so the wake-up "
                "callback could never fire"
            )
        if hops_per_step < 1:
            raise ValueError("hops_per_step must be >= 1")
        if ingest_ring is not None and ingest_ring < hops_per_step:
            raise ValueError(
                f"ingest_ring depth ({ingest_ring}) must be >= hops_per_step "
                f"({hops_per_step}): one dispatch may gather up to K "
                f"consecutive device-ring lanes"
            )
        self.cfg = cfg
        self.capacity = capacity
        self.sample_rate = sample_rate
        self.quant = quant
        self.device = device
        self.backend = backend
        self.hops_per_step = hops_per_step
        if device is not None:
            params = jax.device_put(params, device)
        self._params = params
        self._donate = donate
        self._prune_keep = prune_keep
        self._prune_axis = prune_axis
        self._prune_granularity = prune_granularity
        self._prune_block = prune_block
        self._prune_meta: Dict[str, Any] = {}
        self._ring_depth = ingest_ring
        self._steps: Dict[Any, Any] = step_fns if step_fns is not None else {}
        if step_fn is not None:
            self._steps.setdefault((hops_per_step, ingest_ring, False), step_fn)
        self._step = self._step_for(hops_per_step)  # default full-K step
        state = init_stream(params, cfg, capacity)
        self._state: StreamState = (
            jax.device_put(state, device) if device is not None else state
        )
        self._slot_session: List[Optional[Session]] = [None] * capacity
        self._sessions: Dict[int, Session] = {}
        self._rings: List[_RingBuffer] = [_RingBuffer() for _ in range(capacity)]
        self._out: List[List[np.ndarray]] = [[] for _ in range(capacity)]
        self._sid_counter = itertools.count()
        self._inflight = inflight
        self._max_unread_hops = max_unread_hops
        self._on_unparked = on_unparked
        self._parked = np.zeros((capacity,), bool)
        # one host staging buffer per pipeline stage: buffer i is refilled
        # only after the step that consumed it has been collected (see
        # dispatch). At hops_per_step=K the buffer packs up to K hops per
        # slot so a dispatch ships ONE array instead of re-staging per hop.
        # With a device-resident ingest ring there is no host staging at all.
        if ingest_ring is None:
            shape = (
                (capacity, cfg.hop) if hops_per_step == 1
                else (capacity, hops_per_step, cfg.hop)
            )
            self._hop_bufs = [np.zeros(shape, np.float32) for _ in range(inflight)]
            self._ring_arr = None
            self._ring_start = None
            self._ring_count = None
        else:
            self._hop_bufs = []
            ring = jnp.zeros((capacity, ingest_ring, cfg.hop), jnp.float32)
            self._ring_arr = (
                jax.device_put(ring, device) if device is not None else ring
            )
            # host-side cursors: FIFO position + fill level per slot
            self._ring_start = np.zeros((capacity,), np.int64)
            self._ring_count = np.zeros((capacity,), np.int64)
        self._buf_i = 0
        self._durability = durability
        self._durable_ids: Dict[int, str] = {}  # sid -> durable id
        self._finite_guard = finite_guard
        self._faults = faults
        self._fault_tag = fault_tag
        # sid -> QuarantineRecord for sessions the finite guard detached
        self._quarantined: Dict[int, QuarantineRecord] = {}
        self._fresh_quarantined: List[QuarantineRecord] = []
        self.quarantined_count = 0
        # graceful-brownout ladder (0 = full service .. 3 = passthrough);
        # set per pump by the scheduler's decision via set_brownout()
        self._brownout = 0
        self.brownout_hops = 0
        self._degraded_unread = np.zeros((capacity,), bool)
        # in-flight batched steps launched by dispatch(), drained in FIFO
        # order by collect(); at most ``inflight`` deep
        self._pending: List[_Pending] = []
        self._last_ready_t = 0.0  # when the previous step's output was ready
        self.step_seconds: List[float] = []  # pool-wide per-step latency
        self.steps = self.hops_stepped = self.lanes_offered = 0
        self._ledger = obs.HopLedger(capacity)  # per-hop stamps while recording

    def _step_for(self, k: int, passthrough: bool = False):
        """The compiled step for a ``dispatch(max_hops=k)`` call.

        Built lazily per lane count and cached in ``self._steps`` keyed by
        ``(k, ingest_ring, passthrough)`` — a dict the caller may share
        across pools (``step_fns=``) so elastic tiers and co-located shards
        pay each lane count's XLA compilation once per fleet, not once per
        pool. Ring pools build the ``from_ring`` gather form; staged pools
        the packed buffer form. ``passthrough`` selects the model-free
        brownout step (same plumbing, ``hop_passthrough`` hop core).
        """
        key = (k, self._ring_depth, passthrough)
        step = self._steps.get(key)
        if step is None:
            step = make_stream_hop(
                self._params, self.cfg, quant=self.quant, donate=self._donate,
                backend=self.backend, prune_keep=self._prune_keep,
                prune_axis=self._prune_axis,
                prune_granularity=self._prune_granularity,
                prune_block=self._prune_block, max_hops_per_step=k,
                from_ring=self._ring_depth, prune_meta=self._prune_meta,
                passthrough=passthrough,
            )
            self._steps[key] = step
        return step

    def prewarm(self, lane_counts: Optional[Sequence[int]] = None) -> None:
        """Compile now every program this pool runs while serving.

        Runs each step once on a dummy state with every slot masked out —
        the live state is untouched — so no ``dispatch()`` pays a jit
        compile, and a compile error raises here instead of surfacing as a
        failed step. Also compiles the attach-time slot reset, and the
        finite guard's verdict and the ingest ring's write when the pool
        uses them.

        Args:
            lane_counts: the ``max_hops`` values to compile; default
                ``(hops_per_step,)``, the only one a pool dispatches without
                an adaptive scheduler.
        """
        cap = self.capacity
        put = lambda x: jax.device_put(x, self.device)  # noqa: E731
        for k in lane_counts or (self.hops_per_step,):
            state = reset_slots(
                put(init_stream(self._params, self.cfg, cap)),
                jnp.zeros((cap,), bool).at[0].set(True),
            )
            inputs = warm_inputs(self.cfg, cap, k, self._ring_depth)
            state, out = self._step_for(k)(state, *(put(x) for x in inputs))
            if self._finite_guard:
                out = _finite_slots(state, out)
            jax.block_until_ready(out)
        if self._ring_depth is not None:
            block = np.zeros((self._ring_depth, self.cfg.hop), np.float32)
            jax.block_until_ready(_ring_write(self._ring_arr, 0, 0, block, 0))

    # -- session lifecycle --------------------------------------------------

    @property
    def num_active(self) -> int:
        return len(self._sessions)

    def attach(self, durable_id: Optional[str] = None) -> Session:
        """Claim a free slot for a new stream.

        O(1): only flips the slot's mask and zeroes its state slice via
        ``reset_slots`` — array shapes never change, so attach/detach churn
        NEVER triggers recompilation of the batched hop step (the pool's one
        compilation happens on the first ``step()``/``dispatch()``).

        Args:
            durable_id: the on-disk identity for this stream's crash
                journal when the pool has a ``durability`` manager (default
                ``sess-<sid>``). Any stale durable state under this id is
                wiped — this attach IS the start of the stream. Ignored
                without a manager.

        Returns:
            A fresh ``Session`` handle (zeroed stream state, empty buffers).

        Raises:
            PoolFullError: every slot is occupied.
        """
        sess = self._attach_slot()
        if self._durability is not None:
            did = durable_id if durable_id is not None else f"sess-{sess.sid}"
            self._durable_ids[sess.sid] = did
            self._durability.begin(did)
        return sess

    def _attach_slot(self) -> Session:
        """``attach`` minus durable registration (``import_session``'s path:
        an imported stream is a continuation, never a fresh journal)."""
        try:
            slot = self._slot_session.index(None)
        except ValueError:
            raise PoolFullError(
                f"pool is full (capacity={self.capacity}, "
                f"active={self.num_active}); detach a session first or serve "
                f"through an elastic pool (repro.serve.ElasticSessionPool)"
            ) from None
        mask = jnp.zeros((self.capacity,), bool).at[slot].set(True)
        self._state = reset_slots(self._state, mask)
        sess = Session(sid=next(self._sid_counter), slot=slot)
        self._slot_session[slot] = sess
        self._sessions[sess.sid] = sess
        self._rings[slot] = _RingBuffer()
        self._out[slot] = []
        self._parked[slot] = False
        self._degraded_unread[slot] = False
        self._ledger.clear(slot)
        if self._ring_depth is not None:
            # cursors only: the step masks lanes by hop_counts, so stale
            # device-ring contents from the previous tenant are never read
            self._ring_start[slot] = 0
            self._ring_count[slot] = 0
        return sess

    def detach(self, sess: Session) -> np.ndarray:
        """Release the session's slot.

        The slot becomes immediately reusable; the next occupant starts from
        zeroed state (``attach`` resets it), so no audio leaks between
        tenants. Queued-but-unprocessed input is dropped.

        Returns:
            Any enhanced-but-unread audio (may be empty).

        Raises:
            SessionError: the handle is not live on this pool (double detach).
        """
        self._check(sess)
        tail = self.read(sess)
        sess.detached = True
        self._slot_session[sess.slot] = None
        del self._sessions[sess.sid]
        did = self._durable_ids.pop(sess.sid, None)
        if did is not None and self._durability is not None:
            self._durability.forget(did)  # a clean goodbye needs no replay
        return tail

    def _check(self, sess: Session) -> None:
        rec = self._quarantined.get(sess.sid)
        if rec is not None and rec.session is sess:
            raise SessionPoisonedError(
                f"session {sess.sid} was quarantined after a non-finite "
                f"output/state (last good hop: {rec.good_hops}); its "
                f"pre-poison state is recoverable via durability replay",
                session_id=sess.sid,
                good_hops=rec.good_hops,
                good_samples_in=rec.good_samples_in,
            )
        if sess.detached or self._sessions.get(sess.sid) is not sess:
            raise SessionError(f"session {sess.sid} is not attached to this pool")

    # -- audio I/O ----------------------------------------------------------

    def feed(self, sess: Session, samples) -> None:
        """Queue raw audio for a session.

        Args:
            sess: a live handle from ``attach``.
            samples: any array-like of float samples, any length — a 37-sample
                dribble or a 10-second blob. Ring-buffered; compute happens in
                whole hops during ``pump()``/``step()``.

        Raises:
            SessionError: the handle is not live on this pool.
        """
        self._check(sess)
        # copy: callers often reuse one capture buffer between feed() calls
        arr = np.array(samples, np.float32, copy=True).reshape(-1)
        # journal BEFORE the pool sees the audio (write-ahead): a crash
        # between the two leaves an extra journaled chunk the client was
        # never acked for — replayed on recovery, exactly once
        did = self._durable_ids.get(sess.sid) if self._durability is not None else None
        snapshot_due = False
        if did is not None:
            snapshot_due = self._durability.record_feed(did, arr, self.cfg.hop)
        self._rings[sess.slot].push(arr)
        hops_before = sess.stats.samples_in // self.cfg.hop
        sess.stats.samples_in += arr.size
        # device-resident ingestion: ship every completed hop immediately so
        # dispatch() finds the backlog already on-device (sub-hop remainders
        # stay host-side until the next feed completes them)
        self._fill_ring(sess.slot)
        if obs.recording():
            self._ledger.fed(sess.slot, hops_before,
                             sess.stats.samples_in // self.cfg.hop)
        if snapshot_due:
            self._durability.snapshot(did, self.snapshot_session(sess))

    def read(self, sess: Session) -> np.ndarray:
        """Pop all enhanced audio produced for this session so far.

        Draining a *parked* session (one ``dispatch()`` stopped scheduling
        because its unread output hit ``max_unread_hops``) back below the
        bound un-parks it and fires the pool's ``on_unparked`` callback —
        the wake-up signal for a driver that stopped pumping the stream.

        Returns:
            The enhanced samples not yet read (possibly empty). Each sample is
            final — the COLA normalizer makes every emitted hop exact with no
            lookahead — so callers can play/forward it immediately.

        Raises:
            SessionError: the handle is not live on this pool.
        """
        self._check(sess)
        self.collect()  # fold any in-flight dispatch into the output queues
        self._check(sess)  # collect may have quarantined this very session
        chunks = self._out[sess.slot]
        self._out[sess.slot] = []
        self._ledger.read(sess.slot)
        self._degraded_unread[sess.slot] = False  # queue drained below
        # a parked slot is always below the bound here: collect() above
        # drained the pipeline and the queue was just popped, so unread == 0
        if self._parked[sess.slot]:
            self._parked[sess.slot] = False
            if self._on_unparked is not None:
                self._on_unparked(sess)
        if not chunks:
            return np.zeros((0,), np.float32)
        out = np.concatenate(chunks)
        sess.stats.samples_out += out.size
        if self._durability is not None:
            did = self._durable_ids.get(sess.sid)
            if did is not None:
                # the read cursor is durable BEFORE the caller forwards the
                # audio: recovery never re-delivers samples recorded here
                self._durability.record_read(did, sess.stats.samples_out)
        return out

    def backlog_hops(self, sess: Session) -> int:
        """Whole hops fed to ``sess`` and not yet stepped.

        Raises:
            SessionError: the handle is not live on this pool.
        """
        self._check(sess)
        return self._backlog_hops(sess.slot)

    # -- the batched hop loop ----------------------------------------------

    def _unread_hops(self, slot: int) -> int:
        """Hops of enhanced output this slot holds: queued plus in-flight."""
        hop = self.cfg.hop
        queued = sum(c.size for c in self._out[slot]) // hop
        return queued + sum(int(p.counts[slot]) for p in self._pending)

    def _fill_ring(self, slot: int) -> None:
        """Move whole hops from the slot's host ring into the device ring.

        Called from ``feed()`` (and as a dispatch-time top-up) so sub-hop
        dribbles accumulate host-side but every completed hop ships
        immediately: by dispatch time the backlog is already device-resident
        and the step gathers its lanes in place instead of round-tripping
        through a host staging buffer. No-op without ``ingest_ring``.
        """
        if self._ring_depth is None:
            return
        hop, R = self.cfg.hop, self._ring_depth
        ring = self._rings[slot]
        n = min(len(ring) // hop, R - int(self._ring_count[slot]))
        if n <= 0:
            return
        with obs.span("ring_write"):
            block = np.zeros((R, hop), np.float32)
            block[:n] = ring.pop(n * hop).reshape(n, hop)
            start = (int(self._ring_start[slot]) + int(self._ring_count[slot])) % R
            self._ring_arr = _ring_write(self._ring_arr, slot, start, block, n)
        self._ring_count[slot] += n

    def _backlog_hops(self, slot: int) -> int:
        """Whole hops queued for this slot (host ring + device ring)."""
        n = len(self._rings[slot]) // self.cfg.hop
        if self._ring_depth is not None:
            n += int(self._ring_count[slot])
        return n

    def observation(self) -> SchedulerObservation:
        """Snapshot the scheduler-relevant pool state as pure data.

        Everything an ``AdaptiveScheduler`` decision depends on is captured
        explicitly here, so a recorded (observation, decision) trace replays
        to the same decisions (``AdaptiveScheduler.replay``) — the
        determinism seam the scheduler tests drive. Backlogs count whole
        hops wherever they live (host ring + device ring); headrooms are
        present only under ``max_unread_hops``.
        """
        backlogs: List[int] = []
        headrooms: List[int] = []
        bounded = self._max_unread_hops
        for slot, sess in enumerate(self._slot_session):
            if sess is None:
                continue
            backlogs.append(self._backlog_hops(slot))
            if bounded is not None:
                headrooms.append(bounded - self._unread_hops(slot))
        return SchedulerObservation(
            backlogs=tuple(backlogs),
            headrooms=tuple(headrooms) if bounded is not None else None,
            num_active=self.num_active,
            capacity=self.capacity,
        )

    @obs.spanned("dispatch")
    def dispatch(self, max_hops: Optional[int] = None) -> int:
        """Launch ONE batched (multi-)hop step without waiting for its result.

        Pops up to ``hops_per_step`` whole hops from every backlogged session
        into one packed staging buffer, ships it to the pool's device in a
        single transfer, enqueues the jit step, and records the in-flight
        output for a later ``collect()``. Because JAX dispatch is
        asynchronous, this returns as soon as the work is enqueued — a router
        can dispatch every shard before blocking on any of them, overlapping
        all devices' work (``ShardedSessionPool.pump_all``), and a pool built
        with ``inflight=2`` can keep dispatching while its previous step is
        still on the device (double-buffered ingestion: the host fills the
        staging buffer for step k+1 while the device computes step k).

        When the pipeline is already ``inflight`` deep, the oldest step is
        collected first (so a pool never holds more than ``inflight`` steps,
        and a staging buffer is never refilled under an in-flight step).

        Sessions whose unread output has reached ``max_unread_hops`` are
        *parked* and skipped — the backpressure bound on ``_out`` (see the
        constructor); with ``hops_per_step > 1`` a session near the bound is
        clipped to its remaining headroom rather than skipped outright.

        Args:
            max_hops: cap on hops drained per session by THIS dispatch
                (1 <= max_hops <= ``hops_per_step``; default = the full
                compiled ceiling). This is the adaptive scheduler's seam: a
                controller picks the lane count per dispatch from measured
                backlog, so an idle pool pays the cheap K=1 step and only
                lagging sessions buy deep fused lanes. Each distinct value
                uses a lazily built per-lane-count step (``step_fns``
                shares the cache across pools); running k lanes is
                bit-identical to the full-K step with counts <= k.

        Returns:
            Total hops included in the launched step across all sessions
            (0 = nothing ready, no compute enqueued; at ``hops_per_step=1``
            this is exactly the number of sessions stepped). Starved/empty
            slots and idle scan lanes are masked inside the step: their
            state is kept bit-for-bit.

        Raises:
            ValueError: ``max_hops`` outside ``[1, hops_per_step]``.
            InjectedFaultError: the pool's ``FaultPlan`` scheduled a step
                crash for this dispatch. Raised BEFORE any input is
                consumed, so the call is side-effect-free and a router can
                retry or fail the shard over without losing audio.
        """
        if self._faults is not None and self._faults.step_error(self._fault_tag):
            raise InjectedFaultError(
                f"injected dispatch fault ({self._fault_tag})"
            )
        while len(self._pending) >= self._inflight:
            self._collect_one()
        hop = self.cfg.hop
        k = self.hops_per_step if max_hops is None else max_hops
        if not 1 <= k <= self.hops_per_step:
            raise ValueError(
                f"max_hops must be in [1, hops_per_step="
                f"{self.hops_per_step}], got {k}"
            )
        brownout = self._brownout
        if brownout >= 1:
            # level 1+: clamp the fused depth — shed the throughput lever
            # first, keep per-stream latency and fairness
            k = 1
        browned_out = frozenset()
        if brownout >= 2:
            # level 2+: park the lowest-backlog half of the backlogged
            # streams for this dispatch — serve the streams that are
            # furthest behind, let the rest absorb the overload in their
            # own ring buffers
            backlogged = sorted(
                (self._backlog_hops(s.slot), s.slot)
                for s in self._sessions.values()
                if self._backlog_hops(s.slot) > 0
            )
            browned_out = frozenset(
                slot for _, slot in backlogged[: len(backlogged) // 2]
            )
        use_ring = self._ring_depth is not None
        buf = None if use_ring else self._hop_bufs[self._buf_i]
        counts = np.zeros((self.capacity,), np.int32)
        starts = np.zeros((self.capacity,), np.int32)
        bounded = self._max_unread_hops
        for slot, sess in enumerate(self._slot_session):
            if sess is None or slot in browned_out:
                continue
            if use_ring:
                self._fill_ring(slot)  # top up lanes freed since the feed
                avail = int(self._ring_count[slot])
            else:
                avail = len(self._rings[slot]) // hop
            take = min(avail, k)
            if take == 0:
                continue
            if bounded is not None:
                headroom = bounded - self._unread_hops(slot)
                if headroom < take:
                    take = max(headroom, 0)
                if take == 0:
                    # parked: reader is behind, keep audio in the ring until
                    # a read() drains the queue (which un-parks + wakes up)
                    self._parked[slot] = True
                    continue
            if use_ring:
                # consume in place: advance the FIFO cursor, no host staging
                starts[slot] = int(self._ring_start[slot])
                self._ring_start[slot] = (
                    int(self._ring_start[slot]) + take
                ) % self._ring_depth
                self._ring_count[slot] -= take
            elif buf.ndim == 2:
                buf[slot] = self._rings[slot].pop(hop)
            else:
                buf[slot, :take] = self._rings[slot].pop(take * hop).reshape(take, hop)
            counts[slot] = take
        n_hops = int(counts.sum())
        if n_hops == 0:
            return 0

        # K=1 steps take the (B,) bool active mask; fused steps take the
        # (B,) int hop_counts vector driving the per-lane scan masks
        lanes = counts.astype(bool) if k == 1 else counts
        # level 3: terminal brownout — serve the model-free passthrough
        # step (unenhanced but real-time audio, tagged degraded) instead of
        # going silent under a load the model step can no longer sustain
        step = self._step_for(k, passthrough=brownout >= 3)
        if brownout:
            self.brownout_hops += n_hops
        self.steps += 1
        self.hops_stepped += n_hops
        self.lanes_offered += self.capacity * k
        t0 = time.perf_counter()
        if use_ring:
            if self.device is not None:
                starts_d = jax.device_put(starts, self.device)
                act = jax.device_put(lanes, self.device)
            else:
                starts_d, act = jnp.asarray(starts), jnp.asarray(lanes)
            self._state, out = step(self._state, self._ring_arr, starts_d, act)
        else:
            self._buf_i = (self._buf_i + 1) % len(self._hop_bufs)
            # narrow the staged view to k lanes so the per-lane-count step
            # sees its own shape; lane data beyond each slot's count is
            # stale garbage, masked inside the step
            view = buf if buf.ndim == 2 else (buf[:, 0] if k == 1 else buf[:, :k])
            if self.device is not None:
                hops = jax.device_put(view, self.device)
                act = jax.device_put(lanes, self.device)
            else:
                hops, act = jnp.asarray(view), jnp.asarray(lanes)
            self._state, out = step(self._state, hops, act)
        if self._faults is not None:
            inj = self._faults.poison_slots(
                self._fault_tag, [int(s) for s in np.flatnonzero(counts)]
            )
            if inj:
                out, self._state = self._inject_poison(inj, out)
        finite = None
        if self._finite_guard:
            finite = _finite_slots(self._state, out)
        self._pending.append(
            _Pending(
                out=out, counts=counts, t0=t0, finite=finite,
                degraded=brownout >= 3,
            )
        )
        return n_hops

    def _inject_poison(self, inj, out):
        """Apply a ``FaultPlan``'s NaN injection to a just-launched step.

        Returns the (possibly poisoned) ``(out, state)`` pair. Poisoning
        the OUTPUT models a transiently-corrupt frame; poisoning the
        CARRIED STATE models the sticky failure mode — a blown recurrent
        accumulator that would otherwise corrupt every future hop.
        """

        def mask_for(slots):
            m = np.zeros((self.capacity,), bool)
            m[list(slots)] = True
            return (
                jax.device_put(m, self.device)
                if self.device is not None
                else jnp.asarray(m)
            )

        state = self._state
        if inj.poison_out:
            out = _nan_slots(out, mask_for(inj.poison_out))
        if inj.poison_state:
            state = _nan_slots(state, mask_for(inj.poison_state))
        return out, state

    def _mark_ready(self, pending: _Pending) -> None:
        """Block on one step and record its latency WITHOUT pipeline wait.

        Under ``inflight > 1`` a step is dispatched while its predecessor is
        still on the device, so dispatch→ready would double-count the
        predecessor's runtime. Each step is therefore charged from
        ``max(its dispatch, previous step ready)`` — summed ``dt`` over a
        pipelined pump equals actual device occupancy, and with
        ``inflight=1`` this reduces exactly to dispatch→ready.
        """
        if pending.dt is not None:
            return
        jax.block_until_ready(pending.out)
        t = time.perf_counter()
        pending.dt = t - max(pending.t0, self._last_ready_t)
        pending.ready = self._last_ready_t = t

    @obs.spanned("wait_ready")
    def wait_ready(self) -> None:
        """Block until every in-flight step's output is ready (no accounting).

        Records each step's pipeline-corrected latency for the later
        ``collect()``. A router calls this on every shard before collecting
        any of them, so each shard's recorded step latency is its own
        completion time — not inflated by the host-side work of draining the
        other shards.
        """
        for pending in self._pending:
            self._mark_ready(pending)

    def _collect_one(self, proc_share: Optional[float] = None) -> int:
        """Drain the OLDEST in-flight step; returns its hop count.

        One readback delivers up to ``hops_per_step`` enhanced hops per slot
        (lane k of the fused output is slot b's k-th hop — contiguous audio
        once flattened)."""
        if not self._pending:
            return 0
        pending = self._pending.pop(0)
        self._mark_ready(pending)
        with obs.span("readback"):
            out = np.asarray(pending.out)
            # the finite-guard verdict is a (B,) bool computed on-device at
            # dispatch time; materializing it here amortizes the readback
            # into the output transfer the collect already pays for
            finite = None if pending.finite is None else np.asarray(pending.finite)
        self.step_seconds.append(pending.dt)
        with obs.span("deliver"):
            return self._deliver(pending, out, finite, proc_share)

    def _deliver(self, pending: _Pending, out: np.ndarray,
                 finite: Optional[np.ndarray], proc_share: Optional[float]) -> int:
        """Queue one read-back step's hops for their sessions; returns its
        hop count."""
        n_hops = int(pending.counts.sum())
        max_c = int(pending.counts.max())
        # lane-occupancy cost split: a fused dispatch's wall time scales
        # with its DEEPEST lane (the scan runs max(counts) lanes for every
        # live slot), not with total hops — a flat per-hop share over-bills
        # shallow slots whenever counts vary per slot. Each of the max_c
        # lanes costs total/max_c, split evenly among the slots still live
        # in it. Uniform counts reduce exactly to the per-hop scheme, and
        # the slot shares always sum to the full step cost, so the router's
        # round-wall conservation (see collect) is preserved.
        total = pending.dt if proc_share is None else proc_share * n_hops
        lane_occ = [int((pending.counts > j).sum()) for j in range(max_c)]
        lane_cost = total / max_c if max_c else 0.0
        stamped = [] if obs.recording() else None
        for slot in np.flatnonzero(pending.counts):
            c = int(pending.counts[slot])
            sess = self._slot_session[slot]
            if sess is None:
                # quarantined by an earlier pending step in this same
                # collect: the slot is free, suppress this output too
                continue
            if finite is not None and not bool(finite[slot]):
                # poison containment: suppress THIS slot's output (it is
                # non-finite — it must never reach a reader) and detach the
                # session into quarantine. Neighbouring slots proceed
                # normally below: the hop math is row-independent, so the
                # guard's per-slot verdict is exactly the blast radius.
                self._quarantine(sess)
                continue
            if pending.degraded:
                self._degraded_unread[slot] = True
            if out.ndim == 3:  # fused (B, K, hop): keep only the live lanes
                self._out[slot].append(out[slot, :c].reshape(-1))
            else:
                self._out[slot].append(out[slot])
            if stamped is not None:
                stamped.append((slot, sess.stats.hops, c))
            sess.stats.hops += c
            sess.stats.proc_seconds += lane_cost * sum(
                1.0 / lane_occ[j] for j in range(c)
            )
        if stamped:
            now = time.perf_counter_ns()
            for slot, before, c in stamped:
                self._ledger.delivered(slot, before, c, int(pending.t0 * 1e9),
                                       int(pending.ready * 1e9), now)
        return n_hops

    def _quarantine(self, sess: Session) -> None:
        """Detach a poisoned session into quarantine (finite-guard path).

        The slot is freed immediately (the next ``attach`` zeroes it via
        ``reset_slots``, so NaN left in the freed slice can never leak —
        inactive slots are masked inside the step and never read). Unread
        output is dropped along with the poisoned step's: nothing that was
        queued has been acked, and durability replay regenerates it. The
        durable files are RELEASED, not deleted — the recovery seam.

        ``good_hops``/``good_samples_in`` are the session's counters at
        detection time: ``stats.hops`` has NOT been advanced for the
        poisoning step, so they mark the last state proven finite.
        """
        slot = sess.slot
        did = self._durable_ids.pop(sess.sid, None)
        rec = QuarantineRecord(
            sid=sess.sid,
            session=sess,
            durable_id=did,
            good_hops=sess.stats.hops,
            good_samples_in=sess.stats.hops * self.cfg.hop,
            stats=dataclasses.replace(sess.stats),
            message="non-finite output/state detected by the finite guard",
        )
        sess.detached = True
        self._slot_session[slot] = None
        del self._sessions[sess.sid]
        self._rings[slot] = _RingBuffer()
        self._out[slot] = []
        self._parked[slot] = False
        self._degraded_unread[slot] = False
        if self._ring_depth is not None:
            self._ring_start[slot] = 0
            self._ring_count[slot] = 0
        if did is not None and self._durability is not None:
            self._durability.release(did)  # keep the files: recovery needs them
        self._quarantined[sess.sid] = rec
        self._fresh_quarantined.append(rec)
        self.quarantined_count += 1

    @property
    def quarantined(self) -> Dict[int, QuarantineRecord]:
        """sid -> ``QuarantineRecord`` for every session the guard detached."""
        return dict(self._quarantined)

    def take_quarantined(self) -> List[QuarantineRecord]:
        """Pop the records quarantined since the last call (router harvest).

        The records stay queryable via ``quarantined``; this drains only
        the fresh-events queue so an outer layer (elastic pool, sharded
        router) can translate each record to ITS handle exactly once.
        """
        fresh, self._fresh_quarantined = self._fresh_quarantined, []
        return fresh

    def clear_quarantined(self, sid: Optional[int] = None) -> None:
        """Forget quarantine record(s) (after recovery, or to re-use a sid's
        diagnostics slot); ``None`` clears all."""
        if sid is None:
            self._quarantined.clear()
        else:
            self._quarantined.pop(sid, None)

    def set_brownout(self, level: int) -> None:
        """Set the graceful-degradation level for subsequent dispatches.

        The ladder (normally walked by the adaptive scheduler's
        ``decide()`` under sustained overload or open breakers, see
        ``repro.serve.scheduler``):

        - 0 — full service.
        - 1 — clamp the fused depth to ``max_hops=1`` (shed the
          throughput amplifier, keep fairness and latency).
        - 2 — additionally park the lowest-backlog half of the backlogged
          streams each dispatch (serve whoever is furthest behind).
        - 3 — passthrough: serve the model-free analysis→synthesis hop.
          Audio keeps flowing in real time but UNENHANCED, and every
          ``read_degraded`` containing such audio is flagged (the gateway
          tags the READ reply) — degraded beats silent.

        Levels clamp to [0, 3]; ``brownout_hops`` counts every hop served
        at any non-zero level.
        """
        self._brownout = max(0, min(3, int(level)))

    @property
    def brownout(self) -> int:
        return self._brownout

    def read_degraded(self, sess: Session) -> Tuple[np.ndarray, bool]:
        """``read()`` plus a brownout flag for the returned audio.

        Returns ``(samples, degraded)`` where ``degraded`` is True iff any
        of the returned samples were produced by the brownout passthrough
        step (unenhanced audio). Empty reads are never flagged. The gateway
        uses this to answer READ with the tagged degraded-audio frame.
        """
        self._check(sess)
        self.collect()
        self._check(sess)  # collect may have quarantined this very session
        degraded = bool(self._degraded_unread[sess.slot])
        out = self.read(sess)
        return out, degraded and bool(out.size)

    def collect(self, proc_share: Optional[float] = None) -> int:
        """Block on every in-flight step (if any) and distribute the output.

        Args:
            proc_share: mean per-HOP compute-seconds to charge for this step
                instead of the default (the step's own latency). A router
                passes ``round_wall / total_hops_stepped`` here so that
                summed ``proc_seconds`` across ALL shards equals the round's
                wall-clock — device work that overlapped is not
                double-counted into session RTFs. Either way the step's
                total cost is split across its slots by lane occupancy, not
                per hop (see ``_collect_one``): fused wall time follows the
                deepest lane, so shallow slots in a ragged dispatch are
                charged less than deep ones.

        Returns:
            Number of hops whose output was delivered (0 = nothing was in
            flight). Safe to call at any time; idempotent until the next
            ``dispatch()``.
        """
        if not self._pending:
            return 0
        total = 0
        with obs.span("collect"):
            while self._pending:
                total += self._collect_one(proc_share)
        return total

    def step(self) -> int:
        """Run ONE batched step over every session with a full hop queued.

        Equivalent to ``dispatch()`` + ``collect()`` back to back (the
        pipelined path is ``pump()``/raw ``dispatch()``, not ``step()``).

        Returns:
            The number of hops stepped (0 = nothing ready, no compute
            spent). Starved and empty slots are masked: their state is
            untouched.
        """
        n = self.dispatch()
        if n:
            self.collect()
        return n

    def pump(self, scheduler=None) -> int:
        """Dispatch until no session has a full (eligible) hop buffered.

        With ``inflight=1`` this is the classic serial loop; with
        ``inflight=2`` the ring-buffer drain for hop k+1 overlaps the device
        compute of hop k (double buffering). Either way every launched step
        is collected before returning.

        Args:
            scheduler: optional ``repro.serve.AdaptiveScheduler``. When
                given, every iteration snapshots ``observation()``, asks
                the controller for a decision, and dispatches with
                ``max_hops=decision.k`` (clamped to this pool's compiled
                ``hops_per_step`` ceiling) — deep fused lanes only when
                some session actually lags, the cheap K=1 fast path
                otherwise. Grow/shrink components of the decision are
                ignored here; the elastic pool acts on them.

        Returns total steps dispatched.
        """
        steps = 0
        while True:
            k = None
            if scheduler is not None:
                decision = scheduler.observe(self.observation())
                self.set_brownout(decision.brownout)
                k = min(decision.k, self.hops_per_step)
            if not self.dispatch(max_hops=k):
                break
            steps += 1
        self.collect()
        return steps

    # -- sharding seams: stats export + session migration -------------------

    def _prune_summary(self) -> Optional[Dict[str, Any]]:
        """Skip-rate + realized-sparsity counters for ``shard_stats``.

        The meta dict is filled by ``make_stream_hop`` when this pool
        compiles its first step; if every step so far came out of a shared
        ``step_fns`` cache (so this pool never compiled), the mask
        accounting — folding + masks only, no XLA compile — is rebuilt here.
        """
        if self._prune_keep is None or self._prune_keep >= 1.0:
            return None
        if not self._prune_meta:
            from repro.serve.deploy import build_deploy_plan

            plan = build_deploy_plan(
                self._params, self.cfg, prune_keep=self._prune_keep,
                prune_axis=self._prune_axis,
                prune_granularity=self._prune_granularity,
                prune_block=self._prune_block, use_pallas=False,
            )
            self._prune_meta.update(
                sparsity=plan.sparsity, skip_stats=plan.skip_stats,
                skip_granularity=plan.skip_granularity,
            )
        meta = self._prune_meta
        return {
            "keep": self._prune_keep,
            "granularity": self._prune_granularity,
            "axis": self._prune_axis,
            "skip_granularity": meta["skip_granularity"],
            "realized_keep": meta["sparsity"]["total"]["keep"],
            "realized_sparsity": meta["sparsity"]["total"]["sparsity"],
            "skip_rate": meta["skip_stats"]["total"]["skip_rate"],
            "skip_counters": {
                k: dict(v) for k, v in meta["skip_stats"].items() if k != "total"
            },
        }

    def shard_stats(self) -> Dict[str, object]:
        """Shard-local load counters, exported for a router to balance on.

        Returns:
            dict with ``capacity``, ``active``, ``free`` (slot headroom),
            ``hops`` (total hops enhanced for currently-live sessions),
            ``backlog_hops`` (full hops queued but not yet processed —
            the pressure signal), ``p50_ms`` (median dispatch→ready step
            latency), ``p99_ms``, and ``device`` (where this shard's state
            lives).
            Pruned pools additionally report ``prune``: requested keep,
            exact realized sparsity, and the masked-MAC skip-rate counters
            per masked weight.
        """
        backlog = sum(
            self._backlog_hops(slot)
            for slot, s in enumerate(self._slot_session)
            if s is not None
        )
        pct = self.latency_percentiles((50, 99))
        stats: Dict[str, object] = {
            "capacity": self.capacity,
            "active": self.num_active,
            "free": self.capacity - self.num_active,
            "hops": sum(s.stats.hops for s in self._sessions.values()),
            "backlog_hops": backlog,
            "p50_ms": pct[50],
            "p99_ms": pct[99],
            "device": str(self.device) if self.device is not None else "default",
            "backend": self.backend,
            "hops_per_step": self.hops_per_step,
            "quarantined": self.quarantined_count,
            "brownout": self._brownout,
            "brownout_hops": self.brownout_hops,
            **{k: getattr(self, k) for k in STEP_COUNTERS},
        }
        prune = self._prune_summary()
        if prune is not None:
            stats["prune"] = prune
        return stats

    def export_session(self, sess: Session) -> SessionTicket:
        """Snapshot a live session and release its slot (migration source).

        Extracts the session's slice of the batched recurrent state to host
        memory along with its queued input, unread output, and stats, then
        frees the slot exactly like ``detach`` (without dropping anything).
        Feed the ticket to another pool's ``import_session`` — same or
        different device — and the stream resumes bit-for-bit.

        Raises:
            SessionError: the handle is not live on this pool.
        """
        self._check(sess)
        self.collect()  # the snapshot must include any in-flight step
        self._check(sess)  # collect may have quarantined this very session
        slot = sess.slot
        state = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf[slot]), self._state)
        ring = self._rings[slot]
        parts: List[np.ndarray] = []
        if self._ring_depth is not None and int(self._ring_count[slot]):
            # drain the device ring in FIFO order back to host: the ticket's
            # pending_in must carry the full unprocessed backlog regardless
            # of where it was resident at export time
            R = self._ring_depth
            ring_host = np.asarray(self._ring_arr[slot])
            order = [
                (int(self._ring_start[slot]) + i) % R
                for i in range(int(self._ring_count[slot]))
            ]
            parts.append(ring_host[order].reshape(-1))
            self._ring_start[slot] = 0
            self._ring_count[slot] = 0
        if len(ring):
            parts.append(ring.pop(len(ring)))
        pending = np.concatenate(parts) if parts else np.zeros((0,), np.float32)
        chunks = self._out[slot]
        unread = np.concatenate(chunks) if chunks else np.zeros((0,), np.float32)
        sess.detached = True
        self._slot_session[slot] = None
        self._out[slot] = []
        del self._sessions[sess.sid]
        did = self._durable_ids.pop(sess.sid, None)
        if did is not None and self._durability is not None:
            # the stream lives on elsewhere: close handles, KEEP the files
            self._durability.release(did)
        return SessionTicket(
            state=state, pending_in=pending, unread_out=unread, stats=sess.stats,
            parked=bool(self._parked[slot]),
        )

    def snapshot_session(self, sess: Session) -> SessionTicket:
        """Snapshot a live session WITHOUT disturbing it (durability source).

        The non-destructive twin of ``export_session``: same
        ``SessionTicket``, but the session keeps serving — slot, rings,
        unread output, and cursors are all left exactly as they were. Any
        in-flight dispatch is collected first so the ticket is a consistent
        cut of the stream.

        Raises:
            SessionError: the handle is not live on this pool.
        """
        self._check(sess)
        self.collect()
        self._check(sess)  # collect may have quarantined this very session
        slot = sess.slot
        state = jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf[slot]), self._state
        )
        parts: List[np.ndarray] = []
        if self._ring_depth is not None and int(self._ring_count[slot]):
            R = self._ring_depth
            ring_host = np.asarray(self._ring_arr[slot])
            order = [
                (int(self._ring_start[slot]) + i) % R
                for i in range(int(self._ring_count[slot]))
            ]
            parts.append(ring_host[order].reshape(-1))
        host = self._rings[slot].peek()
        if host.size:
            parts.append(host)
        pending = np.concatenate(parts) if parts else np.zeros((0,), np.float32)
        chunks = self._out[slot]
        unread = (
            np.concatenate(chunks).copy() if chunks else np.zeros((0,), np.float32)
        )
        return SessionTicket(
            state=state,
            pending_in=pending,
            unread_out=unread,
            stats=dataclasses.replace(sess.stats),
            parked=bool(self._parked[slot]),
        )

    def discard_output(self, sess: Session, n: int) -> int:
        """Drop up to ``n`` enhanced samples from the FRONT of the session's
        unread output, as if a client had read them (recovery's fast-forward
        past audio the journal says was already delivered).

        Counts the dropped samples into ``stats.samples_out`` — the
        cumulative read cursor stays truthful — and un-parks the session
        when the drop takes it back below ``max_unread_hops``.

        Returns:
            Samples actually dropped (<= ``n``; limited by what is queued).
        """
        self._check(sess)
        if n <= 0:
            return 0
        self.collect()
        self._check(sess)  # collect may have quarantined this very session
        slot = sess.slot
        chunks = self._out[slot]
        dropped = 0
        while chunks and dropped < n:
            head = chunks[0]
            take = min(n - dropped, head.size)
            if take == head.size:
                chunks.pop(0)
            else:
                chunks[0] = head[take:]
            dropped += take
        sess.stats.samples_out += dropped
        if (
            self._parked[slot]
            and self._max_unread_hops is not None
            and self._unread_hops(slot) < self._max_unread_hops
        ):
            self._parked[slot] = False
            if self._on_unparked is not None:
                self._on_unparked(sess)
        return dropped

    def bind_durable(self, sess: Session, durable_id: str) -> None:
        """Adopt existing on-disk durable state for a live session (the
        recovery path's re-registration — unlike ``attach``, nothing is
        wiped; journaling RESUMES at the current segment)."""
        if self._durability is None:
            raise SessionError("pool has no durability manager")
        self._check(sess)
        self._durable_ids[sess.sid] = durable_id
        self._durability.resume(durable_id)

    def import_session(
        self, ticket: SessionTicket, durable_id: Optional[str] = None
    ) -> Session:
        """Resume an exported session in this pool (migration target).

        Claims a slot via ``attach`` and overwrites its zeroed state slice
        with the ticket's snapshot (host numpy → this pool's device), then
        restores the queued input, unread output, and accounting.

        Args:
            ticket: the exported session.
            durable_id: when the pool has a ``durability`` manager, resume
                journaling under this EXISTING durable identity (the files
                are kept, not wiped — migration continues the same crash
                journal). ``None`` imports the session without durability.

        Returns:
            A fresh ``Session`` handle for the resumed stream (new sid/slot;
            the exported handle stays dead).

        Raises:
            PoolFullError: this pool has no free slot.
        """
        sess = self._attach_slot()
        slot = sess.slot
        self._state = jax.tree_util.tree_map(
            lambda leaf, val: leaf.at[slot].set(val), self._state, ticket.state
        )
        if ticket.pending_in.size:
            self._rings[slot].push(ticket.pending_in)
            self._fill_ring(slot)
        if ticket.unread_out.size:
            self._out[slot] = [ticket.unread_out]
        sess.stats = ticket.stats
        self._parked[slot] = ticket.parked
        if durable_id is not None and self._durability is not None:
            self.bind_durable(sess, durable_id)
        return sess

    # -- reporting ----------------------------------------------------------

    def latency_percentiles(self, qs=(50, 95, 99)) -> Dict[int, float]:
        """Pool-step wall-clock percentiles in milliseconds."""
        if not self.step_seconds:
            return {q: 0.0 for q in qs}
        arr = np.asarray(self.step_seconds) * 1e3
        return {q: float(np.percentile(arr, q)) for q in qs}

    def report(self) -> str:
        hop = self.cfg.hop
        lines = [
            f"SessionPool(capacity={self.capacity}, active={self.num_active}, "
            f"quant={self.quant or 'fp32'})"
        ]
        pct = self.latency_percentiles()
        budget_ms = hop / self.sample_rate * 1e3
        lines.append(
            f"  step latency ms: p50={pct[50]:.2f} p95={pct[95]:.2f} "
            f"p99={pct[99]:.2f} (hop budget {budget_ms:.1f} ms)"
        )
        for sess in self._sessions.values():
            s = sess.stats
            lines.append(
                f"  session {sess.sid} slot {sess.slot}: {s.hops} hops, "
                f"rtf={s.rtf(self.sample_rate, hop):.3f}"
            )
        return "\n".join(lines)
