"""Streaming gateway: the serving fabric's network front door.

Everything below this module multiplexes streams *inside* one process; the
``StreamingGateway`` puts a real socket boundary in front of the pool, so
clients on other processes/hosts feed jittery, variable-sized chunks over
TCP and read enhanced audio back — the `Whisper-Streaming-TPU`-shaped
deployment the ROADMAP's cross-process item asks for. One asyncio event
loop owns the pool: connection handlers and the pump loop interleave only
at ``await`` points, so every pool call is atomic without locks.

The gateway owns a ``ShardedSessionPool`` and runs the serving heartbeat —
each tick is ``check_shards()`` (health-probe every shard, fail dead ones
over through wire tickets) followed by ``pump_all()`` (skip-dead batched
hop steps). A client session therefore survives shard death transparently:
its stream continues bit-exactly from a live shard (or, when the shard's
state is truly gone, its next request fails with a ``lost`` error and the
client re-attaches — bounded loss, never a hang).

Besides the timer, FEEDs drive ticks. A FEED queues its audio and is acked
at once; the first FEED of an event-loop turn schedules one deferred tick
(``loop.call_soon``), and later FEEDs of that turn join it, so every
session fed in the turn shares each batched step. A READ or DETACH of a
session that still holds whole, unstepped hops runs the pending tick
first: a read never misses audio its FEED was acked for.

Wire protocol (all integers little-endian): every frame is

    u32 payload_length | u8 type | payload

Client → gateway:

| type | name | payload |
|---|---|---|
| 1 | ATTACH | UTF-8 session id; empty = generate one. Re-attaching an id whose connection dropped ADOPTS the live session (continuation is bit-exact — unread output included) |
| 2 | FEED | raw float32 samples, any length ≥ 0 (acked once queued, before they are stepped) |
| 3 | READ | — (returns whatever is enhanced so far, possibly empty) |
| 4 | DETACH | — (returns the unread tail, frees the slot) |
| 5 | STATS | — (returns the pool's ``shard_stats()`` + failover totals) |

Gateway → client:

| type | name | payload |
|---|---|---|
| 0x81 | ATTACHED | UTF-8 session id actually attached/adopted |
| 0x82 | AUDIO | raw float32 enhanced samples (READ reply) |
| 0x83 | DETACHED | raw float32 unread tail (DETACH reply) |
| 0x84 | STATS_REPLY | UTF-8 JSON |
| 0x85 | BUSY | u32 retry-after ms + UTF-8 reason (ATTACH load-shed) |
| 0x86 | POISONED | UTF-8 JSON ``{message, good_hops, good_samples_in}`` — the session was quarantined (non-finite output/state); the gateway unbinds it, and re-ATTACHing the same id rolls the stream back to its last finite state when durability is on |
| 0x87 | AUDIO_DEGRADED | raw float32 samples, same as AUDIO, but some of them were produced by brownout level 3 (unenhanced passthrough) — the explicit "you are getting raw audio" tag |
| 0xFF | ERROR | UTF-8 message; the connection stays usable |

A connection owns at most one session at a time. Dropping the connection
WITHOUT detaching orphans the session: it keeps streaming (its ring keeps
draining, output queues under ``max_unread_hops`` backpressure) until a new
connection re-attaches the same id, or ``orphan_ttl`` pump ticks pass and
the gateway detaches it. That policy is what makes the chaos harness's
``drop_client`` op lossless for reconnecting clients.

``GatewayClient`` is the blocking reference client (examples, benchmarks,
tests); ``GatewayThread`` runs a gateway on a daemon event-loop thread so
single-process tests get a real localhost socket boundary.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import json
import logging
import random
import selectors
import socket
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.serve import obs
from repro.serve.faults import FaultPlan
from repro.serve.session_server import (
    STEP_COUNTERS,
    PoolFullError,
    SessionError,
    SessionPoisonedError,
)
from repro.serve.sharded_pool import ShardDownError

# client -> gateway
MSG_ATTACH = 1
MSG_FEED = 2
MSG_READ = 3
MSG_DETACH = 4
MSG_STATS = 5
# gateway -> client
MSG_ATTACHED = 0x81
MSG_AUDIO = 0x82
MSG_DETACHED = 0x83
MSG_STATS_REPLY = 0x84
MSG_BUSY = 0x85  # admission control: u32 retry-after ms + UTF-8 reason
MSG_POISONED = 0x86  # session quarantined: JSON {message, good_*} payload
MSG_AUDIO_DEGRADED = 0x87  # READ reply containing brownout passthrough audio
MSG_ERROR = 0xFF

_FRAME_SPANS = {
    MSG_ATTACH: "frame.attach", MSG_FEED: "frame.feed", MSG_READ: "frame.read",
    MSG_DETACH: "frame.detach", MSG_STATS: "frame.stats",
}
_HEADER = struct.Struct("<IB")
_BUSY_HEAD = struct.Struct("<I")
# one frame must hold minutes of fp32 audio but never an accidental gigabyte
MAX_FRAME_BYTES = 64 * 1024 * 1024

log = logging.getLogger(__name__)


class ProtocolError(RuntimeError):
    """Malformed gateway frame (bad type, oversized payload, truncation)."""


class GatewayBusyError(SessionError):
    """ATTACH load-shed by the gateway: no live shard has a slot right now.

    The typed form of admission control — a full (or fully dead) fleet
    answers ATTACH with a ``MSG_BUSY`` frame instead of a generic error, so
    clients can back off and retry instead of parsing strings.
    ``retry_after_ms`` is the gateway's retry hint.
    """

    def __init__(self, message: str, retry_after_ms: float) -> None:
        super().__init__(message)
        self.retry_after_ms = float(retry_after_ms)


def _frame(msg_type: int, payload: bytes = b"") -> bytes:
    if len(payload) > MAX_FRAME_BYTES:
        raise ProtocolError(
            f"frame payload {len(payload)} exceeds {MAX_FRAME_BYTES} bytes"
        )
    return _HEADER.pack(len(payload), msg_type) + payload


async def _read_frame(reader: asyncio.StreamReader) -> Tuple[int, bytes]:
    header = await reader.readexactly(_HEADER.size)
    length, msg_type = _HEADER.unpack(header)
    if length > MAX_FRAME_BYTES:
        raise ProtocolError(f"frame payload {length} exceeds {MAX_FRAME_BYTES}")
    return msg_type, await reader.readexactly(length)


class WaitSelector(selectors.DefaultSelector):
    """The event loop's selector, timing each wait for I/O or a timer as a
    ``loop_wait`` span while ``obs`` records: the gateway idle, waiting for
    traffic. A poll (``timeout=0``, callbacks already ready) is no wait."""

    def select(self, timeout=None):
        if timeout == 0 or not obs.recording():
            return super().select(timeout)
        with obs.span("loop_wait"):
            return super().select(timeout)


def new_event_loop() -> asyncio.AbstractEventLoop:
    """An asyncio loop whose waits show as ``loop_wait`` spans."""
    return asyncio.SelectorEventLoop(WaitSelector())


class StreamingGateway:
    """Asyncio TCP server owning a sharded pool and its pump/health loop.

    Args:
        pool: the ``ShardedSessionPool`` to serve (anything with the sharded
            surface works: ``attach(session_id)``,
            feed/read/detach/backlog_hops by handle, ``pump_all``;
            ``check_shards`` is used when present).
        host / port: bind address; port 0 (default) picks a free port —
            read the real one from ``.address`` after ``start()``.
        pump_interval: seconds between heartbeat ticks (health check +
            ``pump_all``). FEEDs also run a tick, deferred to the end of
            their event-loop turn (see the module doc), so interactive
            latency is not bound to the interval.
        orphan_ttl: pump ticks an orphaned session (connection dropped
            without DETACH) survives awaiting re-attach; ``None`` = forever.
        busy_retry_ms: the retry-after hint carried by ``MSG_BUSY`` when an
            ATTACH is load-shed (fleet full or every shard dead).
        faults: optional ``FaultPlan`` — its ``corrupt_frame`` hook mangles
            received frames BEFORE parsing (bad type / truncated / mis-sized
            payload), the deterministic stand-in for a hostile or broken
            client. The protocol layer must answer every mangled frame with
            a typed ERROR and keep both the connection and the pool alive.
    """

    def __init__(
        self,
        pool,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        pump_interval: float = 0.002,
        orphan_ttl: Optional[int] = None,
        busy_retry_ms: float = 50.0,
        faults: Optional[FaultPlan] = None,
    ) -> None:
        if pump_interval <= 0:
            raise ValueError("pump_interval must be > 0")
        if orphan_ttl is not None and orphan_ttl < 1:
            raise ValueError("orphan_ttl must be >= 1 (or None)")
        if busy_retry_ms < 0:
            raise ValueError("busy_retry_ms must be >= 0")
        self.pool = pool
        self._host = host
        self._port = port
        self.pump_interval = pump_interval
        self.orphan_ttl = orphan_ttl
        self.busy_retry_ms = busy_retry_ms
        self._faults = faults
        self.sessions_poisoned = 0  # MSG_POISONED frames sent
        self._server: Optional[asyncio.AbstractServer] = None
        self._pump_task: Optional[asyncio.Task] = None
        # the FEED tick scheduled for the end of this event-loop turn
        self._pending_tick: Optional[asyncio.Handle] = None
        # set when the heartbeat raised: every later request gets it as a
        # typed ERROR (clients fail now, not at their deadline) and stop()
        # re-raises it
        self._pump_error: Optional[BaseException] = None
        # open connections: Python >= 3.12's Server.wait_closed() waits for
        # every one of them, so stop() has to close them itself
        self._connections: Dict[asyncio.StreamWriter, asyncio.Task] = {}
        # session id -> live pool handle, for every gateway-attached session
        self._handles: Dict[str, object] = {}
        # session id -> ticks since its connection dropped (un-detached)
        self._orphans: Dict[str, int] = {}
        self.pump_ticks = 0  # heartbeat and FEED ticks
        self.feed_ticks = 0  # ticks run for FEEDs
        self.idle_ticks = 0  # ticks whose pump stepped nothing
        self.feed_frames = 0  # FEED frames acked
        self.orphans_reaped = 0
        self.load_shed = 0  # ATTACHes answered with MSG_BUSY
        self.frames_rejected = 0  # unsyncable frames that dropped a connection
        self.sessions_recovered_at_start = 0  # durable orphans from disk

    # -- lifecycle ----------------------------------------------------------

    @property
    def address(self) -> Tuple[str, int]:
        """(host, port) actually bound (valid after ``start()``)."""
        if self._server is None:
            raise RuntimeError("gateway is not started")
        sock = self._server.sockets[0]
        return sock.getsockname()[:2]

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle_connection, host=self._host, port=self._port
        )
        self._recover_durable_orphans()
        self._pump_task = asyncio.ensure_future(self._pump_loop())

    def _recover_durable_orphans(self) -> None:
        """Cold-restart recovery: a fresh gateway process pointed at a pool
        with a durability directory rebuilds every on-disk session before
        serving. Recovered sessions enter as ORPHANS (subject to the normal
        TTL), so their clients re-adopt by re-ATTACHing the same id — the
        stream continues at the exact byte their last acked read stopped at.
        """
        recover = getattr(self.pool, "recover_sessions", None)
        if recover is None:
            return
        for handle in recover():
            sid = str(handle.session_id)
            self._handles[sid] = handle
            self._orphans[sid] = 0
            self.sessions_recovered_at_start += 1

    async def stop(self) -> None:
        """Stop serving: cancel the pump loop, close the listener and every
        open connection.

        Raises:
            Exception: whatever killed the pump loop, after the shutdown
                completed (a gateway whose heartbeat died must not look
                like one that served cleanly).
        """
        self._cancel_pending_tick()
        if self._pump_task is not None:
            self._pump_task.cancel()
            try:
                await self._pump_task
            except asyncio.CancelledError:
                pass
            except Exception:
                pass  # kept in _pump_error by _tick; raised below
            self._pump_task = None
        if self._server is not None:
            self._server.close()
            for writer in list(self._connections):
                writer.close()
            handlers = list(self._connections.values())
            if handlers:
                await asyncio.wait(handlers)
            await self._server.wait_closed()
            self._server = None
        if self._pump_error is not None:
            raise self._pump_error

    # -- the serving heartbeat ---------------------------------------------

    def _tick(self, feed: bool = False) -> None:
        """One heartbeat: health-probe shards, pump until no whole hop is
        left, reap expired orphans. ``feed``: the tick FEEDs asked for, run
        at the end of their event-loop turn or by a READ that needs it; any
        tick serves them, so it takes the pending one off the loop.

        A heartbeat that raises kills the gateway: the error is logged once
        and kept, every later request is answered with it, and ``stop()``
        re-raises it.
        """
        self._cancel_pending_tick()
        try:
            with obs.span("tick.feed" if feed else "tick.heartbeat"):
                self._beat(feed)
        except Exception as e:
            if self._pump_error is None:
                log.exception("gateway heartbeat failed; failing every request")
                self._pump_error = e
            raise

    def _beat(self, feed: bool) -> None:
        check = getattr(self.pool, "check_shards", None)
        if check is not None:
            check()
        pump = getattr(self.pool, "pump_all", None) or self.pool.pump
        stepped = pump()
        self.pump_ticks += 1
        self.feed_ticks += feed
        self.idle_ticks += not stepped
        if self.orphan_ttl is None:
            return
        for sid in list(self._orphans):
            self._orphans[sid] += 1
            if self._orphans[sid] > self.orphan_ttl:
                del self._orphans[sid]
                handle = self._handles.pop(sid, None)
                if handle is not None:
                    try:
                        self.pool.detach(handle)
                    except SessionError:
                        pass  # already lost in a shard failure
                self.orphans_reaped += 1

    async def _pump_loop(self) -> None:
        while True:
            self._tick()
            await asyncio.sleep(self.pump_interval)

    def _cancel_pending_tick(self) -> None:
        if self._pending_tick is not None:
            self._pending_tick.cancel()
            self._pending_tick = None

    def _deferred_tick(self) -> None:
        """The FEED tick at the end of its event-loop turn."""
        try:
            self._tick(feed=True)
        except Exception:
            pass  # kept in _pump_error by _tick: later requests get it

    # -- per-connection protocol -------------------------------------------

    def _attach(self, requested: str) -> Tuple[str, object]:
        if requested and requested in self._handles:
            if requested not in self._orphans:
                raise SessionError(
                    f"session {requested!r} is attached on another live "
                    "connection"
                )
            # adoption: the stream kept running while the client was gone
            del self._orphans[requested]
            return requested, self._handles[requested]
        handle = self.pool.attach(requested or None)
        sid = str(handle.session_id)
        self._handles[sid] = handle
        return sid, handle

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        self._connections[writer] = asyncio.current_task()
        sid: Optional[str] = None
        try:
            while True:
                try:
                    msg_type, payload = await _read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    break  # client gone: orphan the session (finally below)
                except ProtocolError as e:
                    # an insane declared length: the byte stream can never
                    # be re-synchronized, so answer once and drop only this
                    # connection — the server and every other session live on
                    self.frames_rejected += 1
                    try:
                        writer.write(_frame(MSG_ERROR, str(e).encode("utf-8")))
                        await writer.drain()
                    except (ConnectionResetError, BrokenPipeError):
                        pass
                    break
                # the span ends with the reply written, before any await:
                # frames of other connections never nest inside it
                with obs.span(_FRAME_SPANS.get(msg_type, "frame.other"), sid):
                    sid = self._serve_frame(writer, msg_type, payload, sid)
                await writer.drain()
        except ConnectionError:
            pass  # client vanished mid-reply, or stop() closed the socket
        finally:
            self._connections.pop(writer, None)
            if sid is not None and sid in self._handles:
                self._orphans[sid] = 0  # keeps streaming until re-attach/TTL
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    def _serve_frame(
        self, writer: asyncio.StreamWriter, msg_type: int, payload: bytes,
        sid: Optional[str],
    ) -> Optional[str]:
        """Answer one frame; returns the connection's session id after it."""
        if self._faults is not None:
            # injected hostile client: mangle the frame pre-parse
            msg_type, payload = self._faults.corrupt_frame(msg_type, payload)
        try:
            reply = self._dispatch_msg(msg_type, payload, sid)
            sid = reply[2]
            writer.write(_frame(reply[0], reply[1]))
        except SessionPoisonedError as e:
            # the session was quarantined: a typed frame with the
            # rollback point, and the connection is unbound so the
            # client can re-ATTACH (rolling back via durability)
            self.sessions_poisoned += 1
            if sid is not None:
                self._handles.pop(sid, None)
                self._orphans.pop(sid, None)
                sid = None
            body = json.dumps(
                {
                    "message": str(e),
                    "good_hops": e.good_hops,
                    "good_samples_in": e.good_samples_in,
                }
            ).encode("utf-8")
            writer.write(_frame(MSG_POISONED, body))
        except (SessionError, ProtocolError, ValueError) as e:
            if sid is not None and sid not in self._handles:
                sid = None  # session lost to a shard failure: unbind
                # so this very connection can ATTACH a fresh stream
            writer.write(_frame(MSG_ERROR, str(e).encode("utf-8")))
        return sid

    def _dispatch_msg(
        self, msg_type: int, payload: bytes, sid: Optional[str]
    ) -> Tuple[int, bytes, Optional[str]]:
        """Handle one frame; returns (reply type, reply payload, new sid)."""
        if self._pump_error is not None:
            raise SessionError(
                f"gateway pump loop died: {self._pump_error!r}; the gateway "
                "is not serving"
            )
        if msg_type == MSG_ATTACH:
            if sid is not None:
                raise SessionError(
                    f"this connection already serves session {sid!r}; "
                    "DETACH first"
                )
            try:
                sid, _ = self._attach(payload.decode("utf-8"))
            except (PoolFullError, ShardDownError) as e:
                # admission control: a typed BUSY frame (retry-after hint +
                # reason) instead of a stringified capacity error
                self.load_shed += 1
                body = _BUSY_HEAD.pack(int(self.busy_retry_ms)) + str(e).encode(
                    "utf-8"
                )
                return MSG_BUSY, body, None
            return MSG_ATTACHED, sid.encode("utf-8"), sid
        if msg_type == MSG_STATS:
            stats = {
                "shards": self.pool.shard_stats(),
                "dead_shards": getattr(self.pool, "dead_shards", []),
                "sessions_failed_over": getattr(
                    self.pool, "sessions_failed_over", 0
                ),
                "sessions_lost": getattr(self.pool, "sessions_lost", 0),
                "lost_session_ids": [
                    str(s) for s in getattr(self.pool, "lost_session_ids", [])
                ],
                "pump_ticks": self.pump_ticks,
                "active": self.pool.num_active,
                "orphans": len(self._orphans),
                "load_shed": self.load_shed,
                "frames_rejected": self.frames_rejected,
                "sessions_recovered": getattr(
                    self.pool, "sessions_recovered", 0
                ),
                "sessions_recovered_at_start": self.sessions_recovered_at_start,
                "sessions_quarantined": getattr(
                    self.pool, "sessions_quarantined", 0
                ),
                "quarantined_ids": [
                    str(s) for s in getattr(self.pool, "quarantined", {})
                ],
                "breaker_opens": getattr(self.pool, "breaker_opens", 0),
                "watchdog_failovers": getattr(
                    self.pool, "watchdog_failovers", 0
                ),
                "sessions_poisoned": self.sessions_poisoned,
                "recovery_errors": [
                    [str(s), msg]
                    for s, msg in getattr(self.pool, "recovery_errors", [])
                ],
            }
            stats["trace"] = self._trace_stats(stats["shards"])
            sched_stats = getattr(self.pool, "scheduler_stats", None)
            if sched_stats is not None:
                scheds = sched_stats()
                if scheds is not None:  # adaptive fleet: expose the traces
                    stats["scheduler"] = scheds
            return MSG_STATS_REPLY, json.dumps(stats).encode("utf-8"), sid
        # everything below needs a live session on this connection
        if sid is None:
            raise SessionError("no session on this connection; ATTACH first")
        handle = self._handles.get(sid)
        if handle is None:
            raise SessionError(f"session {sid!r} is gone")
        if msg_type == MSG_FEED:
            if len(payload) % 4:
                raise ProtocolError(
                    f"FEED payload of {len(payload)} bytes is not float32"
                )
            self._guarded(sid, self.pool.feed, handle,
                          np.frombuffer(payload, np.float32))
            self.feed_frames += 1
            if self._pending_tick is None:
                # one tick at the end of this event-loop turn steps every
                # session fed in it, instead of one tick per FEED
                self._pending_tick = asyncio.get_running_loop().call_soon(
                    self._deferred_tick)
            return MSG_AUDIO, b"", sid
        if (msg_type in (MSG_READ, MSG_DETACH) and self._pending_tick is not None
                and self._guarded(sid, self.pool.backlog_hops, handle)):
            try:  # step the hops this session's FEEDs were acked for
                self._tick(feed=True)
            except Exception as e:
                raise SessionError(f"gateway heartbeat failed: {e!r}") from e
        if msg_type == MSG_READ:
            read_degraded = getattr(self.pool, "read_degraded", None)
            if read_degraded is not None:
                out, degraded = self._guarded(sid, read_degraded, handle)
                return (
                    MSG_AUDIO_DEGRADED if degraded else MSG_AUDIO,
                    np.asarray(out, np.float32).tobytes(),
                    sid,
                )
            out = self._guarded(sid, self.pool.read, handle)
            return MSG_AUDIO, np.asarray(out, np.float32).tobytes(), sid
        if msg_type == MSG_DETACH:
            tail = self._guarded(sid, self.pool.detach, handle)
            self._handles.pop(sid, None)
            self._orphans.pop(sid, None)
            return MSG_DETACHED, np.asarray(tail, np.float32).tobytes(), None
        raise ProtocolError(f"unknown message type {msg_type}")

    def _trace_stats(self, shards) -> Dict[str, object]:
        """STATS' ``trace``: tick counts by reason, each shard's step
        counters (zero on a dead shard), and a summary of what ``obs``
        keeps."""
        return {
            "ticks": {
                "feed": self.feed_ticks,
                "feed_frames": self.feed_frames,
                "heartbeat": self.pump_ticks - self.feed_ticks,
                "idle": self.idle_ticks,
            },
            "steps": [{k: s.get(k, 0) for k in STEP_COUNTERS} for s in shards],
            **obs.summary(),
        }

    def _guarded(self, sid: str, op, handle, *args):
        """Run a pool op; a stale handle re-binds through ``pool.lookup``
        (a loss+recovery cycle swaps the live handle underneath the
        gateway), and a session truly lost drops its gateway handle so the
        client's error is final."""
        try:
            return op(handle, *args)
        except SessionError:
            lookup = getattr(self.pool, "lookup", None)
            fresh = lookup(sid) if lookup is not None else None
            if fresh is not None and fresh is not handle:
                self._handles[sid] = fresh
                return op(fresh, *args)
            if sid in getattr(self.pool, "lost_session_ids", ()):
                self._handles.pop(sid, None)
                self._orphans.pop(sid, None)
            raise


class GatewayThread:
    """Run a ``StreamingGateway`` on its own daemon event-loop thread.

    The single-process stand-in for a gateway *process*: tests, examples,
    and benchmarks get a real localhost TCP boundary (real sockets, real
    frame protocol, the gateway's own pump loop) without managing a child
    process. All pool access stays on the gateway thread.

    Usage::

        gw = GatewayThread(pool)           # starts serving immediately
        host, port = gw.address
        ... GatewayClient(host, port) ...
        gw.stop()

    ``call(fn)`` runs ``fn(pool)`` ON the gateway thread (blocking for the
    result) — the chaos harness uses it to inject ``kill_shard`` without
    racing the pump loop.

    ``call_timeout`` bounds every blocking wait on the gateway thread
    (``call()`` results, ``stop()``'s shutdown and join): a wedged event
    loop surfaces as a ``TimeoutError`` naming the pending operation
    instead of a silent infinite hang.
    """

    def __init__(
        self, pool, *, gateway_cls=None, call_timeout: float = 60.0,
        **gateway_kwargs,
    ) -> None:
        # gateway_cls: a StreamingGateway subclass (fault-injecting test
        # gateways override _dispatch_msg to kill connections mid-request)
        if call_timeout <= 0:
            raise ValueError("call_timeout must be > 0")
        self.call_timeout = float(call_timeout)
        self.gateway = (gateway_cls or StreamingGateway)(pool, **gateway_kwargs)
        self._loop = new_event_loop()
        self._started = threading.Event()
        self._startup_error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="gateway", daemon=True
        )
        self._thread.start()
        self._started.wait()
        if self._startup_error is not None:
            raise self._startup_error

    def _run(self) -> None:
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(self.gateway.start())
        except BaseException as e:  # surface bind errors in the caller
            self._startup_error = e
            self._started.set()
            return
        self._started.set()
        self._loop.run_forever()
        # drain cancellations scheduled by stop()
        self._loop.run_until_complete(self._loop.shutdown_asyncgens())
        self._loop.close()

    @property
    def address(self) -> Tuple[str, int]:
        return self.gateway.address

    @property
    def pool(self):
        return self.gateway.pool

    def call(self, fn):
        """Run ``fn(pool)`` on the gateway thread; return its result.

        Raises:
            TimeoutError: the gateway thread did not produce a result
                within ``call_timeout`` seconds (wedged event loop); the
                error names the function that was pending.
        """
        fut = asyncio.run_coroutine_threadsafe(self._call_async(fn), self._loop)
        try:
            return fut.result(timeout=self.call_timeout)
        except concurrent.futures.TimeoutError as exc:
            fut.cancel()
            name = getattr(fn, "__name__", repr(fn))
            raise TimeoutError(
                f"gateway thread call {name!r} still pending after "
                f"{self.call_timeout}s — the event loop is wedged"
            ) from exc

    async def _call_async(self, fn):
        return fn(self.gateway.pool)

    def stop(self) -> None:
        """Shut the gateway down and join its thread.

        Raises:
            TimeoutError: the shutdown or the join outlived ``call_timeout``.
            Exception: what killed the gateway's heartbeat, if anything did
                (re-raised from ``StreamingGateway.stop`` after the thread
                has been joined).
        """
        if not self._thread.is_alive():
            return
        fut = asyncio.run_coroutine_threadsafe(self.gateway.stop(), self._loop)
        try:
            fut.result(timeout=self.call_timeout)
        except concurrent.futures.TimeoutError as exc:
            fut.cancel()
            raise TimeoutError(
                f"gateway stop() still pending after {self.call_timeout}s — "
                "the event loop is wedged mid-shutdown"
            ) from exc
        except Exception:
            self._join()  # the shutdown completed; only the heartbeat failed
            raise
        self._join()

    def _join(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=self.call_timeout)
        if self._thread.is_alive():
            raise TimeoutError(
                f"gateway thread did not join within {self.call_timeout}s "
                "after stop() completed"
            )


class GatewayClient:
    """Blocking, self-healing reference client for the gateway protocol.

    One TCP connection, one session: ``attach`` → ``feed`` (any chunk
    sizes) → ``read``/``read_until`` → ``detach``. ``drop()`` severs the
    connection WITHOUT detaching (the chaos harness's client-failure op);
    re-creating a client and attaching the same id resumes the stream with
    nothing lost.

    Resilience (each request, not just each connect):

    - **Per-request deadline** — every request gets ``timeout`` seconds of
      wall clock; each socket op runs with the REMAINING budget, so a
      request can never hang past its deadline no matter how many
      reconnects it burns. A blown deadline raises ``TimeoutError`` and is
      never blindly retried.
    - **Reconnect with capped exponential backoff + jitter** — a dropped /
      refused connection tears the socket down, sleeps
      ``min(backoff_cap, backoff_base * 2^attempt)`` scaled by a random
      jitter in [0.5, 1.5), reconnects, and retries the request, up to
      ``max_retries`` times within the deadline.
    - **Idempotent re-attach** — when a session is held, every reconnect
      first re-ATTACHes the same id: the gateway hands back the orphaned
      (or durably recovered) session, so the retried request lands on the
      same stream. At-most-once caveat: a FEED whose connection died after
      the gateway processed it but before the reply arrived is re-sent on
      retry — the gateway kills connections BEFORE processing in the
      failure modes tested here; exactly-once FEED needs an app-level
      sequence number.

    ``GatewayBusyError`` (typed ATTACH load-shed) is NOT retried by default
    — the caller owns admission backoff policy; ``retry_after_ms`` is the
    hint. Opt in with ``retry_busy=N``: the client then honors the BUSY
    frame's own ``retry_after_ms``, sleeping it (scaled by jitter in
    [0.5, 1.5) so a herd of shed clients does not retry in lockstep) and
    re-sending, up to N times within the request deadline.

    A ``MSG_POISONED`` reply raises ``SessionPoisonedError`` and clears
    ``session_id`` (the gateway unbound the quarantined session); attach
    the same id again to roll the stream back to its last finite state.
    ``read()`` sets ``last_degraded`` when the reply was
    ``MSG_AUDIO_DEGRADED`` (brownout passthrough audio).
    """

    def __init__(
        self,
        host: str,
        port: int,
        timeout: float = 30.0,
        *,
        max_retries: int = 4,
        backoff_base: float = 0.05,
        backoff_cap: float = 2.0,
        reconnect: bool = True,
        retry_busy: int = 0,
    ) -> None:
        if timeout <= 0:
            raise ValueError("timeout must be > 0")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if retry_busy < 0:
            raise ValueError("retry_busy must be >= 0")
        self._host = host
        self._port = int(port)
        self._timeout = float(timeout)
        self._max_retries = int(max_retries)
        self._backoff_base = float(backoff_base)
        self._backoff_cap = float(backoff_cap)
        self._auto_reconnect = bool(reconnect)
        self._retry_busy = int(retry_busy)
        self._rng = random.Random()
        self._closed = False
        self._sock: Optional[socket.socket] = None
        self.session_id: Optional[str] = None
        self.reconnects = 0  # successful re-connections (observability)
        self.last_degraded = False  # last read() carried brownout audio
        self._connect(time.monotonic() + self._timeout)

    # -- framing / transport -------------------------------------------------

    def _remaining(self, deadline: float) -> float:
        rem = deadline - time.monotonic()
        if rem <= 0:
            raise TimeoutError("gateway request deadline exceeded")
        return rem

    def _connect(self, deadline: float) -> None:
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._remaining(deadline)
        )

    def _teardown(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _recv_exact(self, n: int, deadline: float) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            self._sock.settimeout(self._remaining(deadline))
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("gateway closed the connection")
            buf += chunk
        return bytes(buf)

    def _raw_request(
        self, msg_type: int, payload: bytes, deadline: float
    ) -> Tuple[int, bytes]:
        """One attempt on the current socket (no reconnect, no retry)."""
        self._sock.settimeout(self._remaining(deadline))
        self._sock.sendall(_frame(msg_type, payload))
        length, reply_type = _HEADER.unpack(self._recv_exact(_HEADER.size, deadline))
        if length > MAX_FRAME_BYTES:
            raise ProtocolError(f"oversized reply frame ({length} bytes)")
        reply = self._recv_exact(length, deadline)
        if reply_type == MSG_ERROR:
            raise SessionError(reply.decode("utf-8"))
        if reply_type == MSG_BUSY:
            (retry_ms,) = _BUSY_HEAD.unpack_from(reply)
            raise GatewayBusyError(
                reply[_BUSY_HEAD.size :].decode("utf-8"), retry_ms
            )
        if reply_type == MSG_POISONED:
            info = json.loads(reply.decode("utf-8"))
            sid = self.session_id
            self.session_id = None  # the gateway unbound the session
            raise SessionPoisonedError(
                info.get("message", "session quarantined"),
                session_id=sid,
                good_hops=info.get("good_hops"),
                good_samples_in=info.get("good_samples_in"),
            )
        return reply_type, reply

    def _reconnect(self, deadline: float, reattach: bool) -> None:
        self._connect(deadline)
        self.reconnects += 1
        if reattach and self.session_id is not None:
            # re-adopt the orphaned session before resuming the stream —
            # idempotent: the gateway hands the same live session back
            rtype, reply = self._raw_request(
                MSG_ATTACH, self.session_id.encode("utf-8"), deadline
            )
            granted = reply.decode("utf-8")
            if rtype != MSG_ATTACHED or granted != self.session_id:
                raise SessionError(
                    f"re-attach after reconnect granted {granted!r} instead "
                    f"of {self.session_id!r}"
                )

    def _request(
        self, msg_type: int, payload: bytes = b"", timeout: Optional[float] = None
    ) -> Tuple[int, bytes]:
        deadline = time.monotonic() + (
            self._timeout if timeout is None else timeout
        )
        attempt = 0
        busy = 0
        while True:
            try:
                if self._sock is None:
                    if self._closed:
                        raise ConnectionError("client is closed")
                    self._reconnect(deadline, reattach=msg_type != MSG_ATTACH)
                return self._raw_request(msg_type, payload, deadline)
            except GatewayBusyError as e:
                if busy >= self._retry_busy:
                    raise
                # honor the gateway's own hint, jittered so a herd of shed
                # clients spreads out instead of retrying in lockstep
                delay = (e.retry_after_ms / 1000.0) * (0.5 + self._rng.random())
                if time.monotonic() + delay >= deadline:
                    raise
                time.sleep(delay)
                busy += 1
            except TimeoutError:
                raise  # the per-request deadline is final: no blind retry
            except (ConnectionError, OSError):
                self._teardown()
                if (
                    self._closed
                    or not self._auto_reconnect
                    or attempt >= self._max_retries
                ):
                    raise
                delay = min(
                    self._backoff_cap, self._backoff_base * (2**attempt)
                ) * (0.5 + self._rng.random())
                if time.monotonic() + delay >= deadline:
                    raise
                time.sleep(delay)
                attempt += 1

    # -- the chunked streaming surface --------------------------------------

    def attach(self, session_id: str = "") -> str:
        """Attach (or re-adopt) a session; returns the id actually granted."""
        _, reply = self._request(MSG_ATTACH, session_id.encode("utf-8"))
        self.session_id = reply.decode("utf-8")
        return self.session_id

    def feed(self, samples) -> None:
        """Ship raw audio (any length — dribbles or blobs) to the session."""
        arr = np.ascontiguousarray(np.asarray(samples, np.float32).reshape(-1))
        self._request(MSG_FEED, arr.tobytes())

    def read(self) -> np.ndarray:
        """Pop all enhanced audio the gateway has for this session.

        Sets ``last_degraded`` when the reply was ``MSG_AUDIO_DEGRADED`` —
        the gateway is under brownout level 3 and some of these samples are
        unenhanced passthrough audio."""
        rtype, reply = self._request(MSG_READ)
        self.last_degraded = rtype == MSG_AUDIO_DEGRADED
        return np.frombuffer(reply, np.float32).copy()

    def read_until(
        self, n_samples: int, timeout: float = 30.0, poll: float = 0.001
    ) -> np.ndarray:
        """Poll ``read`` until ``n_samples`` have arrived (or timeout).

        The deterministic way to collect a known-length stream: the caller
        fed N samples, so ``N // hop * hop`` enhanced samples must arrive.
        """
        chunks = []
        got = 0
        deadline = time.monotonic() + timeout
        while got < n_samples:
            chunk = self.read()
            if chunk.size:
                chunks.append(chunk)
                got += chunk.size
            elif time.monotonic() > deadline:
                raise TimeoutError(
                    f"read_until: {got}/{n_samples} samples after {timeout}s"
                )
            else:
                time.sleep(poll)
        out = np.concatenate(chunks) if chunks else np.zeros((0,), np.float32)
        if out.size > n_samples:
            raise ProtocolError(
                f"read_until: stream overshot ({out.size} > {n_samples})"
            )
        return out

    def detach(self) -> np.ndarray:
        """End the session; returns the unread tail."""
        _, reply = self._request(MSG_DETACH)
        self.session_id = None
        return np.frombuffer(reply, np.float32).copy()

    def stats(self) -> dict:
        """The gateway's shard/failover stats as a dict."""
        _, reply = self._request(MSG_STATS)
        return json.loads(reply.decode("utf-8"))

    def close(self) -> None:
        """Close politely (detach first if a session is still attached)."""
        try:
            if self.session_id is not None and self._sock is not None:
                self.detach()
        except (SessionError, TimeoutError, OSError, ConnectionError):
            pass
        self._closed = True
        self._teardown()

    def drop(self) -> None:
        """Sever the connection WITHOUT detaching — the session is orphaned
        on the gateway and resumable by ``attach(same_id)`` elsewhere.

        Also disables auto-reconnect on this client object: a dropped
        client stays dropped (the chaos harness relies on this)."""
        self._closed = True
        self._teardown()

    def __enter__(self) -> "GatewayClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
