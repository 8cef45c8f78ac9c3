"""The serving stack's instrumentation: spans and a per-hop timeline.

Recording is off by default. It is on while ``enable()`` is in effect, and
while a JAX profiler trace is being captured (``jax.profiler.start_trace``,
or a capture through a profiler server), so a profile of a serving process
always carries the serving spans. With it off, a span site costs one global
check and one query of the profiler's "is anyone tracing" flag: it reads no
clock and allocates nothing.

**Spans.** ``with obs.span(name, sid=None):`` enters
``jax.profiler.TraceAnnotation(name)``, so the span lands in the profile on
the clock the profiler gives the device's ``XLA Ops``, and keeps a
``SpanRecord`` (its sequence number, name, start and end in
``time.perf_counter_ns`` nanoseconds, the sequence number of the span open
around it on the same thread, and the session id) in a bounded buffer.
``SPAN_NAMES`` lists every name the program uses.

**Per-hop timeline.** Each ``SessionPool`` keeps a ``HopLedger``: per slot,
one ``(first hop, last hop, frame read, fed)`` entry per ``feed`` that
completed whole hops, and the stamps of the hops its steps delivered and no
``read`` has taken yet. A ``read`` hands those stamps to the outermost span
open on its thread (the gateway's frame span); when that span ends (the
reply written), each hop's parts go into ``hop_times()``:

    server = ingest + wait + step + collect + unread

- ingest: FEED frame read -> the ``feed`` that completed the hop returned;
- wait: -> its step dispatched;
- step: -> the step's output ready on the device;
- collect: -> the output read back and queued for the session;
- unread: -> the READ reply carrying the hop's last sample written.

Counters are not kept here: they are plain ints on the objects that own them
(``StreamingGateway.pump_ticks``, ``SessionPool.steps``, ...), always on.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

SPAN_NAMES = (
    # gateway
    "loop_wait",
    "frame.attach", "frame.feed", "frame.read", "frame.detach", "frame.stats",
    "frame.other",
    "tick.feed", "tick.heartbeat",
    # pool and dispatch (the first six are the names the benchmark's own
    # wrappers give the same calls)
    "pump_all", "dispatch", "wait_ready", "collect", "feed", "read",
    "readback", "deliver", "ring_write",
)
HOP_PARTS = ("ingest", "wait", "step", "collect", "unread", "server")
MAX_SPANS = 1 << 16
MAX_HOPS = 1 << 16

_forced = False
_profiling = TraceAnnotation.is_enabled
_NULL = contextlib.nullcontext()
_local = threading.local()
_seq = itertools.count()
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_hops: collections.deque = collections.deque(maxlen=MAX_HOPS)


class SpanRecord(NamedTuple):
    seq: int
    name: str
    start_ns: int
    end_ns: int
    parent: int  # seq of the span open around this one on its thread, or -1
    sid: Optional[str]


class HopTimes(NamedTuple):
    """One delivered hop's server time and its parts, in nanoseconds."""

    sid: Optional[str]
    ingest: int
    wait: int
    step: int
    collect: int
    unread: int
    server: int


class _HopStamp(NamedTuple):
    frame_ns: int
    fed_ns: int
    dispatch_ns: int
    ready_ns: int
    collect_ns: int


def enable() -> None:
    """Record spans and hop timelines until ``disable()``."""
    global _forced
    _forced = True


def disable() -> None:
    """Stop recording (a running profiler capture still records)."""
    global _forced
    _forced = False


def recording() -> bool:
    return _forced or _profiling()


def reset() -> None:
    """Drop every kept span and hop."""
    _spans.clear()
    _hops.clear()


def spans() -> List[SpanRecord]:
    return [SpanRecord(*s) for s in list(_spans)]  # list(): one atomic copy


def hop_times() -> List[HopTimes]:
    return list(_hops)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "sid", "seq", "parent", "start", "ann", "hops", "stack")

    def __init__(self, name: str, sid: Optional[str]) -> None:
        self.name, self.sid, self.hops = name, sid, None

    def __enter__(self) -> "_Span":
        stack = self.stack = _stack()
        self.parent = stack[-1].seq if stack else -1
        self.seq = next(_seq)
        stack.append(self)
        # the annotation matters only to a running profiler capture
        self.ann = TraceAnnotation(self.name) if _profiling() else None
        if self.ann is not None:
            self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        if self.stack and self.stack[-1] is self:
            self.stack.pop()
        _spans.append((self.seq, self.name, self.start, end, self.parent, self.sid))
        if self.hops:
            _finish(self.hops, end, self.sid)


def span(name: str, sid: Optional[str] = None):
    """A context manager timing one piece of work (see the module doc)."""
    if not (_forced or _profiling()):
        return _NULL
    return _Span(name, sid)


def spanned(name: str):
    """Decorate a function so each call is a span named ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def spanning(*args, **kwargs):
            if not (_forced or _profiling()):
                return fn(*args, **kwargs)
            with _Span(name, None):
                return fn(*args, **kwargs)

        return spanning

    return wrap


def _finish(stamps: List[_HopStamp], written_ns: int, sid: Optional[str]) -> None:
    for s in stamps:
        _hops.append(HopTimes(
            sid, s.fed_ns - s.frame_ns, s.dispatch_ns - s.fed_ns,
            s.ready_ns - s.dispatch_ns, s.collect_ns - s.ready_ns,
            written_ns - s.collect_ns, written_ns - s.frame_ns))


def _hand_over(stamps: List[_HopStamp]) -> None:
    """Hops a ``read`` returned: they end with the outermost open span
    (the frame whose reply carries them), or now when none is open."""
    stack = _stack()
    if not stack:
        _finish(stamps, time.perf_counter_ns(), None)
    elif stack[0].hops is None:
        stack[0].hops = stamps
    else:
        stack[0].hops.extend(stamps)


class HopLedger:
    """One pool's per-slot hop stamps (filled only while recording)."""

    def __init__(self, capacity: int) -> None:
        self._fed = [collections.deque() for _ in range(capacity)]
        self._done: List[List[_HopStamp]] = [[] for _ in range(capacity)]

    def clear(self, slot: int) -> None:
        """A slot changed hands: its stamps belong to no hop any more."""
        self._fed[slot].clear()
        self._done[slot].clear()

    def fed(self, slot: int, hops_before: int, hops_after: int) -> None:
        """A ``feed`` completed the slot's hops ``hops_before+1 .. hops_after``."""
        if hops_after > hops_before:
            stack = _stack()
            now = time.perf_counter_ns()
            frame = stack[0].start if stack else now
            self._fed[slot].append((hops_before, hops_after, frame, now))

    def delivered(self, slot: int, hops_before: int, n: int, dispatch_ns: int,
                  ready_ns: int, collect_ns: int) -> None:
        """A step delivered the slot's hops ``hops_before+1 .. hops_before+n``;
        hops fed while nothing recorded have no entry and are skipped."""
        fed, done = self._fed[slot], self._done[slot]
        for j in range(hops_before + 1, hops_before + n + 1):
            while fed and fed[0][1] < j:
                fed.popleft()
            if fed and fed[0][0] < j:
                done.append(_HopStamp(fed[0][2], fed[0][3], dispatch_ns,
                                      ready_ns, collect_ns))

    def read(self, slot: int) -> None:
        """The slot's output was read: its delivered hops leave with it."""
        done = self._done[slot]
        if done:
            self._done[slot] = []
            if recording():
                _hand_over(done)


def _percentiles_ms(values, qs=(50, 95, 99)) -> Dict[str, float]:
    if not len(values):
        return {}
    arr = np.asarray(values, np.float64) / 1e6
    return {f"p{q}": float(np.percentile(arr, q)) for q in qs}


def summary() -> Dict[str, object]:
    """What STATS reports of the kept records: counts, and percentiles of
    each hop part in milliseconds."""
    hops = list(_hops)
    return {
        "recording": recording(),
        "spans_kept": len(_spans),
        "hops_kept": len(hops),
        "hop_ms": {p: _percentiles_ms([getattr(h, p) for h in hops])
                   for p in HOP_PARTS} if hops else {},
    }
