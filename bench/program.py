"""What the program records about itself (``repro.serve.obs``), for the
benchmark: the readers of its per-hop timeline and spans, and the reduction
of a profiler trace over its spans.

A program without ``repro.serve.obs`` (commits before it) records nothing:
every reader then returns None and the reduction attributes idle time over
the benchmark's own span names alone.

The program records while a profiler trace is captured, so in a traced run
(``run.py --trace 1``) what ``obs`` keeps is the traced second.

Reduction (``reduce``), over the traced window, from the ``.xplane.pb``:

- ``clock``: the check that host spans and device events share a clock.
  Each served step's ``XLA Modules`` event (``STEP_MODULES``) must start after
  the ``dispatch`` span that launched it starts and end before the
  ``wait_ready`` span after it ends: the (dispatch, wait_ready) pair that
  started last before the step must contain it. ``offset_ns`` is the device
  clock's lead over the host's that puts the most steps inside their pairs
  (0 where 0 does as well as any); ``inside``/``inside_after`` count the
  steps inside their pairs before and after that correction.
- ``idle_by_program_span``: device idle time, moved by ``offset_ns``, split
  at every span boundary: each piece goes to the innermost (latest started)
  span of the program or the benchmark open over it, else ``no_span``; mean
  over chips. Unlike ``tracing``'s ``idle_by_span``, which gives each whole
  gap to the span open at its middle, a step's own idle time stays with the
  step when the gap runs on into the idle time between steps.
- ``host_bound_idle_pct``: the idle share of the window not under
  ``loop_wait``, that is, with the host busy rather than waiting for traffic.
"""

from __future__ import annotations

import bisect
import heapq
import importlib
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

import tracing


# the served step's XLA module: ``jit_step``, or ``jit_masked`` where the
# step is the one-hop masked step itself (K = 1, no ingest ring)
STEP_MODULES = ("jit_step(", "jit_masked(")


def obs():
    """The program's ``repro.serve.obs``, or None where it has none."""
    try:
        return importlib.import_module("repro.serve.obs")
    except ImportError:
        return None


def hop_part_ms(part: str) -> Optional[float]:
    """Median over the recorded hops of one timeline part, in ms."""
    o = obs()
    hops = o.hop_times() if o is not None else []
    return float(np.median([getattr(h, part) for h in hops])) / 1e6 if hops else None


def span_ms(name: str) -> Optional[float]:
    """Median duration of the recorded spans named ``name``, in ms."""
    o = obs()
    got = [s.end_ns - s.start_ns for s in (o.spans() if o is not None else [])
           if s.name == name]
    return float(np.median(got)) / 1e6 if got else None


def span_names() -> Tuple[str, ...]:
    o = obs()
    extra = o.SPAN_NAMES if o is not None else ()
    return tuple(dict.fromkeys(tracing.SPAN_NAMES + tuple(extra)))


def _outermost(spans: List[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Drop a span nested in an open span of the same name (the benchmark's
    wrapper and the program's own span around one call)."""
    out, open_end = [], {}
    for a, b, name in sorted(spans):
        if a < open_end.get(name, float("-inf")):
            continue
        out.append((a, b, name))
        open_end[name] = b
    return out


def step_pairs(spans) -> List[Tuple[float, float]]:
    """(dispatch start, wait_ready end) of each waited-for step: each
    ``wait_ready`` with the last ``dispatch`` that started before it."""
    pairs, last = [], None
    for a, b, name in sorted(spans):
        if name == "dispatch":
            last = a
        elif name == "wait_ready" and last is not None:
            pairs.append((last, b))
            last = None
    return pairs


def clock_check(modules, pairs) -> Dict[str, float]:
    """How many step modules lie inside a (dispatch, wait_ready) pair, as
    recorded and with the device clock's lead over the host's that puts the
    most inside (see the module doc). Pairs do not overlap (one shard)."""
    pairs = sorted(pairs)
    starts = [p0 for p0, _ in pairs]

    def inside(lead):
        n = 0
        for m0, m1 in modules:
            i = bisect.bisect_right(starts, m0 - lead) - 1
            n += i >= 0 and m1 - lead <= pairs[i][1]
        return n

    # a lead in [m1 - p1, m0 - p0] puts a step inside pair p: try the bounds
    # of the pairs that start near each step
    candidates = [0.0]
    for m0, m1 in modules:
        i = bisect.bisect_right(starts, m0)
        for p0, p1 in pairs[max(0, i - 3):i + 3]:
            candidates += [float(m1 - p1), float(m0 - p0)]
    best = max(candidates, key=lambda s: (inside(s), s == 0.0, -abs(s)))
    return {"steps": len(modules), "inside": inside(0.0), "offset_ns": best,
            "inside_after": inside(best)}


def split_idle(gaps, spans) -> Dict[str, float]:
    """Seconds of the gaps under each span: every piece between span
    boundaries goes to the innermost span open over it, the latest started
    (of two that start together, the one that ends first), else
    ``no_span``. Gaps and spans in ns; gaps sorted and disjoint."""
    times = sorted({t for a, b, _ in spans for t in (a, b)} | {t for g in gaps for t in g})
    by_start = sorted(spans)
    out: Dict[str, float] = {}
    heap, si, gi = [], 0, 0  # open spans, innermost on top; closed ones popped late
    for t0, t1 in zip(times, times[1:]):
        while si < len(by_start) and by_start[si][0] <= t0:
            a, b, name = by_start[si]
            heapq.heappush(heap, (-a, b, name))
            si += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        while gi < len(gaps) and gaps[gi][1] <= t0:
            gi += 1
        if gi < len(gaps) and gaps[gi][0] <= t0:
            who = heap[0][2] if heap else "no_span"
            out[who] = out.get(who, 0.0) + (t1 - t0) / 1e9
    return out


def reduce(profile, chips: int = 1, window_span: str = "bench_window") -> Dict:
    """The traced window's idle time over the program's spans (module doc)."""
    names = set(span_names())
    devices, spans, window = [], [], None
    for plane in profile.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name in names:
                        spans.append(iv)
                    elif e.name == window_span and window is None:
                        window = iv[:2]
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    spans = _outermost(spans)
    ops, modules = {}, []
    for plane in devices:
        ops[plane.name] = []
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            if line.name == "XLA Ops":
                ops[plane.name] = evs
            elif line.name == "XLA Modules":
                modules += [(a, b) for a, b, nm in evs if nm.startswith(STEP_MODULES)]
    clock = clock_check(modules, step_pairs(spans))
    shift = clock["offset_ns"]
    if window is None:
        evs = [e for v in ops.values() for e in v]
        window = (min(e[0] for e in evs) - shift, max(e[1] for e in evs) - shift)
    w0, w1 = window
    idle: Dict[str, float] = {}
    busy = []
    for evs in ops.values():
        merged = tracing._union(tracing._clip(
            [(a - shift, b - shift) for a, b, _ in evs], w0, w1))
        busy.append(sum(b - a for a, b in merged) / 1e9)
        gaps, t = [], w0
        for a, b in merged:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if w1 > t:
            gaps.append((t, w1))
        for who, seconds in split_idle(gaps, spans).items():
            idle[who] = idle.get(who, 0.0) + seconds / max(1, len(ops))
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / max(1, len(busy))
    idle_s = window_s - busy_s
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_by_program_span": idle,
        "host_bound_idle_pct": 100.0 * (idle_s - idle.get("loop_wait", 0.0)) / window_s,
        "loop_wait_idle_pct": 100.0 * idle.get("loop_wait", 0.0) / window_s,
        "no_span_share_of_idle": idle.get("no_span", 0.0) / idle_s if idle_s > 0 else None,
        "clock": clock,
    }
