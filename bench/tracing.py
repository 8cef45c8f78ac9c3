"""Host spans for traced runs, and the reduction of a profiler trace.

Spans: ``install_spans`` wraps the public calls into each serving layer on
the live pool objects with ``jax.profiler.TraceAnnotation``: the router's
``pump_all``, ``feed`` and ``read`` (the gateway answers READ through
``read_degraded``), and each shard's ``dispatch``, ``wait_ready`` and
``collect``. Only traced runs install them.

Reduction (``reduce``): from the ``.xplane.pb`` the profiler wrote, over the
traced window,

- busy seconds per chip: the union of the intervals of the device's
  "XLA Ops" events (nested ops count once);
- device ops by self time (an op's time minus that of the ops nested in it),
  grouped by HLO name without its numeric suffix;
- Pallas kernel calls (HLO custom calls named ``*_pallas``): their device
  time and operand and result shapes, parsed from the event name, which is
  the HLO instruction;
- idle time on the device, attributed to the innermost host span that was
  open at the middle of each gap.
"""

from __future__ import annotations

import functools
import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_NAMES = ("pump_all", "dispatch", "wait_ready", "collect", "feed", "read")
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def install_spans(pool) -> None:
    """Wrap the layer entry points of a sharded pool and its shards."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def traced(*a, **kw):
            with TraceAnnotation(name):
                return fn(*a, **kw)

        setattr(obj, attr, traced)

    wrap(pool, "pump_all", "pump_all")
    wrap(pool, "feed", "feed")
    wrap(pool, "read", "read")
    if hasattr(pool, "read_degraded"):
        wrap(pool, "read_degraded", "read")
    for shard in pool._pools:
        for attr in ("dispatch", "wait_ready", "collect"):
            wrap(shard, attr, attr)


def op_family(name: str) -> str:
    """``%dilated_split_conv_pallas.64 = ...`` -> ``dilated_split_conv_pallas``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def parse_shapes(instr: str) -> Tuple[List[Tuple[str, Tuple[int, ...]]], List[Tuple[str, Tuple[int, ...]]]]:
    """(results, operands) as (dtype, dims) lists, from an HLO instruction."""
    lhs, _, rhs = instr.partition(" = ")
    if "(" in rhs:
        res_txt, _, rest = rhs.partition(" custom-call(")
        ops_txt = rest.split("), custom_call_target")[0] if rest else ""
    else:
        res_txt, ops_txt = rhs, ""

    def shapes(txt):
        return [(dt, tuple(int(d) for d in dims.split(",") if d)) for dt, dims in _SHAPE.findall(txt)]

    return shapes(res_txt), shapes(ops_txt)


def nbytes(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _BYTES[dt]
    return total


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _attribute(gaps, spans):
    """(span name, seconds) per gap: the innermost span (latest started)
    open at the gap's middle, or ``no_span``. Gaps and spans sorted."""
    i, active = 0, []
    for a, b in gaps:
        mid = (a + b) / 2
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > mid]
        yield (max(active)[2] if active else "no_span"), (b - a) / 1e9


def load(path_or_dir: str):
    from jax.profiler import ProfileData

    if path_or_dir.endswith(".xplane.pb"):
        path = path_or_dir
    else:
        found = sorted(glob.glob(f"{path_or_dir}/**/*.xplane.pb", recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
        path = found[-1]
    return ProfileData.from_file(path)


def reduce(profile, window_ns: Tuple[float, float] | None = None, chips: int = 1,
           window_span: str | None = None) -> Dict:
    """The trace's numbers over ``window_ns``, or over the host span named
    ``window_span``, or else over the whole trace.

    Returns ``busy_s`` (mean over the first ``chips`` TPU planes),
    ``window_s``, ``ops`` (family -> self seconds, all chips), ``kernels``
    (one dict per Pallas call: family, seconds, result and operand shapes),
    ``idle_by_span`` (host span -> idle device seconds, mean over chips) and
    ``chips_seen``.
    """
    devices, host = [], []
    for plane in profile.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    spans = []
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPAN_NAMES or e.name == window_span:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    events = {}
    lo, hi = float("inf"), float("-inf")
    for plane in devices:
        evs = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
        events[plane.name] = evs
        if evs:
            lo = min(lo, min(e[0] for e in evs))
            hi = max(hi, max(e[1] for e in evs))
    if window_ns is None and window_span is not None:
        marks = [s for s in spans if s[2] == window_span]
        if marks:
            window_ns = marks[0][:2]
    if window_ns is None:
        window_ns = (lo, hi)
    w0, w1 = window_ns
    layer_spans = sorted(s for s in spans if s[2] in SPAN_NAMES)
    busy, ops, kernels = [], defaultdict(float), []
    idle = defaultdict(float)
    for name, evs in events.items():
        evs = [e for e in evs if e[1] > w0 and e[0] < w1]
        merged = _union(_clip([(a, b) for a, b, _ in evs], w0, w1))
        busy.append(sum(b - a for a, b in merged) / 1e9)
        # self time: subtract the time of ops nested directly inside an op
        stack: List[List] = []
        for a, b, nm in sorted(evs, key=lambda e: (e[0], -e[1])):
            while stack and stack[-1][1] <= a:
                top = stack.pop()
                ops[op_family(top[2])] += top[3]
            rec = [a, b, nm, min(b, w1) - max(a, w0)]
            if stack:
                stack[-1][3] -= rec[3]
            stack.append(rec)
            fam = op_family(nm)
            if (fam.endswith("_pallas") or fam.startswith("pallas_call")) and "custom-call(" in nm:
                res, opnds = parse_shapes(nm)
                kernels.append({"family": fam, "seconds": (min(b, w1) - max(a, w0)) / 1e9,
                                "results": res, "operands": opnds})
        for top in stack:
            ops[op_family(top[2])] += top[3]
        gaps, t = [], w0
        for a, b in merged:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if w1 > t:
            gaps.append((t, w1))
        for who, seconds in _attribute(gaps, layer_spans):
            idle[who] += seconds / max(1, len(devices))
    return {
        "busy_s": sum(busy) / max(1, len(busy)),
        "window_s": (w1 - w0) / 1e9,
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "kernels": kernels,
        "idle_by_span": dict(idle),
        "chips_seen": len(devices),
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The ``breakdown`` of a traced result line."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in idle]}
