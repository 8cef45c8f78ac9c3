"""Shared arithmetic of the metric readers under ``bench/metrics/``.

Each reader gets the run's context (``run.Context``) and returns a number,
or ``None`` when its run has nothing for it to read.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import flops
import hops


def window_s(ctx) -> float:
    t0, t1 = ctx.summary["window"]
    return t1 - t0


def hop_pct(ctx, q: float) -> Optional[float]:
    lat = ctx.summary["latency_ms"]
    return hops.percentile(lat, q) if lat else None


def audio_throughput(ctx) -> Optional[float]:
    n = ctx.summary["delivered_samples_in_window"]
    return n / ctx.sample_rate / window_s(ctx) if n else None


def step_seconds(ctx):
    return [s for shard in ctx.steps for s in shard]


def hops_per_step(ctx) -> Optional[float]:
    steps = len(step_seconds(ctx))
    n = ctx.summary["hops_delivered_in_window"]
    return n / steps if steps and n else None


def step_ms(ctx) -> Optional[float]:
    s = step_seconds(ctx)
    return float(np.median(s)) * 1e3 if s else None


def pump_ticks_per_hop(ctx) -> Optional[float]:
    n = ctx.summary["hops_delivered_in_window"]
    return ctx.pump_ticks / n if n else None


def device_idle_pct(ctx) -> Optional[float]:
    tr = ctx.trace
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu_pct(ctx) -> Optional[float]:
    n = ctx.summary["hops_delivered_in_window"]
    if not n:
        return None
    rate = n / window_s(ctx) * ctx.flops_per_hop
    return 100.0 * rate / (ctx.chips * ctx.peaks["flops_bf16"])


def roofline_pct(ctx, family: Optional[str] = None) -> Optional[float]:
    """Least time over device time of the traced Pallas calls (of one
    kernel family, or of all), in percent; None where none ran."""
    tr = ctx.trace
    calls = [k for k in (tr["kernels"] if tr else [])
             if family is None or k["family"] == family]
    if not calls:
        return None
    least = spent = 0.0
    for k in calls:
        ops, moved = flops.kernel_cost(k["family"], k["results"], k["operands"])
        least += max(ops / ctx.peaks["flops_bf16"], moved / ctx.peaks["hbm_bytes_per_s"])
        spent += k["seconds"]
    return 100.0 * least / spent if spent > 0 else None
