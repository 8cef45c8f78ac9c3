"""Multi-session server throughput: sessions x RTF curve, single or sharded.

Default mode sweeps the number of concurrent streams served by ONE
fixed-capacity ``SessionPool`` (one compiled batched hop step per backend,
no recompilation across sweep points — the server's core scaling property)
and reports, per point:

- aggregate RTF: total compute seconds per total audio seconds (< 1 means the
  whole batch is served in real time) and rt_capacity = 1 / aggregate RTF,
- per-session RTF (mean),
- pool step latency p50/p95 in ms against the 16 ms hop budget.

Three sweep axes compare the serving configurations this benchmark exists
for:

- ``--backend xla,pallas`` — the training graph lowered through XLA vs the
  deploy-compiled fused graph (``repro.serve.deploy``: BN folded, Pallas
  kernels). Off-TPU the Pallas kernels run in INTERPRET mode — correctness
  smoke, not a speed claim; sweep it on TPU for real numbers.
- ``--buffering single,double`` — classic serial pump vs double-buffered
  ingestion (``SessionPool(inflight=2)``: host ring drain overlaps the
  in-flight device step).
- ``--hops-per-step 1,4,8`` — multi-hop fused dispatch depth
  (``SessionPool(hops_per_step=K)``): how many hops each backlogged session
  drains per device call. K>1 amortizes the per-hop host->device->host +
  Python dispatch cost; the ``comparisons`` block reports the aggregate-RTF
  ratio of each K against K=1 (``hops{K}_vs_hops1``) — the speedup the
  fused path buys on this host.
- ``--transport inproc,socket`` — direct pool calls vs the cross-process
  fabric: socket points serve every session through a localhost
  ``StreamingGateway`` (real TCP, framed protocol, the gateway's own pump
  loop over a 1-shard ``ShardedSessionPool``), so ``socket_vs_inproc`` is
  the measured price of the network front door. Sessions-sweep mode only.
- ``--durability off,on`` — the crash-recovery tax: ``on`` points serve
  through a pool wired to a ``DurabilityManager`` (write-ahead hop journal
  on every feed, ticket snapshot every ``--snapshot-every`` hops), so
  ``durability_vs_off`` is the measured RTF overhead of crash-proof
  sessions. Durable points additionally record the raw I/O the manager
  performed (``journal_records`` / ``journal_bytes`` / ``snapshots`` /
  ``snapshot_bytes``). Sessions-sweep mode, inproc transport only.
- ``--guards off,on`` — the fault-containment tax: ``on`` points serve
  through a pool with the post-collect finite guard armed (every collected
  hop's output and carried state checked for NaN/Inf before release; on
  the socket transport the 1-shard router additionally runs its circuit
  breaker + step watchdog), so ``guards_vs_off`` is the measured RTF
  overhead of the containment plane — the acceptance bar is <= 5% on a CPU
  smoke run. Sessions-sweep mode only.

``--ramp`` instead drives an **elastic** pool (``ElasticSessionPool``,
``--tiers`` capacity ladder) through a session ramp that climbs past at
least two tier boundaries and back down: at every target occupancy it feeds
all live sessions and pumps, while one pilot session streams continuously
across the whole ramp (so a dropped or corrupted stream is detected, not
averaged away). Each point records the current tier plus cumulative
grow/shrink counts; the JSON artifact additionally gets a ``resizes``
summary (counts + migration-pause ms) per backend — the numbers the
ROADMAP's elastic-capacity item asks for.

``--adaptive`` instead runs the **bursty-trace scheduler sweep**: the same
seeded ragged burst arrivals (0..2*k_max hops per session per round, ~30%
silent rounds) are served three ways — a static K=1 pool, a static K=k_max
pool, and an adaptive pool (``AdaptiveScheduler`` picking per-dispatch K
from measured backlog, device ingestion ring) — and the JSON gains
``adaptive_vs_hops1`` / ``adaptive_vs_hops{k_max}`` scorecards (mean
aggregate-RTF ratio and mean per-pump p50 ratio, matched on backend and
session count). The claim under test: adaptive p50 pump latency tracks the
K=1 fast path while bursty throughput tracks the deep static pool.

``--shards N`` instead sweeps SHARD COUNT at full per-shard load through
``ShardedSessionPool`` (one pool per device, overlapped ``pump_all``). If
capacity scales linearly with devices, rt_capacity grows ~linearly in the
shard sweep (faked CPU devices share one core: expect a flat curve there).
On a CPU-only host, fake devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python benchmarks/server_throughput.py --shards 4

Results go to BOTH stdout (CSV via benchmarks.common.emit, human-scannable)
and a machine-readable ``BENCH_server_throughput.json`` (``--json`` to move
it): full config, every sweep point, and cross-config RTF ratios — the
artifact CI and regression tooling consume.

``--smoke`` shrinks everything (capacity 2, 0.25 s audio, 1-2 sessions) so
the pallas/interpret path finishes in seconds — the CI guard that keeps the
deploy path from rotting.

Run:  PYTHONPATH=src python benchmarks/server_throughput.py [--capacity N]
          [--seconds S] [--quant] [--shards N] [--backend xla,pallas]
          [--buffering single,double] [--hops-per-step 1,4,8] [--ramp]
          [--adaptive] [--transport inproc,socket] [--durability off,on]
          [--guards off,on] [--snapshot-every N] [--tiers 4,16,64]
          [--smoke] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
from common import emit  # noqa: E402

from repro.audio.synthetic import batch_for_step  # noqa: E402
from repro.core.quant import FP10  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    enable_compile_cache,
    parse_tiers,
    reduced_cfg,
)
from repro.models import tftnn as tft  # noqa: E402
from repro.serve import (  # noqa: E402
    DurabilityManager,
    ElasticSessionPool,
    PoolFullError,
    SessionPool,
    ShardedSessionPool,
    make_stream_hop,
    scheduler_for_pool,
)


def bench_cfg() -> tft.TFTConfig:
    """Paper front end (512/128 @ 8 kHz), reduced trunk for CPU wall-clock —
    the same profile the launcher's --reduced flag uses."""
    return reduced_cfg(tft.tftnn_config())


def run_point(pool: SessionPool, n_sessions: int, audio: np.ndarray) -> dict:
    sessions = [pool.attach() for _ in range(n_sessions)]
    pool.step_seconds.clear()
    for i, s in enumerate(sessions):
        pool.feed(s, audio[i % audio.shape[0]])
    # wall-clock, not summed step latencies: under double buffering (inflight
    # > 1) a step's dispatch->ready time includes pipeline queueing, so the
    # sum double-counts overlapped work — wall time compares modes honestly.
    t0 = time.perf_counter()
    pool.pump()
    wall = time.perf_counter() - t0
    hop, sr = pool.cfg.hop, pool.sample_rate
    audio_sec = sum(s.stats.hops for s in sessions) * hop / sr
    rtfs = [s.stats.rtf(sr, hop) for s in sessions]
    pct = pool.latency_percentiles()
    for s in sessions:
        pool.detach(s)
    rtf = wall / audio_sec
    return {
        "sessions": n_sessions,
        "aggregate_rtf": rtf,
        "rt_capacity": 1.0 / rtf if rtf > 0 else float("inf"),
        "mean_session_rtf": float(np.mean(rtfs)),
        "p50_ms": pct[50],
        "p95_ms": pct[95],
    }




def run_bursty_point(pool: SessionPool, n_sessions: int, audio: np.ndarray,
                     *, rounds: int, k_max: int, seed: int = 1234,
                     sched=None) -> dict:
    """One bursty-trace point: seeded ragged bursts, per-pump latency p50.

    Every round feeds each session an independent burst of 0..2*k_max hops
    (~30% of rounds are silent for a session) and pumps — with the adaptive
    scheduler when ``sched`` is given, the static full-K pump otherwise.
    The SAME ``seed`` drives every configuration, so adaptive and static
    points see identical arrival sequences and the ratios compare schedules,
    not workloads. p50/p95 are over per-PUMP wall times (what a caller's
    event loop blocks on), aggregate RTF over the whole trace.
    """
    import random

    rnd = random.Random(seed)
    hop, sr = pool.cfg.hop, pool.sample_rate
    sessions = [pool.attach() for _ in range(n_sessions)]
    pool.step_seconds.clear()
    pump_walls = []
    wall = 0.0
    for _ in range(rounds):
        for i, s in enumerate(sessions):
            if rnd.random() < 0.3:
                continue  # silent round for this session
            hops = rnd.randint(1, 2 * k_max)
            pool.feed(s, audio[i % audio.shape[0]][: hops * hop])
        t0 = time.perf_counter()
        pool.pump(sched) if sched is not None else pool.pump()
        dt = time.perf_counter() - t0
        pump_walls.append(dt)
        wall += dt
    audio_sec = sum(s.stats.hops for s in sessions) * hop / sr
    for s in sessions:
        pool.detach(s)
    rtf = wall / audio_sec if audio_sec else float("inf")
    walls_ms = np.asarray(pump_walls) * 1e3
    point = {
        "sessions": n_sessions,
        "aggregate_rtf": rtf,
        "rt_capacity": 1.0 / rtf if rtf > 0 else float("inf"),
        "p50_pump_ms": float(np.percentile(walls_ms, 50)),
        "p95_pump_ms": float(np.percentile(walls_ms, 95)),
        "rounds": rounds,
    }
    if sched is not None:
        stats = sched.stats()
        point["k_mean"] = stats["k_mean"]
        point["k_max_seen"] = stats["k_max_seen"]
    return point


def run_socket_point(gw, n_sessions: int, audio: np.ndarray) -> dict:
    """One sessions-sweep point across the fabric: every session is a real
    ``GatewayClient`` TCP connection to the gateway's localhost socket.

    Same accounting shape as ``run_point`` so the ``socket_vs_inproc``
    ratio compares like with like; wall-clock covers feed-to-last-sample
    (the gateway's pump loop serves continuously, so readback latency is
    part of what the transport costs).
    """
    from repro.serve.gateway import GatewayClient

    hop, sr = gw.pool.cfg.hop, gw.pool.sample_rate
    expect = (audio.shape[1] // hop) * hop
    host, port = gw.address
    gw.call(lambda p: [q.step_seconds.clear() for q in p._pools])
    clients = [GatewayClient(host, port) for _ in range(n_sessions)]
    try:
        for c in clients:
            c.attach()
        t0 = time.perf_counter()
        for i, c in enumerate(clients):
            c.feed(audio[i % audio.shape[0]])
        outs = [c.read_until(expect, timeout=300) for c in clients]
        wall = time.perf_counter() - t0
    finally:
        for c in clients:
            c.close()
    assert all(o.size == expect for o in outs)
    pct = gw.call(lambda p: p._pools[0].latency_percentiles())
    audio_sec = n_sessions * expect / sr
    rtf = wall / audio_sec
    return {
        "sessions": n_sessions,
        "aggregate_rtf": rtf,
        "rt_capacity": 1.0 / rtf if rtf > 0 else float("inf"),
        "mean_session_rtf": rtf,
        "p50_ms": pct[50],
        "p95_ms": pct[95],
    }


def run_sharded_point(params, cfg, n_shards: int, per_shard: int,
                      audio: np.ndarray, quant, backend: str,
                      hops_per_step: int, step_cache: dict) -> dict:
    """One shard-sweep point: fill n_shards x per_shard sessions, pump_all.

    ``step_cache`` is shared across sweep points so each device compiles the
    hop step once for the whole sweep (cfg/capacity/quant/backend/
    hops_per_step constant)."""
    pool = ShardedSessionPool(params, cfg, per_shard, shards=n_shards,
                              quant=quant, backend=backend,
                              hops_per_step=hops_per_step,
                              step_cache=step_cache)
    n_sessions = n_shards * per_shard
    handles = [pool.attach(f"bench-{i}", rebalance_on_full=True)
               for i in range(n_sessions)]
    # warm up each shard's one compilation outside the timed window
    for i, h in enumerate(handles):
        pool.feed(h, audio[i % audio.shape[0]][: 2 * cfg.hop])
    pool.pump_all()
    warm_hops = sum(h.stats.hops for h in handles)  # exclude from timed audio
    for i, h in enumerate(handles):
        pool.feed(h, audio[i % audio.shape[0]])
    t0 = time.perf_counter()
    pool.pump_all()
    wall = time.perf_counter() - t0
    timed_hops = sum(h.stats.hops for h in handles) - warm_hops
    audio_sec = timed_hops * cfg.hop / pool.sample_rate
    rtf = wall / audio_sec
    for h in handles:
        pool.detach(h)
    return {
        "shards": n_shards,
        "sessions": n_sessions,
        "aggregate_rtf": rtf,
        # sustainable real-time streams: total audio seconds / wall second.
        # rtf's denominator already sums audio over every session, so this is
        # 1/rtf — NOT sessions/rtf, which would double-count session count.
        "rt_capacity": 1.0 / rtf if rtf > 0 else float("inf"),
        "wall_s": wall,
    }


def _ramp_targets(tiers: tuple) -> list:
    """Occupancy targets that fill each tier, cross its boundary (grow), then
    descend below the shrink watermarks (shrink) — every grow AND shrink edge
    of the ladder is exercised once."""
    up = []
    for lo in tiers[:-1]:
        up.extend([lo, lo + 1])  # fill the tier, then force a grow
    up.append(min(tiers[-1], tiers[-2] + 2))
    # descend to half of each lower tier: under the default shrink_fraction
    # watermark, so the lazy shrinker steps back down the ladder
    down = [max(1, t // 2) for t in reversed(tiers[:-1])]
    return up + down + [1]


def run_ramp(params, cfg, tiers: tuple, audio: np.ndarray, quant,
             backend: str, buffering: str, hops_per_step: int = 1,
             step_fn=None) -> tuple:
    """Drive an ElasticSessionPool through the ramp; returns (points, summary).

    One **pilot** session streams continuously across every target (attached
    first, never detached): its hop count must equal the total audio it was
    fed, so a session dropped or corrupted by a resize fails the run instead
    of vanishing into an average. ``shrink_patience=1`` makes the down-ramp
    shrink on the next pump instead of waiting out the serving-loop
    hysteresis; ``prewarm()`` compiles every tier up front so per-tier RTF
    measures serving, not jit.
    """
    pool = ElasticSessionPool(
        params, cfg, tiers, quant=quant, backend=backend,
        inflight=2 if buffering == "double" else 1,
        hops_per_step=hops_per_step, step_fn=step_fn,
        shrink_patience=1,
    )
    pool.prewarm()
    hop, sr = cfg.hop, pool.sample_rate
    pilot = pool.attach()
    handles = []
    points = []
    pilot_samples = 0
    dropped = 0  # attaches the elastic pool refused (should never happen:
    # every ramp target fits under the top tier)
    for target in _ramp_targets(tiers):
        while pool.num_active < target:
            try:
                handles.append(pool.attach())
            except PoolFullError:
                dropped += 1
                break
        while pool.num_active > target and handles:
            pool.detach(handles.pop())
        live = [pilot] + handles
        for i, h in enumerate(live):
            pool.feed(h, audio[i % audio.shape[0]])
        t0 = time.perf_counter()
        pool.pump()
        wall = time.perf_counter() - t0
        pilot_samples += pool.read(pilot).size  # pilot continuity, and keeps _out flat
        audio_sec = len(live) * (audio.shape[1] // hop) * hop / sr
        rtf = wall / audio_sec
        points.append({
            "sessions": target,
            "tier": pool.capacity,
            "aggregate_rtf": rtf,
            "rt_capacity": 1.0 / rtf if rtf > 0 else float("inf"),
            "grows": pool.grow_count,
            "shrinks": pool.shrink_count,
            "wall_s": wall,
        })
    for _ in range(len(tiers)):
        pool.pump()  # idle heartbeats: let the lazy shrinker settle
    expected = pilot.stats.samples_in // hop * hop
    if pilot_samples != expected or pilot.stats.hops * hop != expected:
        raise SystemExit(
            f"pilot stream lost audio across the ramp: read {pilot_samples} "
            f"of {expected} samples ({pilot.stats.hops} hops)"
        )
    pauses = np.asarray(pool.resize_seconds) * 1e3 if pool.resize_seconds else np.zeros(1)
    summary = {
        "backend": backend,
        "buffering": buffering,
        "hops_per_step": hops_per_step,
        "tiers": list(tiers),
        "grows": pool.grow_count,
        "shrinks": pool.shrink_count,
        "resize_log": [list(t) for t in pool.resize_log],
        "mean_pause_ms": float(pauses.mean()),
        "max_pause_ms": float(pauses.max()),
        "final_tier": pool.capacity,
        "dropped_sessions": dropped,  # measured: refused attaches (pilot
        # integrity is enforced separately by the SystemExit check above)
        "pilot_hops": pilot.stats.hops,
    }
    return points, summary


def _shard_sweep(n_max: int) -> list:
    s, out = 1, []
    while s < n_max:
        out.append(s)
        s *= 2
    out.append(n_max)
    return sorted(set(out))


def _csv_list(raw: str, allowed: tuple) -> list:
    vals = [v.strip() for v in raw.split(",") if v.strip()]
    for v in vals:
        if v not in allowed:
            raise SystemExit(f"unknown value {v!r}: expected one of {allowed}")
    if not vals:
        raise SystemExit(f"need at least one of {allowed}")
    return vals


def _csv_ints(raw: str, what: str) -> list:
    try:
        vals = [int(v) for v in raw.split(",") if v.strip()]
    except ValueError:
        raise SystemExit(f"{what} must be a comma list of ints, got {raw!r}")
    if not vals or any(v < 1 for v in vals):
        raise SystemExit(f"{what} needs one or more ints >= 1, got {raw!r}")
    return sorted(set(vals))


_SWEEP_AXES = ("backend", "buffering", "hops_per_step", "transport",
               "scheduler", "durability", "guards")


def _ratio(points: list, key: str, a: str, b: str) -> dict:
    """Mean aggregate-RTF ratio b/a between sweep points that match on every
    OTHER axis (mode, sessions, shards, and the non-compared config axis) —
    e.g. pallas/single is only ever divided by xla/single, never xla/double."""
    others = tuple(ax for ax in _SWEEP_AXES if ax != key)
    def mk(p):
        return (p["mode"], p.get("sessions"), p.get("shards"),
                *(p.get(ax) for ax in others))
    pa = {mk(p): p["aggregate_rtf"] for p in points if p[key] == a}
    ratios = [p["aggregate_rtf"] / pa[mk(p)]
              for p in points if p[key] == b and mk(p) in pa]
    return {"num_points": len(ratios),
            "mean_rtf_ratio": float(np.mean(ratios)) if ratios else None}


def _adaptive_ratio(points: list, static_k: int) -> dict:
    """Adaptive-vs-static ratios on the bursty sweep, matched on
    (backend, sessions): mean aggregate-RTF ratio AND mean per-pump p50
    ratio of the adaptive points against the static K=``static_k`` points
    (< 1.0 = the adaptive schedule is cheaper on that metric)."""
    base = {
        (p["backend"], p["sessions"]): p
        for p in points
        if p.get("mode") == "bursty" and p["scheduler"] == "static"
        and p["hops_per_step"] == static_k
    }
    rtf, p50 = [], []
    for p in points:
        if p.get("mode") != "bursty" or p["scheduler"] != "adaptive":
            continue
        ref = base.get((p["backend"], p["sessions"]))
        if ref is None:
            continue
        rtf.append(p["aggregate_rtf"] / ref["aggregate_rtf"])
        p50.append(p["p50_pump_ms"] / ref["p50_pump_ms"])
    return {
        "num_points": len(rtf),
        "mean_rtf_ratio": float(np.mean(rtf)) if rtf else None,
        "mean_p50_ratio": float(np.mean(p50)) if p50 else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Multi-session server throughput: sessions x RTF "
        "(single pool) or shard-count sweep (--shards, one pool per device), "
        "with xla-vs-pallas and single-vs-double-buffered comparisons; "
        "machine-readable results in BENCH_server_throughput.json."
    )
    ap.add_argument("--capacity", type=int, default=16,
                    help="slots compiled into each pool (per shard when --shards > 0)")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="seconds of audio fed to each session")
    ap.add_argument("--quant", action="store_true",
                    help="serve on the paper's FP10 deployment grid")
    ap.add_argument("--backend", default="xla",
                    help="comma list of hop backends to sweep: xla,pallas "
                    "(pallas = deploy-compiled fused graph; interpret mode off-TPU)")
    ap.add_argument("--buffering", default="single",
                    help="comma list of ingestion modes to sweep: single,double "
                    "(double = inflight=2 host/device overlap); single-pool mode only")
    ap.add_argument("--hops-per-step", default="1",
                    help="comma list of fused-dispatch depths to sweep, e.g. "
                    "1,4,8 — K>1 drains up to K hops per session per device "
                    "call (scan-batched step, bit-identical to K=1); the "
                    "JSON gains a hops{K}_vs_hops1 RTF ratio per K")
    ap.add_argument("--transport", default="inproc",
                    help="comma list of serving transports to sweep: "
                    "inproc,socket — socket serves each point through a "
                    "localhost StreamingGateway (real TCP clients, framed "
                    "chunk protocol); sessions-sweep mode only")
    ap.add_argument("--durability", default="off",
                    help="comma list of crash-recovery modes to sweep: "
                    "off,on — on serves through a pool wired to a "
                    "DurabilityManager (write-ahead hop journal + periodic "
                    "ticket snapshots in a temp dir), recording the RTF tax "
                    "and the raw journal/snapshot I/O per point; "
                    "sessions-sweep mode, inproc transport only")
    ap.add_argument("--snapshot-every", type=int, default=16,
                    help="snapshot cadence in hops for --durability on points")
    ap.add_argument("--guards", default="off",
                    help="comma list of fault-containment modes to sweep: "
                    "off,on — on serves through a pool with the post-collect "
                    "finite guard armed (and, on the socket transport, shard "
                    "circuit breakers + step watchdog), recording the RTF "
                    "tax of the containment plane; the JSON gains a "
                    "guards_vs_off ratio; sessions-sweep mode only")
    ap.add_argument("--adaptive", action="store_true",
                    help="bursty-trace sweep comparing the self-tuning "
                    "scheduler (AdaptiveScheduler + device ingestion ring) "
                    "against static K=1 and static K=k_max pools on "
                    "IDENTICAL seeded burst arrivals; the JSON gains "
                    "adaptive_vs_hops1 / adaptive_vs_hops{k_max} ratios "
                    "(aggregate RTF and per-pump p50)")
    ap.add_argument("--shards", type=int, default=0,
                    help="sweep ShardedSessionPool from 1 up to N shards at full "
                    "per-shard load (0 = single-pool sessions sweep); fake CPU "
                    "devices with XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--ramp", action="store_true",
                    help="elastic ramp workload: sweep sessions up past the "
                    "--tiers boundaries and back down through an "
                    "ElasticSessionPool, recording tier, RTF, resize counts "
                    "and migration pause per point")
    ap.add_argument("--tiers", default="4,16,64",
                    help="--ramp capacity ladder (comma list, strictly "
                    "increasing, each >= 2; needs >= 2 tiers)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="best-of-N repeats per single-pool sweep point, "
                    "interleaved round-robin across configs (min wall-clock "
                    "wins, as in timeit) — noisy scheduler phases hit every "
                    "config equally instead of skewing the comparison "
                    "ratios; --smoke raises it to >= 5 when sweeping "
                    "multiple --hops-per-step values")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run (capacity<=2, ~0.26s audio, 1-2 "
                    "sessions; best-of-5 points when sweeping "
                    "--hops-per-step) so the pallas/interpret path stays "
                    "fast")
    ap.add_argument("--json", default="BENCH_server_throughput.json",
                    help="where to write the machine-readable results")
    args = ap.parse_args()
    enable_compile_cache()

    backends = _csv_list(args.backend, ("xla", "pallas"))
    bufferings = _csv_list(args.buffering, ("single", "double"))
    hops_sweep = _csv_ints(args.hops_per_step, "--hops-per-step")
    transports = _csv_list(args.transport, ("inproc", "socket"))
    durabilities = _csv_list(args.durability, ("off", "on"))
    guard_modes = _csv_list(args.guards, ("off", "on"))
    if "socket" in transports and (args.ramp or args.shards > 0):
        raise SystemExit("--transport socket only sweeps in sessions mode")
    if "on" in durabilities and (args.ramp or args.shards > 0 or args.adaptive):
        raise SystemExit("--durability on only sweeps in sessions mode")
    if "on" in guard_modes and (args.ramp or args.shards > 0 or args.adaptive):
        raise SystemExit("--guards on only sweeps in sessions mode")
    if "on" in durabilities and "socket" in transports:
        raise SystemExit("--durability on sweeps the inproc transport only")
    if args.snapshot_every < 1:
        raise SystemExit("--snapshot-every must be >= 1")
    if args.adaptive and (args.ramp or args.shards > 0):
        raise SystemExit("--adaptive is its own mode: drop --ramp/--shards")
    if args.adaptive and "socket" in transports:
        raise SystemExit("--adaptive sweeps in-process pools only")
    # the adaptive sweep's static reference depths: K=1 and the ceiling
    adaptive_kmax = max(hops_sweep) if max(hops_sweep) > 1 else 8
    if args.repeats < 1:
        raise SystemExit("--repeats must be >= 1")
    if args.smoke:
        args.capacity = min(args.capacity, 2)
        # 0.26 s = 16 hops: a whole number of K=8 fused dispatches, so the
        # hops sweep measures amortization rather than a ragged final lane
        args.seconds = min(args.seconds, 0.26)
        if len(hops_sweep) > 1:
            # only the hops{K}_vs_hops1 ratios need best-of-N stability;
            # don't quintuple the pallas-interpret smoke for other sweeps
            args.repeats = max(args.repeats, 5)
        if args.adaptive:
            args.repeats = max(args.repeats, 3)
        if len(guard_modes) > 1:
            # the guards_vs_off ratio carries a <= 5% overhead contract:
            # best-of-N keeps scheduler noise out of a few-percent comparison
            args.repeats = max(args.repeats, 5)
        if args.ramp and args.tiers == "4,16,64":
            args.tiers = "2,4,8"  # CI-sized ladder, still two boundaries
    tiers = parse_tiers(args.tiers)
    if args.ramp and len(tiers) < 2:
        raise SystemExit(f"--ramp needs >= 2 tiers, got {tiers}")

    cfg = bench_cfg()
    params = tft.init_tft(jax.random.PRNGKey(0), cfg)
    quant = FP10 if args.quant else None

    sample_rate = 8000
    # at least one whole hop, else nothing is ever enhanced (div-by-zero)
    samples = max(cfg.hop, int(args.seconds * sample_rate) // cfg.hop * cfg.hop)
    noisy, _ = batch_for_step(1, 0, batch=4, num_samples=samples)
    audio = np.asarray(noisy, np.float32)
    budget_ms = cfg.hop / sample_rate * 1e3

    result = {
        "benchmark": "server_throughput",
        "config": {
            "capacity": args.capacity,
            "seconds_per_session": args.seconds,
            "quant": "fp10" if args.quant else "fp32",
            "backends": backends,
            "bufferings": bufferings,
            "hops_per_step": hops_sweep,
            "transports": transports,
            "durability": durabilities,
            "guards": guard_modes,
            "snapshot_every": args.snapshot_every if "on" in durabilities else None,
            "shards_max": args.shards,
            "ramp": args.ramp,
            "adaptive": args.adaptive,
            "adaptive_k_max": adaptive_kmax if args.adaptive else None,
            "tiers": list(tiers) if args.ramp else None,
            "smoke": args.smoke,
            "hop_budget_ms": budget_ms,
            "devices": len(jax.local_devices()),
            "jax_backend": jax.default_backend(),
        },
        "points": [],
    }
    points = result["points"]
    print("name,us_per_call,derived")

    if args.ramp:
        print(f"# elastic ramp over tiers={tiers}, audio/session/point="
              f"{args.seconds}s, backends={backends}, bufferings={bufferings}, "
              f"hops_per_step={hops_sweep}, "
              f"quant={'fp10' if args.quant else 'fp32'}")
        result["resizes"] = []
        for backend in backends:
            for hps in hops_sweep:
                # buffering is host-side only: share one compiled step per
                # (backend, K) so the second ramp's prewarm hits the jit cache
                step = make_stream_hop(params, cfg, quant=quant,
                                       backend=backend, max_hops_per_step=hps)
                for buffering in bufferings:
                    ramp_points, summary = run_ramp(
                        params, cfg, tiers, audio, quant, backend, buffering,
                        hops_per_step=hps, step_fn=step)
                    for r in ramp_points:
                        r.update(mode="ramp", backend=backend,
                                 buffering=buffering, hops_per_step=hps,
                                 transport="inproc")
                        points.append(r)
                        emit(
                            f"backend={backend} buffering={buffering} "
                            f"hops={hps} ramp sessions={r['sessions']}",
                            r["wall_s"] * 1e6,
                            f"tier={r['tier']} aggregate_rtf={r['aggregate_rtf']:.3f} "
                            f"grows={r['grows']} shrinks={r['shrinks']}",
                        )
                    result["resizes"].append(summary)
                    print(f"# resizes[{backend}/{buffering}/hops={hps}]: "
                          f"grows={summary['grows']} shrinks={summary['shrinks']} "
                          f"max_pause={summary['max_pause_ms']:.2f}ms "
                          f"dropped={summary['dropped_sessions']}")
    elif args.adaptive:
        kmax = adaptive_kmax
        rounds = 6 if args.smoke else 16
        sweep = [n for n in (1, 2, 4, 8, 16) if n <= args.capacity]
        print(f"# bursty adaptive sweep: k_max={kmax}, rounds={rounds}, "
              f"backends={backends}, repeats={args.repeats}, "
              f"quant={'fp10' if args.quant else 'fp32'}")
        variants = [("static", 1), ("static", kmax), ("adaptive", kmax)]
        combos = []
        for backend in backends:
            steps: dict = {}  # ONE step cache per backend: static keys are
            # (k, None), adaptive ring keys (k, 2*kmax) — shared across
            # variants and every interleaved repeat, no recompiles mid-sweep
            for label, k in variants:
                ring = 2 * kmax if label == "adaptive" else None
                pool = SessionPool(
                    params, cfg, capacity=args.capacity, quant=quant,
                    backend=backend, hops_per_step=k, ingest_ring=ring,
                    step_fns=steps,
                )
                # warm every lane depth this variant can pick OUTSIDE the
                # timed points (the adaptive pool compiles its whole ladder)
                ladder = (
                    scheduler_for_pool(k).config.k_ladder
                    if label == "adaptive" else (k,)
                )
                w = pool.attach()
                for kk in ladder:
                    pool.feed(w, audio[0][: kk * cfg.hop])
                    pool.pump(scheduler_for_pool(k)
                              if label == "adaptive" else None)
                pool.detach(w)
                combos.append((backend, label, k, pool))
        # interleaved best-of-N, exactly like the sessions sweep: every
        # variant sees the same seeded arrival trace on every repeat
        best = {}
        for _ in range(args.repeats):
            for backend, label, k, pool in combos:
                for n in sweep:
                    sched = (scheduler_for_pool(k)
                             if label == "adaptive" else None)
                    r = run_bursty_point(pool, n, audio, rounds=rounds,
                                         k_max=kmax, sched=sched)
                    key = (backend, label, k, n)
                    if key not in best or r["aggregate_rtf"] < best[key]["aggregate_rtf"]:
                        best[key] = r
        for backend, label, k, _pool in combos:
            for n in sweep:
                r = best[(backend, label, k, n)]
                r.update(mode="bursty", backend=backend, buffering="single",
                         hops_per_step=k, transport="inproc", scheduler=label)
                points.append(r)
                emit(
                    f"backend={backend} scheduler={label} hops={k} "
                    f"sessions={n}",
                    r["p50_pump_ms"] * 1e3,
                    f"aggregate_rtf={r['aggregate_rtf']:.3f} "
                    f"p95_pump_ms={r['p95_pump_ms']:.2f}"
                    + (f" k_mean={r['k_mean']:.2f}"
                       f" k_max_seen={r['k_max_seen']}"
                       if label == "adaptive" else ""),
                )
    elif args.shards > 0:
        print(f"# shard sweep up to {args.shards}, capacity/shard={args.capacity}, "
              f"audio/session={args.seconds}s, backends={backends}, "
              f"hops_per_step={hops_sweep}, "
              f"quant={'fp10' if args.quant else 'fp32'}")
        for backend in backends:
            for hps in hops_sweep:
                step_cache = {}  # one compilation per device across the sweep
                for s in _shard_sweep(args.shards):
                    r = run_sharded_point(params, cfg, s, args.capacity, audio,
                                          quant, backend, hps, step_cache)
                    r.update(mode="shards", backend=backend,
                             buffering="single", hops_per_step=hps,
                             transport="inproc")
                    points.append(r)
                    # space-separated name: emit() quotes nothing, so a comma
                    # here would break the 3-column CSV contract
                    emit(
                        f"backend={backend} hops={hps} shards={s}",
                        r["wall_s"] * 1e6,
                        f"sessions={r['sessions']} aggregate_rtf={r['aggregate_rtf']:.3f} "
                        f"rt_capacity={r['rt_capacity']:.1f} "
                        f"real_time={'yes' if r['aggregate_rtf'] < 1 else 'no'}",
                    )
    else:
        print(f"# capacity={args.capacity} audio/session={args.seconds}s "
              f"hop_budget={budget_ms:.1f}ms backends={backends} "
              f"bufferings={bufferings} hops_per_step={hops_sweep} "
              f"transports={transports} "
              f"quant={'fp10' if args.quant else 'fp32'}")
        sweep = [n for n in (1, 2, 4, 8, 16) if n <= args.capacity]
        combos = []
        gateways = []
        tmpdirs = []
        for backend in backends:
            for hps in hops_sweep:
                # buffering changes only host-side pipelining, not the
                # compiled step — compile once per (backend, K) and share it
                step = make_stream_hop(params, cfg, quant=quant,
                                       backend=backend, max_hops_per_step=hps)
                for buffering in bufferings:
                  for transport in transports:
                    for durability in durabilities:
                        for guard in guard_modes:
                            inflight = 2 if buffering == "double" else 1
                            armed = guard == "on"
                            manager = None
                            if durability == "on":
                                # temp-dir journal/snapshot store; detach at
                                # the end of each point forgets the files, so
                                # repeats never replay a prior point's state
                                tmp = tempfile.TemporaryDirectory(
                                    prefix="bench_durability_")
                                tmpdirs.append(tmp)
                                manager = DurabilityManager(
                                    tmp.name,
                                    snapshot_every=args.snapshot_every)
                            if transport == "inproc":
                                pool = SessionPool(params, cfg,
                                                   capacity=args.capacity,
                                                   quant=quant, backend=backend,
                                                   inflight=inflight,
                                                   hops_per_step=hps,
                                                   step_fn=step,
                                                   durability=manager,
                                                   finite_guard=armed)
                                # warm up the compilation outside the timed points
                                w = pool.attach()
                                pool.feed(w, audio[0][: 2 * hps * cfg.hop])
                                pool.pump()
                                pool.detach(w)
                                runner = pool
                            else:
                                from repro.serve.gateway import GatewayThread
                                # one shard: same batched step as the in-process
                                # pool, so the delta IS the socket + gateway loop.
                                # guards=on arms the full containment plane here
                                # (finite guard + breaker + a generous watchdog
                                # that never fires on a healthy CPU run).
                                spool = ShardedSessionPool(
                                    params, cfg, args.capacity, shards=1,
                                    quant=quant, backend=backend,
                                    inflight=inflight, hops_per_step=hps,
                                    finite_guard=armed,
                                    breaker_threshold=3 if armed else None,
                                    watchdog_seconds=30.0 if armed else None)
                                h = spool.attach("warmup")
                                spool.feed(h, audio[0][: 2 * hps * cfg.hop])
                                spool.pump_all()
                                spool.detach(h)
                                runner = GatewayThread(spool, pump_interval=0.001)
                                gateways.append(runner)
                            combos.append((backend, hps, buffering, transport,
                                           durability, guard, manager, runner))
        # --repeats are INTERLEAVED across configurations (round-robin, min
        # wall-clock per point wins, as in timeit): a noisy scheduler phase
        # spanning one whole pass penalizes every config equally instead of
        # silently skewing the cross-config comparison ratios.
        best: dict = {}
        for _ in range(args.repeats):
            for (backend, hps, buffering, transport, durability, guard,
                 manager, runner) in combos:
                for n in sweep:
                    pre = manager.totals() if manager is not None else None
                    if transport == "inproc":
                        r = run_point(runner, n, audio)
                    else:
                        r = run_socket_point(runner, n, audio)
                    if manager is not None:
                        # raw I/O the manager performed during this point —
                        # delta, because totals() accumulate across repeats
                        post = manager.totals()
                        for field in ("journal_records", "journal_bytes",
                                      "snapshots", "snapshot_bytes"):
                            r[field] = post[field] - pre[field]
                    key = (backend, hps, buffering, transport, durability,
                           guard, n)
                    if key not in best or r["aggregate_rtf"] < best[key]["aggregate_rtf"]:
                        best[key] = r
        for gw in gateways:
            gw.stop()
        for (backend, hps, buffering, transport, durability, guard, _manager,
             _runner) in combos:
            for n in sweep:
                r = best[(backend, hps, buffering, transport, durability,
                          guard, n)]
                r.update(mode="sessions", backend=backend,
                         buffering=buffering, hops_per_step=hps,
                         transport=transport, durability=durability,
                         guards=guard)
                points.append(r)
                emit(
                    f"backend={backend} buffering={buffering} "
                    f"hops={hps} transport={transport} "
                    f"durability={durability} guards={guard} sessions={n}",
                    r["p50_ms"] * 1e3,
                    f"aggregate_rtf={r['aggregate_rtf']:.3f} "
                    f"rt_capacity={r['rt_capacity']:.1f} "
                    f"mean_session_rtf={r['mean_session_rtf']:.3f} "
                    f"p95_ms={r['p95_ms']:.2f} "
                    f"real_time={'yes' if r['aggregate_rtf'] < 1 else 'no'}",
                )
        for tmp in tmpdirs:
            tmp.cleanup()

    comparisons = {}
    if "xla" in backends and "pallas" in backends:
        comparisons["pallas_vs_xla"] = _ratio(points, "backend", "xla", "pallas")
    if "single" in bufferings and "double" in bufferings:
        comparisons["double_vs_single"] = _ratio(points, "buffering", "single", "double")
    if "inproc" in transports and "socket" in transports:
        # > 1.0 is the fabric's measured overhead (socket framing + gateway
        # pump loop) relative to direct pool calls on the same host
        comparisons["socket_vs_inproc"] = _ratio(points, "transport", "inproc", "socket")
    if "off" in durabilities and "on" in durabilities:
        # > 1.0 is the crash-recovery tax (write-ahead journal append per
        # feed + periodic ticket snapshot) relative to the same pool with
        # durability disabled
        comparisons["durability_vs_off"] = _ratio(points, "durability", "off", "on")
    if "off" in guard_modes and "on" in guard_modes:
        # > 1.0 is the containment tax (post-collect finite scan per hop,
        # plus breaker/watchdog bookkeeping on the socket transport); the
        # acceptance bar for a CPU smoke run is <= 1.05
        comparisons["guards_vs_off"] = _ratio(points, "guards", "off", "on")
    for k in hops_sweep:
        if k != 1 and 1 in hops_sweep and not args.adaptive:
            # < 1.0 means the fused path lowered aggregate RTF (a speedup of
            # 1/ratio); the acceptance bar for K=8 on a backlogged CPU smoke
            # run is <= 1/1.5
            comparisons[f"hops{k}_vs_hops1"] = _ratio(
                points, "hops_per_step", 1, k)
    if args.adaptive:
        # the self-tuning scheduler's scorecard: against the always-shallow
        # static pool (throughput headroom) and against the always-deep one
        # (p50 pump latency), on the SAME seeded bursty arrivals
        comparisons["adaptive_vs_hops1"] = _adaptive_ratio(points, 1)
        comparisons[f"adaptive_vs_hops{adaptive_kmax}"] = _adaptive_ratio(
            points, adaptive_kmax)
    result["comparisons"] = comparisons

    out_path = Path(args.json)
    out_path.write_text(json.dumps(result, indent=2) + "\n", encoding="utf-8")
    print(f"# wrote {out_path} ({len(points)} points)")

    if args.smoke and args.adaptive:
        # CI contract for the adaptive sweep: both scorecard ratios must be
        # populated (num_points and both metric means), else the sweep
        # silently lost a configuration
        for name in ("adaptive_vs_hops1", f"adaptive_vs_hops{adaptive_kmax}"):
            ratio = comparisons[name]
            if (not ratio["num_points"] or ratio["mean_rtf_ratio"] is None
                    or ratio["mean_p50_ratio"] is None):
                raise SystemExit(
                    f"smoke: {name} comparison is empty — adaptive points "
                    "found no matching static points"
                )
            print(f"# {name}: rtf_ratio={ratio['mean_rtf_ratio']:.3f} "
                  f"p50_ratio={ratio['mean_p50_ratio']:.3f} "
                  f"({ratio['num_points']} matched points)")
    if args.smoke and not args.adaptive:
        # CI contract: a smoke sweep must actually produce the comparison
        # fields it claims (an empty ratio means the sweep silently skipped
        # a configuration)
        for k in hops_sweep:
            if k == 1 or 1 not in hops_sweep:
                continue
            ratio = comparisons[f"hops{k}_vs_hops1"]
            if not ratio["num_points"] or ratio["mean_rtf_ratio"] is None:
                raise SystemExit(
                    f"smoke: hops{k}_vs_hops1 comparison is empty — the "
                    f"K={k} sweep produced no points matching the K=1 sweep"
                )
            print(f"# hops{k}_vs_hops1 mean RTF ratio: "
                  f"{ratio['mean_rtf_ratio']:.3f} "
                  f"({1.0 / ratio['mean_rtf_ratio']:.2f}x speedup)")
    if args.smoke and "on" in durabilities:
        # CI contract for the durability sweep: every durable point must
        # carry the manager's I/O accounting, and journaling must actually
        # have happened (a zero journal_bytes point means feeds bypassed the
        # write-ahead log and the overhead being measured is fiction)
        durable_points = [p for p in points
                          if p.get("mode") == "sessions"
                          and p.get("durability") == "on"]
        if not durable_points:
            raise SystemExit("smoke: --durability on produced no points")
        for p in durable_points:
            for field in ("journal_records", "journal_bytes", "snapshots",
                          "snapshot_bytes"):
                if field not in p:
                    raise SystemExit(
                        f"smoke: durable point missing {field!r}")
            if p["journal_bytes"] <= 0 or p["journal_records"] <= 0:
                raise SystemExit(
                    "smoke: durable point recorded no journal writes")
        if "off" in durabilities:
            ratio = comparisons["durability_vs_off"]
            if not ratio["num_points"] or ratio["mean_rtf_ratio"] is None:
                raise SystemExit(
                    "smoke: durability_vs_off comparison is empty — the "
                    "durable sweep produced no points matching the "
                    "non-durable sweep"
                )
            print(f"# durability_vs_off mean RTF ratio: "
                  f"{ratio['mean_rtf_ratio']:.3f} "
                  f"(journal_bytes/point max "
                  f"{max(p['journal_bytes'] for p in durable_points)})")
    if args.smoke and "on" in guard_modes:
        # CI contract for the guards sweep: guarded points must exist, and
        # when both modes ran, the containment tax must stay within the
        # <= 5% acceptance bar (best-of-N repeats keep this comparison out
        # of scheduler-noise territory)
        guarded_points = [p for p in points
                          if p.get("mode") == "sessions"
                          and p.get("guards") == "on"]
        if not guarded_points:
            raise SystemExit("smoke: --guards on produced no points")
        if "off" in guard_modes:
            ratio = comparisons["guards_vs_off"]
            if not ratio["num_points"] or ratio["mean_rtf_ratio"] is None:
                raise SystemExit(
                    "smoke: guards_vs_off comparison is empty — the guarded "
                    "sweep produced no points matching the unguarded sweep"
                )
            print(f"# guards_vs_off mean RTF ratio: "
                  f"{ratio['mean_rtf_ratio']:.3f} "
                  f"({ratio['num_points']} matched points)")
            # the <= 5% bar is the POOL's guard tax: enforce it on the
            # inproc subset, where the only delta is the finite scan (the
            # socket points fold in gateway pump-loop jitter that has
            # nothing to do with the guard itself)
            if "inproc" in transports:
                inproc = _ratio([p for p in points
                                 if p.get("transport") == "inproc"],
                                "guards", "off", "on")
                if (inproc["mean_rtf_ratio"] is not None
                        and inproc["mean_rtf_ratio"] > 1.05):
                    raise SystemExit(
                        f"smoke: guards overhead "
                        f"{inproc['mean_rtf_ratio']:.3f}x on the inproc "
                        "sweep exceeds the 1.05x acceptance bar"
                    )


if __name__ == "__main__":
    main()
