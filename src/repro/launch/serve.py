"""Serving launcher: streaming speech enhancement (the paper's deployment).

Loads TFTNN weights (or inits fresh), then enhances audio hop-by-hop with
16 ms algorithmic latency, reporting per-hop wall time against the real-time
budget. Other tasks: ``--task pool`` serves many sessions through one
``SessionPool`` (``--elastic --tiers 4,16,64`` swaps in an
``ElasticSessionPool`` that grows/shrinks along a pre-compiled capacity
ladder); ``--task sharded`` runs one pool per device behind the
consistent-hash router (``--shards N``, elastic shards with ``--elastic``;
fake CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); ``--task gateway``
puts the sharded fleet behind the cross-process socket front door
(``--port``, health-checked shards with ticket failover — point
``examples/gateway_client.py --connect`` at it); ``--task lm`` runs
batched greedy decode on a reduced arch. See docs/serving.md.

Every pool task compiles all of its step shapes before serving, and JAX's
persistent compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else in ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is set here. Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout: a fixed path, because the path is part of what a
    later run must find again. Call before the first compile.
    """
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_label() -> str:
    """What JAX runs on, e.g. ``tpu/TPU v5 lite x1``."""
    devs = jax.devices()
    return f"{devs[0].platform}/{devs[0].device_kind} x{len(devs)}"


def reduced_cfg(cfg):
    """The CPU-demo trunk shared by every serving task's ``--reduced`` flag
    (and by ``benchmarks/server_throughput.py``): paper front end, small
    model."""
    return dataclasses.replace(cfg, freq_bins=64, channels=16, att_dim=8,
                               num_heads=1, gru_hidden=16, dilation_rates=(1, 2, 4))


def model(args):
    """``(cfg, params)``: the paper's TFTNN (``--reduced``: the CPU-demo
    trunk) with fresh weights from PRNG key 0 — the repo ships none."""
    from repro.models import tftnn as tft

    cfg = tft.tftnn_config()
    if args.reduced:
        cfg = reduced_cfg(cfg)
    return cfg, tft.init_tft(jax.random.PRNGKey(0), cfg)


def serve_se(args) -> None:
    from repro.audio.metrics import all_metrics
    from repro.audio.synthetic import batch_for_step
    from repro.serve.streaming_se import init_stream, stream_hop
    from repro.train.checkpoint import Checkpointer

    cfg, params = model(args)
    if args.ckpt_dir:
        try:
            _, state = Checkpointer(args.ckpt_dir).restore(
                {"params": params}, step=None
            )
            params = state["params"]
            print(f"loaded checkpoint from {args.ckpt_dir}")
        except FileNotFoundError:
            print("no checkpoint found; serving with random init")
    noisy, clean = batch_for_step(1, 0, batch=args.batch, num_samples=args.samples)
    state = init_stream(params, cfg, args.batch)
    hop = cfg.hop
    step = jax.jit(lambda s, x: stream_hop(params, cfg, s, x))
    jax.block_until_ready(step(state, noisy[:, :hop]))  # compile, untimed
    outs, times = [], []
    n = args.samples // hop
    for i in range(n):
        chunk = noisy[:, i * hop : (i + 1) * hop]
        t0 = time.perf_counter()
        state, y = step(state, chunk)
        y.block_until_ready()
        times.append(time.perf_counter() - t0)
        outs.append(y)
    est = jnp.concatenate(outs, axis=1)
    times = sorted(times)
    p50, p99 = times[len(times) // 2], times[int(len(times) * 0.99)]
    budget = hop / 8000.0
    print(f"hops={n} p50={p50 * 1e3:.2f}ms p99={p99 * 1e3:.2f}ms budget={budget * 1e3:.1f}ms "
          f"real-time={'YES' if p99 < budget else 'no'} on {device_label()}")
    scores = {k: round(float(v), 3) for k, v in all_metrics(est, clean[:, : est.shape[1]]).items()}
    print(f"quality vs clean: {scores}")


def parse_tiers(raw: str) -> tuple:
    """'4,16,64' -> (4, 16, 64); validation happens in ElasticSessionPool."""
    try:
        return tuple(int(v) for v in raw.split(",") if v.strip())
    except ValueError:
        raise SystemExit(f"--tiers must be a comma list of ints, got {raw!r}")


def prune_kw(args) -> dict:
    """``--prune-*`` pool kwargs shared by the pool/sharded/gateway tasks."""
    kw = dict(prune_keep=args.prune_keep,
              prune_granularity=args.prune_granularity or None)
    try:
        bk, bn = (int(v) for v in args.prune_block.split(","))
        kw["prune_block"] = (bk, bn)
    except ValueError:
        raise SystemExit(f"--prune-block must be 'bk,bn', got {args.prune_block!r}")
    return kw


def adaptive_setup(args):
    """``--adaptive`` wiring shared by the pool/sharded/gateway tasks.

    Returns ``(hops_per_step, scheduler-or-None, extra pool kwargs)``: the
    fused-dispatch ceiling the controller may use (the given
    ``--hops-per-step`` when fused, else 8), a fresh ``AdaptiveScheduler``
    for single-pool tasks, and the device-ingestion-ring kwarg.
    """
    if not args.adaptive:
        return args.hops_per_step, None, {}
    from repro.serve import scheduler_for_pool
    from repro.serve.scheduler import ring_depth_for

    kmax = args.hops_per_step if args.hops_per_step > 1 else 8
    sched = scheduler_for_pool(kmax)
    return kmax, sched, {"ingest_ring": ring_depth_for(sched.config)}


def guard_kw(args) -> dict:
    """``--finite-guard`` pool kwarg shared by the pool/sharded/gateway tasks."""
    return {"finite_guard": True} if args.finite_guard else {}


def breaker_kw(args) -> dict:
    """``--breaker-threshold``/``--watchdog-seconds`` kwargs for the
    sharded/gateway tasks (0 leaves the legacy fail-fast / no-watchdog
    behavior)."""
    kw = {}
    if args.breaker_threshold > 0:
        kw["breaker_threshold"] = args.breaker_threshold
    if args.watchdog_seconds > 0:
        kw["watchdog_seconds"] = args.watchdog_seconds
    return kw


def durability_setup(args) -> dict:
    """``--durability-dir`` wiring shared by the pool/sharded/gateway tasks.

    Returns the extra pool kwarg: a ``DurabilityManager`` rooted at the
    given directory, snapshotting every ``--snapshot-every`` hops (0 means
    journal-only — replay from the last full snapshot or from birth).
    Restarting any task against the same directory recovers its sessions
    bit-exactly.
    """
    if not args.durability_dir:
        return {}
    from repro.serve import DurabilityManager

    every = args.snapshot_every if args.snapshot_every > 0 else None
    return {"durability": DurabilityManager(args.durability_dir,
                                            snapshot_every=every)}


def serve_pool(args) -> None:
    """Multi-session server: --batch concurrent streams through one
    SessionPool (or an ElasticSessionPool tier ladder with --elastic)."""
    from repro.audio.synthetic import batch_for_step
    from repro.core.quant import FP10
    from repro.serve import ElasticSessionPool, SessionPool

    cfg, params = model(args)
    kmax, sched, extra = adaptive_setup(args)
    extra.update(durability_setup(args))
    extra.update(guard_kw(args))
    if args.elastic:
        # starts at the smallest tier and grows as sessions attach
        pool = ElasticSessionPool(params, cfg, parse_tiers(args.tiers),
                                  quant=FP10 if args.quant else None,
                                  backend=args.backend, **prune_kw(args),
                                  inflight=2 if args.double_buffer else 1,
                                  hops_per_step=kmax, **extra)
    else:
        pool = SessionPool(params, cfg, capacity=max(args.batch, 1),
                           quant=FP10 if args.quant else None,
                           backend=args.backend, **prune_kw(args),
                           inflight=2 if args.double_buffer else 1,
                           hops_per_step=kmax, **extra)
    pool.prewarm(sched.config.k_ladder if sched is not None else None)
    noisy, _ = batch_for_step(1, 0, batch=args.batch, num_samples=args.samples)
    audio = jnp.asarray(noisy)
    sessions = [pool.attach() for _ in range(args.batch)]
    for i, s in enumerate(sessions):
        pool.feed(s, audio[i])
    pool.pump(sched)
    print(pool.report())
    if sched is not None:
        print(f"scheduler: {sched.stats()}")
    for s in sessions:
        pool.detach(s)


def build_sharded_pool(args, params, cfg, devices=None):
    """The ``ShardedSessionPool`` the sharded and gateway tasks serve from,
    built from the launcher's parsed arguments, with every step shape
    already compiled (``prewarm``).

    ``devices`` defaults to every local device. Each shard gets
    ``ceil(batch / shards)`` slots, at least 2 (XLA specializes batch-1
    steps). A compile error raises here, before any session exists.
    """
    from repro.core.quant import FP10
    from repro.serve import ShardedSessionPool

    per_shard = max(2, -(-args.batch // args.shards))
    tiers = parse_tiers(args.tiers) if args.elastic else None
    kmax, _, extra = adaptive_setup(args)
    extra.update(durability_setup(args))
    extra.update(guard_kw(args))
    extra.update(breaker_kw(args))
    pool = ShardedSessionPool(params, cfg, per_shard, shards=args.shards,
                              devices=devices,
                              quant=FP10 if args.quant else None,
                              backend=args.backend, **prune_kw(args),
                              inflight=2 if args.double_buffer else 1,
                              hops_per_step=kmax,
                              tiers=tiers, adaptive=args.adaptive or None,
                              **extra)
    pool.prewarm()
    return pool


def serve_sharded(args) -> None:
    """Sharded server: --shards SessionPools behind the consistent-hash router."""
    from repro.audio.synthetic import batch_for_step

    cfg, params = model(args)
    pool = build_sharded_pool(args, params, cfg)
    slots = (f"tiers {parse_tiers(args.tiers)}" if args.elastic
             else f"{pool.capacity // args.shards} slots")
    print(f"{args.shards} shards x {slots} over {len(jax.local_devices())} "
          f"local device(s) [{device_label()}]"
          + (" [adaptive]" if args.adaptive else ""))
    noisy, _ = batch_for_step(1, 0, batch=args.batch, num_samples=args.samples)
    audio = jnp.asarray(noisy)
    # rebalance_on_full: consistent hashing is not perfectly uniform, so a
    # near-capacity fleet migrates sessions off a hot shard instead of failing
    handles = [pool.attach(f"client-{i}", rebalance_on_full=True)
               for i in range(args.batch)]
    for i, h in enumerate(handles):
        pool.feed(h, audio[i])
    pool.pump_all()
    print(pool.report())
    for h in handles:
        pool.detach(h)


def serve_gateway(args) -> None:
    """Network front door: a ShardedSessionPool behind the asyncio gateway.

    Binds ``--host``/``--port`` and serves the framed streaming protocol
    (see ``repro.serve.gateway``) until interrupted: attach / feed jittery
    chunks / read / detach from any process, with shard health checks and
    wire-ticket failover running on every pump tick. ``--trace`` records
    the serving spans and per-hop timeline (``repro.serve.obs``) from the
    start; STATS reports their percentiles.
    """
    import asyncio

    from repro.serve import obs
    from repro.serve.gateway import StreamingGateway, new_event_loop

    if args.trace:
        obs.enable()
    cfg, params = model(args)
    pool = build_sharded_pool(args, params, cfg)
    gateway = StreamingGateway(pool, host=args.host, port=args.port)

    async def _serve() -> None:
        await gateway.start()
        host, port = gateway.address
        print(f"gateway listening on {host}:{port} "
              f"({args.shards} shards, {pool.capacity} slots, "
              f"{device_label()}); Ctrl-C stops")
        try:
            await asyncio.Event().wait()
        finally:
            await gateway.stop()

    try:
        asyncio.run(_serve(), loop_factory=new_event_loop)
    except KeyboardInterrupt:
        print("\n" + pool.report())


def serve_lm(args) -> None:
    import repro.configs as C
    from repro.models.transformer_lm import init_lm
    from repro.serve.engine import greedy_generate

    cfg = C.reduced_config(args.arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompt = jnp.ones((args.batch, 8), jnp.int32)
    t0 = time.perf_counter()
    out = greedy_generate(params, cfg, prompt, steps=args.tokens)
    out.tokens.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"generated {args.batch}x{args.tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s); sample: {out.tokens[0][:16].tolist()}")


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (also parsed by ``chip_smoke.py``, so the
    smoke builds its pools from the same flags a user passes)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["se", "pool", "sharded", "gateway", "lm"],
                    default="se")
    ap.add_argument("--quant", action="store_true",
                    help="pool/sharded tasks: serve on the paper's FP10 grid")
    ap.add_argument("--backend", choices=["xla", "pallas"], default="xla",
                    help="pool/sharded tasks: hop-step implementation — xla "
                    "(training graph) or pallas (deploy-compiled fused graph: "
                    "BN folded, Pallas kernels; interpret mode off-TPU)")
    ap.add_argument("--elastic", action="store_true",
                    help="pool/sharded tasks: serve through an elastic pool "
                    "that grows/shrinks along the --tiers capacity ladder "
                    "with live bit-exact session migration")
    ap.add_argument("--tiers", default="4,16,64",
                    help="--elastic capacity ladder (comma list, strictly "
                    "increasing, each >= 2)")
    ap.add_argument("--double-buffer", action="store_true",
                    help="pool/sharded tasks: inflight=2 — overlap the host "
                    "ring-buffer drain with the in-flight device step")
    ap.add_argument("--hops-per-step", type=int, default=1,
                    help="pool/sharded tasks: multi-hop fused dispatch — "
                    "drain up to K hops per session per device call "
                    "(scan-batched step, bit-identical to K=1; amortizes "
                    "the per-hop dispatch overhead for backlogged sessions)")
    ap.add_argument("--adaptive", action="store_true",
                    help="pool/sharded/gateway tasks: closed-loop scheduling "
                    "— per-dispatch K from measured backlog (deep fused "
                    "lanes only for lagging sessions), slope-triggered tier "
                    "growth and cost-modeled shrink on elastic pools, plus "
                    "a device-resident ingestion ring; decisions are "
                    "recorded and replayable")
    ap.add_argument("--prune-keep", type=float, default=None,
                    help="pool/sharded/gateway tasks: keep-fraction for the "
                    "deploy-time zero-skipping weight masks (lossy, the "
                    "paper's pruned serving point); works on both backends")
    ap.add_argument("--prune-granularity", default="",
                    choices=["", "weight", "block", "unit"],
                    help="mask granularity for --prune-keep (arXiv "
                    "2111.02351): 'weight' (unstructured, strip skip), "
                    "'block' (tile skip), 'unit' (whole output columns, "
                    "column skip); empty = legacy unstructured masks")
    ap.add_argument("--prune-block", default="8,8",
                    help="'bk,bn' tile shape for --prune-granularity block "
                    "and the strip/tile skip units (default 8,8)")
    ap.add_argument("--durability-dir", default="",
                    help="pool/sharded/gateway tasks: root directory for "
                    "durable session state (ticket snapshots + hop "
                    "journals); restarting against the same directory "
                    "recovers every session bit-exactly")
    ap.add_argument("--snapshot-every", type=int, default=64,
                    help="snapshot cadence in hops per session (0 = journal "
                    "only; smaller = shorter replay on recovery, more "
                    "snapshot I/O while serving)")
    ap.add_argument("--finite-guard", action="store_true",
                    help="pool/sharded/gateway tasks: post-collect finite "
                    "guard — any session whose output or carried state goes "
                    "NaN/Inf is quarantined (SessionPoisonedError / POISONED "
                    "frame) instead of streaming garbage; other slots in the "
                    "same batched step are untouched")
    ap.add_argument("--breaker-threshold", type=int, default=0,
                    help="sharded/gateway tasks: per-shard circuit breaker — "
                    "open (fail the shard over) after N consecutive pump "
                    "failures instead of on the first; half-open probe via "
                    "shard health checks, closed again after restart_shard "
                    "(0 = legacy fail-fast)")
    ap.add_argument("--watchdog-seconds", type=float, default=0.0,
                    help="sharded/gateway tasks: wall-clock bound on each "
                    "shard's dispatch->collect; a shard stuck past it is "
                    "failed over through the wire-ticket path (0 = off)")
    ap.add_argument("--shards", type=int, default=2,
                    help="sharded/gateway tasks: number of SessionPool shards")
    ap.add_argument("--host", default="127.0.0.1",
                    help="gateway task: bind address")
    ap.add_argument("--port", type=int, default=7861,
                    help="gateway task: TCP port (0 picks a free one)")
    ap.add_argument("--trace", action="store_true",
                    help="gateway task: record spans and the per-hop "
                    "timeline (repro.serve.obs); STATS reports them")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--samples", type=int, default=16000)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    {"se": serve_se, "pool": serve_pool, "sharded": serve_sharded,
     "gateway": serve_gateway, "lm": serve_lm}[args.task](args)


if __name__ == "__main__":
    main()
