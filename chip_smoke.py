"""Chip smoke: the paper's TFTNN served through the socket gateway on a TPU.

Drives the main serving path once, through the entry points a user calls:
the pools that ``python -m repro.launch.serve --task gateway`` builds
(``repro.launch.serve.build_sharded_pool``, fed the launcher's own flags), a
``StreamingGateway`` on a ``GatewayThread``, and one ``GatewayClient`` per
session over localhost TCP, each on its own thread. The model is
``tftnn_config()`` at its published widths with random weights from
``--seed`` (the repo ships no trained weights). Every session feeds 2 s of
8 kHz synthetic noisy speech (125 hops) in jittery chunks that are not
multiples of the hop, then reads its enhanced stream back and detaches.

Legs, 8 sessions each on one chip:

  (a) ``--backend xla``, fp32, one hop per step;
  (b) the deploy graph: ``--backend pallas --quant --hops-per-step 4``
      (BN folded, FP10 weights and activations, native Pallas kernels).

Each leg compiles every step shape before the gateway listens. The run fails
(non-zero exit, no ``ok`` line) unless every session's audio is finite and
matches the fp32 ``enhance_offline`` reference within the leg's SI-SNR
threshold, the gateway's STATS show no lost, failed-over, shed or
pump-failed session and no dead shard, and leg (b)'s compiled step holds
native kernels (``tpu_custom_call``). Timings printed on the way are
informational, not metrics. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--four-chips`` runs only leg (b), on four shards over four chips with 16
sessions: it checks that each shard's weights and carried state sit on its
own chip, kills one shard mid-stream so its sessions fail over as wire
tickets to shards on other chips, and compares every session with the same
sessions served on one chip in the same run.

Run:  python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time
from pathlib import Path
from typing import Callable, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SAMPLES = 16000  # 2 s at 8 kHz: 125 hops of 128 samples
SESSIONS = 8
FOUR_CHIP_SESSIONS = 16
CLIENT_TIMEOUT_S = 120.0

# Served vs reference, per session, in dB of SI-SNR.
#
# Leg (a) serves fp32 with matmuls at "highest" precision, the reference's
# own, so the two differ only by float reassociation between the streaming
# and the offline graph: 119-134 dB per session on a TPU v5e over two seeds.
# One precision step down ("high", three bf16 passes) measured 75-98 dB, the
# default (one bf16 pass) 30-52 dB; 100 dB fails both.
FP32_MIN_SI_SNR_DB = 100.0
# Leg (b) rounds weights, spectral features and masks onto FP10's 4-bit
# mantissa (about 30 dB per rounding), and with random weights the error
# compounds through the trunk: 17.8-33.0 dB per session on a TPU v5e over
# two seeds. 12 dB keeps a margin under that (the 15 dB gate of
# benchmarks/deploy_parity.py is for a trained model); a wrong kernel, a
# dropped or repeated hop or a misaligned stream lands near 0 dB.
FP10_MIN_SI_SNR_DB = 12.0
# --four-chips: the four-chip fleet and the one-chip fleet run the same
# compiled program on the same kind of chip, and a failover moves a
# session's state bit-exactly, so only float noise may separate them; a lost
# hop or a misplaced state costs tens of dB.
FOUR_CHIP_MIN_SI_SNR_DB = 60.0


class SmokeFailure(Exception):
    """A check failed: the run must exit non-zero without the ok line."""


@dataclasses.dataclass(frozen=True)
class Leg:
    name: str
    flags: tuple  # launcher flags (``repro.launch.serve.build_parser``)
    min_si_snr_db: float
    precision: Optional[str]  # jax_default_matmul_precision while serving
    hops_per_step: int = 1
    native_kernels: bool = False  # the step must hold tpu_custom_call


LEG_A = Leg("a:xla-fp32-K1", ("--backend", "xla"), FP32_MIN_SI_SNR_DB,
            precision="highest")
LEG_B = Leg("b:pallas-fp10-K4",
            ("--backend", "pallas", "--quant", "--hops-per-step", "4"),
            FP10_MIN_SI_SNR_DB, precision=None, hops_per_step=4,
            native_kernels=True)


def device_phase() -> dict:
    """Require a TPU; enable the compile cache; print what runs where."""
    import jax

    from repro.kernels import interpret_default
    from repro.launch.serve import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s); "
            "this smoke runs only on the chip"
        )
    cache = enable_compile_cache()
    interpret = interpret_default()
    print(f"device: {devs[0].device_kind} x{len(devs)} platform={devs[0].platform} "
          f"jax={jax.__version__} pallas_interpret={interpret} "
          f"compile_cache={cache}")
    if interpret:
        raise SmokeFailure("Pallas kernels would run in interpret mode on the TPU")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_audio(seed: int, sessions: int, samples: int):
    """(sessions, samples) float32 noisy speech, a pure function of seed."""
    import numpy as np

    from repro.audio.synthetic import batch_for_step

    noisy, _ = batch_for_step(seed, 0, batch=sessions, num_samples=samples)
    return np.asarray(noisy, np.float32)


def reference(params, cfg, audio):
    """fp32 ``enhance_offline`` at 'highest' matmul precision, on host."""
    import jax
    import numpy as np

    from repro.serve.streaming_se import enhance_offline

    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda w: enhance_offline(params, cfg, w))(audio)
        return np.asarray(out, np.float32)


def build_pool(leg: Leg, params, cfg, *, slots: int, shards: int,
               devices=None):
    """The launcher's gateway pool for this leg; returns (pool, compile s)."""
    from repro.launch.serve import build_parser, build_sharded_pool

    args = build_parser().parse_args(
        ["--task", "gateway", "--batch", str(slots), "--shards", str(shards),
         *leg.flags]
    )
    t0 = time.perf_counter()
    pool = build_sharded_pool(args, params, cfg, devices=devices)
    return pool, time.perf_counter() - t0


def serve(pool, audio, *, seed: int, hop: int,
          midstream: Optional[Callable] = None) -> dict:
    """Serve every row of ``audio`` as one gateway session over TCP.

    Each client thread attaches ``session-<i>``, feeds its row in jittery
    chunks (1 .. 3 hops, never a whole number of hops), reads the whole
    enhanced stream and detaches. ``midstream(gateway_thread)`` runs once
    the sessions have fed a third of their audio on average. Returns the
    outputs and the gateway's STATS.
    """
    import numpy as np

    from repro.serve.gateway import GatewayClient, GatewayThread

    n, total = audio.shape
    n_out = total // hop * hop
    outs: List[Optional[np.ndarray]] = [None] * n
    fed = [0] * n
    errors: List[str] = []

    def client(i: int, host: str, port: int) -> None:
        rng = np.random.default_rng([seed, i])
        try:
            with GatewayClient(host, port, timeout=CLIENT_TIMEOUT_S) as c:
                c.attach(f"session-{i}")
                while fed[i] < total:
                    size = int(rng.integers(1, 3 * hop))
                    if size % hop == 0:
                        size += 1
                    c.feed(audio[i, fed[i]:fed[i] + size])
                    fed[i] = min(total, fed[i] + size)
                got = c.read_until(n_out, timeout=CLIENT_TIMEOUT_S)
                outs[i] = np.concatenate([got, c.detach()])
        except Exception as e:  # reported by the main thread
            errors.append(f"session-{i}: {e!r}")

    gw = GatewayThread(pool, pump_interval=0.002)
    try:
        host, port = gw.address
        threads = [threading.Thread(target=client, args=(i, host, port),
                                    name=f"client-{i}") for i in range(n)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        if midstream is not None:
            while sum(fed) < n * total // 3 and any(t.is_alive() for t in threads):
                time.sleep(0.005)
            midstream(gw)
        for t in threads:
            t.join(timeout=4 * CLIENT_TIMEOUT_S)
        wall = time.perf_counter() - t0
        if any(t.is_alive() for t in threads):
            errors.append("a client thread did not finish")
        with GatewayClient(host, port, timeout=CLIENT_TIMEOUT_S) as c:
            stats = c.stats()
    finally:
        gw.stop()
    if errors:
        raise SmokeFailure("; ".join(errors))
    return {"outs": np.stack(outs), "stats": stats, "wall_s": wall}


def served_step_text(pool, k: int) -> str:
    """Compiled HLO text of shard 0's ``max_hops=k`` step, as served.

    Lowering with the live state and the dispatch's input shapes hits the
    executable the pool already compiled, so this costs no compile.
    """
    import jax

    from repro.serve.session_server import warm_inputs

    shard = pool._pools[0]
    inputs = warm_inputs(shard.cfg, shard.capacity, k, shard._ring_depth)
    args = [jax.device_put(x, shard.device) for x in inputs]
    return shard._step_for(k).lower(shard._state, *args).compile().as_text()


def si_snr(outs, ref):
    """Per-session SI-SNR (dB) of served audio against the reference."""
    import numpy as np

    from repro.audio.metrics import si_snr_db

    return np.asarray(si_snr_db(outs, ref), np.float64)


def check_stats(stats: dict, *, failed_over: int = 0,
                dead: tuple = ()) -> List[str]:
    """Containment counters that must be clean after a healthy run."""
    bad = []
    if stats["sessions_lost"] != 0:
        bad.append(f"sessions_lost={stats['sessions_lost']}")
    if stats["sessions_failed_over"] != failed_over:
        bad.append(f"sessions_failed_over={stats['sessions_failed_over']} "
                   f"(want {failed_over})")
    for key in ("breaker_opens", "load_shed", "sessions_poisoned",
                "frames_rejected", "sessions_quarantined"):
        if stats[key] != 0:
            bad.append(f"{key}={stats[key]}")
    if sorted(stats["dead_shards"]) != sorted(dead):
        bad.append(f"dead_shards={stats['dead_shards']} (want {list(dead)})")
    for i, shard in enumerate(stats["shards"]):
        if shard["pump_failures"] != 0:
            bad.append(f"shard {i} pump_failures={shard['pump_failures']}")
    return bad


def check_outputs(name: str, outs, ref, min_db: float) -> List[str]:
    """Finite audio of the full length, within ``min_db`` of the reference."""
    import numpy as np

    bad = []
    if outs.shape != ref.shape:
        bad.append(f"{name}: served shape {outs.shape} != reference {ref.shape}")
        return bad
    if not np.isfinite(outs).all():
        bad.append(f"{name}: non-finite audio in sessions "
                   f"{np.flatnonzero(~np.isfinite(outs).all(axis=1)).tolist()}")
        return bad
    db = si_snr(outs, ref)
    print(f"[{name}] SI-SNR vs fp32 reference: min={db.min():.2f} dB "
          f"median={np.median(db):.2f} dB threshold={min_db} dB")
    if db.min() < min_db:
        bad.append(f"{name}: SI-SNR {db.min():.2f} dB < {min_db} dB")
    return bad


def run_leg(leg: Leg, params, cfg, audio, ref, *, seed: int, kind: str,
            devices=None) -> List[str]:
    """Build, serve and check one leg on one chip; returns failures."""
    import jax

    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", leg.precision)
    try:
        pool, compile_s = build_pool(leg, params, cfg, slots=audio.shape[0],
                                     shards=1, devices=devices)
        print(f"[{leg.name}] compile+prewarm {compile_s:.2f} s on {kind}")
        res = serve(pool, audio, seed=seed, hop=cfg.hop)
        text = served_step_text(pool, leg.hops_per_step)
    finally:
        jax.config.update("jax_default_matmul_precision", old)
    report(leg.name, res, kind, cfg.hop, leg.hops_per_step)
    bad = check_outputs(leg.name, res["outs"], ref, leg.min_si_snr_db)
    bad += [f"{leg.name}: {b}" for b in check_stats(res["stats"])]
    kernels = text.count("tpu_custom_call")
    print(f"[{leg.name}] tpu_custom_call in compiled step: {kernels}")
    if leg.native_kernels and not kernels:
        bad.append(f"{leg.name}: no native Pallas kernel in the compiled step")
    return bad


def report(name: str, res: dict, kind: str, hop: int, k: int) -> None:
    shards = res["stats"]["shards"]
    hops = res["outs"].shape[0] * (res["outs"].shape[1] // hop)
    p50 = max(s["p50_ms"] for s in shards)
    p99 = max(s["p99_ms"] for s in shards)
    print(f"[{name}] served {hops} hops for {res['outs'].shape[0]} sessions "
          f"over TCP in {res['wall_s']:.2f} s on {kind}; step wall "
          f"p50={p50:.3f} ms p99={p99:.3f} ms (worst shard, up to {k} "
          f"hop(s)/step)")


def placement_failures(pool, devices) -> List[str]:
    """Each shard's weights and carried state must sit on its own device."""
    import jax

    bad = []
    for i, (shard, dev) in enumerate(zip(pool._pools, devices)):
        if i in pool.dead_shards:
            continue
        for what, tree in (("params", shard._params), ("state", shard._state)):
            where = {d for leaf in jax.tree_util.tree_leaves(tree)
                     for d in leaf.devices()}
            if where != {dev}:
                bad.append(f"shard {i} {what} on {sorted(map(str, where))}, "
                           f"want {dev}")
    return bad


def four_chips(params, cfg, seed: int, kind: str) -> List[str]:
    """Leg (b) on four shards, one per chip, with a mid-stream shard kill,
    against the same sessions on four co-located shards on one chip."""
    import jax
    import numpy as np

    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    devs = devs[:4]
    audio = make_audio(seed, FOUR_CHIP_SESSIONS, SAMPLES)
    ref = reference(params, cfg, audio)
    # twice the sessions in slots: one dead shard's residents must fit on
    # the three survivors after the failover
    slots = 2 * FOUR_CHIP_SESSIONS
    bad: List[str] = []
    killed = {}

    def kill(gw) -> None:
        def busiest_down(p):  # one call: no session may detach in between
            active = [s["active"] for s in p.shard_stats()]
            victim = max(range(len(active)), key=active.__getitem__)
            p.kill_shard(victim)
            return victim, active[victim]

        victim, residents = gw.call(busiest_down)
        killed.update(shard=victim, residents=residents)
        print(f"[four-chips] killed shard {victim} on {devs[victim]} with "
              f"{residents} live sessions")

    runs = {}
    for name, devices, midstream in (("4-chip", devs, kill),
                                     ("1-chip", devs[:1], None)):
        pool, compile_s = build_pool(LEG_B, params, cfg, slots=slots,
                                     shards=4, devices=devices)
        print(f"[four-chips {name}] compile+prewarm {compile_s:.2f} s on "
              f"{len(devices)} x {kind}")
        if name == "4-chip":
            bad += placement_failures(pool, devices)
            print("[four-chips] shard devices: "
                  + ", ".join(str(p.device) for p in pool._pools))
        res = serve(pool, audio, seed=seed, hop=cfg.hop, midstream=midstream)
        report(f"four-chips {name}", res, kind, cfg.hop, LEG_B.hops_per_step)
        bad += check_outputs(f"four-chips {name}", res["outs"], ref,
                             LEG_B.min_si_snr_db)
        if name == "4-chip":
            bad += placement_failures(pool, devices)
            st = res["stats"]
            print(f"[four-chips] failover: sessions_failed_over="
                  f"{st['sessions_failed_over']} sessions_lost="
                  f"{st['sessions_lost']} dead_shards={st['dead_shards']} "
                  f"failover_log={pool.failover_log}")
            bad += [f"4-chip: {b}" for b in check_stats(
                st, failed_over=killed["residents"], dead=(killed["shard"],))]
            if killed["residents"] < 1:
                bad.append("4-chip: the killed shard held no session")
        else:
            bad += [f"1-chip: {b}" for b in check_stats(res["stats"])]
        runs[name] = res["outs"]
    diff = np.abs(runs["4-chip"] - runs["1-chip"])
    db = si_snr(runs["4-chip"], runs["1-chip"])
    print(f"[four-chips] 4-chip vs 1-chip: max abs diff={diff.max():.3e} "
          f"min SI-SNR={db.min():.2f} dB threshold={FOUR_CHIP_MIN_SI_SNR_DB} dB")
    if db.min() < FOUR_CHIP_MIN_SI_SNR_DB:
        bad.append(f"4-chip vs 1-chip SI-SNR {db.min():.2f} dB < "
                   f"{FOUR_CHIP_MIN_SI_SNR_DB} dB")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the audio")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-shard, four-chip failover leg")
    args = ap.parse_args(argv)
    try:
        device = device_phase()
        import jax

        from repro.models import tftnn as tft

        cfg = tft.tftnn_config()
        params = tft.init_tft(jax.random.PRNGKey(args.seed), cfg)
        kind = device["kind"]
        if args.four_chips:
            bad = four_chips(params, cfg, args.seed, kind)
        else:
            audio = make_audio(args.seed, SESSIONS, SAMPLES)
            ref = reference(params, cfg, audio)
            bad = []
            for leg in (LEG_A, LEG_B):
                bad += run_leg(leg, params, cfg, audio, ref, seed=args.seed,
                               kind=kind, devices=jax.devices()[:1])
    except (SmokeFailure, ImportError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if bad:
        for b in bad:
            print(f"chip_smoke: FAILED: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
