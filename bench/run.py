"""Benchmark harness: one cell of ``BENCHMARK.json`` per process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell as a user runs the system: weights made on the chip from the
seed, the pool built by the launcher's own ``build_sharded_pool`` from the
configuration's launcher flags, served by a ``StreamingGateway`` on a
``GatewayThread`` in this process, and driven over localhost TCP by
``loadgen.py`` in a child process that never loads JAX. After the measured
window the served audio is compared with the model family's plain
reference (``check.py``).

Everything cell-specific is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, the configuration's
model family (program config, weight shapes, plain reference, MACs a hop)
in ``bench/models/<family>.py``, its traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py`` and each Pallas kernel's operation count in
``bench/kernels/<kernel>.py`` (``flops.kernel_cost``).

The last line of stdout is the result, one JSON object; the numbers the
check compared are the last lines of stderr. Without a TPU, with fewer chips
than the cell asks for, or with Pallas in interpret mode, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import loader  # noqa: E402

FAMILY_API = ("program_config", "param_shapes", "enhance", "macs_per_hop", "TINY")
TRACE_LEAD_S = 1.0  # into the window before the profiler starts
TRACE_S = 1.0  # traced seconds: the device runs ~1e6 ops/s, all of them traced
CHILD_GRACE_S = 30.0


class NoChip(RuntimeError):
    """The machine cannot run the cell as asked: no result is printed."""


@dataclasses.dataclass
class Context:
    """What a metric reader may read (``bench/metrics/*.py``)."""

    summary: dict  # the load generator's result.json
    steps: List[List[float]]  # per shard, the step seconds of the window
    pump_ticks: int  # gateway heartbeat ticks in the window
    trace: Optional[dict]  # tracing.reduce() of the traced window
    flops_per_hop: float  # the model family's analytic count
    sample_rate: int
    chips: int
    peaks: dict
    setup_s: float


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str):
    """(benchmark, cell, config, mix) for a cell named in BENCHMARK.json.

    Stops with a message where the configuration's model family cannot be
    found, or its mix and configuration state different sample rates.
    """
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    load_family(config["family"])  # stops here where the family is not whole
    if mix["sample_rate"] != config["sample_rate"]:
        raise SystemExit(
            f"cell {name!r}: traffic {cell['traffic']!r} is at {mix['sample_rate']} Hz, "
            f"configuration {cell['config']!r} at {config['sample_rate']} Hz")
    return bench, cell, config, mix


def load_family(name: str):
    """The model family module ``bench/models/<name>.py``, which gives:

    - ``program_config(model)``: the object the launcher's
      ``build_sharded_pool`` serves, from a configuration's ``model`` dict;
    - ``param_shapes(cfg)``: the weight tree as shapes, which
      ``weights.make_params`` fills from the seed;
    - ``enhance(params, model, waves, grid, precision)``: the plain
      reference, each wave enhanced as a fresh stream and aligned as the
      server emits it;
    - ``macs_per_hop(model)``: the analytic multiply-adds of one hop;
    - ``TINY``: width cuts of ``model`` that the CPU rehearsal runs at.
    """
    path = BENCH / "models" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"model family {name!r}: no {path.relative_to(ROOT)}")
    mod = loader.load(path)
    missing = [a for a in FAMILY_API if not hasattr(mod, a)]
    if missing:
        raise SystemExit(f"model family {name!r} ({path.relative_to(ROOT)}) lacks {missing}")
    return mod


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end untraced, per-layer traced."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    return loader.load(BENCH / "metrics" / f"{name}.py").read


def device_phase(chips: int, require_tpu: bool) -> dict:
    """Check the devices; turn on the compile cache; returns ``device``."""
    import jax

    from repro.kernels import interpret_default

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
        if interpret_default():
            raise NoChip("Pallas kernels would run in interpret mode")
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "compile_cache": cache}


class CompileCounter:
    """Counts the compilations (and cache loads) JAX reports, with times."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


class GcPauses:
    """Garbage collections in this process, the serving one: counts by
    generation, and each collection of ``MIN_S`` or more with its time."""

    MIN_S = 0.005

    def __init__(self):
        self.counts = [0, 0, 0]
        self.long: List[tuple] = []  # (end time, generation, seconds)
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._t = now
            return
        self.counts[info["generation"]] += 1
        if now - self._t >= self.MIN_S:
            self.long.append((now, info["generation"], now - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def window(self, t0: float, t1: float, counts0, counts1) -> dict:
        """The window's collections: counts by generation, and the long ones
        as (seconds into the window, generation, ms)."""
        return {"by_generation": [b - a for a, b in zip(counts0, counts1)],
                "long_ms": [[round(t - t0, 3), g, round(s * 1e3, 3)]
                            for t, g, s in self.long if t0 <= t < t1]}


def build_pool(config: dict, mix: dict, chips: int, params, cfg):
    """The launcher's gateway pool for this configuration and mix."""
    import jax

    from repro.launch.serve import build_parser, build_sharded_pool

    flags = config["serving"]["launcher_flags"]
    args = build_parser().parse_args(
        ["--task", "gateway", "--batch", str(mix["slots_per_chip"] * chips),
         "--shards", str(chips), *flags])
    return build_sharded_pool(args, params, cfg, devices=jax.devices()[:chips])


def snapshot(gw) -> dict:
    """Counters at one instant, read on the gateway's own thread."""

    def read(pool):
        return {"pump_ticks": gw.gateway.pump_ticks,
                "steps": [list(getattr(p, "step_seconds", [])) for p in pool._pools]}

    return gw.call(read)


def server_counters(gw) -> dict:
    keys = ("sessions_lost", "sessions_failed_over", "breaker_opens",
            "sessions_quarantined", "watchdog_failovers")

    def read(pool):
        out = {k: getattr(pool, k, 0) for k in keys}
        out.update(load_shed=gw.gateway.load_shed,
                   frames_rejected=gw.gateway.frames_rejected,
                   sessions_poisoned=gw.gateway.sessions_poisoned,
                   dead_shards=list(pool.dead_shards))
        return out

    return gw.call(read)


def sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def start_trace(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotations only
    jax.profiler.start_trace(path, profiler_options=opts)


def run(cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
        traced: bool, require_tpu: bool = True, quant=None,
        program_precision: Optional[str] = None) -> dict:
    """Serve one cell and check it; returns what the result line needs.

    ``require_tpu=False`` is the tests' CPU rehearsal. ``quant`` (the
    serving grid of a ``--quant`` configuration) and ``program_precision``
    (the matmul precision the program serves at) change the program only,
    never the reference: they make the control's lower precision.
    """
    chips = cell["chips"]
    device = device_phase(chips, require_tpu)
    import jax
    import numpy as np

    from repro.serve.gateway import GatewayThread

    import check
    import peaks as peak_table
    import synth
    import tracing
    import weights

    peaks = peak_table.peaks(device["kind"]) if require_tpu else \
        peak_table.PEAKS["TPU v5 lite"]
    phases = {"jax_ready": time.monotonic() - T_PROCESS}
    compiles = CompileCounter()
    gcs = GcPauses()
    family = load_family(config["family"])
    model = dict(config["model"])
    cfg = family.program_config(model)
    stated = config["serving"]["matmul_precision"]  # None: the TPU default, one bf16 pass
    old_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", program_precision or stated)
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    child = None
    try:
        params = jax.block_until_ready(weights.make_params(seed, family.param_shapes(cfg)))
        phases["weights"] = time.monotonic() - T_PROCESS
        if quant is not None:
            import repro.core.quant as q

            saved, q.FP10 = q.FP10, quant
        try:
            pool = build_pool(config, mix, chips, params, cfg)
        finally:
            if quant is not None:
                q.FP10 = saved
        phases["pool_built"] = time.monotonic() - T_PROCESS
        if traced:
            tracing.install_spans(pool)
        gw = GatewayThread(pool)
        host, port = gw.address
        (work / "mix.json").write_text(json.dumps(mix))
        child = subprocess.Popen(
            [sys.executable, str(BENCH / "loadgen.py"), "--host", host,
             "--port", str(port), "--traffic", str(work / "mix.json"),
             "--seed", str(seed), "--seconds", str(seconds), "--hop", str(model["hop"]),
             "--out", str(work)],
            stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        line = child.stdout.readline().split()
        if not line or line[0] != "WINDOW":
            raise RuntimeError(f"load generator failed to start: {line!r}")
        t0, t1 = float(line[1]), float(line[2])
        phases["traffic_started"] = time.monotonic() - T_PROCESS
        phases["window_start"] = t0 - T_PROCESS
        print("bench: set-up phases, s since process start: "
              + ", ".join(f"{k} {v:.2f}" for k, v in phases.items()), file=sys.stderr)
        snaps = {}

        def take_snapshots():
            for key, t in (("start", t0), ("end", t1)):
                sleep_until(t)
                snaps[key] = dict(snapshot(gw), gc=list(gcs.counts))

        snapper = threading.Thread(target=take_snapshots, name="snapshots")
        snapper.start()
        setup_s = t0 - T_PROCESS
        red = None
        if traced:
            tdir = str(work / "trace")
            sleep_until(t0 + TRACE_LEAD_S)
            start_trace(tdir)
            with jax.profiler.TraceAnnotation("bench_window"):
                time.sleep(TRACE_S)
            jax.profiler.stop_trace()
        snapper.join()
        c0, c1 = snaps["start"], snaps["end"]
        rest, _ = child.communicate(timeout=t1 - time.monotonic() + 60 + CHILD_GRACE_S)
        sys.stdout.write(rest)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        counters = server_counters(gw)
        gw.stop()
        mem = 0
        for d in jax.devices()[:chips]:
            stats = d.memory_stats() or {}
            mem = max(mem, int(stats.get("peak_bytes_in_use", 0)))
        summary = json.loads((work / "result.json").read_text())
        steps = [s1[len(s0):] for s0, s1 in zip(c0["steps"], c1["steps"])]
        del pool, gw
        gc.collect()
        if traced:
            prof = tracing.load(tdir)
            red = tracing.reduce(prof, chips=chips, window_span="bench_window")
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = Context(summary=summary, steps=steps,
                      pump_ticks=c1["pump_ticks"] - c0["pump_ticks"], trace=red,
                      flops_per_hop=2.0 * family.macs_per_hop(model),
                      sample_rate=config["sample_rate"], chips=chips,
                      peaks=peaks, setup_s=setup_s)
        # the check: every stream against the plain reference
        bank = synth.bank(seed, mix["sample_rate"])
        audio = np.load(work / "streams.npz")
        served, fed = [], []
        for st in summary["streams"]:
            if not st["refused"]:
                served.append(audio[f"{st['seat']}.{st['index']}"])
                fed.append(synth.stream(bank, st["offset"], st["fed"]))
        ref = family.enhance(params, model, fed, grid=config.get("reference_grid"),
                             precision=stated or "default") if fed else []
        numbers = check.compare(served, ref)
        errors = [st["error"] for st in summary["streams"] if st["error"]]
        undelivered = sum(st["undelivered_hops"] for st in summary["streams"])
        limits = config["check"]
        correct = check.verdict(numbers, limits) and not errors and undelivered == 0
        return {
            "ctx": ctx, "numbers": numbers, "limits": limits, "correct": correct,
            "errors": errors, "undelivered_hops": undelivered,
            "compiles_in_window": compiles.between(t0, t1),
            "gc_in_window": gcs.window(t0, t1, c0["gc"], c1["gc"]),
            "server": counters, "memory_peak_bytes": mem, "device": device,
        }
    finally:
        gcs.close()
        jax.config.update("jax_default_matmul_precision", old_precision)
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        shutil.rmtree(work, ignore_errors=True)


def result_line(bench: dict, cell: dict, out: dict, traced: bool) -> dict:
    import hops

    ctx = out["ctx"]
    metrics: Dict[str, dict] = {}
    for m in cell_metrics(bench, cell["name"], traced):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = out["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": ctx.summary["attempted"],
            "failed": ctx.summary["failed"], "metrics": metrics, "device": device}
    if traced and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        import tracing

        line["breakdown"] = tracing.breakdown(ctx.trace)
    lat = ctx.summary["latency_ms"]
    if lat:  # the tail, for readers of the line; the benchmark bounds the median
        line["hop_latency_ms"] = {f"p{q}": hops.percentile(lat, q) for q in (50, 95, 99, 99.9)}
        line["hop_latency_ms"]["max"] = max(lat)
    line["compiles_in_window"] = out["compiles_in_window"]
    line["gc_in_window"] = out["gc_in_window"]
    line["server"] = out["server"]
    line["checked"] = {k: {"value": out["numbers"][k], "limit": v}
                       for k, v in out["limits"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    try:
        out = run(cell, config, mix, seed=args.seed, seconds=args.seconds,
                  traced=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    line = result_line(bench, cell, out, bool(args.trace))
    n = out["numbers"]
    print(f"bench: compared {n['streams']} streams, {n['samples']} samples "
          f"(err_energy_max {n['err_energy_max']!r}, median {n['err_energy_median']!r}); "
          f"compiles in window: {out['compiles_in_window']}; garbage collections in "
          f"window: {out['gc_in_window']}; server: {out['server']}",
          file=sys.stderr)
    if out["errors"] or out["undelivered_hops"]:
        print(f"bench: stream errors {out['errors'][:5]}; undelivered hops "
              f"{out['undelivered_hops']}", file=sys.stderr)
    for k, v in line["checked"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
