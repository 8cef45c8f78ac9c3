"""Knee sweep: one cell's traffic at several session counts, in one process.

    python bench/sweep.py --workload fleet-rt-fp32 --sessions 4,6,8 \
        --seconds 8 --seed 11 [--out chiprun_out/sweep.jsonl]

For each count the cell runs as ``run.py`` runs it, with the mix's
``sessions`` set to that count, and one JSON line per
count reports the hop latency percentiles, the failed hops and how late the
generator ran. The knee is the highest count whose ``hop_p95_ms`` stays at
or under one hop period (16 ms) on every seed not hit by a machine stall,
with no failed hop and no growing backlog; the cell's mix file then holds
4/5 of it. Every count runs the same seeds, so the served step compiles once
per seed. This tool is not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import hops  # noqa: E402
import readers  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sessions", required=True, help="comma list of counts")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=1, help="seeds per count")
    ap.add_argument("--out", default="")
    ap.add_argument("--stop-ms", type=float, default=200.0,
                    help="stop once hop_p95_ms passes this (far past the knee)")
    args = ap.parse_args(argv)
    _, cell, config, mix = run.load_cell(args.workload)
    counts = [int(v) for v in args.sessions.split(",")]
    for n, rep in ((n, r) for n in counts for r in range(args.repeats)):
        m = dict(mix, sessions=n)
        out = run.run(cell, config, m, seed=args.seed + 100 * rep,
                      seconds=args.seconds, traced=False)
        s, ctx = out["ctx"].summary, out["ctx"]
        lat = s["latency_ms"]
        row = {"sessions": n, "hop_p50_ms": hops.percentile(lat, 50) if lat else None,
               "hop_p95_ms": hops.percentile(lat, 95) if lat else None,
               "hop_p99_ms": hops.percentile(lat, 99) if lat else None,
               "hop_max_ms": max(lat) if lat else None,
               "attempted": s["attempted"], "failed": s["failed"],
               "audio_throughput": readers.audio_throughput(ctx),
               "hops_per_step": readers.hops_per_step(ctx), "step_ms": readers.step_ms(ctx),
               "pump_ticks_per_hop": readers.pump_ticks_per_hop(ctx),
               "loop_lag_ms": s["loop_lag_ms"], "loop_stalls": s["loop_stalls"],
               "send_lateness_ms": s["send_lateness_ms"],
               "correct": out["correct"], "checked": out["numbers"]}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        if row["hop_p95_ms"] is None or row["hop_p95_ms"] > args.stop_ms:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
