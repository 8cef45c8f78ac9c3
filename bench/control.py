"""Readings that set a cell's check limit: sound runs and the control.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 8 \
        [--sound] [--out chiprun_out/control.jsonl]

Runs the cell on each seed as ``run.py`` does and prints, per seed, the
numbers the check compares. With ``--sound`` the program runs as its
configuration states (the lower readings); without it, as the
configuration's ``control`` entry says: the next precision down, which a
sound check must fail: ``matmul_precision: high`` (three bf16 passes) for
fp32 served at ``highest``, or the program's own FP8 grid for FP10. The
control changes the program only; the reference stays at the stated
precision. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def control_overrides(config: dict) -> dict:
    """``run.run``'s keyword arguments that make the program the control."""
    ctl = config["control"]
    if "quant" in ctl:
        import repro.core.quant as q

        return {"quant": getattr(q, ctl["quant"])}
    return {"program_precision": ctl["matmul_precision"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--sound", action="store_true", help="run as configured")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    _, cell, config, mix = run.load_cell(args.workload)
    kw = {} if args.sound else control_overrides(config)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run(cell, config, mix, seed=seed, seconds=args.seconds,
                      traced=False, **kw)
        row = {"workload": cell["name"], "seed": seed,
               "kind": "sound" if args.sound else "control",
               "correct": out["correct"], **out["numbers"],
               "attempted": out["ctx"].summary["attempted"],
               "failed": out["ctx"].summary["failed"]}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
