"""Dev tool: one run of a cell with the program's own spans read from its
trace, or with the program recording throughout.

    python bench/timeline.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--record 0|1]

Runs the cell as ``run.py`` does and prints its result line, one JSON
object, with two more keys: ``program``, in traced runs, is
``program.reduce`` of the same trace (device idle time by the program's
spans, the clock check, the host-bound idle share); ``record`` is 1 where
the program recorded its spans and hop timeline for the whole run
(``obs.enable()``), which with ``--trace 0`` measures what recording costs
with the profiler off. Not the benchmark's command: it never changes what
``run.py`` reports.
"""

from __future__ import annotations

import argparse
import json
import sys

import run  # noqa: I001  (puts bench/ and src/ on the path)
import program
import tracing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--record", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = run.load_cell(args.workload)
    kept = {}
    reduce = tracing.reduce

    def both(profile, **kw):
        kept["program"] = program.reduce(profile, chips=kw.get("chips", 1),
                                         window_span=kw.get("window_span"))
        return reduce(profile, **kw)

    tracing.reduce = both
    if args.record:
        program.obs().enable()
    try:
        out = run.run(cell, config, mix, seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace))
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    line = run.result_line(bench, cell, out, bool(args.trace))
    line.update(program=kept.get("program"), record=args.record)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
