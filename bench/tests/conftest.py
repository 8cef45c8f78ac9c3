"""Benchmark harness tests: ``pytest bench/tests`` from the repository root.

CPU-only except ``test_control.py``, which needs the chip. The CPU
rehearsals run the harness at a tiny model size through ``run.run`` with
``require_tpu=False``; they never print a device metric.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# Two K=4 steps per closed-loop chunk, so a state left unchanged shows; a
# 64-slot step takes ~0.15 s in interpret mode, one round of 64 seats ~20 s.
CPU_CHUNK = 1024


@pytest.fixture
def tiny_cell():
    """(cell, config, mix) of a named cell, cut to a size the CPU can run.

    The model's widths (its family's ``TINY``), the streams' lengths and the
    closed loop's chunks are cut; the mix's seats and slots stay as
    committed, so the check sees the same slots in use as a run on the chip.
    """
    import run

    def make(name, **mix_kw):
        _, cell, config, mix = run.load_cell(name)
        config = json.loads(json.dumps(config))
        config["model"].update(run.load_family(config["family"]).TINY)
        mix = dict(mix, warmup_s=0.0, length_median_s=1.5, length_cap_s=3.0,
                   chunk_samples=min(mix["chunk_samples"], CPU_CHUNK), **mix_kw)
        return cell, config, mix

    return make
