"""The harness end to end on the CPU at a tiny size, and its refusals.

The rehearsals drive ``run.run`` with ``require_tpu=False``: the launcher's
pool, the gateway on its thread, the load generator in a child process, and
the check against the plain reference. The fault cases break the served
step underneath the pool and must come out not correct.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run
from conftest import BENCH, ROOT


def _no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "fleet-rt-fp32",
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "offline-fp10",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_every_cell_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))


@pytest.mark.parametrize("cell_name", ["fleet-rt-fp32", "offline-fp10"])
def test_cpu_rehearsal_is_correct(tiny_cell, cell_name):
    cell, config, mix = tiny_cell(cell_name)
    out = run.run(cell, config, mix, seed=2**31 + 3, seconds=1.5, traced=False,
                  require_tpu=False)
    s = out["ctx"].summary
    assert out["correct"], out["numbers"]
    assert s["attempted"] > 0 and s["failed"] == 0
    assert out["numbers"]["streams"] >= mix["sessions"]
    assert out["compiles_in_window"] == 0


def _break(kind):
    """A ``make_stream_hop`` whose step has one fault."""
    from repro.serve import session_server

    orig = session_server.make_stream_hop

    def make(*a, **kw):
        step = orig(*a, **{**kw, "donate": False})

        def broken(state, *args):
            new, out = step(state, *args)
            if kind == "state_unchanged":
                return state, out
            if kind == "half_batch_dropped":
                return new, out.at[1::2].set(0.0)
            return new, out.at[0].multiply(-1.0)  # one slot's answer altered

        return broken

    return make


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch_dropped", "answer_altered"])
@pytest.mark.parametrize("cell_name", ["fleet-rt-fp32", "offline-fp10"])
def test_broken_step_is_not_correct(tiny_cell, monkeypatch, cell_name, kind):
    from repro.serve import session_server

    monkeypatch.setattr(session_server, "make_stream_hop", _break(kind))
    cell, config, mix = tiny_cell(cell_name)
    out = run.run(cell, config, mix, seed=2**31 + 5, seconds=1.5, traced=False,
                  require_tpu=False)
    assert not out["correct"], out["numbers"]
    assert np.isfinite(out["numbers"]["err_energy_max"])
