"""The readers of the program's own records (``program.py``): the traced
rehearsal reports them, a program without ``repro.serve.obs`` leaves them
out, and the reduction over the program's spans partitions idle time."""

import gzip
import shutil
import sys

import pytest

import program
import run
import tracing
from test_tracing import FIXTURE

NEW = {
    "fleet-rt-fp32": {"hop_server_ms.rt", "hop_wait_ms.rt", "hop_unread_ms.rt"},
    "offline-fp10": {"collect_readback_ms.offline", "collect_deliver_ms.offline"},
}


def _traced_line(tiny_cell, name):
    bench, _, _, _ = run.load_cell(name)
    cell, config, mix = tiny_cell(name)
    out = run.run(cell, config, mix, seed=2**31 + 3, seconds=2.5, traced=True,
                  require_tpu=False)
    assert out["correct"], out["numbers"]
    return run.result_line(bench, cell, out, traced=True)


@pytest.fixture
def fresh_records():
    obs = program.obs()
    obs.disable()
    obs.reset()
    yield
    obs.reset()


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_rehearsal_reports_the_program_metrics(tiny_cell, fresh_records, name):
    metrics = _traced_line(tiny_cell, name)["metrics"]
    assert NEW[name] <= set(metrics)
    assert all(metrics[m]["value"] > 0 for m in NEW[name])
    # recording stopped with the profiler
    assert not program.obs().recording()


def test_without_obs_only_the_new_metrics_go(tiny_cell, fresh_records, monkeypatch):
    name = "fleet-rt-fp32"
    with_obs = _traced_line(tiny_cell, name)
    program.obs().reset()
    monkeypatch.setitem(sys.modules, "repro.serve.obs", None)  # a program without it
    assert program.obs() is None and program.hop_part_ms("server") is None
    without = _traced_line(tiny_cell, name)
    assert set(with_obs["metrics"]) - set(without["metrics"]) == NEW[name]
    assert set(without) == set(with_obs)
    assert without["checked"] == with_obs["checked"]


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "fp10_step.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.load(str(path))


def test_program_spans_partition_idle_time(profile):
    red = program.reduce(profile)
    idle = red["idle_by_program_span"]
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(idle) <= set(program.span_names()) | {"no_span"}
    assert red["host_bound_idle_pct"] + red["loop_wait_idle_pct"] == pytest.approx(
        100.0 * (1 - red["busy_s"] / red["window_s"]))
    # the fixture's one step starts before its dispatch span: a lead is found
    assert red["clock"]["inside"] == 0 and red["clock"]["inside_after"] == 1
    assert red["busy_s"] == pytest.approx(
        tracing.reduce(profile, window_span="bench_window")["busy_s"])


def test_idle_is_split_at_span_boundaries():
    spans = [(0, 100, "pump_all"), (10, 60, "dispatch"), (10, 20, "ring_write"),
             (70, 90, "collect"), (200, 300, "loop_wait")]
    gaps = [(5, 80), (150, 250)]  # ns
    got = program.split_idle(gaps, spans)
    want = {"pump_all": 5 + 10, "ring_write": 10, "dispatch": 40, "collect": 10,
            "no_span": 50, "loop_wait": 50}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})


def test_clock_check_finds_a_lead():
    pairs = [(0, 100), (200, 300), (400, 500)]
    steps = [(10, 90), (210, 290), (410, 490)]
    assert program.clock_check(steps, pairs) == {
        "steps": 3, "inside": 3, "offset_ns": 0.0, "inside_after": 3}
    late = [(a + 50, b + 50) for a, b in steps]  # device clock 50 ns ahead
    got = program.clock_check(late, pairs)
    assert got["inside"] == 0 and got["inside_after"] == 3
    assert 40 <= got["offset_ns"] <= 60


def test_nested_twins_count_once():
    spans = [(0, 10, "collect"), (1, 9, "collect"), (2, 3, "readback"), (20, 30, "collect")]
    assert program._outermost(spans) == [(0, 10, "collect"), (2, 3, "readback"),
                                         (20, 30, "collect")]
    assert program.step_pairs([(0, 1, "dispatch"), (2, 3, "dispatch"), (4, 9, "wait_ready"),
                               (10, 11, "dispatch")]) == [(2, 9)]
