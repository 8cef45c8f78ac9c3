"""chip_smoke.py rehearsed on the CPU, at the reduced trunk.

The smoke itself runs only on a TPU; here its phases run on the CPU device
(Pallas in interpret mode) so that its control flow — launcher-built pools,
the gateway on its own thread, TCP clients on theirs, the reference and the
STATS checks — is exercised on every test run. ``main()`` must refuse a
non-TPU device and print no ``ok`` line.
"""

import dataclasses
import importlib.util
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.launch import serve as launch
from repro.models import tftnn as tft

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small(cs):
    """Reduced trunk, 2 sessions of 0.5 s, and their fp32 reference."""
    cfg = launch.reduced_cfg(tft.tftnn_config())
    params = tft.init_tft(jax.random.PRNGKey(0), cfg)
    audio = cs.make_audio(0, 2, 4000)
    return cfg, params, audio, cs.reference(params, cfg, audio)


def test_main_refuses_a_host_without_tpu(cs, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


@pytest.mark.parametrize("leg", ["a", "b"])
def test_leg_serves_over_tcp_and_passes_its_checks(cs, small, leg):
    cfg, params, audio, ref = small
    spec = {"a": cs.LEG_A, "b": cs.LEG_B}[leg]
    if leg == "b":
        # random weights on the reduced trunk run FP10 further from fp32
        # than the published widths do (12.4 dB vs >= 17.8 dB per session)
        spec = dataclasses.replace(spec, min_si_snr_db=10.0)
    bad = cs.run_leg(spec, params, cfg, audio, ref, seed=0, kind="cpu",
                     devices=jax.devices()[:1])
    # the CPU runs the deploy step's kernels interpreted, so leg (b) must
    # report that and nothing else; every other check has to pass
    want = [] if leg == "a" else [
        f"{spec.name}: no native Pallas kernel in the compiled step"
    ]
    assert bad == want


def _clean_stats():
    return {
        "sessions_lost": 0, "sessions_failed_over": 0, "breaker_opens": 0,
        "load_shed": 0, "sessions_poisoned": 0, "frames_rejected": 0,
        "sessions_quarantined": 0, "dead_shards": [],
        "shards": [{"pump_failures": 0}, {"pump_failures": 0}],
    }


@pytest.mark.parametrize(
    "key, value",
    [("sessions_lost", 1), ("sessions_failed_over", 2), ("breaker_opens", 1),
     ("load_shed", 3), ("dead_shards", [1])],
)
def test_check_stats_flags_each_counter(cs, key, value):
    assert cs.check_stats(_clean_stats()) == []
    stats = _clean_stats()
    stats[key] = value
    assert cs.check_stats(stats)


def test_check_stats_flags_a_pump_failure(cs):
    stats = _clean_stats()
    stats["shards"][1]["pump_failures"] = 1
    assert cs.check_stats(stats) == ["shard 1 pump_failures=1"]


def test_check_outputs_rejects_nan_and_drift(cs):
    ref = np.sin(np.linspace(0, 60, 2000, dtype=np.float32))[None].repeat(2, 0)
    assert cs.check_outputs("x", ref.copy(), ref, 60.0) == []
    nan = ref.copy()
    nan[1, 7] = np.nan
    assert "non-finite" in cs.check_outputs("x", nan, ref, 60.0)[0]
    noisy = ref + 0.01 * np.cos(np.arange(2000, dtype=np.float32) * 3.1)
    assert "SI-SNR" in cs.check_outputs("x", noisy, ref, 60.0)[0]
    assert "shape" in cs.check_outputs("x", ref[:, :-128], ref, 60.0)[0]


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    was = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv(launch.CACHE_ENV, str(tmp_path))
    else:
        monkeypatch.delenv(launch.CACHE_ENV, raising=False)
    try:
        where = launch.enable_compile_cache()
        if env_set:
            assert where == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was  # JAX's own
        else:
            assert where == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_ok_line_is_the_contract(cs, small, monkeypatch, capsys):
    """With the device phase and the legs stubbed, ``main`` ends on exactly
    the contract line."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(cs, "device_phase", lambda: device)
    monkeypatch.setattr(cs, "run_leg", lambda *a, **k: [])
    monkeypatch.setattr(cs, "reference", lambda params, cfg, audio: audio)
    monkeypatch.setattr(cs, "make_audio", lambda *a: np.zeros((1, 128), np.float32))
    assert cs.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}
