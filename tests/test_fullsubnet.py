"""FullSubNet through the serving seam, on the CPU at the benchmark's tiny widths.

The yardstick is the one plain reference in ``bench/models/fullsubnet.py``,
loaded by file path: it lives with the benchmark, where the program cannot
move it, and this file keeps no second copy.

- (a) ``stream_step`` frame by frame equals the offline ``apply`` shifted by
  the look-ahead, and ``enhance_streaming`` equals ``enhance_offline``;
- (b) audio served by ``SessionPool`` (K=1 and K=4, with attach/detach
  churn in other slots) and by the launcher's ``build_sharded_pool`` equals
  the reference;
- (c) a session moved mid-stream as a wire ticket, or rebuilt from a
  durability snapshot, continues bit-identically;
- (d) Pallas, ``--quant`` and ``--prune-keep`` are refused by name;
- (e) brownout passthrough hops and model hops emit with the same delay;

and the move of TFTNN's mask into its module left TFTNN's hop bit-identical
(outputs recorded before the move, ``fixtures/tftnn_tiny_hops.npz``).
"""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import FP10
from repro.launch import serve as launch
from repro.models import fullsubnet as fsn
from repro.models import tftnn as tft
from repro.serve import SessionPool, decode_ticket, encode_ticket
from repro.serve.durability import DurabilityManager, recover_session
from repro.serve.streaming_se import (
    enhance_offline,
    enhance_streaming,
    init_stream,
    make_stream_hop,
    stream_hop,
)

ROOT = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "tftnn_tiny_hops.npz"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location("bench_models_fullsubnet", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(ROOT / "bench" / "models" / "fullsubnet.py")
CONFIG = json.loads((ROOT / "bench" / "configs" / "fullsubnet-fp32.json").read_text())
MODEL = {**CONFIG["model"], **REF.TINY}
CFG = REF.program_config(MODEL)
PARAMS = fsn.init_params(jax.random.PRNGKey(0), CFG)
HOP = CFG.hop
DELAY = CFG.n_fft - CFG.hop + CFG.look_ahead * CFG.hop  # 768 samples
# Served audio against the reference: both run the same float32 arithmetic
# in a different order (matmul sums, |z| as hypot or sqrt, the complex
# product), so they differ by float32 rounding, which the LSTMs' bounded
# gates do not amplify: the relative error energy reads ~1e-14. A hop out of
# place, a lost state or a wrong delay reads ~1. 1e-10 sits between.
ERR_MAX = 1e-10


def _audio(seed: int, hops: int, extra: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (0.3 * rng.standard_normal(hops * HOP + extra)).astype(np.float32)


def _err(served: np.ndarray, ref: np.ndarray) -> float:
    r = ref[: served.size].astype(np.float64)
    return float(np.sum((served - r) ** 2) / np.sum(r * r))


def _reference(wave: np.ndarray) -> np.ndarray:
    return REF.enhance(PARAMS, MODEL, [wave], None, "highest", batch=2, block_frames=16)[0]


# -- (a) streaming equals offline ---------------------------------------------

@pytest.mark.parametrize("level", ["spectra", "waveform"])
def test_stream_step_equals_offline_apply(level):
    B, T = 2, 14
    if level == "spectra":
        spec = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (B, CFG.freq_bins, T, 2))
        st = fsn.init_stream_state(PARAMS, CFG, B)
        _, est = jax.lax.scan(lambda s, f: fsn.stream_step(PARAMS, s, f, CFG), st,
                              jnp.moveaxis(spec, 2, 0))
        got = np.asarray(jnp.moveaxis(est, 0, 2))  # (B, F, T)
        off = np.asarray(fsn.apply_crm(spec, fsn.apply(PARAMS, spec, CFG)))
        La = CFG.look_ahead
        assert np.all(got[:, :, :La] == 0)  # nothing held yet
        np.testing.assert_allclose(got[:, :, La:], off[:, :, :T - La],
                                   rtol=1e-4, atol=1e-5 * np.abs(off).max())
    else:
        wave = jnp.asarray(np.stack([_audio(2, T), _audio(3, T)]))
        ys = np.asarray(enhance_streaming(PARAMS, CFG, wave))
        yo = np.asarray(enhance_offline(PARAMS, CFG, wave))
        np.testing.assert_allclose(ys, yo, atol=1e-5 * np.abs(yo).max())


# -- (b) served audio equals the reference --------------------------------------

def _launcher_args(*flags):
    return launch.build_parser().parse_args(
        ["--task", "gateway", "--model", "fullsubnet", "--reduced", "--batch", "4",
         "--shards", "1", *flags])


def _serve_with_churn(pool, wave, seed):
    """Feed ``wave`` in ragged chunks while other slots attach, feed and
    detach around it; returns what the target session received."""
    rng = np.random.default_rng(seed)
    target = pool.attach()
    others, out, at = [], [], 0
    while at < wave.size:
        n = int(rng.integers(HOP // 3, 3 * HOP))
        pool.feed(target, wave[at:at + n])
        at += n
        if others and rng.random() < 0.4:
            pool.detach(others.pop(int(rng.integers(len(others)))))
        if len(others) < 2 and rng.random() < 0.6:
            others.append(pool.attach())
        for o in others:
            pool.feed(o, _audio(int(rng.integers(1 << 30)), 1, int(rng.integers(HOP))))
        pool.pump_all() if hasattr(pool, "pump_all") else pool.pump()
        out.append(pool.read(target))
    pool.pump_all() if hasattr(pool, "pump_all") else pool.pump()
    out.append(pool.read(target))
    return np.concatenate(out)


@pytest.mark.parametrize("server", ["pool-k1", "pool-k4", "launcher-sharded-k4"])
def test_served_audio_equals_the_reference(server):
    wave = _audio(11, 23, extra=100)
    if server == "launcher-sharded-k4":
        args = _launcher_args("--hops-per-step", "4")
        cfg, params = launch.model(args)
        assert cfg == CFG  # the launcher's --reduced size is the reference's TINY
        jax.tree_util.tree_map(np.testing.assert_array_equal, params, PARAMS)
        pool = launch.build_sharded_pool(args, params, cfg)
        assert pool.sample_rate == 16000
    else:
        pool = SessionPool(PARAMS, CFG, capacity=3, hops_per_step=int(server[-1]))
        assert pool.sample_rate == 16000
    got = _serve_with_churn(pool, wave, seed=5)
    assert got.size == (wave.size // HOP) * HOP
    assert _err(got, _reference(wave)) < ERR_MAX


def test_state_bytes_per_slot_is_reported():
    args = _launcher_args()
    pool = launch.build_sharded_pool(args, PARAMS, CFG)
    per_slot = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(init_stream(PARAMS, CFG, 1)))
    assert [s["state_bytes_per_slot"] for s in pool.shard_stats()] == [per_slot]
    from repro.serve.gateway import GatewayClient, GatewayThread

    gw = GatewayThread(pool)
    try:
        with GatewayClient(*gw.address) as c:
            assert c.stats()["state_bytes_per_slot"] == per_slot
    finally:
        gw.stop()
    # at the published widths: ~1.6 MB a FullSubNet stream, under 40 KB a TFTNN one
    size = lambda cfg: sum(  # noqa: E731
        math.prod(s.shape) * 4 for s in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_stream(None, cfg, 1))))
    assert 1.55e6 < size(fsn.FullSubNetConfig()) < 1.65e6
    assert size(tft.tftnn_config()) < 40e3


# -- (c) a moved session continues bit-identically -----------------------------

def _drive(pool, sess, wave, cuts):
    out, at = [], 0
    for n in cuts:
        pool.feed(sess, wave[at:at + n])
        at += n
        pool.pump()
        out.append(pool.read(sess))
    return np.concatenate(out), at


@pytest.mark.parametrize("path", ["wire-ticket", "durability-snapshot"])
def test_moved_session_continues_bit_identically(tmp_path, path):
    wave = _audio(21, 20)
    cuts = [HOP * 3 + 17, HOP * 4, HOP * 2 + 200, HOP * 5]
    solo = SessionPool(PARAMS, CFG, capacity=2)
    whole, _ = _drive(solo, solo.attach(), wave, cuts + [wave.size])

    if path == "wire-ticket":
        src, dst = SessionPool(PARAMS, CFG, capacity=2), SessionPool(PARAMS, CFG, capacity=2)
        src.attach()  # a neighbour, so the session is not in slot 0
        moved = src.attach()
        pre, at = _drive(src, moved, wave, cuts[:2])
        blob = encode_ticket(src.export_session(moved))
        assert len(blob) > 0.9 * sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(init_stream(PARAMS, CFG, 1)))
        sess = dst.import_session(decode_ticket(blob))
    else:
        man = DurabilityManager(tmp_path, snapshot_every=4)
        src = SessionPool(PARAMS, CFG, capacity=2, durability=man)
        pre, at = _drive(src, src.attach(durable_id="t"), wave, cuts[:2])
        assert man.entry_stats("t")["gen"] >= 1, "no snapshot was written"
        del src  # crash
        man2 = DurabilityManager(tmp_path, snapshot_every=4)
        dst = SessionPool(PARAMS, CFG, capacity=2, durability=man2)
        sess = recover_session(dst, man2, "t")
        dst.pump()
        pre = np.concatenate([pre, dst.read(sess)])
    post, _ = _drive(dst, sess, wave[at:], cuts[2:] + [wave.size])
    got = np.concatenate([pre, post])
    assert np.array_equal(got, whole)


# -- (d) what FullSubNet cannot serve is refused by name ------------------------

@pytest.mark.parametrize("flags", [["--backend", "pallas"], ["--quant"], ["--prune-keep", "0.5"]],
                         ids=["pallas", "quant", "prune-keep"])
def test_deploy_flags_are_refused(flags):
    args = _launcher_args(*flags)
    with pytest.raises(ValueError, match="fullsubnet serves in float32 on the xla backend only"):
        launch.build_sharded_pool(args, PARAMS, CFG)


def test_step_builder_names_every_refused_option():
    """The one refusal sits where every pool builds its step, and names
    each option that needs a deploy plan."""
    with pytest.raises(ValueError, match="fullsubnet serves in float32 on the xla backend only: "
                       "backend 'pallas', quant need"):
        make_stream_hop(PARAMS, CFG, quant=FP10, backend="pallas")


# -- (e) passthrough and model hops keep one delay ------------------------------

def _identity_params():
    """FullSubNet weights whose cIRM is exactly 1: the sub-band linear layer
    emits the compressed value of 1 whatever its input."""
    c = fsn.CRM_K * math.tanh(1.0 / (2 * fsn.CRM_K))  # -K ln((K-c)/(K+c)) == 1
    sb = dict(PARAMS["sb"], linear={"w": jnp.zeros_like(PARAMS["sb"]["linear"]["w"]),
                                    "b": jnp.array([c, 0.0], jnp.float32)})
    return dict(PARAMS, sb=sb)


@pytest.mark.parametrize("schedule", ["model", "passthrough", "alternating"])
def test_passthrough_keeps_the_model_delay(schedule):
    params = _identity_params()
    model = make_stream_hop(params, CFG, donate=False)
    passthrough = make_stream_hop(params, CFG, donate=False, passthrough=True)
    wave = _audio(31, 16)
    st, out = init_stream(params, CFG, 1), []
    for k in range(wave.size // HOP):
        use_model = {"model": True, "passthrough": False,
                     "alternating": (k // 3) % 2 == 0}[schedule]
        step = model if use_model else passthrough
        st, y = step(st, jnp.asarray(wave[None, k * HOP:(k + 1) * HOP]), jnp.array([True]))
        out.append(np.asarray(y[0]))
    got = np.concatenate(out)
    assert np.abs(got[:DELAY]).max() < 1e-6
    np.testing.assert_allclose(got[DELAY:], wave[:-DELAY], atol=2e-5)


# -- TFTNN: the seam moved its mask and changed no bit ---------------------------

TFT_CFG = launch.reduced_cfg(tft.tftnn_config())


def _tftnn_hops(name: str) -> np.ndarray:
    """TFTNN's served audio at the CPU-demo widths, as recorded in the fixture."""
    cfg, B, n = TFT_CFG, 2, 8
    params = tft.init_tft(jax.random.PRNGKey(0), cfg)
    wave = jax.random.normal(jax.random.PRNGKey(1), (B, n * cfg.hop))
    if name in ("fp32", "fp10"):
        q = FP10 if name == "fp10" else None
        st = init_stream(params, cfg, B)
        step = jax.jit(lambda s, x: stream_hop(params, cfg, s, x, quant=q))
        ys = []
        for i in range(n):
            st, y = step(st, wave[:, i * cfg.hop:(i + 1) * cfg.hop])
            ys.append(np.asarray(y))
        return np.concatenate(ys, axis=1)
    step = make_stream_hop(params, cfg, max_hops_per_step=4, donate=False,
                           passthrough=name == "passthrough_k4")
    hops = wave[:, :4 * cfg.hop].reshape(B, 4, cfg.hop)
    _, y = step(init_stream(params, cfg, B), hops, jnp.array([4, 2]))
    return np.asarray(y).reshape(B, -1)


@pytest.mark.parametrize("name", ["fp32", "fp10", "fused_k4", "passthrough_k4"])
def test_tftnn_hop_is_bit_identical_to_before_the_seam(name):
    assert dataclasses.asdict(TFT_CFG)["sample_rate"] == 8000
    want = np.load(FIXTURE)[name]
    assert np.array_equal(_tftnn_hops(name), want)
