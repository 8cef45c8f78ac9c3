"""Model families and kernel costs are found by name, and the inputs follow
the cell's sample rate (CPU).

A configuration of a new family needs only new files: its family module
under ``bench/models/``, its configuration, mix and kernel counts, and
entries in ``BENCHMARK.json``; ``test_a_new_family_needs_only_new_files``
builds such a tree and finds both.
"""

import gzip
import hashlib
import json
import shutil

import numpy as np
import pytest

import flops
import run
import synth
import tracing
from conftest import BENCH, ROOT

# sha256 of synth.bank(2**31 + 7) before the bank took a sample rate
BANK_SHA256 = "481c214cc28b081b377a3e51f485a4ee62bbc3ec1cb7103e9de37417e5cb0294"
FIXTURE = BENCH / "tests" / "fixtures" / "fp10_step.xplane.pb.gz"


def _benchmark():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [c["name"] for c in _benchmark()["configs"]])
def test_every_configuration_names_a_whole_family(name):
    entry = next(c for c in _benchmark()["configs"] if c["name"] == name)
    config = json.loads((ROOT / entry["file"]).read_text())
    fam = run.load_family(config["family"])
    for attr in run.FAMILY_API:
        assert hasattr(fam, attr), attr
    assert set(fam.TINY) <= set(config["model"])


def _tree(tmp_path, edit_config=None, edit_mix=None):
    """A copy of the benchmark's files under ``tmp_path``, edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if edit_config:
        path = tmp_path / "bench" / "configs" / "tftnn-fp32.json"
        path.write_text(json.dumps(edit_config(json.loads(path.read_text()))))
    if edit_mix:
        path = tmp_path / "bench" / "traffic" / "realtime-calls.json"
        path.write_text(json.dumps(edit_mix(json.loads(path.read_text()))))
    return tmp_path


@pytest.fixture
def tree_root(monkeypatch):
    def use(root):
        monkeypatch.setattr(run, "ROOT", root)
        monkeypatch.setattr(run, "BENCH", root / "bench")

    return use


def test_an_unknown_family_is_refused_by_name(tmp_path, tree_root):
    tree_root(_tree(tmp_path, edit_config=lambda c: dict(c, family="nosuchnet")))
    with pytest.raises(SystemExit, match="model family 'nosuchnet': no bench/models/nosuchnet.py"):
        run.load_cell("fleet-rt-fp32")


def test_a_family_without_the_whole_interface_is_refused(tmp_path, tree_root):
    root = _tree(tmp_path, edit_config=lambda c: dict(c, family="half"))
    (root / "bench" / "models" / "half.py").write_text("TINY = {}\n")
    tree_root(root)
    with pytest.raises(SystemExit, match=r"model family 'half' .* lacks \['program_config'"):
        run.load_cell("fleet-rt-fp32")


def test_a_mix_at_another_sample_rate_is_refused(tmp_path, tree_root):
    tree_root(_tree(tmp_path, edit_mix=lambda m: dict(m, sample_rate=16000)))
    with pytest.raises(SystemExit, match="'realtime-calls' is at 16000 Hz, configuration "
                                         "'tftnn-fp32' at 8000 Hz"):
        run.load_cell("fleet-rt-fp32")


TOY_FAMILY = '''
TINY = {"width": 4}


def program_config(model):
    return dict(model)


def param_shapes(cfg):
    return {}


def enhance(params, model, waves, grid, precision):
    return [w[: len(w) // model["hop"] * model["hop"]] for w in waves]


def macs_per_hop(model):
    return 3.0 * model["width"]
'''
TOY_KERNEL = '''
def cost(results, operands):
    return 7.0 * results[0][1][0]
'''


def test_a_new_family_needs_only_new_files(tmp_path, tree_root, monkeypatch):
    root = _tree(tmp_path)
    bench = root / "bench"
    (bench / "models" / "toynet.py").write_text(TOY_FAMILY)
    (bench / "kernels" / "toy_scan_step_pallas.py").write_text(TOY_KERNEL)
    (bench / "configs" / "toynet.json").write_text(json.dumps(
        {"name": "toynet", "family": "toynet", "model": {"hop": 256, "width": 64},
         "sample_rate": 16000}))
    (bench / "traffic" / "toy-files.json").write_text(json.dumps(
        {"sessions": 1, "slots_per_chip": 2, "sample_rate": 16000}))
    index = json.loads((root / "BENCHMARK.json").read_text())
    index["configs"].append({"name": "toynet", "file": "bench/configs/toynet.json"})
    index["workloads"].append({"name": "toy.files", "config": "toynet",
                               "traffic": "toy-files", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(index))
    tree_root(root)
    _, cell, config, mix = run.load_cell("toy.files")
    fam = run.load_family(config["family"])
    assert fam.macs_per_hop(config["model"]) == 3.0 * 64
    assert fam.enhance(None, config["model"], [np.ones(600)], None, "highest")[0].size == 512

    monkeypatch.setattr(flops, "KERNELS", bench / "kernels")
    flops._counter.cache_clear()
    try:
        ops, moved = flops.kernel_cost("toy_scan_step_pallas", [("f32", (8, 16))],
                                       [("f32", (8, 16))])
    finally:
        flops._counter.cache_clear()
    assert (ops, moved) == (7.0 * 8, 2 * 4 * 8 * 16)


def test_bank_at_the_default_rate_is_unchanged():
    seed = 2**31 + 7
    b = synth.bank(seed)
    assert np.array_equal(b, synth.bank(seed, 8000))
    assert b.dtype == np.float32 and b.size == 32 * 3 * 8000
    assert hashlib.sha256(b.tobytes()).hexdigest() == BANK_SHA256


def test_bank_at_16_khz_has_3_s_utterances():
    b = synth.bank(5, 16000, utterances=3)
    assert b.shape == (3 * 48000,)
    # every utterance is scaled to peak 1 on its own
    peaks = np.abs(b).reshape(3, 48000).max(axis=1)
    assert np.allclose(peaks, 1.0, atol=1e-5)


def test_tftnn_macs_per_hop_through_the_family():
    config = json.loads((BENCH / "configs" / "tftnn-fp32.json").read_text())
    fam = run.load_family(config["family"])
    assert fam.macs_per_hop(config["model"]) == pytest.approx(8.81e6, rel=1e-3)
    assert fam.macs_per_hop(config["model"]) == flops.macs_per_hop(config["model"])


def _if_chain_ops(family, results, operands):
    """The operation counts ``flops.kernel_cost`` gave before the kernel files."""
    dims = lambda shape: shape[1]  # noqa: E731
    if family == "dilated_split_conv_pallas":
        B, F, _ = dims(results[0])
        k, cin, cout = dims(operands[1])
        return 2.0 * B * F * k * cin * cout
    if family == "masked_matmul_pallas":
        M, K = dims(operands[0])
        N = dims(operands[1])[1]
        return 2.0 * M * K * N
    if family.startswith("linear_attention"):
        BH, L, D = dims(operands[0])
        return 4.0 * BH * L * D * D
    return 0.0


@pytest.fixture(scope="module")
def fixture_kernels(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "fp10_step.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce(tracing.load(str(path)), window_span="bench_window")["kernels"]


# the kernel files made from the families the old ``if`` chain costed
MOVED = ("dilated_split_conv_pallas", "masked_matmul_pallas", "linear_attention")


@pytest.mark.parametrize("stem", MOVED)
def test_kernel_file_counts_as_the_if_chain_did(fixture_kernels, stem):
    calls = [k for k in fixture_kernels if k["family"].startswith(stem)]
    assert calls, f"no call of {stem} in the fixture"
    for k in calls:
        ops, _ = flops.kernel_cost(k["family"], k["results"], k["operands"])
        assert ops > 0
        assert ops == _if_chain_ops(k["family"], k["results"], k["operands"])


def test_a_kernel_file_costs_only_the_families_it_names(tmp_path, monkeypatch):
    kernels = shutil.copytree(BENCH / "kernels", tmp_path / "kernels",
                              ignore=shutil.ignore_patterns("__pycache__"))
    # stems that prefix kernel families: neither may cost them
    (kernels / "masked.py").write_text("def cost(results, operands):\n    return 1.0\n")
    (kernels / "toy.py").write_text("def cost(results, operands):\n    return 1.0\n")
    monkeypatch.setattr(flops, "KERNELS", kernels)
    flops._counter.cache_clear()
    try:
        shapes = [("f32", (8, 16))], [("f32", (8, 16)), ("f32", (16, 4))]
        assert flops.kernel_cost("toy_scan_pallas", *shapes)[0] == 0.0
        assert flops.kernel_cost("masked_matmul_pallas", *shapes)[0] == 2.0 * 8 * 16 * 4
        assert flops.kernel_cost("linear_attention_causal_pallas",
                                 [("f32", (2, 8, 4))], [("f32", (2, 8, 4))] * 3)[0] \
            == 4.0 * 2 * 8 * 4 * 4
    finally:
        flops._counter.cache_clear()
