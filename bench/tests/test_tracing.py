"""The trace reduction, on a small trace of the served FP10 step recorded on
a TPU v5e (8 slots, two hops per slot, K=4), and on hand-made intervals."""

import gzip
import shutil
from pathlib import Path

import pytest

import flops
import peaks
import tracing

FIXTURE = Path(__file__).parent / "fixtures" / "fp10_step.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "fp10_step.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce(tracing.load(str(path)), window_span="bench_window")


def test_busy_and_idle_partition_the_window(reduced):
    r = reduced
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6, abs=1e-9)
    # top-level ops do not overlap, so their self times add up to busy time
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert set(r["idle_by_span"]) <= set(tracing.SPAN_NAMES) | {"no_span"}


def test_kernels_are_found_with_their_shapes(reduced):
    fams = {k["family"] for k in reduced["kernels"]}
    assert fams == {"dilated_split_conv_pallas", "masked_matmul_pallas",
                    "linear_attention_step_pallas"}
    conv = next(k for k in reduced["kernels"] if k["family"] == "dilated_split_conv_pallas")
    assert conv["results"][0][1][0] == 8  # the pool's slots
    assert len(conv["operands"]) == 3


def test_roofline_shares_stay_under_one(reduced):
    pk = peaks.peaks("TPU v5 lite")
    for k in reduced["kernels"]:
        ops, moved = flops.kernel_cost(k["family"], k["results"], k["operands"])
        least = max(ops / pk["flops_bf16"], moved / pk["hbm_bytes_per_s"])
        assert 0 < least <= k["seconds"]


def test_breakdown_lists_at_most_ten(reduced):
    b = tracing.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])


def test_interval_helpers():
    assert tracing._union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    spans = sorted([(0, 100, "pump_all"), (10, 20, "dispatch"), (50, 60, "collect")])
    got = list(tracing._attribute([(12, 14), (30, 40), (55, 57), (150, 160)], spans))
    assert [w for w, _ in got] == ["dispatch", "pump_all", "collect", "no_span"]
    assert tracing.op_family("%dilated_split_conv_pallas.64 = f32[1] x") == \
        "dilated_split_conv_pallas"
    assert tracing.op_family("%broadcast.332.clone = f32[1] x") == "broadcast.332.clone"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
