"""The benchmark's FLOP and byte counts."""

import dataclasses

import pytest

import flops
import run
import tracing


def test_macs_per_hop_matches_the_program_at_published_widths():
    from repro.models import tftnn

    cfg = tftnn.tftnn_config()
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    assert flops.macs_per_hop(m) == tftnn.macs_per_frame(cfg)
    assert flops.macs_per_hop(m) == pytest.approx(8.81e6, rel=1e-3)
    assert run.load_family("tftnn").macs_per_hop(m) == flops.macs_per_hop(m)


CONV = ("%dilated_split_conv_pallas.64 = f32[64,256,32]{2,1,0:T(8,128)S(1)} custom-call("
        "f32[64,260,32]{2,1,0:T(8,128)S(1)} %pad.44, f32[5,16,16]{2,1,0:T(8,128)S(1)} "
        "%copy-done.127, f32[16]{0:T(128)S(1)} %copy-done.143), custom_call_target="
        "\"tpu_custom_call\"")


def test_kernel_cost_from_an_hlo_instruction():
    res, ops = tracing.parse_shapes(CONV)
    assert res == [("f32", (64, 256, 32))]
    assert ops == [("f32", (64, 260, 32)), ("f32", (5, 16, 16)), ("f32", (16,))]
    n, moved = flops.kernel_cost("dilated_split_conv_pallas", res, ops)
    assert n == 2 * 64 * 256 * 5 * 16 * 16
    assert moved == 4 * (64 * 256 * 32 + 64 * 260 * 32 + 5 * 16 * 16 + 16)
    mm = flops.kernel_cost("masked_matmul_pallas", [("f32", (8192, 16))],
                           [("f32", (8192, 32)), ("f32", (32, 16)), ("f32", (16,))])
    assert mm[0] == 2 * 8192 * 32 * 16
    la = flops.kernel_cost("linear_attention_step_pallas",
                           [("f32", (128, 128, 8)), ("f32", (128, 8, 8))],
                           [("f32", (128, 128, 8))] * 3 + [("f32", (128, 8, 8))])
    assert la[0] == 4 * 128 * 128 * 8 * 8
    # a family without an operation count is costed by its bytes alone
    assert flops.kernel_cost("new_kernel_pallas", res, ops) == (0.0, moved)
