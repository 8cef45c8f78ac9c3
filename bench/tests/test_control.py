"""The control, the next precision down, must come out not correct.

``tftnn-fp10-deploy``'s control is the program's own FP8 grid; it is
emulated, so it also runs on the CPU at a tiny size. ``tftnn-fp32``'s
control is matmul precision ``high`` (three bf16 passes), which only a TPU
computes differently from ``highest``: on the CPU that case skips. On the
chip (``pytest bench/tests/test_control.py``) both run at the cells' own
sizes with a short window, on three seeds.
"""

import pytest

import control
import run

SEEDS = (2**31 + 21, 2**31 + 22, 2**31 + 23)


def _on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def test_fp10_control_fails_at_a_tiny_size(tiny_cell):
    cell, config, mix = tiny_cell("offline-fp10")
    out = run.run(cell, config, mix, seed=SEEDS[0], seconds=1.5, traced=False,
                  require_tpu=False, **control.control_overrides(config))
    assert not out["correct"], out["numbers"]


@pytest.mark.parametrize("cell_name", ["fleet-rt-fp32", "offline-fp10"])
def test_control_fails_on_the_chip(cell_name):
    if not _on_tpu():
        pytest.skip("the chip's own precision modes need a TPU")
    _, cell, config, mix = run.load_cell(cell_name)
    for seed in SEEDS:
        out = run.run(cell, config, mix, seed=seed, seconds=4.0, traced=False,
                      **control.control_overrides(config))
        assert not out["correct"], (seed, out["numbers"])
