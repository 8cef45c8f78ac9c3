"""The ``offline-fsn-fp32`` cell end to end on the CPU at a tiny size, and its
refusals: FullSubNet served through the launcher's pool and the gateway,
checked against the plain reference in ``bench/models/fullsubnet.py``.
"""

import numpy as np
import pytest

import run
from test_harness import _break

CELL = "offline-fsn-fp32"


def test_cpu_rehearsal_is_correct(tiny_cell):
    cell, config, mix = tiny_cell(CELL)
    out = run.run(cell, config, mix, seed=2**31 + 3, seconds=1.5, traced=False,
                  require_tpu=False)
    s = out["ctx"].summary
    assert out["correct"], out["numbers"]
    assert s["attempted"] > 0 and s["failed"] == 0
    assert out["numbers"]["streams"] >= mix["sessions"]
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch_dropped", "answer_altered"])
def test_broken_step_is_not_correct(tiny_cell, monkeypatch, kind):
    from repro.serve import session_server

    monkeypatch.setattr(session_server, "make_stream_hop", _break(kind))
    cell, config, mix = tiny_cell(CELL)
    out = run.run(cell, config, mix, seed=2**31 + 5, seconds=1.5, traced=False,
                  require_tpu=False)
    assert not out["correct"], out["numbers"]
    assert np.isfinite(out["numbers"]["err_energy_max"])
