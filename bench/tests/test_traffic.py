"""The traffic generator gives every seed the same work, in another order."""

import json

import numpy as np
import pytest

import traffic
from conftest import BENCH


@pytest.mark.parametrize("mix_name", ["realtime-calls", "offline-files"])
def test_every_seed_gets_the_same_lengths_and_phases(mix_name):
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    a, b = (traffic.lengths(mix, np.random.default_rng(s)) for s in (1, 2**31 + 9))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    pa, pb = traffic.seat_phases(mix, 1), traffic.seat_phases(mix, 2**31 + 9)
    assert pa.shape == (mix["sessions"],)
    assert np.allclose(np.sort(pa), np.sort(pb))
