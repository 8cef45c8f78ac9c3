"""The plain reference agrees with the program's offline path (CPU, f32).

The reference imports nothing of the program; this test is where the two
meet. Blocks of 8 frames make every wave cross several block boundaries, so
the carried GRU states and overlap-add tails are checked too.
"""

import dataclasses

import numpy as np
import pytest

import reference
import run
import weights

TFTNN = run.load_family("tftnn")


@pytest.mark.parametrize("tiny", [True, False], ids=["tiny", "published"])
def test_reference_matches_program_offline(tiny):
    import jax
    import jax.numpy as jnp

    from repro.models import tftnn
    from repro.serve.streaming_se import enhance_offline

    cfg = tftnn.tftnn_config()
    if tiny:
        cfg = dataclasses.replace(cfg, **{**TFTNN.TINY, "dilation_rates": (1, 2, 4)})
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    params = weights.make_params(2**33 + 5, TFTNN.param_shapes(cfg))
    rng = np.random.default_rng(0)
    waves = [0.3 * rng.standard_normal(n).astype(np.float32) for n in (1000, 4000, 2600)]
    outs = reference.enhance(params, model, waves, batch=4, block_frames=8)
    with jax.default_matmul_precision("highest"):
        for w, o in zip(waves, outs):
            r = np.asarray(enhance_offline(params, cfg, jnp.asarray(w)[None]))[0]
            assert o.shape == r.shape == (len(w) // 128 * 128,)
            assert np.sum((o - r) ** 2) / np.sum(r ** 2) < 1e-10


def test_weights_are_a_function_of_the_seed():
    import jax

    from repro.models import tftnn

    shapes = TFTNN.param_shapes(tftnn.tftnn_config())
    a, b = weights.make_params(7, shapes), weights.make_params(7, shapes)
    c = weights.make_params(2**32 + 7, shapes)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not all(np.array_equal(x, y) for x, y in zip(la, lc))
