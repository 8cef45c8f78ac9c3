"""TFTNN (arXiv 2503.21335): the model family of the ``tftnn-*`` configurations.

The interface ``run.load_family`` asks for, over ``reference.py`` and
``flops.macs_per_hop``. Output sample ``i`` of a stream is input sample
``i - (n_fft - hop)``.
"""

from __future__ import annotations

import flops
import reference

TINY = dict(freq_bins=64, channels=16, att_dim=8, num_heads=1, gru_hidden=16,
            dilation_rates=[1, 2, 4])


def program_config(model: dict):
    from repro.models import tftnn

    return tftnn.TFTConfig(**{**model, "dilation_rates": tuple(model["dilation_rates"])})


def param_shapes(cfg):
    import jax

    from repro.models import tftnn

    return jax.eval_shape(lambda k: tftnn.init_tft(k, cfg), jax.random.PRNGKey(0))


def enhance(params, model: dict, waves, grid, precision: str):
    return reference.enhance(params, model, waves, grid=grid, precision=precision)


def macs_per_hop(model: dict) -> float:
    return flops.macs_per_hop(model)
