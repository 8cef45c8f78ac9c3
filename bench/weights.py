"""Random weights from the run's seed, made on the device.

The tree layout is the one the program serves, as shapes only (the model
family's ``param_shapes``, through ``jax.eval_shape``); every value comes
from the seed here, in one jitted call, in float32 (the dtype the TFTNN
configurations load; the FP10 configuration rounds them itself when it
builds its deploy plan).

Batch-norm leaves get non-trivial inference statistics (scale near 1, small
shift and mean, variance in [0.5, 1.5]) so that folding them into the
neighbouring layers, as the deploy plan does, is exercised and checked.
"""

from __future__ import annotations

import math


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def make_params(seed: int, shapes):
    """The weight pytree of ``shapes`` (a tree of ``ShapeDtypeStruct``), on
    the default device.

    One uniform and one normal draw cover every leaf (a program of a few
    ops, quick to compile); each leaf takes its own slice.
    """
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [math.prod(sd.shape) for _, sd in paths]
    total = sum(sizes)

    def build(key):
        ku, kn = jax.random.split(key)
        uni = jax.random.uniform(ku, (total,), jnp.float32, -1.0, 1.0)
        nrm = jax.random.normal(kn, (total,), jnp.float32)
        leaves, at = [], 0
        for (path, sd), n in zip(paths, sizes):
            name = str(getattr(path[-1], "key", path[-1]))
            u = uni[at:at + n].reshape(sd.shape)
            z = nrm[at:at + n].reshape(sd.shape)
            at += n
            if name == "scale":
                leaves.append(1.0 + 0.2 * u)
            elif name in ("bias", "mean"):
                leaves.append(0.1 * z)
            elif name == "var":
                leaves.append(1.0 + 0.5 * u)
            else:
                fan = math.prod(sd.shape[:-1]) if len(sd.shape) > 1 else sd.shape[0]
                leaves.append(u / math.sqrt(fan))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
