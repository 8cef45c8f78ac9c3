"""Pallas kernel: minifloat (FP10 = s1/e5/m4) RNE quantization.

Emulates the paper's FP10 PE datapath (Table VI) on TPU: rounds f32 values to
the nearest representable minifloat, saturating at the max finite value, with
subnormal support. Used for quantize-dequantize in QAT and PTQ sweeps.

Tiling: inputs are flattened and padded to (rows, 128) lanes; each grid step
processes a (block_rows, 128) VMEM tile — pure VPU (elementwise) work, no MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, o_ref, *, exp_bits: int, man_bits: int):
    x = x_ref[...].astype(jnp.float32)
    bias = 2 ** (exp_bits - 1) - 1
    min_exp = 1 - bias
    max_exp = 2**exp_bits - 2 - bias
    max_val = (2.0 - 2.0**-man_bits) * 2.0**max_exp

    sign = jnp.sign(x)
    mag = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(mag, 1e-45)))
    e = jnp.clip(e, min_exp, max_exp)
    step = jnp.exp2(e - man_bits)
    q = jnp.round(mag / step) * step
    q = jnp.minimum(q, max_val)
    q = jnp.where(mag == 0, 0.0, q)
    o_ref[...] = (sign * q).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("exp_bits", "man_bits", "block_rows", "interpret"))
def fp10_quantize_pallas(
    x: jax.Array,
    *,
    exp_bits: int = 5,
    man_bits: int = 4,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    shape, dtype = x.shape, x.dtype
    lanes = 128
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // lanes)
    rows_pad = -(-rows // block_rows) * block_rows
    padded = jnp.zeros((rows_pad * lanes,), dtype).at[:n].set(flat).reshape(rows_pad, lanes)
    grid = (rows_pad // block_rows,)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, exp_bits=exp_bits, man_bits=man_bits),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows_pad, lanes), dtype),
        interpret=interpret,
        name="fp10_quantize_pallas",
    )(padded)
    return out.reshape(-1)[:n].reshape(shape)
