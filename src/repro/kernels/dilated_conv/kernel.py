"""Pallas kernel: fused channel-split dilated residual 1-D conv (Fig. 2b).

The TFTNN encoder/decoder hot loop. One grid step processes one batch
element's full (F, C) frame — the whole feature map is VMEM-resident, the TPU
analogue of the ASIC's all-on-chip SRAM strategy (DESIGN.md §5.6). The conv
is decomposed into k tap-matmuls (shifted (F, C/2) @ (C/2, C/2)), mirroring
the paper's reduction of every op onto one MAC datapath, and the dilation
rate only changes the tap offsets — the BlockSpec/index arithmetic analogue
of the ASIC's "configurable SRAM addressing".

Block-level zero skipping: when an input frame is entirely zero (silence),
the tap-matmuls are skipped and the output is the algebraic short-circuit
relu(bias) + residual — the TPU-granularity version of the ASIC's
per-element zero gating (DESIGN.md §5.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(
    x_ref, w_ref, b_ref, o_ref, *, k: int, dilation: int, F: int, half: int,
    zero_skip: bool, swap_halves: bool,
):
    x = x_ref[0]  # (F + (k-1)*d, C) padded input frame
    w = w_ref[...]  # (k, half, half)
    b = b_ref[...]  # (half,)
    pad = (k - 1) * dilation // 2
    xp = x[:, :half]
    center = xp[pad : pad + F, :]  # un-padded processed half
    xb = x[pad : pad + F, half:]  # bypass half

    def compute():
        acc = jnp.zeros((F, half), jnp.float32)
        for t in range(k):  # static unroll: k tap-matmuls on the MXU
            acc = acc + xp[t * dilation : t * dilation + F, :].astype(jnp.float32) @ w[t].astype(jnp.float32)
        return acc

    if zero_skip:
        is_zero = jnp.all(x == 0.0)
        # skip path: conv(0) + b = b; computed path: full tap-matmuls
        acc = jax.lax.cond(is_zero, lambda: jnp.zeros((F, half), jnp.float32), compute)
    else:
        acc = compute()
    y = jnp.maximum(acc + b.astype(jnp.float32), 0.0) + center.astype(jnp.float32)
    if swap_halves:  # TFTNN layer layout: successive layers alternate halves
        o_ref[0] = jnp.concatenate([xb, y.astype(o_ref.dtype)], axis=-1)
    else:
        o_ref[0] = jnp.concatenate([y.astype(o_ref.dtype), xb], axis=-1)


@functools.partial(
    jax.jit, static_argnames=("dilation", "zero_skip", "swap_halves", "interpret")
)
def dilated_split_conv_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    dilation: int = 1,
    zero_skip: bool = True,
    swap_halves: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """x: (B, F, C); w: (k, C//2, C//2); b: (C//2,). SAME padding."""
    B, F, C = x.shape
    k = w.shape[0]
    half = C // 2
    pad = (k - 1) * dilation // 2
    xpad = jnp.pad(x, ((0, 0), (pad, pad), (0, 0)))
    Fp = F + 2 * pad
    out = pl.pallas_call(
        functools.partial(
            _kernel, k=k, dilation=dilation, F=F, half=half,
            zero_skip=zero_skip, swap_halves=swap_halves,
        ),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Fp, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((k, half, half), lambda i: (0, 0, 0)),
            pl.BlockSpec((half,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, F, C), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, F, C), x.dtype),
        interpret=interpret,
        name="dilated_split_conv_pallas",
    )(xpad, w, b)
    return out
