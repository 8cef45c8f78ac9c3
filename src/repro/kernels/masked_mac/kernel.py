"""Pallas kernel: matmul over a pruned weight matrix with zero skipping.

The TPU mirror of the paper's deployment datapath (Section III-D / Fig. 12):
after structured pruning, 93.9% of TFTNN's weights are zero and the ASIC's
1-D MAC array gates those multiplies off element-by-element. A TPU cannot
gate single MACs, so we skip at the granularity it does have: the weight
matrix is cut into ``block_k`` input-channel strips, and a strip whose
weights are ALL zero contributes nothing — its tap-matmul is skipped with
``jax.lax.cond`` instead of executed (DESIGN.md §5.4, the same block-level
zero-skip idea as kernels/dilated_conv, applied to weights instead of
activations).

The weight (with its dense 0/1 pruning mask already multiplied in) is small
enough to sit whole in VMEM for every TFTNN matmul (≤ 64x64); the grid runs
over row-blocks of the activation matrix, so one weight fetch serves the
whole batch — the analogue of the ASIC holding all weights on-chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, b_ref, o_ref, *, nk: int, block_k: int):
    x = x_ref[...].astype(jnp.float32)  # (block_m, K)
    w = w_ref[...].astype(jnp.float32)  # (K, N)
    b = b_ref[...].astype(jnp.float32)  # (N,)
    M, N = x.shape[0], w.shape[1]
    acc = jnp.zeros((M, N), jnp.float32)
    for i in range(nk):  # static unroll over input-channel strips
        wb = w[i * block_k : (i + 1) * block_k, :]
        xb = x[:, i * block_k : (i + 1) * block_k]
        # zero-skip: a fully-pruned strip never reaches the MXU
        acc = acc + jax.lax.cond(
            jnp.any(wb != 0.0),
            lambda xb=xb, wb=wb: xb @ wb,
            lambda: jnp.zeros((M, N), jnp.float32),
        )
    o_ref[...] = (acc + b).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "interpret"))
def masked_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_k: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) @ w: (K, N) + b: (N,), skipping all-zero K-strips of w.

    M must be a multiple of ``block_m`` and K of ``block_k`` (the ops wrapper
    pads both).
    """
    M, K = x.shape
    N = w.shape[1]
    if M % block_m or K % block_k:
        raise ValueError(f"M={M}, K={K} not multiples of ({block_m}, {block_k})")
    out = pl.pallas_call(
        functools.partial(_kernel, nk=K // block_k, block_k=block_k),
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
            pl.BlockSpec((N,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name="masked_matmul_pallas",
    )(x, w, b)
    return out
