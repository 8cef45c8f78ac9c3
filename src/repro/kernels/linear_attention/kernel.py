"""Pallas TPU kernels for softmax-free (linear) attention.

TPU adaptation of the paper's Fig. 10b "optimal matmul order": the (D, D)
K^T V product is accumulated in a VMEM scratch buffer (fp32) across
sequence-length grid steps — the analogue of the ASIC's partial sums in the
local register buffer — and the per-block output Q_blk @ state stays
MXU-shaped. The L x L attention map is never materialized.

Grid layout: (batch*heads, L // block_l), length innermost, so the scratch
accumulator carries across the length blocks of one (b, h) pair and is reset
when the outer index advances (TPU grids execute sequentially).

Causal kernel, per length block:
    inter  = q_blk @ state                      # tokens before this block
    intra  = (q_blk k_blk^T * tril) @ v_blk     # within-block causal part
    state += k_blk^T @ v_blk

Non-causal kernel makes two passes over the length axis (phase grid dim):
pass 0 accumulates K^T V, pass 1 emits q_blk @ state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _causal_kernel(q_ref, k_ref, v_ref, o_ref, state_ref, *, block_l: int, length: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)  # (block_l, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    inter = q @ state_ref[...]  # (block_l, D)
    att = q @ k.T  # (block_l, block_l) — small, VMEM-resident
    tril = jnp.tril(jnp.ones((block_l, block_l), jnp.float32))
    intra = (att * tril) @ v
    o_ref[0] = ((inter + intra) * (1.0 / length)).astype(o_ref.dtype)
    state_ref[...] = state_ref[...] + k.T @ v


def _step_kernel(q_ref, k_ref, v_ref, kv_ref, o_ref, kv_out_ref, state_ref, *, nblocks: int):
    """State-carrying hop step: state' = kv_in + K^T V; out = Q @ state'.

    The deploy-path variant (Fig. 10b run *across* hops): the carried (D, D)
    K^T V state enters as a tensor, this hop's keys fold into it in VMEM, and
    the queries read the updated state — no recomputation of earlier hops'
    K/V. Outputs are UNNORMALIZED; the caller divides by its running key
    count (the "K-sum" half of the carried state, a scalar per stream).
    """
    phase = pl.program_id(1)
    li = pl.program_id(2)

    @pl.when((phase == 0) & (li == 0))
    def _():
        state_ref[...] = kv_ref[0].astype(jnp.float32)

    @pl.when(phase == 0)
    def _():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        state_ref[...] = state_ref[...] + k.T @ v

    @pl.when((phase == 0) & (li == nblocks - 1))
    def _():
        kv_out_ref[0] = state_ref[...].astype(kv_out_ref.dtype)

    @pl.when(phase == 1)
    def _():
        q = q_ref[0].astype(jnp.float32)
        o_ref[0] = (q @ state_ref[...]).astype(o_ref.dtype)


def _noncausal_kernel(q_ref, k_ref, v_ref, o_ref, state_ref, *, length: int):
    phase = pl.program_id(1)
    li = pl.program_id(2)

    @pl.when((phase == 0) & (li == 0))
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    @pl.when(phase == 0)
    def _():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        state_ref[...] = state_ref[...] + k.T @ v

    @pl.when(phase == 1)
    def _():
        q = q_ref[0].astype(jnp.float32)
        o_ref[0] = (q @ state_ref[...] * (1.0 / length)).astype(o_ref.dtype)


def _flatten_bh(x: jax.Array) -> jax.Array:
    B, H, L, D = x.shape
    return x.reshape(B * H, L, D)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def linear_attention_causal_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_l: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Causal linear attention. q,k,v: (B, H, L, D); L % block_l == 0."""
    B, H, L, D = q.shape
    block_l = min(block_l, L)
    if L % block_l:
        raise ValueError(f"L={L} not a multiple of block_l={block_l}")
    qf, kf, vf = map(_flatten_bh, (q, k, v))
    grid = (B * H, L // block_l)
    spec = pl.BlockSpec((1, block_l, D), lambda bh, li: (bh, li, 0))
    out = pl.pallas_call(
        functools.partial(_causal_kernel, block_l=block_l, length=L),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
        name="linear_attention_causal_pallas",
    )(qf, kf, vf)
    return out.reshape(B, H, L, D)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def linear_attention_step_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv: jax.Array,
    *,
    block_l: int = 256,
    interpret: bool = False,
):
    """One hop of state-carrying linear attention.

    q, k, v: (B, H, L, D) with L % block_l == 0; kv: (B, H, D, D) fp32
    carried K^T V state. Returns (out, new_kv): out = Q @ (kv + K^T V),
    unnormalized; new_kv the updated state.
    """
    B, H, L, D = q.shape
    block_l = min(block_l, L)
    if L % block_l:
        raise ValueError(f"L={L} not a multiple of block_l={block_l}")
    qf, kf, vf = map(_flatten_bh, (q, k, v))
    kvf = kv.reshape(B * H, D, D).astype(jnp.float32)
    nblocks = L // block_l
    grid = (B * H, 2, nblocks)
    spec = pl.BlockSpec((1, block_l, D), lambda bh, phase, li: (bh, li, 0))
    kv_spec = pl.BlockSpec((1, D, D), lambda bh, phase, li: (bh, 0, 0))
    out, kv_out = pl.pallas_call(
        functools.partial(_step_kernel, nblocks=nblocks),
        grid=grid,
        in_specs=[spec, spec, spec, kv_spec],
        out_specs=[spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
        name="linear_attention_step_pallas",
    )(qf, kf, vf, kvf)
    return out.reshape(B, H, L, D), kv_out.reshape(B, H, D, D)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def linear_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_l: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Non-causal linear attention (sub-band attention in TFTNN)."""
    B, H, L, D = q.shape
    block_l = min(block_l, L)
    if L % block_l:
        raise ValueError(f"L={L} not a multiple of block_l={block_l}")
    qf, kf, vf = map(_flatten_bh, (q, k, v))
    grid = (B * H, 2, L // block_l)
    spec = pl.BlockSpec((1, block_l, D), lambda bh, phase, li: (bh, li, 0))
    out = pl.pallas_call(
        functools.partial(_noncausal_kernel, length=L),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
        name="linear_attention_pallas",
    )(qf, kf, vf)
    return out.reshape(B, H, L, D)
