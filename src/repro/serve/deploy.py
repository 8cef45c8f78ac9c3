"""Deploy compilation: trained TFTNN graph -> the ASIC-shaped serving graph.

The paper's deployed model is *not* the training graph (Sections III-D/F,
Table VI): every BatchNorm is constant at inference and folds into the
adjacent convolution or projection, attention is softmax-free with the Q/K
BNs folded into W_q/W_k, 93.9% of weights are pruned and their MACs gated
off, and everything runs on the FP10 deployment grid. This module performs
that compilation once, ahead of serving:

``build_deploy_plan(params, cfg)`` returns a :class:`DeployPlan` —

- **BN folding** — ``core.bn.fold_bn_into_conv2d`` removes every encoder/
  decoder BN; ``core.bn_transformer.fold_qk_bn`` (wired in at last, per
  ROADMAP) folds the extra Q/K BNs; the pre-norm BN1/BN2 of each
  transformer stage fold *forward* into the Q/K/V projections and the GRU
  input transforms. The folded graph contains ZERO normalization ops.
- **Zero-skipping masks** — ``core.pruning.prune_mask`` materializes dense
  0/1 masks for the mask/decode matmuls; ``kernels.masked_mac`` skips
  fully-masked weight strips on the MXU (the TPU-granularity version of the
  ASIC gating pruned MACs off).
- **FP10 pre-quantization** — folded weights are rounded onto the paper's
  deployment grid once (``core.quant``), not per hop.

``stream_hop_fused(plan, state, hops)`` is the fused per-hop step: same
signature contract as ``streaming_se.stream_hop`` (it shares the exact
STFT/OLA front/back halves), but the model body routes

- encoder/decoder dilated residual convs -> ``kernels.dilated_conv``
  (VMEM-resident tap-matmuls, block zero skipping),
- sub-band softmax-free attention -> ``kernels.linear_attention.
  linear_attention_step`` (the state-carrying K^T V form of Eq. 1),
- mask-module / attention-projection matmuls -> ``kernels.masked_mac``.

Parity: ``stream_hop_fused`` equals ``stream_hop`` up to float error (BN
folding is exact algebra), property-tested in tests/test_deploy.py. Serving
picks it up via ``make_stream_hop(..., backend="pallas")`` and the
``SessionPool(..., backend=...)`` knob — see docs/deploy.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.core.bn import fold_bn_into_conv2d, fold_bn_into_linear
from repro.core.bn_transformer import fold_qk_bn
from repro.core.pruning import granular_mask, prune_mask, sparsity_report
from repro.core.quant import QuantSpec, quantize, quantize_tree
from repro.kernels.dilated_conv import dilated_split_conv
from repro.kernels.linear_attention import linear_attention_step
from repro.kernels.masked_mac import masked_matmul, skip_stats
from repro.models import tftnn as tft_mod
from repro.models.tftnn import _sub_cfg
from repro.serve.streaming_se import StreamState, hop_analysis, hop_synthesis

Params = Dict[str, Any]

# weights served through the masked-MAC kernel (the paper's pruned matmuls)
MASKED_WEIGHTS = ("att_in", "att_out", "mask_conv1", "mask_conv2")


@dataclasses.dataclass(frozen=True)
class DeployPlan:
    """The compiled serving artifact: folded weights + masks + number format.

    Attributes:
        cfg: the (causal, BN, ReLU, softmax-free) TFTNN config the plan was
            compiled for.
        params: folded parameter tree. Contains NO BatchNorm entries — every
            norm is an affine already multiplied into its neighbour. Conv
            weights keep the (kf, kt=1, cin, cout) layout; dilated-block and
            1x1 weights are squeezed to the kernel-native (k, cin, cout) /
            (cin, cout) layouts.
        masks: dense 0/1 zero-skipping masks for ``MASKED_WEIGHTS`` (None =
            unpruned; masks are not quantized — they gate, not scale).
        quant: activation/weight grid (weights are already rounded onto it
            inside ``params``; activations are rounded per hop at the same
            two points as ``stream_hop``).
        use_pallas: route through the Pallas kernels (False = the pure-jnp
            reference path, used by parity tests and the dry-run lowering).
        skip_granularity: the masked-MAC skip path the plan's masks were
            built for (``"strip"``/``"tile"``/``"column"``; None = unpruned).
        prune_block: the ``(block_k, block_n)`` tile shape for block masks
            and the strip/tile skip units.
        skip_stats: per-masked-weight skip counters
            (``kernels.masked_mac.skip_stats``) plus a ``"total"`` aggregate
            — the numbers ``SessionPool.shard_stats()`` surfaces.
        sparsity: exact realized-sparsity accounting over ``masks``
            (``core.pruning.sparsity_report``; None = unpruned).
    """

    cfg: tft_mod.TFTConfig
    params: Params
    masks: Optional[Params]
    quant: Optional[QuantSpec]
    use_pallas: bool = True
    skip_granularity: Optional[str] = None
    prune_block: Tuple[int, int] = (8, 8)
    skip_stats: Optional[Dict[str, Any]] = None
    sparsity: Optional[Dict[str, Any]] = None


def _squeeze_kt(w: jax.Array) -> jax.Array:
    """(kf, kt=1, cin, cout) -> (kf, cin, cout) for the 1-D kernels."""
    if w.shape[1] != 1:
        raise ValueError(f"deploy path requires kt=1 convs, got kt={w.shape[1]}")
    return w[:, 0]


def _fold_conv(conv: Params, bn: Params) -> Params:
    w, b = fold_bn_into_conv2d(conv["w"], conv.get("b"), bn)
    return {"w": w, "b": b}


def _fold_gru(gru: Params, bn_pre: Params) -> Params:
    """Pre-fold a BN into the GRU's input transform (x @ wi + bi)."""
    wi, bi = fold_bn_into_linear(gru["wi"], gru["bi"], bn_pre, pre=True)
    return {**gru, "wi": wi, "bi": bi}


def _fold_dilated(layers: List[Params]) -> List[Params]:
    """Fold each dilated layer's BN into its conv, kernel-native layout."""
    out = []
    for layer in layers:
        w, b = fold_bn_into_conv2d(layer["conv"]["w"], layer["conv"].get("b"), layer["norm"])
        out.append({"w": _squeeze_kt(w), "b": b})
    return out


def _dense_pair(p: Params) -> Params:
    return {"w": p["w"], "b": p.get("b", jnp.zeros((p["w"].shape[-1],), p["w"].dtype))}


def validate_deployable(cfg: tft_mod.TFTConfig) -> None:
    """The deploy path compiles exactly the paper's deployment graph."""
    problems = []
    if cfg.norm != "bn":
        problems.append(f"norm={cfg.norm!r} (need 'bn' — LN does not fold)")
    if cfg.activation != "relu":
        problems.append(f"activation={cfg.activation!r} (need 'relu')")
    if not cfg.softmax_free:
        problems.append("softmax attention (need softmax-free, Eq. 1)")
    if cfg.mask_gtu:
        problems.append("GTU mask module (pruned away in TFTNN)")
    if cfg.dilated_block != "residual_split":
        problems.append(f"dilated_block={cfg.dilated_block!r} (need 'residual_split')")
    if not cfg.is_causal:
        problems.append("non-causal config (streaming deploy needs kt=1, "
                        "sub-band-only attention, uni-directional full-band GRU)")
    if problems:
        raise ValueError(
            f"config {cfg.name!r} is not deploy-compilable: " + "; ".join(problems)
        )


def skip_granularity_for(
    prune_granularity: Optional[str], prune_axis: Optional[int]
) -> str:
    """Map a mask granularity (or legacy axis) to a masked-MAC skip path."""
    if prune_granularity is not None:
        kind = {"weight": "strip", "block": "tile", "unit": "column"}.get(
            prune_granularity
        )
        if kind is None:
            raise ValueError(
                f"unknown prune_granularity {prune_granularity!r}: "
                "expected 'weight', 'block' or 'unit'"
            )
        return kind
    # legacy structured-axis masks: axis 1/-1 zeroes whole output columns
    return "column" if prune_axis in (1, -1) else "strip"


def build_deploy_plan(
    params: Params,
    cfg: tft_mod.TFTConfig,
    *,
    quant: Optional[QuantSpec] = None,
    prune_keep: Optional[float] = None,
    prune_axis: Optional[int] = None,
    prune_granularity: Optional[str] = None,
    prune_block: Tuple[int, int] = (8, 8),
    use_pallas: bool = True,
) -> DeployPlan:
    """Compile trained params into the deployment graph (see module doc).

    Args:
        params: trained TFTNN parameter tree (``tftnn.init_tft`` layout).
        cfg: its config; must be the deployable (TFTNN) corner — validated.
        quant: optional deployment grid (e.g. ``core.quant.FP10``): folded
            weights are pre-rounded here, activations per hop.
        prune_keep: optional keep-fraction in (0, 1] for the masked matmuls
            (``MASKED_WEIGHTS``); materialized as dense zero-skipping masks
            with exact realized counts. None/1.0 = no pruning (the
            parity-test configuration).
        prune_axis: legacy structured masks — None = unstructured magnitude
            masks; an int = channel masks along that axis of (in, out)
            weights. Ignored when ``prune_granularity`` is given.
        prune_granularity: ``"weight"`` / ``"block"`` / ``"unit"``
            (``core.pruning.granular_mask``, arXiv 2111.02351); selects the
            matching masked-MAC skip path (strip / tile / column).
        prune_block: ``(block_k, block_n)`` tile shape for block masks and
            the strip/tile skip units.
        use_pallas: False switches every kernel to its pure-jnp oracle.

    Returns:
        A ``DeployPlan``. Folding is exact: with ``quant=None`` and no
        pruning, ``stream_hop_fused(plan, ...) == stream_hop(params, ...)``
        up to float error.
    """
    validate_deployable(cfg)
    dp: Params = {
        "enc_in": _fold_conv(params["enc_in"], params["enc_in_norm"]),
        "enc_dilated": _fold_dilated(params["enc_dilated"]["layers"]),
        "enc_down": _fold_conv(params["enc_down"], params["enc_down_norm"]),
        "att_in": _dense_pair(params["att_in"]),
        "att_out": _dense_pair(params["att_out"]),
        "mask_conv1": {"w": params["mask_conv1"]["w"][0, 0], "b": params["mask_conv1"]["b"]},
        "mask_conv2": {"w": params["mask_conv2"]["w"][0, 0], "b": params["mask_conv2"]["b"]},
        "dec_dilated": _fold_dilated(params["dec_dilated"]["layers"]),
        "dec_up": _fold_conv(params["dec_up"], params["dec_up_norm"]),
        # no BN after dec_out — keep the 4-D conv layout for the F-conv path
        "dec_out": {"w": params["dec_out"]["w"], "b": params["dec_out"]["b"]},
    }

    blocks: List[Params] = []
    sub_cfg = _sub_cfg(cfg)
    for blk in params["blocks"]:
        # 1. the ROADMAP item: fold the extra Q/K BNs into W_q/W_k (post)
        sub = fold_qk_bn(blk["sub"], sub_cfg)
        # 2. fold the pre-norm BN1 forward into all three projections (pre)
        folded_sub: Params = {}
        for proj in ("wq", "wk", "wv"):
            w, b = fold_bn_into_linear(
                sub[proj]["w"], sub[proj].get("b"), blk["sub"]["bn1"], pre=True
            )
            folded_sub[proj] = {"w": w, "b": b}
        folded_sub["wo"] = _dense_pair(sub["wo"])
        # 3. fold BN2 forward into the (bi-)GRU input transforms (pre)
        folded_sub["gru_f"] = _fold_gru(sub["gru_f"], blk["sub"]["bn2"])
        folded_sub["gru_b"] = _fold_gru(sub["gru_b"], blk["sub"]["bn2"])
        folded_sub["w_out"] = _dense_pair(sub["w_out"])
        full = {
            "gru_f": _fold_gru(blk["full"]["gru_f"], blk["full"]["bn2"]),
            "w_out": _dense_pair(blk["full"]["w_out"]),
        }
        blocks.append({"sub": folded_sub, "full": full})
    dp["blocks"] = blocks

    masks: Optional[Params] = None
    skip_kind: Optional[str] = None
    stats: Optional[Dict[str, Any]] = None
    sparsity: Optional[Dict[str, Any]] = None
    if prune_keep is not None and prune_keep < 1.0:
        skip_kind = skip_granularity_for(prune_granularity, prune_axis)
        bk, bn = prune_block
        if prune_granularity is not None:
            masks = {
                name: granular_mask(dp[name]["w"], prune_keep, prune_granularity, prune_block)
                for name in MASKED_WEIGHTS
            }
        else:
            masks = {
                name: prune_mask(dp[name]["w"], prune_keep, axis=prune_axis)
                for name in MASKED_WEIGHTS
            }
        stats = {
            name: skip_stats(masks[name], skip_kind, block_k=bk, block_n=bn)
            for name in MASKED_WEIGHTS
        }
        total = sum(s["total"] for s in stats.values())
        skipped = sum(s["skipped"] for s in stats.values())
        stats["total"] = {
            "granularity": skip_kind,
            "total": total,
            "skipped": skipped,
            "skip_rate": skipped / total if total else 0.0,
        }
        sparsity = sparsity_report(masks)
    if quant is not None and quant.kind != "none":
        dp = quantize_tree(dp, quant)
    return DeployPlan(
        cfg=cfg, params=dp, masks=masks, quant=quant, use_pallas=use_pallas,
        skip_granularity=skip_kind, prune_block=prune_block,
        skip_stats=stats, sparsity=sparsity,
    )


# ---------------------------------------------------------------------------
# The fused forward (one spectrogram frame), kernels in the hot spots
# ---------------------------------------------------------------------------

def _conv_f(p: Params, x: jax.Array, *, stride: int = 1) -> jax.Array:
    """SAME-padded conv along F on (B, F, C) with a folded (kf,1,cin,cout)."""
    w = p["w"][:, 0]  # (kf, cin, cout)
    kf = w.shape[0]
    pad = (kf - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride,), [(pad, kf - 1 - pad)],
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    return y + p["b"]


def _mm(plan: DeployPlan, name: str, x: jax.Array) -> jax.Array:
    """Masked-MAC matmul for one of the plan's pruned weights.

    The mask is a trace-time constant here, so ``masked_matmul`` compiles
    the skip plan in: pruned columns/tiles/strips never reach the compiled
    graph at all — the serving-speed payoff of granular pruning.
    """
    p = plan.params[name]
    mask = plan.masks.get(name) if plan.masks is not None else None
    bk, bn = plan.prune_block
    return masked_matmul(
        x, p["w"], p["b"], mask=mask,
        granularity=plan.skip_granularity or "strip",
        block_k=bk, block_n=bn, use_pallas=plan.use_pallas,
    )


def _dilated_fused(plan: DeployPlan, layers: List[Params], x: jax.Array) -> jax.Array:
    """The dilated residual block as a chain of fused Pallas convs.

    ``swap_halves=True`` reproduces the model's alternate-half layout
    (models/tftnn.py ``_apply_dilated_block``, residual_split branch).
    """
    out = x
    for lp, d in zip(layers, plan.cfg.dilation_rates):
        out = dilated_split_conv(
            out, lp["w"], lp["b"], dilation=d, swap_halves=True,
            use_pallas=plan.use_pallas,
        )
    return out


def _sub_stage_fused(plan: DeployPlan, sp: Params, z: jax.Array) -> jax.Array:
    """Sub-band transformer stage on (B, Fp, d), all BNs pre-folded.

    Attention runs through the state-carrying kernel with a zero carried
    state and this frame's Fp keys as the hop — which IS the non-causal
    Q @ (K^T V) / Fp of Eq. 1 (the state-carry form never materializes
    Fp x Fp and reuses the same VMEM accumulation as the streaming path).
    """
    B, Fp, d = z.shape
    H = plan.cfg.num_heads
    hd = d // H

    def heads(t: jax.Array) -> jax.Array:
        return t.reshape(B, Fp, H, hd).transpose(0, 2, 1, 3)

    q = heads(nn.dense(sp["wq"], z))
    k = heads(nn.dense(sp["wk"], z))
    v = heads(nn.dense(sp["wv"], z))
    kv0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    oh, _ = linear_attention_step(q, k, v, kv0, use_pallas=plan.use_pallas)
    oh = oh / Fp  # Eq. 1's constant 1/L normalizer (L = sub-band length)
    att = nn.dense(sp["wo"], oh.transpose(0, 2, 1, 3).reshape(B, Fp, d))
    y = z + att
    g = nn.bigru(sp["gru_f"], sp["gru_b"], y)  # BN2 folded into wi/bi
    return y + nn.dense(sp["w_out"], g)


def fused_stream_step(
    plan: DeployPlan, state: Params, frame_ri: jax.Array
) -> Tuple[Params, jax.Array]:
    """One spectrogram frame through the folded graph. (B, F, 2) -> mask.

    Mirrors ``tftnn.stream_step`` exactly, minus every normalization op
    (folded) and with the three kernel hot spots routed through Pallas.
    """
    cfg = plan.cfg
    dp = plan.params
    B = frame_ri.shape[0]
    x = frame_ri[:, : cfg.freq_bins]  # (B, F, 2), nyquist cropped

    with jax.named_scope("encoder"):
        # conv -> relu (BN folded), dilated block, strided conv -> relu
        y = nn.relu(_conv_f(dp["enc_in"], x))
        y = _dilated_fused(plan, dp["enc_dilated"], y)
        enc = nn.relu(_conv_f(dp["enc_down"], y, stride=cfg.downsample))  # (B, Fp, C)
        z = _mm(plan, "att_in", enc)  # (B, Fp, d)

    # transformer trunk (streaming): sub-band stage + full-band GRU step
    Fp = z.shape[1]
    new_state = dict(state)
    for i, blk in enumerate(dp["blocks"]):
        with jax.named_scope("subband"):
            z = _sub_stage_fused(plan, blk["sub"], z)
        with jax.named_scope("fullband"):
            zf = z.reshape(B * Fp, cfg.att_dim)
            h0 = state[f"block{i}"].reshape(B * Fp, cfg.gru_hidden)
            h, g = nn.gru_step(blk["full"]["gru_f"], h0, zf)  # BN2 folded into wi/bi
            z_out = zf + nn.dense(blk["full"]["w_out"], g)
            new_state[f"block{i}"] = h.reshape(B, Fp, cfg.gru_hidden)
            z = z_out.reshape(B, Fp, cfg.att_dim)

    with jax.named_scope("mask_decoder"):
        tr = _mm(plan, "att_out", z)  # (B, Fp, C)
        # mask module (gateless): two pruned 1x1 matmuls around ReLU
        m = nn.relu(_mm(plan, "mask_conv1", tr))
        m = _mm(plan, "mask_conv2", m)
        hfeat = enc * m
        # decoder: dilated block, up-conv -> relu (BN folded), sub-pixel, out conv
        hfeat = _dilated_fused(plan, dp["dec_dilated"], hfeat)
        hfeat = nn.relu(_conv_f(dp["dec_up"], hfeat))
        Bh, Fph, Cr = hfeat.shape
        r = cfg.downsample
        hfeat = hfeat.reshape(Bh, Fph, r, Cr // r).reshape(Bh, Fph * r, Cr // r)
        mask = _conv_f(dp["dec_out"], hfeat)  # (B, F, 2)
        F_in = frame_ri.shape[1]
        if F_in > cfg.freq_bins:
            mask = jnp.concatenate(
                [mask, jnp.zeros_like(frame_ri[:, cfg.freq_bins :])], axis=1
            )
    return new_state, mask


def stream_hop_fused(
    plan: DeployPlan,
    state: StreamState,
    hop_samples: jax.Array,
) -> Tuple[StreamState, jax.Array]:
    """Push one hop of audio through the DEPLOYED graph; emit one hop.

    Drop-in fused replacement for ``streaming_se.stream_hop``: identical
    STFT analysis and weighted-OLA synthesis (literally the same shared
    helpers), identical activation-quantization points, but the model body
    is the folded/pruned/kernel-routed deployment graph. Parity with the
    training graph is property-tested (tests/test_deploy.py).

    Pure in (state, hop_samples), so it composes with ``lax.scan``: the
    multi-hop fused dispatch path (``make_stream_hop(backend="pallas",
    max_hops_per_step=K)``) scans this hop over K staged lanes — the
    state-carrying ``linear_attention_step`` / GRU carries simply ride the
    scan carry — and ``benchmarks/deploy_parity.py`` scans it over whole
    utterances.
    """
    with jax.named_scope("analysis"):
        analysis, frame_ri = hop_analysis(state, hop_samples, plan.cfg, plan.quant)
    model_state, mask = fused_stream_step(plan, state.model, frame_ri)
    with jax.named_scope("mask"):
        if plan.quant is not None:
            mask = quantize(mask, plan.quant)
        est = tft_mod.apply_mask(frame_ri, mask)
    with jax.named_scope("synthesis"):
        return hop_synthesis(state, analysis, est, model_state, plan.cfg)
