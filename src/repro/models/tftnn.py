"""TFTNN (the paper's model) and TSTNN (the baseline) as one config family.

The paper derives TFTNN from TSTNN through the Table VII ladder; we implement
the whole family behind ``TFTConfig`` so every rung is a config transform
(see ``repro.core.pruning.apply_ladder``):

  TSTNN-ish baseline: dense dilated blocks, (2,3) 2-D kernels, LN, PReLU,
      softmax MHA, sub-band + full-band two-stage transformers x4,
      bi-directional full-band GRU, GTU mask module.
  TFTNN: residual-split dilated blocks, (1,5) 1-D kernels, BN, ReLU,
      softmax-free MHA with extra BN on Q/K, sub-band-only attention,
      uni-directional full-band GRU, gateless mask module, 2 blocks,
      halved channels. Fully causal => streaming per 16 ms frame.

Data layout: spectrogram features are (B, F, T, C) — batch, frequency,
time, channels. The model consumes the noisy STFT (B, F, T, 2) and emits a
complex-ratio mask (B, F, T, 2) (TF mask domain; Table II) or a time-domain
mask (TSTNN's original mask domain).

The streaming path (``init_stream_state`` / ``stream_step``) processes one
time frame; it is exact (bit-identical to offline) because after the
streaming-aware prune no op has time-axis taps except the uni-directional
full-band GRUs, whose hidden states are the entire streaming state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.core.bn import BatchNorm
from repro.core.bn_transformer import (
    BNTransformerConfig,
    apply_bn_transformer,
    init_bn_transformer,
    streaming_gru_substep,
)
from repro.core.quant import QuantSpec, quantize

Params = Dict[str, Any]
# has a deploy plan (serve/deploy.py): BN folding, Pallas kernels, FP10, pruning
DEPLOYABLE = True


@dataclasses.dataclass(frozen=True)
class TFTConfig:
    """The TSTNN->TFTNN family. Defaults = TFTNN (the paper's final model)."""

    name: str = "tftnn"
    # front end
    sample_rate: int = 8000  # the paper's
    n_fft: int = 512
    hop: int = 128
    freq_bins: int = 256  # 257 rfft bins, nyquist dropped for a pow-2 axis
    # trunk — exactly half of the TSTNN baseline widths (Table VII "1/2 ch.")
    channels: int = 32  # encoder/decoder width (TSTNN: 64)
    att_dim: int = 16  # attention embedding (TSTNN: 32); head_dim = w = 8 (Eq. 1)
    num_heads: int = 2
    gru_hidden: int = 32  # (TSTNN: 64)
    num_transformer_blocks: int = 2  # TSTNN: 4
    dilation_rates: Tuple[int, ...] = (1, 2, 4, 8)
    dilated_block: str = "residual_split"  # | "dense"
    conv_kernel_t: int = 1  # TSTNN: 2
    conv_kernel_f: int = 5  # TSTNN: 3
    downsample: int = 2  # F -> F/2 for the attention stage (h=128)
    # normalization / activation / attention flavor
    norm: str = "bn"  # | "ln"
    activation: str = "relu"  # | "prelu"
    softmax_free: bool = True
    extra_bn: bool = True  # the extra BN on Q/K inside softmax-free MHA
    full_band_attention: bool = False  # TSTNN: True (non-causal!)
    bidirectional_fullband_gru: bool = False  # TSTNN: True
    mask_gtu: bool = False  # TSTNN: True
    mask_domain: str = "tf"  # | "t"

    @property
    def att_len(self) -> int:
        """Sub-band attention length h (Eq. 1: h = 128)."""
        return self.freq_bins // self.downsample

    @property
    def is_causal(self) -> bool:
        return (
            self.conv_kernel_t == 1
            and not self.full_band_attention
            and not self.bidirectional_fullband_gru
        )


def tstnn_config() -> TFTConfig:
    """The TSTNN-family baseline (time-frequency port, for the ladders)."""
    return TFTConfig(
        name="tstnn",
        channels=64,
        att_dim=32,
        num_heads=4,
        gru_hidden=64,
        num_transformer_blocks=4,
        dilated_block="dense",
        conv_kernel_t=2,
        conv_kernel_f=3,
        norm="ln",
        activation="prelu",
        softmax_free=False,
        extra_bn=False,
        full_band_attention=True,
        bidirectional_fullband_gru=True,
        mask_gtu=True,
        mask_domain="tf",
    )


def tftnn_config() -> TFTConfig:
    return TFTConfig()


# ---------------------------------------------------------------------------
# Norm/activation helpers (LN for TSTNN, BN for TFTNN)
# ---------------------------------------------------------------------------

def _init_norm(cfg: TFTConfig, c: int, dtype) -> Params:
    if cfg.norm == "bn":
        return BatchNorm(c).init(dtype)
    return nn.init_layernorm(c, dtype)


def _apply_norm(cfg: TFTConfig, p: Params, x: jax.Array, train: bool) -> Tuple[jax.Array, Params]:
    if cfg.norm == "bn":
        return BatchNorm(x.shape[-1]).apply(p, x, train=train)
    return nn.layernorm(p, x), p


def _init_act(cfg: TFTConfig, key, c: int, dtype) -> Params:
    if cfg.activation == "prelu":
        return {"alpha": jnp.full((c,), 0.25, dtype)}
    return {}


def _apply_act(cfg: TFTConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.activation == "prelu":
        return nn.prelu(x, p["alpha"])
    return nn.relu(x)


# ---------------------------------------------------------------------------
# 2-D conv on (B, F, T, C): kernel (kf, kt); TFTNN uses kt=1 (1-D, streaming)
# ---------------------------------------------------------------------------

def _init_conv2d(key, kf, kt, cin, cout, dtype) -> Params:
    kw, kb = jax.random.split(key)
    fan = kf * kt * cin
    bound = 1.0 / math.sqrt(fan)
    return {
        "w": jax.random.uniform(kw, (kf, kt, cin, cout), dtype, -bound, bound),
        "b": jax.random.uniform(kb, (cout,), dtype, -bound, bound),
    }


def _conv2d(p: Params, x: jax.Array, *, stride_f: int = 1, dil_f: int = 1, causal_t: bool = True) -> jax.Array:
    """Conv over (F, T) with SAME-f padding and causal-t padding."""
    kf, kt = p["w"].shape[0], p["w"].shape[1]
    pad_f = (kf - 1) * dil_f // 2
    pad_t = (kt - 1, 0) if causal_t else ((kt - 1) // 2, kt // 2)
    y = jax.lax.conv_general_dilated(
        x,
        p["w"],
        window_strides=(stride_f, 1),
        padding=[(pad_f, (kf - 1) * dil_f - pad_f), pad_t],
        rhs_dilation=(dil_f, 1),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["b"]


# ---------------------------------------------------------------------------
# Dilated blocks (Fig. 2)
# ---------------------------------------------------------------------------

def _init_dilated_block(cfg: TFTConfig, key, dtype) -> Params:
    C = cfg.channels
    keys = jax.random.split(key, 2 * len(cfg.dilation_rates))
    layers: List[Params] = []
    for i, _ in enumerate(cfg.dilation_rates):
        if cfg.dilated_block == "dense":
            cin = C * (i + 1)  # dense connections grow the input channels
            conv = _init_conv2d(keys[2 * i], cfg.conv_kernel_f, cfg.conv_kernel_t, cin, C, dtype)
        else:  # residual_split: process half the channels, bypass half
            conv = _init_conv2d(keys[2 * i], cfg.conv_kernel_f, cfg.conv_kernel_t, C // 2, C // 2, dtype)
        width = C if cfg.dilated_block == "dense" else C // 2
        layers.append(
            {
                "conv": conv,
                "norm": _init_norm(cfg, width, dtype),
                "act": _init_act(cfg, keys[2 * i + 1], width, dtype),
            }
        )
    return {"layers": layers}


def _apply_dilated_block(
    cfg: TFTConfig, p: Params, x: jax.Array, train: bool
) -> Tuple[jax.Array, Params]:
    new_layers = []
    if cfg.dilated_block == "dense":
        feats = [x]
        for layer, d in zip(p["layers"], cfg.dilation_rates):
            inp = jnp.concatenate(feats, axis=-1)
            y = _conv2d(layer["conv"], inp, dil_f=d, causal_t=True)
            y, n2 = _apply_norm(cfg, layer["norm"], y, train)
            y = _apply_act(cfg, layer["act"], y)
            feats.append(y)
            new_layers.append({**layer, "norm": n2})
        out = feats[-1]
    else:  # residual_split (Fig. 2b) — matches kernels/dilated_conv
        out = x
        for layer, d in zip(p["layers"], cfg.dilation_rates):
            C = out.shape[-1]
            xp, xb = out[..., : C // 2], out[..., C // 2 :]
            y = _conv2d(layer["conv"], xp, dil_f=d, causal_t=True)
            y, n2 = _apply_norm(cfg, layer["norm"], y, train)
            y = _apply_act(cfg, layer["act"], y) + xp  # residual
            # swap halves so successive layers process alternate channels
            out = jnp.concatenate([xb, y], axis=-1)
            new_layers.append({**layer, "norm": n2})
    return out, {"layers": new_layers}


# ---------------------------------------------------------------------------
# Two-stage transformer (Fig. 3 / Fig. 7)
# ---------------------------------------------------------------------------

def _sub_cfg(cfg: TFTConfig) -> BNTransformerConfig:
    return BNTransformerConfig(
        d_model=cfg.att_dim,
        num_heads=cfg.num_heads,
        gru_hidden=cfg.gru_hidden,
        use_attention=True,
        causal=False,  # sub-band attention runs along F — streamable
        bidirectional_gru=True,  # along F: both directions available per frame
        softmax_free=cfg.softmax_free,
    )


def _full_cfg(cfg: TFTConfig) -> BNTransformerConfig:
    return BNTransformerConfig(
        d_model=cfg.att_dim,
        num_heads=cfg.num_heads,
        gru_hidden=cfg.gru_hidden,
        use_attention=cfg.full_band_attention,
        causal=False,
        bidirectional_gru=cfg.bidirectional_fullband_gru,
        softmax_free=cfg.softmax_free,
    )


def _init_ln_transformer(cfg: TFTConfig, key, tcfg: BNTransformerConfig, dtype) -> Params:
    """TSTNN-style LN transformer reuses the BN block's weight layout but with
    LN params; selected by cfg.norm."""
    p = init_bn_transformer(key, tcfg, dtype)
    if cfg.norm == "ln":
        for k in ("bn1", "bn2"):
            if k in p:
                p[k] = nn.init_layernorm(tcfg.d_model, dtype)
    return p


def _apply_stage(
    cfg: TFTConfig,
    p: Params,
    x: jax.Array,
    tcfg: BNTransformerConfig,
    train: bool,
) -> Tuple[jax.Array, Params]:
    """Apply one transformer stage on (N, L, d)."""
    if cfg.norm == "bn":
        return apply_bn_transformer(p, x, tcfg, train=train)
    # LN path (baseline): same topology with layernorm + softmax attention
    from repro.core.bn_transformer import mha_softmax_free

    new_p = dict(p)
    y = x
    if tcfg.use_attention:
        h = nn.layernorm(p["bn1"], x)
        att, att_p = mha_softmax_free(p, h, tcfg, train=train)
        for k in ("bn_q", "bn_k"):
            if k in att_p:
                new_p[k] = att_p[k]
        y = x + att
    h = nn.layernorm(p["bn2"], y)
    if tcfg.bidirectional_gru:
        g = nn.bigru(p["gru_f"], p["gru_b"], h)
    else:
        g, _ = nn.gru(p["gru_f"], h)
    return y + nn.dense(p["w_out"], g), new_p


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_tft(key, cfg: TFTConfig, dtype=jnp.float32) -> Params:
    C, d = cfg.channels, cfg.att_dim
    keys = jax.random.split(key, 16 + 2 * cfg.num_transformer_blocks)
    p: Params = {}
    # encoder
    p["enc_in"] = _init_conv2d(keys[0], cfg.conv_kernel_f, cfg.conv_kernel_t, 2, C, dtype)
    p["enc_in_norm"] = _init_norm(cfg, C, dtype)
    p["enc_in_act"] = _init_act(cfg, keys[1], C, dtype)
    p["enc_dilated"] = _init_dilated_block(cfg, keys[2], dtype)
    p["enc_down"] = _init_conv2d(keys[3], cfg.conv_kernel_f, cfg.conv_kernel_t, C, C, dtype)
    p["enc_down_norm"] = _init_norm(cfg, C, dtype)
    p["enc_down_act"] = _init_act(cfg, keys[4], C, dtype)
    # project trunk channels C -> attention width d and back
    p["att_in"] = nn.init_dense(keys[5], C, d, dtype=dtype)
    p["att_out"] = nn.init_dense(keys[6], d, C, dtype=dtype)
    # transformer blocks (each = sub-band stage + full-band stage)
    blocks = []
    for i in range(cfg.num_transformer_blocks):
        bk = jax.random.split(keys[7 + i], 2)
        blocks.append(
            {
                "sub": _init_ln_transformer(cfg, bk[0], _sub_cfg(cfg), dtype),
                "full": _init_ln_transformer(cfg, bk[1], _full_cfg(cfg), dtype),
            }
        )
    p["blocks"] = blocks
    kb = 7 + cfg.num_transformer_blocks
    # mask module (Fig. 4)
    p["mask_conv1"] = _init_conv2d(keys[kb], 1, 1, C, C, dtype)
    if cfg.mask_gtu:
        p["mask_gate"] = _init_conv2d(keys[kb + 1], 1, 1, C, C, dtype)
    p["mask_act"] = _init_act(cfg, keys[kb + 2], C, dtype)
    p["mask_conv2"] = _init_conv2d(keys[kb + 3], 1, 1, C, C, dtype)
    # decoder
    p["dec_dilated"] = _init_dilated_block(cfg, keys[kb + 4], dtype)
    p["dec_up"] = _init_conv2d(keys[kb + 5], cfg.conv_kernel_f, cfg.conv_kernel_t, C, C * cfg.downsample, dtype)
    p["dec_up_norm"] = _init_norm(cfg, C * cfg.downsample, dtype)
    p["dec_up_act"] = _init_act(cfg, keys[kb + 6], C * cfg.downsample, dtype)
    p["dec_out"] = _init_conv2d(keys[kb + 7], cfg.conv_kernel_f, cfg.conv_kernel_t, C, 2, dtype)
    return p


def _encode(cfg, p, new_p, x, train):
    y = _conv2d(p["enc_in"], x, causal_t=cfg.conv_kernel_t == 1)
    y, new_p["enc_in_norm"] = _apply_norm(cfg, p["enc_in_norm"], y, train)
    y = _apply_act(cfg, p["enc_in_act"], y)
    y, new_p["enc_dilated"] = _apply_dilated_block(cfg, p["enc_dilated"], y, train)
    y = _conv2d(p["enc_down"], y, stride_f=cfg.downsample, causal_t=cfg.conv_kernel_t == 1)
    y, new_p["enc_down_norm"] = _apply_norm(cfg, p["enc_down_norm"], y, train)
    y = _apply_act(cfg, p["enc_down_act"], y)
    return y


def _transform(cfg, p, new_p, y, train):
    """Two-stage transformer trunk on (B, F', T, C)."""
    B, Fp, T, C = y.shape
    z = nn.dense(p["att_in"], y)  # (B, F', T, d)
    d = cfg.att_dim
    new_blocks = []
    for blk in p["blocks"]:
        # sub-band stage: sequence along F' for each time frame
        zs = z.transpose(0, 2, 1, 3).reshape(B * T, Fp, d)
        zs, sub_p = _apply_stage(cfg, blk["sub"], zs, _sub_cfg(cfg), train)
        z = zs.reshape(B, T, Fp, d).transpose(0, 2, 1, 3)
        # full-band stage: sequence along T for each frequency
        zf = z.reshape(B * Fp, T, d)
        zf, full_p = _apply_stage(cfg, blk["full"], zf, _full_cfg(cfg), train)
        z = zf.reshape(B, Fp, T, d)
        new_blocks.append({"sub": sub_p, "full": full_p})
    new_p["blocks"] = new_blocks
    return nn.dense(p["att_out"], z)  # (B, F', T, C)


def _mask_and_decode(cfg, p, new_p, enc, tr, train):
    # mask module (Fig. 4): gate the encoder features
    m = _conv2d(p["mask_conv1"], tr, causal_t=True)
    if cfg.mask_gtu:
        g = _conv2d(p["mask_gate"], tr, causal_t=True)
        m = jnp.tanh(m) * jax.nn.sigmoid(g)  # GTU
    else:
        m = _apply_act(cfg, p["mask_act"], m)
    m = _conv2d(p["mask_conv2"], m, causal_t=True)
    h = enc * m
    # decoder
    h, new_p["dec_dilated"] = _apply_dilated_block(cfg, p["dec_dilated"], h, train)
    h = _conv2d(p["dec_up"], h, causal_t=cfg.conv_kernel_t == 1)
    h, new_p["dec_up_norm"] = _apply_norm(cfg, p["dec_up_norm"], h, train)
    h = _apply_act(cfg, p["dec_up_act"], h)
    # sub-pixel upsample along F: (B, F', T, C*r) -> (B, F'*r, T, C)
    B, Fp, T, Cr = h.shape
    r = cfg.downsample
    h = h.reshape(B, Fp, T, r, Cr // r).transpose(0, 1, 3, 2, 4).reshape(B, Fp * r, T, Cr // r)
    return _conv2d(p["dec_out"], h, causal_t=cfg.conv_kernel_t == 1)  # (B, F, T, 2)


def apply_tft(
    p: Params,
    spec_ri: jax.Array,
    cfg: TFTConfig,
    *,
    train: bool = False,
) -> Tuple[jax.Array, Params]:
    """Forward pass: noisy spectrogram -> complex-ratio mask.

    spec_ri: (B, F, T, 2) with F == cfg.freq_bins (+1 nyquist bin allowed,
    cropped internally and restored as zeros).
    Returns (mask_ri (B, F_in, T, 2), new_params).
    """
    new_p = dict(p)
    F_in = spec_ri.shape[1]
    x = spec_ri[:, : cfg.freq_bins]  # crop nyquist bin if present
    enc = _encode(cfg, p, new_p, x, train)
    tr = _transform(cfg, p, new_p, enc, train)
    mask = _mask_and_decode(cfg, p, new_p, enc, tr, train)
    if F_in > cfg.freq_bins:
        pad = jnp.zeros_like(spec_ri[:, cfg.freq_bins :])
        mask = jnp.concatenate([mask, pad], axis=1)
    return mask, new_p


# ---------------------------------------------------------------------------
# Streaming inference (Section III-E): one time frame per step
# ---------------------------------------------------------------------------

def init_stream_state(p: Params, cfg: TFTConfig, batch: int, dtype=jnp.float32) -> Params:
    """Streaming state = the full-band GRU hidden per block, per (B, F').

    Layout is (batch, F', hidden) with batch as the leading axis so a server
    holding many sessions in one batched state can reset/select single slots
    (``state[k]``) without knowing the model internals.
    """
    if not cfg.is_causal:
        raise ValueError(f"{cfg.name} is not causal; streaming unsupported")
    Fp = cfg.att_len
    return {
        f"block{i}": jnp.zeros((batch, Fp, cfg.gru_hidden), dtype)
        for i in range(cfg.num_transformer_blocks)
    }


def stream_step(
    p: Params,
    state: Params,
    frame_ri: jax.Array,
    cfg: TFTConfig,
) -> Tuple[Params, jax.Array]:
    """Process one spectrogram frame. frame_ri: (B, F, 2) -> mask (B, F, 2).

    Exactness: with kt=1 all convs are frame-local; the sub-band stage is
    frame-local; only the full-band uni-directional GRU carries state.
    Each stage runs under a ``jax.named_scope`` (``encoder``, ``subband``,
    ``fullband``, ``mask_decoder``) that the device trace's op names carry.
    """
    B = frame_ri.shape[0]
    x = frame_ri[:, :, None, :]  # (B, F, 1, 2)
    new_p = dict(p)
    with jax.named_scope("encoder"):
        enc = _encode(cfg, p, new_p, x[:, : cfg.freq_bins], train=False)
        # transformer trunk, streaming variant
        Bq, Fp, _, C = enc.shape
        z = nn.dense(p["att_in"], enc[:, :, 0, :])  # (B, F', d)
    new_state = dict(state)
    for i, blk in enumerate(p["blocks"]):
        with jax.named_scope("subband"):
            zs, _ = _apply_stage(cfg, blk["sub"], z, _sub_cfg(cfg), train=False)
        with jax.named_scope("fullband"):
            zf = zs.reshape(B * Fp, cfg.att_dim)
            h0 = state[f"block{i}"].reshape(B * Fp, cfg.gru_hidden)
            h, z_out = streaming_gru_substep(blk["full"], _full_cfg(cfg), h0, zf)
            new_state[f"block{i}"] = h.reshape(B, Fp, cfg.gru_hidden)
            z = z_out.reshape(B, Fp, cfg.att_dim)
    with jax.named_scope("mask_decoder"):
        tr = nn.dense(p["att_out"], z)[:, :, None, :]
        mask = _mask_and_decode(cfg, p, new_p, enc, tr, train=False)  # (B, F, 1, 2)
        mask = mask[:, :, 0, :]
        F_in = frame_ri.shape[1]
        if F_in > cfg.freq_bins:
            mask = jnp.concatenate([mask, jnp.zeros_like(frame_ri[:, cfg.freq_bins :])], axis=1)
    return new_state, mask


def apply_mask(spec_ri: jax.Array, mask: jax.Array) -> jax.Array:
    """TFTNN's bounded complex-ratio mask: ``2 tanh(mask)`` times the noisy
    spectrum, both (..., 2) real/imaginary; returns the complex product."""
    a, b = spec_ri[..., 0], spec_ri[..., 1]
    m = 2.0 * jnp.tanh(mask)
    mc, md = m[..., 0], m[..., 1]
    return (a * mc - b * md) + 1j * (a * md + b * mc)


def stream_estimate(
    p: Params,
    state: Params,
    frame_ri: jax.Array,
    cfg: TFTConfig,
    quant: Optional[QuantSpec] = None,
) -> Tuple[Params, jax.Array]:
    """The serving seam's model step: ``stream_step``, the mask rounded onto
    ``quant``'s grid when given, applied to the current frame. Returns
    ``(new_state, est)`` with ``est`` the (B, F) complex spectrum to
    synthesise."""
    state, mask = stream_step(p, state, frame_ri, cfg)
    with jax.named_scope("mask"):
        if quant is not None:
            mask = quantize(mask, quant)
        return state, apply_mask(frame_ri, mask)


def passthrough_estimate(state: Params, frame_ri: jax.Array, cfg: TFTConfig):
    """The brownout hop's spectrum: the current frame, unmasked (TFTNN has no
    look-ahead); the recurrent state is carried untouched."""
    return state, frame_ri[..., 0] + 1j * frame_ri[..., 1]


def offline_estimate(p: Params, spec_ri: jax.Array, cfg: TFTConfig) -> jax.Array:
    """(B, F, T, 2) noisy spectrogram -> (B, F, T) complex enhanced spectra."""
    mask, _ = apply_tft(p, spec_ri, cfg)
    return apply_mask(spec_ri, mask)


# ---------------------------------------------------------------------------
# Analytics: parameter and MAC counting (Tables I / VII, §IV-A)
# ---------------------------------------------------------------------------

def param_count(p: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(p))


def macs_per_frame(cfg: TFTConfig) -> float:
    """Analytic multiply-accumulate count to process ONE time frame."""
    C, d, F = cfg.channels, cfg.att_dim, cfg.freq_bins
    Fp = cfg.att_len
    kf, kt = cfg.conv_kernel_f, cfg.conv_kernel_t
    m = 0.0
    # encoder
    m += kf * kt * 2 * C * F  # enc_in
    for i, _ in enumerate(cfg.dilation_rates):  # enc dilated
        if cfg.dilated_block == "dense":
            m += kf * kt * (C * (i + 1)) * C * F
        else:
            m += kf * kt * (C // 2) * (C // 2) * F
    m += kf * kt * C * C * Fp  # enc_down (stride-f)
    # attention projections C<->d
    m += C * d * Fp + d * C * Fp
    # transformer blocks
    gru_macs = lambda din, h: 3 * (din * h + h * h)
    for _ in range(cfg.num_transformer_blocks):
        # sub-band stage over length Fp
        m += 3 * d * d * Fp + d * d * Fp  # QKV + out proj
        if cfg.softmax_free:
            m += d * Fp * d + Fp * d * d  # K^T V then Q (K^T V)  (Eq. 1 new)
        else:
            m += Fp * d * Fp + Fp * Fp * d  # (QK^T) V            (Eq. 1 orig)
        m += 2 * gru_macs(d, cfg.gru_hidden) * Fp  # bi-GRU along F
        m += 2 * cfg.gru_hidden * d * Fp
        # full-band stage: per frame, one step along T
        if cfg.full_band_attention:
            m += 3 * d * d * Fp + d * d * Fp
            m += Fp * (d * 1 * d + 1 * d * d)  # decode-style attention per frame
        ngru = 2 if cfg.bidirectional_fullband_gru else 1
        m += ngru * gru_macs(d, cfg.gru_hidden) * Fp
        m += ngru * cfg.gru_hidden * d * Fp
    # mask module
    m += C * C * Fp * (3 if cfg.mask_gtu else 2)
    # decoder
    for i, _ in enumerate(cfg.dilation_rates):
        if cfg.dilated_block == "dense":
            m += kf * kt * (C * (i + 1)) * C * Fp
        else:
            m += kf * kt * (C // 2) * (C // 2) * Fp
    m += kf * kt * C * (C * cfg.downsample) * Fp  # dec_up
    m += kf * kt * C * 2 * F  # dec_out
    return m


def gmacs_per_second(cfg: TFTConfig, sample_rate: int = 8000) -> float:
    frames_per_second = sample_rate / cfg.hop
    return macs_per_frame(cfg) * frames_per_second / 1e9
