"""FullSubNet (Hao et al., ICASSP 2021, arXiv 2010.15508), streamed per frame.

The DNS Challenge baseline for 16 kHz wideband enhancement, at the widths of
the upstream recipe ``recipes/dns_interspeech_2020/fullsubnet/train.toml``
(github.com/Audio-WestlakeU/FullSubNet):

- STFT ``n_fft`` 512, hop 256, periodic Hann: 257 bins, 50% overlap.
- Full-band model: a 2-layer LSTM of 512 over the normalised noisy
  magnitude (257 values a frame), then Linear 512->257 and ReLU.
- Sub-band model: a 2-layer LSTM of 384, then Linear 384->2, shared by all
  257 bins. Each bin's input is 32 values: its +-15 magnitude neighbours
  (reflect padding at the band edges) and the full-band output at that
  bin, normalised before the LSTM.
- A look-ahead of 2 frames: the mask the LSTMs emit at frame ``t`` belongs
  to frame ``t - 2``.
- The output is a compressed complex ideal ratio mask (cIRM), decompressed
  with K=10 (clamp to +-9.9, then ``-10 ln((10 - m) / (10 + m))``), that
  multiplies the noisy complex frame.

The recipe normalises over the whole utterance, which cannot stream; this
module uses the upstream's causal ``cumulative_laplace_norm``
(``audio_zen/model/base_model.py``): every frame is divided by the running
mean of all values seen so far, plus float32 machine epsilon.

Weights use PyTorch's LSTM layout (``W_ih`` (4H, in), ``W_hh`` (4H, H), two
biases, gate order i, f, g, o); the linear layers are ``w`` (in, out), ``b``.

The streaming path (``init_stream_state`` / ``stream_step``) processes one
frame per call; its state is the LSTMs' (h, c), the two norms' running sums,
the frame count and the two held complex frames the look-ahead delays.
``apply`` runs a whole spectrogram at once, as the upstream forward does.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
EPSILON = float(np.finfo(np.float32).eps)  # the upstream norm's
CRM_K, CRM_LIMIT = 10.0, 9.9  # the upstream decompress_cIRM's bound and clamp
DEPLOYABLE = False  # float32 on the xla backend only: no Pallas, FP10 or pruning


@dataclasses.dataclass(frozen=True)
class FullSubNetConfig:
    """FullSubNet at the upstream DNS recipe's widths (the defaults)."""

    name: str = "fullsubnet"
    sample_rate: int = 16000
    n_fft: int = 512
    hop: int = 256
    fb_hidden: int = 512
    fb_layers: int = 2
    sb_hidden: int = 384
    sb_layers: int = 2
    sb_neighbors: int = 15  # magnitude neighbours on each side of a bin
    look_ahead: int = 2  # frames

    @property
    def freq_bins(self) -> int:
        return self.n_fft // 2 + 1

    @property
    def sb_input(self) -> int:
        """Values a bin feeds the sub-band LSTM: its neighbourhood and the
        full-band output at that bin."""
        return 2 * self.sb_neighbors + 2


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _init_lstm(key, n_in: int, hidden: int, layers: int, dtype) -> List[Params]:
    bound = 1.0 / math.sqrt(hidden)  # PyTorch's LSTM init
    out = []
    for layer, k in enumerate(jax.random.split(key, layers)):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        d = n_in if layer == 0 else hidden
        u = lambda kk, shape: jax.random.uniform(kk, shape, dtype, -bound, bound)  # noqa: E731
        out.append({"W_ih": u(k1, (4 * hidden, d)), "W_hh": u(k2, (4 * hidden, hidden)),
                    "b_ih": u(k3, (4 * hidden,)), "b_hh": u(k4, (4 * hidden,))})
    return out


def _init_linear(key, n_in: int, n_out: int, dtype) -> Params:
    kw, kb = jax.random.split(key)
    bound = 1.0 / math.sqrt(n_in)
    return {"w": jax.random.uniform(kw, (n_in, n_out), dtype, -bound, bound),
            "b": jax.random.uniform(kb, (n_out,), dtype, -bound, bound)}


def init_params(key, cfg: FullSubNetConfig, dtype=jnp.float32) -> Params:
    """Fresh weights: ``{"fb": {"lstm", "linear"}, "sb": {"lstm", "linear"}}``."""
    k = jax.random.split(key, 4)
    F = cfg.freq_bins
    return {
        "fb": {"lstm": _init_lstm(k[0], F, cfg.fb_hidden, cfg.fb_layers, dtype),
               "linear": _init_linear(k[1], cfg.fb_hidden, F, dtype)},
        "sb": {"lstm": _init_lstm(k[2], cfg.sb_input, cfg.sb_hidden, cfg.sb_layers, dtype),
               "linear": _init_linear(k[3], cfg.sb_hidden, 2, dtype)},
    }


# ---------------------------------------------------------------------------
# Building blocks shared by the streaming and the offline path
# ---------------------------------------------------------------------------

def _lstm_step(layers: List[Params], x: jax.Array, hs: List[jax.Array],
               cs: List[jax.Array]):
    """One time step of a stacked LSTM on (N, in) rows; ``hs``, ``cs``: per
    layer (N, H). Returns the top layer's output and the new lists."""
    new_h, new_c = [], []
    for p, h, c in zip(layers, hs, cs):
        gates = x @ p["W_ih"].T + p["b_ih"] + h @ p["W_hh"].T + p["b_hh"]
        gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(gf) * c + jax.nn.sigmoid(gi) * jnp.tanh(gg)
        x = jax.nn.sigmoid(go) * jnp.tanh(c)
        new_h.append(x)
        new_c.append(c)
    return x, new_h, new_c


def _magnitude(spec_ri: jax.Array) -> jax.Array:
    return jnp.sqrt(spec_ri[..., 0] * spec_ri[..., 0] + spec_ri[..., 1] * spec_ri[..., 1])


def _units(mag: jax.Array, fb_out: jax.Array, n: int) -> jax.Array:
    """(..., F) magnitudes and full-band output -> (..., F, 2n + 2) sub-band
    inputs: the bin's neighbours ``f - n .. f + n`` (reflect padding at the
    edges), then the full-band output at ``f``."""
    F = mag.shape[-1]
    pad = [(0, 0)] * (mag.ndim - 1) + [(n, n)]
    padded = jnp.pad(mag, pad, mode="reflect")
    return jnp.stack([padded[..., k:k + F] for k in range(2 * n + 1)] + [fb_out], axis=-1)


def decompress_crm(raw: jax.Array) -> jax.Array:
    """The upstream ``decompress_cIRM``: clamp to +-limit, undo the K-bounded
    sigmoid compression."""
    m = jnp.clip(raw, -CRM_LIMIT, CRM_LIMIT)
    return -CRM_K * jnp.log((CRM_K - m) / (CRM_K + m))


def apply_crm(spec_ri: jax.Array, mask: jax.Array) -> jax.Array:
    """Complex mask (..., 2) times the noisy complex frame (..., 2)."""
    a, b = spec_ri[..., 0], spec_ri[..., 1]
    mr, mi = mask[..., 0], mask[..., 1]
    return (a * mr - b * mi) + 1j * (a * mi + b * mr)


# ---------------------------------------------------------------------------
# Streaming inference: one frame per step
# ---------------------------------------------------------------------------

def init_stream_state(p: Params, cfg: FullSubNetConfig, batch: int,
                      dtype=jnp.float32) -> Params:
    """Zeroed stream state; every leaf has the batch as its leading axis and
    at least one more (a wire ticket's slot slice is never 0-d). The LSTMs'
    (h, c) are one leaf per layer (``fb_h0``, ``sb_c1``, ...), so a layer's
    sub-band rows are a free reshape of (B, F, H)."""
    F = cfg.freq_bins
    z = lambda *shape: jnp.zeros((batch,) + shape, dtype)  # noqa: E731
    lstm = {f"fb_{k}{i}": z(cfg.fb_hidden) for i in range(cfg.fb_layers) for k in "hc"}
    lstm.update({f"sb_{k}{i}": z(F, cfg.sb_hidden)
                 for i in range(cfg.sb_layers) for k in "hc"})
    return {
        **lstm,
        "fb_sum": z(1),  # running sum of every magnitude seen
        "sb_sum": z(F),  # per bin: running sum of its sub-band inputs
        "frames": z(1),  # frames seen
        "held": z(cfg.look_ahead, F, 2),  # the look-ahead's delayed frames
    }


def stream_step(
    p: Params,
    state: Params,
    frame_ri: jax.Array,
    cfg: FullSubNetConfig,
) -> Tuple[Params, jax.Array]:
    """Process one frame; returns ``(new_state, est)``.

    frame_ri: (B, F, 2) the noisy frame ``t``. ``est`` (B, F) complex is the
    enhanced frame ``t - look_ahead`` (zeros before the stream had one): the
    mask computed at ``t`` times that frame, held in the state since.
    Stages run under the ``jax.named_scope`` names ``normalize``,
    ``fullband``, ``subband`` and ``mask``.
    """
    B, F = frame_ri.shape[:2]
    S = cfg.sb_input
    layers = lambda band, k, n: [state[f"{band}_{k}{i}"] for i in range(n)]  # noqa: E731
    with jax.named_scope("normalize"):
        mag = _magnitude(frame_ri)  # (B, F)
        frames = state["frames"] + 1.0
        fb_sum = state["fb_sum"] + jnp.sum(mag, axis=-1, keepdims=True)
        fb_in = mag / (fb_sum / (frames * F) + EPSILON)
    with jax.named_scope("fullband"):
        y, fb_h, fb_c = _lstm_step(p["fb"]["lstm"], fb_in, layers("fb", "h", cfg.fb_layers),
                                   layers("fb", "c", cfg.fb_layers))
        fb_out = jax.nn.relu(y @ p["fb"]["linear"]["w"] + p["fb"]["linear"]["b"])
    with jax.named_scope("subband"):
        units = _units(mag, fb_out, cfg.sb_neighbors)  # (B, F, S)
        sb_sum = state["sb_sum"] + jnp.sum(units, axis=-1)
        sb_in = units / (sb_sum / (frames * S) + EPSILON)[..., None]
        L, H = cfg.sb_layers, cfg.sb_hidden
        rows = lambda k: [t.reshape(B * F, H) for t in layers("sb", k, L)]  # noqa: E731
        y, sb_h, sb_c = _lstm_step(p["sb"]["lstm"], sb_in.reshape(B * F, S),
                                   rows("h"), rows("c"))
        raw = (y @ p["sb"]["linear"]["w"] + p["sb"]["linear"]["b"]).reshape(B, F, 2)
    with jax.named_scope("mask"):
        mask = decompress_crm(raw)
        held = jnp.concatenate([state["held"], frame_ri[:, None]], axis=1)
        est = apply_crm(held[:, 0], mask)
    new_state = {"fb_sum": fb_sum, "sb_sum": sb_sum, "frames": frames, "held": held[:, 1:]}
    for i in range(cfg.fb_layers):
        new_state.update({f"fb_h{i}": fb_h[i], f"fb_c{i}": fb_c[i]})
    for i in range(L):
        new_state.update({f"sb_h{i}": sb_h[i].reshape(B, F, H),
                          f"sb_c{i}": sb_c[i].reshape(B, F, H)})
    return new_state, est


def stream_estimate(p: Params, state: Params, frame_ri: jax.Array,
                    cfg: FullSubNetConfig, quant: Optional[Any] = None):
    """The serving seam's model step: ``stream_step`` (float32 only:
    ``make_stream_hop`` refuses ``quant`` for this model)."""
    return stream_step(p, state, frame_ri, cfg)


def passthrough_estimate(state: Params, frame_ri: jax.Array, cfg: FullSubNetConfig):
    """The brownout hop's spectrum: the unmasked frame ``t - look_ahead``, so
    the output keeps the model path's delay. Only the held frames move; the
    LSTM states and the norms' sums are carried untouched."""
    held = jnp.concatenate([state["held"], frame_ri[:, None]], axis=1)
    past = held[:, 0]
    return {**state, "held": held[:, 1:]}, past[..., 0] + 1j * past[..., 1]


# ---------------------------------------------------------------------------
# Offline: a whole spectrogram at once
# ---------------------------------------------------------------------------

def _scan_lstm(layers: List[Params], x: jax.Array, hidden: int) -> jax.Array:
    """Stacked LSTM over axis 0 of (T, N, in) from zero state; (T, N, H)."""
    h0 = [jnp.zeros((x.shape[1], hidden), x.dtype) for _ in layers]

    def body(carry, xt):
        y, h, c = _lstm_step(layers, xt, *carry)
        return (h, c), y

    _, ys = jax.lax.scan(body, (h0, h0), x)
    return ys


def apply(p: Params, spec_ri: jax.Array, cfg: FullSubNetConfig) -> jax.Array:
    """The upstream forward: noisy spectrogram (B, F, T, 2) -> decompressed
    cIRM (B, F, T, 2) for each frame ``t`` (computed at step ``t +
    look_ahead`` over a spectrogram padded with ``look_ahead`` zero frames).
    """
    B, F, T, _ = spec_ri.shape
    S, La = cfg.sb_input, cfg.look_ahead
    mag = _magnitude(spec_ri).transpose(2, 0, 1)  # (T, B, F)
    mag = jnp.concatenate([mag, jnp.zeros((La, B, F), mag.dtype)], axis=0)
    n = jnp.arange(1, T + La + 1, dtype=mag.dtype)[:, None]  # frames seen
    fb_mean = jnp.cumsum(jnp.sum(mag, axis=-1), axis=0) / (n * F)
    fb_in = mag / (fb_mean + EPSILON)[..., None]
    fb_y = _scan_lstm(p["fb"]["lstm"], fb_in, cfg.fb_hidden)
    fb_out = jax.nn.relu(fb_y @ p["fb"]["linear"]["w"] + p["fb"]["linear"]["b"])
    units = _units(mag, fb_out, cfg.sb_neighbors)  # (T', B, F, S)
    sb_mean = jnp.cumsum(jnp.sum(units, axis=-1), axis=0) / (n[..., None] * S)
    sb_in = units / (sb_mean + EPSILON)[..., None]
    sb_y = _scan_lstm(p["sb"]["lstm"], sb_in.reshape(T + La, B * F, S), cfg.sb_hidden)
    raw = (sb_y @ p["sb"]["linear"]["w"] + p["sb"]["linear"]["b"]).reshape(T + La, B, F, 2)
    return decompress_crm(raw[La:]).transpose(1, 2, 0, 3)


def offline_estimate(p: Params, spec_ri: jax.Array, cfg: FullSubNetConfig) -> jax.Array:
    """(B, F, T, 2) noisy spectrogram -> (B, F, T) complex spectra as the
    stream emits them: at position ``t``, enhanced frame ``t - look_ahead``."""
    est = apply_crm(spec_ri, apply(p, spec_ri, cfg))
    La = cfg.look_ahead
    return jnp.pad(est, ((0, 0), (0, 0), (La, 0)))[:, :, : est.shape[2]]


# ---------------------------------------------------------------------------
# Analytics
# ---------------------------------------------------------------------------

def macs_per_frame(cfg: FullSubNetConfig) -> float:
    """Multiply-accumulates of the LSTMs and linear layers for one frame
    (471.4 M at the recipe's widths, 99.2% of it in the sub-band model)."""
    F = cfg.freq_bins

    def lstm(n_in, h, layers):
        return sum(4 * h * ((n_in if i == 0 else h) + h) for i in range(layers))

    fb = lstm(F, cfg.fb_hidden, cfg.fb_layers) + cfg.fb_hidden * F
    sb = lstm(cfg.sb_input, cfg.sb_hidden, cfg.sb_layers) + cfg.sb_hidden * 2
    return float(fb + F * sb)
