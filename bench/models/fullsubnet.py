"""FullSubNet (arXiv 2010.15508): the model family of ``fullsubnet-*``, and
its plain reference for the ``correct`` check.

The reference is a straightforward float32 ``jax.numpy`` transcription of
the upstream model (github.com/Audio-WestlakeU/FullSubNet,
``recipes/dns_interspeech_2020/fullsubnet``; ``audio_zen/model/base_model.py``
for the norm, the unfold and the cIRM decompression), streamed frame by
frame as a server would. It imports nothing of the program and takes only
the weight pytree the benchmark made from the seed:

- frames of ``n_fft`` samples every ``hop``, periodic Hann, with zero
  history before the stream's first sample;
- the noisy magnitude divided by the running mean of every magnitude so
  far (``cumulative_laplace_norm``, epsilon float32 machine epsilon);
- the full-band LSTM, Linear and ReLU; each bin's 31 reflect-padded
  magnitude neighbours and the full-band output at that bin, divided by
  their own running mean; the sub-band LSTM and Linear on every bin;
- the cIRM clamped to +-limit and decompressed, ``-K ln((K - m)/(K + m))``,
  times the noisy frame ``look_ahead`` frames back;
- inverse FFT and weighted overlap-add by the running sum of squared
  windows.

Output sample ``i`` of a stream is input sample ``i - (n_fft - hop) -
look_ahead * hop`` (768 at the published widths). Matmuls run at the
precision ``enhance`` is given. Streams are enhanced ``batch`` at a time,
each padded to its block's longest, in chunks of ``block_frames`` frames
that carry the state, so one compiled program serves any number and length
of streams.

The weight layout is the program's: ``{"fb"|"sb": {"lstm": [per layer
{"W_ih" (4H, in), "W_hh" (4H, H), "b_ih", "b_hh"}], "linear": {"w" (in,
out), "b"}}}``, gate order i, f, g, o.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import numpy as np

EPS = float(np.finfo(np.float32).eps)
CRM_K, CRM_LIMIT = 10.0, 9.9  # decompress_cIRM's defaults upstream
TINY = dict(fb_hidden=32, sb_hidden=16)


def program_config(model: dict):
    from repro.models import fullsubnet

    return fullsubnet.FullSubNetConfig(**model)


def param_shapes(cfg):
    import jax

    from repro.models import fullsubnet

    return jax.eval_shape(lambda k: fullsubnet.init_params(k, cfg), jax.random.PRNGKey(0))


def macs_per_hop(m: Dict) -> float:
    """Multiply-adds of the LSTMs and linear layers for one frame (one hop):
    471,387,392 at the published widths."""
    F = m["n_fft"] // 2 + 1
    S = 2 * m["sb_neighbors"] + 2

    def lstm(n_in, h, layers):
        return 4 * h * (n_in + h) + (layers - 1) * 4 * h * (2 * h)

    fb = lstm(F, m["fb_hidden"], m["fb_layers"]) + m["fb_hidden"] * F
    sb = lstm(S, m["sb_hidden"], m["sb_layers"]) + m["sb_hidden"] * 2
    return float(fb + F * sb)


def _hann(n):
    import jax.numpy as jnp

    i = jnp.arange(n, dtype=jnp.float32)
    return 0.5 * (1.0 - jnp.cos(2.0 * jnp.pi * i / n))


def _lstm(layers, x, hs, cs):
    """One step of a stacked LSTM; ``hs``, ``cs``: per-layer (N, H) lists."""
    import jax
    import jax.numpy as jnp

    new_h, new_c = [], []
    for p, h, c in zip(layers, hs, cs):
        z = x @ p["W_ih"].T + h @ p["W_hh"].T + p["b_ih"] + p["b_hh"]
        H = h.shape[-1]
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H:2 * H])
        g = jnp.tanh(z[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H:])
        c = f * c + i * g
        x = o * jnp.tanh(c)
        new_h.append(x)
        new_c.append(c)
    return x, new_h, new_c


def _frame(params, m, carry, spec):
    """One frame for a batch of streams: spec (B, F) complex -> the enhanced
    frame ``look_ahead`` frames back, (B, F) complex."""
    import jax
    import jax.numpy as jnp

    fb_h, fb_c, sb_h, sb_c, fb_sum, sb_sum, count, held = carry
    B, F = spec.shape
    n = m["sb_neighbors"]
    mag = jnp.abs(spec)
    count = count + 1.0
    # full band
    fb_sum = fb_sum + jnp.sum(mag, axis=1)
    x = mag / (fb_sum / (count * F) + EPS)[:, None]
    y, fb_h, fb_c = _lstm(params["fb"]["lstm"], x, fb_h, fb_c)
    fb_out = jnp.maximum(y @ params["fb"]["linear"]["w"] + params["fb"]["linear"]["b"], 0.0)
    # sub band: bin f sees magnitudes f-n .. f+n (reflected at the edges)
    left = mag[:, 1:n + 1][:, ::-1]
    right = mag[:, F - n - 1:F - 1][:, ::-1]
    padded = jnp.concatenate([left, mag, right], axis=1)  # (B, F + 2n)
    idx = jnp.arange(F)[:, None] + jnp.arange(2 * n + 1)[None, :]
    units = jnp.concatenate([padded[:, idx], fb_out[:, :, None]], axis=2)  # (B, F, 2n+2)
    S = units.shape[2]
    sb_sum = sb_sum + jnp.sum(units, axis=2)
    x = (units / (sb_sum / (count[:, None] * S) + EPS)[:, :, None]).reshape(B * F, S)
    y, sb_h, sb_c = _lstm(params["sb"]["lstm"], x, sb_h, sb_c)
    crm = (y @ params["sb"]["linear"]["w"] + params["sb"]["linear"]["b"]).reshape(B, F, 2)
    # cIRM decompression, applied to the held frame
    lim, K = CRM_LIMIT, CRM_K
    crm = jnp.where(crm >= lim, lim, jnp.where(crm <= -lim, -lim, crm))
    crm = -K * jnp.log((K - crm) / (K + crm))
    past = held[0]
    est = past * (crm[..., 0] + 1j * crm[..., 1])
    held = held[1:] + [spec]
    return (fb_h, fb_c, sb_h, sb_c, fb_sum, sb_sum, count, held), est


def _block(params, m, carry, seg, precision):
    """Enhance one block of frames for a batch of streams.

    seg: (B, T*hop + n_fft - hop) input samples, the block's frames' span.
    carry: (model state, OLA tail, window-square tail).
    Returns (new carry, (B, T*hop) output samples).
    """
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision(precision):
        n_fft, hop = m["n_fft"], m["hop"]
        T = (seg.shape[1] - (n_fft - hop)) // hop
        w = _hann(n_fft)
        idx = jnp.arange(T)[:, None] * hop + jnp.arange(n_fft)[None, :]
        spec = jnp.fft.rfft(seg[:, idx] * w, axis=-1)  # (B, T, F)
        state, tail, wtail = carry
        state, est = jax.lax.scan(lambda c, s: _frame(params, m, c, s), state,
                                  jnp.swapaxes(spec, 0, 1))
        frames = jnp.fft.irfft(jnp.swapaxes(est, 0, 1), n=n_fft, axis=-1) * w
        span = T * hop + n_fft - hop
        acc = jnp.zeros(frames.shape[:1] + (span,), jnp.float32).at[:, : n_fft - hop].set(tail)
        acc = acc.at[:, idx].add(frames)
        wsq = jnp.zeros((span,), jnp.float32).at[: n_fft - hop].set(wtail)
        wsq = wsq.at[idx].add(jnp.broadcast_to(w * w, (T, n_fft)))
        out = acc[:, : T * hop] / jnp.maximum(wsq[: T * hop], 1e-8)
        return (state, acc[:, T * hop:], wsq[T * hop:]), out


@functools.lru_cache(maxsize=8)
def _block_fn(model_key, precision):
    import jax

    m = dict(model_key)
    return jax.jit(lambda p, c, s: _block(p, m, c, s, precision))


def _fresh(m: Dict, batch: int):
    import jax.numpy as jnp

    F = m["n_fft"] // 2 + 1
    zeros = lambda *s: jnp.zeros((batch,) + s, jnp.float32)  # noqa: E731
    fb = [zeros(m["fb_hidden"]) for _ in range(m["fb_layers"])]
    sb = [zeros(F, m["sb_hidden"]).reshape(batch * F, -1) for _ in range(m["sb_layers"])]
    held = [jnp.zeros((batch, F), jnp.complex64) for _ in range(m["look_ahead"])]
    return (fb, list(fb), sb, list(sb), zeros(), zeros(F), zeros(), held)


def enhance(params, model: Dict, waves: Sequence[np.ndarray], grid=None,
            precision: str = "highest", *, batch: int = 64,
            block_frames: int = 256) -> List[np.ndarray]:
    """Enhance each wave as a fresh stream; returns ``len // hop * hop``
    output samples per wave (the samples a streaming server emits).

    ``grid`` must be ``None``: FullSubNet is referenced in float32 only.
    """
    import jax.numpy as jnp

    if grid is not None:
        raise ValueError("FullSubNet has no deployment number format")
    hop, n_fft = model["hop"], model["n_fft"]
    key = tuple(sorted(model.items()))
    fn = _block_fn(key, precision)
    order = sorted(range(len(waves)), key=lambda i: len(waves[i]))
    outs: List[np.ndarray] = [None] * len(waves)
    for g in range(0, len(order), batch):
        group = order[g: g + batch]
        hops = [len(waves[i]) // hop for i in group]
        blocks = max(1, math.ceil(max(hops) / block_frames))
        span = blocks * block_frames * hop
        x = np.zeros((batch, n_fft - hop + span), np.float32)
        for r, i in enumerate(group):
            x[r, n_fft - hop: n_fft - hop + hops[r] * hop] = waves[i][: hops[r] * hop]
        carry = (_fresh(model, batch), jnp.zeros((batch, n_fft - hop), jnp.float32),
                 jnp.zeros((n_fft - hop,), jnp.float32))
        pieces = []
        step = block_frames * hop
        for j in range(blocks):
            carry, y = fn(params, carry, jnp.asarray(x[:, j * step: j * step + step + n_fft - hop]))
            pieces.append(np.asarray(y))
        y = np.concatenate(pieces, axis=1)
        for r, i in enumerate(group):
            outs[i] = y[r, : hops[r] * hop]
    return outs
