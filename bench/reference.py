"""Plain reference of TFTNN streaming enhancement, for the `correct` check.

A straightforward float32 `jax.numpy` transcription of the paper's model
(arXiv 2503.21335; the layer equations as the program writes them in
``models/tftnn.py``, ``core/bn_transformer.py`` and
``serve/streaming_se.enhance_offline``). It imports nothing of the program
and takes only the weight pytree the benchmark made from the seed.

Every operation but the full-band GRU is local to one STFT frame (all
convolutions have a time kernel of 1), so the reference runs whole blocks of
frames at once and carries only the full-band GRU states and the
overlap-add tail from block to block. Matmuls run at the precision given to
``enhance``: the one the configuration states, ``highest`` for fp32 and the
TPU default (one bf16 pass) for the FP10 deployment.

A configuration that states a deployment number format (FP10: 1 sign, 5
exponent, 4 mantissa bits) is referenced in that format, as the paper
deploys it: every batch norm folded into its neighbouring layer, every
folded weight and bias rounded onto the grid once, and the spectral
features entering the model and the mask leaving it rounded on every frame.
Everything else stays float32. ``round_to_grid`` is a copy of the rounding
the paper describes (round to nearest even, subnormals, saturating at the
largest finite value), kept here so that the yardstick cannot move.

Output sample ``i`` of a stream is the enhanced input sample ``i - 384``
(``n_fft - hop``): output hop ``k`` is final once input hop ``k`` arrived,
which is what a streaming server emits after each hop.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import numpy as np

EPS_BN = 1e-5


def _hann(n):
    import jax.numpy as jnp

    i = jnp.arange(n, dtype=jnp.float32)
    return 0.5 * (1.0 - jnp.cos(2.0 * jnp.pi * i / n))


def _affine(p):
    """Batch norm at inference as ``x * a + c``."""
    import jax

    a = jax.lax.rsqrt(p["var"] + EPS_BN) * p["scale"]
    return a, p["bias"] - p["mean"] * a


def _bn(p, x):
    """Batch norm at inference; ``None`` is one already folded away."""
    if p is None:
        return x
    a, c = _affine(p)
    return x * a + c


def round_to_grid(x, grid):
    """Round float32 ``x`` onto a minifloat grid ``(exp_bits, man_bits)``."""
    import jax.numpy as jnp

    if grid is None:
        return x
    e_bits, m_bits = grid
    bias = 2 ** (e_bits - 1) - 1
    max_exp = 2 ** e_bits - 2 - bias
    max_val = (2.0 - 2.0 ** -m_bits) * 2.0 ** max_exp
    mag = jnp.abs(x)
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(mag, 1e-45))), 1 - bias, max_exp)
    step = jnp.exp2(e - m_bits)
    q = jnp.minimum(jnp.round(mag / step) * step, max_val)
    return (jnp.sign(x) * jnp.where(mag == 0, 0.0, q)).astype(x.dtype)


def deploy_params(params, grid):
    """Fold every batch norm into its neighbour, then round every leaf."""
    import jax

    def post(conv, bn):  # BN(x @ w + b): scale the output channels
        a, c = _affine(bn)
        return {"w": conv["w"] * a, "b": conv.get("b", 0.0) * a + c}

    def pre(lin, bn, w="w", b="b"):  # (BN(x)) @ w + b: scale the input rows
        a, c = _affine(bn)
        return {**lin, w: lin[w] * a[:, None], b: c @ lin[w] + lin.get(b, 0.0)}

    p = dict(params)
    for conv, norm in (("enc_in", "enc_in_norm"), ("enc_down", "enc_down_norm"),
                       ("dec_up", "dec_up_norm")):
        p[conv], p[norm] = post(params[conv], params[norm]), None
    for stack in ("enc_dilated", "dec_dilated"):
        p[stack] = {"layers": [{**layer, "conv": post(layer["conv"], layer["norm"]),
                                "norm": None} for layer in params[stack]["layers"]]}
    blocks = []
    for blk in params["blocks"]:
        sub, full = dict(blk["sub"]), dict(blk["full"])
        for proj, norm in (("wq", "bn_q"), ("wk", "bn_k"), ("wv", None)):
            lin = post(sub[proj], sub[norm]) if norm else sub[proj]
            sub[proj] = pre(lin, blk["sub"]["bn1"])
        sub["bn1"] = sub["bn_q"] = sub["bn_k"] = None
        for g in ("gru_f", "gru_b"):
            sub[g] = pre(sub[g], blk["sub"]["bn2"], "wi", "bi")
        sub["bn2"] = None
        full["gru_f"] = pre(full["gru_f"], blk["full"]["bn2"], "wi", "bi")
        full["bn2"] = None
        blocks.append({"sub": sub, "full": full})
    p["blocks"] = blocks
    return jax.tree_util.tree_map(lambda x: round_to_grid(x, grid), p)


def _conv_f(p, x, dil=1, stride=1):
    """Conv along F of (N, F, C) with kernel (k, 1, cin, cout), 'same' pad."""
    import jax.numpy as jnp

    w = p["w"][:, 0]  # (k, cin, cout)
    k = w.shape[0]
    pad = (k - 1) * dil // 2
    xp = jnp.pad(x, ((0, 0), (pad, (k - 1) * dil - pad), (0, 0)))
    F = x.shape[1]
    y = sum(
        jnp.einsum("nfc,cd->nfd", xp[:, j * dil: j * dil + F], w[j])
        for j in range(k)
    )
    y = y[:, ::stride]
    return y + p["b"]


def _dilated(p, x, rates):
    import jax.numpy as jnp

    out = x
    for layer, d in zip(p["layers"], rates):
        C = out.shape[-1]
        xp, xb = out[..., : C // 2], out[..., C // 2:]
        y = jnp.maximum(_bn(layer["norm"], _conv_f(layer["conv"], xp, dil=d)), 0.0)
        out = jnp.concatenate([xb, y + xp], axis=-1)
    return out


def _dense(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def _gru_scan(p, x, h0, reverse=False):
    """GRU over axis 1 of (N, L, D) from h0 (N, H); returns (ys, h_last)."""
    import jax
    import jax.numpy as jnp

    H = p["wh"].shape[0]

    def cell(h, xt):
        gi = xt @ p["wi"] + p["bi"]
        gh = h @ p["wh"] + p["bh"]
        r = jax.nn.sigmoid(gi[:, :H] + gh[:, :H])
        z = jax.nn.sigmoid(gi[:, H:2 * H] + gh[:, H:2 * H])
        n = jnp.tanh(gi[:, 2 * H:] + r * gh[:, 2 * H:])
        h = (1.0 - z) * n + z * h
        return h, h

    h, ys = jax.lax.scan(cell, h0, jnp.swapaxes(x, 0, 1), reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), h


def _sub_band(p, z, heads):
    """Sub-band stage on (N, L, d): BN attention sub-block + bi-GRU."""
    import jax.numpy as jnp

    N, L, d = z.shape
    h = _bn(p["bn1"], z)
    q = _bn(p.get("bn_q"), _dense(p["wq"], h))
    k = _bn(p.get("bn_k"), _dense(p["wk"], h))
    v = _dense(p["wv"], h)
    split = lambda t: t.reshape(N, L, heads, d // heads)  # noqa: E731
    kv = jnp.einsum("nlhd,nlhe->nhde", split(k), split(v)) / L
    att = jnp.einsum("nlhd,nhde->nlhe", split(q), kv).reshape(N, L, d)
    y = z + _dense(p["wo"], att)
    h = _bn(p["bn2"], y)
    H = p["gru_f"]["wh"].shape[0]
    zero = jnp.zeros((N, H), z.dtype)
    gf, _ = _gru_scan(p["gru_f"], h, zero)
    gb, _ = _gru_scan(p["gru_b"], h, zero, reverse=True)
    return y + _dense(p["w_out"], jnp.concatenate([gf, gb], axis=-1))


def _block(params, m, carry, seg, precision, grid):
    """Enhance one block of frames for a batch of streams.

    seg: (B, T*hop + n_fft - hop) input samples, the block's frames' span.
    carry: (gru states per transformer block, OLA tail, window-square tail).
    Returns (new carry, (B, T*hop) output samples).
    """
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision(precision):
        n_fft, hop = m["n_fft"], m["hop"]
        B = seg.shape[0]
        T = (seg.shape[1] - (n_fft - hop)) // hop
        w = _hann(n_fft)
        idx = jnp.arange(T)[:, None] * hop + jnp.arange(n_fft)[None, :]
        spec = jnp.fft.rfft(seg[:, idx] * w, axis=-1)  # (B, T, n_fft/2+1)
        Fb = m["freq_bins"]
        spec_ri = round_to_grid(jnp.stack([spec.real, spec.imag], -1), grid)
        x = spec_ri[:, :, :Fb]  # (B, T, F, 2)
        x = x.reshape(B * T, Fb, 2)
        rates = m["dilation_rates"]
        # encoder
        y = jnp.maximum(_bn(params["enc_in_norm"], _conv_f(params["enc_in"], x)), 0)
        y = _dilated(params["enc_dilated"], y, rates)
        y = _conv_f(params["enc_down"], y, stride=m["downsample"])
        enc = jnp.maximum(_bn(params["enc_down_norm"], y), 0)  # (BT, F', C)
        # two-stage transformer
        z = _dense(params["att_in"], enc)  # (BT, F', d)
        Fp, d = z.shape[1], z.shape[2]
        grus, tail, wtail = carry
        new_grus = []
        for blk, h0 in zip(params["blocks"], grus):
            z = _sub_band(blk["sub"], z, m["num_heads"])
            # full-band stage: a GRU along time for every (stream, F') row
            zf = z.reshape(B, T, Fp, d).transpose(0, 2, 1, 3).reshape(B * Fp, T, d)
            g, h_last = _gru_scan(blk["full"]["gru_f"], _bn(blk["full"]["bn2"], zf),
                                  h0.reshape(B * Fp, -1))
            zf = zf + _dense(blk["full"]["w_out"], g)
            new_grus.append(h_last.reshape(B, Fp, -1))
            z = zf.reshape(B, Fp, T, d).transpose(0, 2, 1, 3).reshape(B * T, Fp, d)
        tr = _dense(params["att_out"], z)
        # mask module and decoder
        mk = _conv_f(params["mask_conv2"],
                     jnp.maximum(_conv_f(params["mask_conv1"], tr), 0))
        h = _dilated(params["dec_dilated"], enc * mk, rates)
        h = jnp.maximum(_bn(params["dec_up_norm"], _conv_f(params["dec_up"], h)), 0)
        r = m["downsample"]
        N, _, Cr = h.shape
        h = h.reshape(N, Fp, r, Cr // r).reshape(N, Fp * r, Cr // r)  # sub-pixel along F
        mask = _conv_f(params["dec_out"], h).reshape(B, T, Fb, 2)
        nb = spec.shape[-1]
        mask = jnp.concatenate([mask, jnp.zeros((B, T, nb - Fb, 2), mask.dtype)], 2)
        mm = 2.0 * jnp.tanh(round_to_grid(mask, grid))
        a, b = spec_ri[..., 0], spec_ri[..., 1]
        est = (a * mm[..., 0] - b * mm[..., 1]) + 1j * (a * mm[..., 1] + b * mm[..., 0])
        frames = jnp.fft.irfft(est, n=n_fft, axis=-1) * w  # (B, T, n_fft)
        # weighted overlap-add, carrying the n_fft - hop tail to the next block
        span = T * hop + n_fft - hop
        acc = jnp.zeros((B, span), jnp.float32).at[:, : n_fft - hop].set(tail)
        acc = acc.at[:, idx].add(frames)
        wsq = jnp.zeros((span,), jnp.float32).at[: n_fft - hop].set(wtail)
        wsq = wsq.at[idx].add(jnp.broadcast_to(w * w, (T, n_fft)))
        out = acc[:, : T * hop] / jnp.maximum(wsq[: T * hop], 1e-8)
        return (new_grus, acc[:, T * hop:], wsq[T * hop:]), out


@functools.lru_cache(maxsize=8)
def _block_fn(model_key, precision, grid):
    import jax

    m = dict(model_key)
    return jax.jit(lambda p, c, s: _block(p, m, c, s, precision, grid))


def enhance(params, model: Dict, waves: Sequence[np.ndarray], *,
            grid=None, batch: int = 16, block_frames: int = 1024,
            precision: str = "highest") -> List[np.ndarray]:
    """Enhance each wave as a fresh stream; returns ``len // hop * hop``
    output samples per wave (the samples a streaming server emits).

    ``grid``: ``(exp_bits, man_bits)`` of the configuration's deployment
    format, or ``None`` for plain float32. Waves are processed ``batch`` at
    a time in blocks of ``block_frames`` frames, so one compiled program
    serves any number and length of waves.
    """
    import jax
    import jax.numpy as jnp

    if grid is not None:
        grid = tuple(grid)
        with jax.default_matmul_precision(precision):
            params = jax.jit(lambda p: deploy_params(p, grid))(params)

    hop, n_fft = model["hop"], model["n_fft"]
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in model.items()))
    fn = _block_fn(key, precision, grid)
    H, Fp = model["gru_hidden"], model["freq_bins"] // model["downsample"]
    nblk = model["num_transformer_blocks"]
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
        carry = ([jnp.zeros((batch, Fp, H), jnp.float32)] * nblk,
                 jnp.zeros((batch, n_fft - hop), jnp.float32),
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
