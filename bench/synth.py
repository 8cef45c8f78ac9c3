"""Synthetic noisy speech for the load generator, in numpy.

A copy of the program's ``repro.audio.synthetic`` (harmonic voice with a
drifting pitch, syllabic gating and a formant envelope, mixed at 2.5 dB SNR
with coloured noise, mains-like hum and clatter), kept here so that a change
to the program cannot move the benchmark's inputs. Only numpy: the process
that sends traffic never loads JAX.

Every stream reads a slice of one bank of utterances that is a pure function
of the seed, so the process that holds the chip rebuilds the exact samples a
stream was fed when it checks the output.
"""

from __future__ import annotations

import numpy as np

UTTERANCE_S = 3  # the paper's 3 s training segment
SNR_DB = 2.5  # the paper's mixing SNR


def _voice(rng, n, sr):
    t = np.arange(n) / sr
    f0 = rng.uniform(80.0, 260.0)
    drift = 20.0 * np.sin(2 * np.pi * rng.uniform(0.5, 2.0) * t)
    phase = 2 * np.pi * np.cumsum(f0 + drift) / sr
    h = np.arange(1, 13)[:, None]
    sig = np.sum(h ** -1.2 * np.sin(h * phase[None, :]), axis=0)
    env = 0.5 + 0.5 * np.sin(2 * np.pi * rng.uniform(0.2, 0.6) * t)
    syl = 1.0 / (1.0 + np.exp(-8.0 * np.sin(2 * np.pi * 3.7 * t + rng.uniform(0, 6.28))))
    gate = 1.0 if rng.uniform() > 0.15 else 0.6
    sig = sig * env * syl * gate
    return sig / (np.std(sig) + 1e-6)


def _noise(rng, n, sr):
    spec = np.fft.rfft(rng.standard_normal(n))
    f = np.linspace(0, 1, spec.shape[0])
    colored = np.fft.irfft(spec / (1.0 + 8.0 * f) ** rng.uniform(0.5, 2.0), n=n)
    t = np.arange(n) / sr
    hum = 0.3 * np.sin(2 * np.pi * rng.uniform(50.0, 400.0) * t)
    impulses = (rng.uniform(size=n) > 0.999) * rng.standard_normal(n) * 4.0
    noise = colored / (np.std(colored) + 1e-6) + hum + impulses
    return noise / (np.std(noise) + 1e-6)


def bank(seed: int, sample_rate: int = 8000, utterances: int = 32) -> np.ndarray:
    """``utterances`` 3 s noisy utterances at ``sample_rate`` back to back,
    float32, peak 1 each."""
    rng = np.random.default_rng([seed, 0x5E])
    n = UTTERANCE_S * sample_rate
    out = []
    for _ in range(utterances):
        clean = _voice(rng, n, sample_rate)
        noise = _noise(rng, n, sample_rate)
        scale = np.sqrt(np.mean(clean ** 2) / (np.mean(noise ** 2) * 10 ** (SNR_DB / 10) + 1e-12))
        noisy = clean + scale * noise
        out.append(noisy / (np.max(np.abs(noisy)) + 1e-6))
    return np.concatenate(out).astype(np.float32)


def stream(bank_: np.ndarray, offset: int, n: int) -> np.ndarray:
    """``n`` samples of the bank from ``offset``, wrapping round its end."""
    idx = (offset + np.arange(n)) % bank_.size
    return bank_[idx]
