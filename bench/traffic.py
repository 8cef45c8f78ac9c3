"""The one traffic generator: a seat plan from a mix's parameters and a seed.

A traffic mix is a JSON file of parameters under ``bench/traffic/``. Its
``sessions`` seats each run one stream after another. A stream attaches,
feeds its audio in ``chunk_samples`` chunks, sends one READ after every FEED,
drains its output and detaches; the seat then starts its next stream.

- ``paced: true`` is an open loop: chunk ``i`` of a stream is due at the
  stream's start plus ``(i + 1)`` chunk durations, jittered uniformly by
  ``jitter_ms``, and the seat's next stream starts when the last one's
  holding time ends, whatever the server did. The seats' first streams start
  at offsets spread evenly over one chunk duration, in an order drawn from
  the seed, so every seed offers the same interleaving of packets. A
  stream's latency counts from when its chunk was due, not from when it
  was sent.
- ``paced: false`` is a closed loop: chunks go back to back and the next
  stream starts after the previous one detached.

Stream lengths are the quantiles ``(i + 0.5) / 4096`` of a lognormal with
``length_median_s`` and ``length_sigma``, capped at ``length_cap_s``, in an
order drawn from the seed: every seed serves the same set of lengths. With
``pre_aged`` the first stream of each seat is the remainder of a call already
in progress (a length-biased draw, cut at a uniform point), so the run sees
steady attach and detach churn from its start.

Only numpy: the process that sends the traffic never loads JAX.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

QUANTILES = 4096


@dataclasses.dataclass(frozen=True)
class Stream:
    seat: int
    index: int  # position on the seat
    samples: int  # audio the stream feeds if it runs to its end
    offset: int  # where its audio starts in the synthetic bank


def lengths(mix: dict, rng: np.random.Generator) -> np.ndarray:
    """The mix's stream lengths in samples, one quantile set in seed order."""
    sr = mix["sample_rate"]
    mu, sigma = math.log(mix["length_median_s"]), mix["length_sigma"]
    nd = NormalDist()
    q = np.array([math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / QUANTILES))
                  for i in range(QUANTILES)])
    q = np.minimum(q, mix["length_cap_s"])
    return np.maximum(1, np.round(rng.permutation(q) * sr)).astype(np.int64)


def plan(mix: dict, seed: int, bank_size: int, max_streams: int = 4096) -> List[List[Stream]]:
    """Per seat, the streams it serves in order (more than a run can use)."""
    rng = np.random.default_rng([seed, 0x7A])
    seats = mix["sessions"]
    lens = lengths(mix, rng)
    sr = mix["sample_rate"]
    mu, sigma = math.log(mix["length_median_s"]), mix["length_sigma"]
    out: List[List[Stream]] = []
    per_seat = max(2, max_streams // seats)
    for s in range(seats):
        streams = []
        for i in range(per_seat):
            n = int(lens[(s + i * seats) % lens.size])
            if i == 0 and mix["pre_aged"]:
                # the rest of a call already under way: length-biased draw
                # (lognormal with mu + sigma^2), cut at a uniform point
                full = math.exp(rng.normal(mu + sigma ** 2, sigma))
                full = min(full, mix["length_cap_s"])
                n = max(1, int(round(full * rng.uniform() * sr)))
            streams.append(Stream(s, i, n, int(rng.integers(bank_size))))
        out.append(streams)
    return out


def seat_phases(mix: dict, seed: int) -> np.ndarray:
    """Per seat, the start offset (s) of its first stream."""
    if not mix["paced"]:
        return np.zeros(mix["sessions"])
    n, dur = mix["sessions"], mix["chunk_samples"] / mix["sample_rate"]
    order = np.random.default_rng([seed, 0xF0]).permutation(n)
    return (order + 0.5) / n * dur


def chunk_due(mix: dict, start: float, n_chunks: int, rng: np.random.Generator) -> np.ndarray:
    """Scheduled send times of a paced stream's chunks."""
    dur = mix["chunk_samples"] / mix["sample_rate"]
    jit = mix["jitter_ms"] / 1e3
    return start + dur * np.arange(1, n_chunks + 1) + rng.uniform(-jit, jit, n_chunks)
