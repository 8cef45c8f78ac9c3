"""Per-hop accounting of the streams a run served: pure arithmetic.

A stream's hop ``h`` is due when the chunk carrying its last input sample,
``(h + 1) * hop - 1``, was due (paced traffic) or sent (closed loop), and
delivered when the reply that carried its last output sample arrived. Its
latency is the difference. A hop is attempted when it is due inside the
measured window ``[t0, t1)``; one never delivered has failed, and its latency
is counted up to the end of the drain.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def hop_times(feed_due, feed_cum, recv_t, recv_cum, hop: int):
    """(due, delivered) time per whole hop fed; delivered is NaN if missing.

    feed_due / feed_cum: per FEED, its due time and the samples fed so far;
    recv_t / recv_cum: per reply with audio, its arrival time and the
    samples received so far (both cumulative arrays non-decreasing).
    """
    feed_cum = np.asarray(feed_cum, np.int64)
    n = int(feed_cum[-1]) // hop if feed_cum.size else 0
    ends = (np.arange(n, dtype=np.int64) + 1) * hop
    due = np.asarray(feed_due, np.float64)[np.searchsorted(feed_cum, ends - 1, side="right")]
    recv_cum = np.asarray(recv_cum, np.int64)
    r = np.searchsorted(recv_cum, ends, side="left")
    got = r < recv_cum.size
    done = np.full(n, np.nan)
    done[got] = np.asarray(recv_t, np.float64)[r[got]]
    return due, done


def window_stats(due, done, t0: float, t1: float, t_end: float) -> Dict[str, object]:
    """Attempted and failed hops due in ``[t0, t1)`` and their latencies (s).

    A hop never delivered counts as failed with the latency it had reached at
    ``t_end``, so the tail includes it.
    """
    due, done = np.asarray(due), np.asarray(done)
    inw = (due >= t0) & (due < t1)
    lost = inw & np.isnan(done)
    lat = np.where(np.isnan(done), t_end, done)[inw] - due[inw]
    return {"attempted": int(inw.sum()), "failed": int(lost.sum()), "latency_s": lat}


def delivered_in_window(recv_t, recv_n, t0: float, t1: float) -> int:
    """Samples that arrived in ``[t0, t1)``, partial streams included."""
    t, n = np.asarray(recv_t), np.asarray(recv_n, np.int64)
    return int(n[(t >= t0) & (t < t1)].sum())


def percentile(values, q: float) -> float:
    """The q-th percentile over all values (linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
