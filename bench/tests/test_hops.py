"""The metric arithmetic: due-time hop latency, percentiles, throughput,
attempted and failed counts."""

import numpy as np
import pytest

import hops


def test_hop_due_and_delivery_times():
    # 160-sample packets at 20 ms; 128-sample hops
    feed_due = [0.02, 0.04, 0.06, 0.08]
    feed_cum = [160, 320, 480, 640]
    # replies: hop 0 at 0.025, hops 1-2 at 0.067 (384 samples), nothing more
    recv_t, recv_cum = [0.025, 0.067], [128, 384]
    due, done = hops.hop_times(feed_due, feed_cum, recv_t, recv_cum, 128)
    # hop h's last input sample (h+1)*128-1: 127 -> packet 0, 255 -> 1,
    # 383 -> 2, 511 -> 3, 639 -> 3
    np.testing.assert_allclose(due, [0.02, 0.04, 0.06, 0.08, 0.08])
    np.testing.assert_allclose(done[:3], [0.025, 0.067, 0.067])
    assert np.isnan(done[3:]).all()
    lat = done[:3] - due[:3]
    np.testing.assert_allclose(lat, [0.005, 0.027, 0.007])


def test_window_counts_failed_hops_with_their_wait():
    due = np.array([0.5, 1.0, 1.5, 2.0, 2.5])
    done = np.array([0.6, 1.1, np.nan, 2.2, 2.6])
    w = hops.window_stats(due, done, t0=1.0, t1=2.5, t_end=4.0)
    assert w["attempted"] == 3  # due 1.0, 1.5, 2.0 (2.5 is outside)
    assert w["failed"] == 1
    np.testing.assert_allclose(sorted(w["latency_s"]), [0.1, 0.2, 2.5])


def test_percentile_over_all_hops():
    lat = np.arange(1, 101, dtype=float)
    assert hops.percentile(lat, 50) == pytest.approx(50.5)
    assert hops.percentile(lat, 99) == pytest.approx(99.01)


def test_throughput_counts_partial_streams_in_window():
    recv_t = [0.5, 1.2, 1.9, 2.1]
    recv_n = [8000, 8000, 3000, 8000]  # third reply: the end of a partial file
    assert hops.delivered_in_window(recv_t, recv_n, 1.0, 2.0) == 11000


def test_refused_stream_counts_every_due_hop_failed():
    feed_due = [1.1, 1.2]
    feed_cum = [160, 320]
    due, done = hops.hop_times(feed_due, feed_cum, [], [], 128)
    w = hops.window_stats(due, np.full_like(due, np.nan), 1.0, 2.0, 3.0)
    assert w["attempted"] == 2 and w["failed"] == 2
