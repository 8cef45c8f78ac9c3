"""The serving spans, counters and per-hop timeline (``repro.serve.obs``).

Recording is a process-wide switch, so every test here leaves it off and
the kept records empty. The gateway tests drive one small pool through a
real socket; the timeline test scripts the clock.
"""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import tftnn as tft
from repro.serve import obs
from repro.serve.gateway import GatewayClient, GatewayThread
from repro.serve.sharded_pool import ShardedSessionPool
from repro.serve.streaming_se import init_stream, make_stream_hop

CFG = dataclasses.replace(
    tft.tftnn_config(), n_fft=64, hop=16, freq_bins=32, channels=8, att_dim=8,
    num_heads=2, gru_hidden=8, dilation_rates=(1, 2),
)
HOP = CFG.hop
STAGES = ("analysis", "encoder", "subband", "fullband", "mask_decoder", "synthesis")


@pytest.fixture(scope="module")
def params():
    return tft.init_tft(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def gw(params):
    pool = ShardedSessionPool(params, CFG, 2, shards=1)
    pool.prewarm()
    # a long heartbeat: the tick the FEED asks for steps the hop
    g = GatewayThread(pool, pump_interval=0.5)
    yield g
    g.stop()


def _feed_read(gw, sid, hops):
    audio = np.random.default_rng(0).standard_normal(hops * HOP).astype(np.float32)
    with GatewayClient(*gw.address) as c:
        c.attach(sid)
        c.feed(audio)
        out = c.read()
        stats = c.stats()
    assert out.size == hops * HOP
    return stats


class CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1

    def __exit__(self, *exc):
        pass


def test_recording_off_keeps_nothing(clean, gw, monkeypatch):
    monkeypatch.setattr(obs, "TraceAnnotation", CountingAnnotation)
    CountingAnnotation.entered = 0
    assert not obs.recording()
    assert obs.span("dispatch") is obs.span("collect")  # one shared no-op
    _feed_read(gw, "off", 2)
    assert obs.spans() == [] and obs.hop_times() == []
    assert CountingAnnotation.entered == 0


def test_one_feed_nests_every_layer(clean, gw):
    obs.enable()
    _feed_read(gw, "nest", 1)
    obs.disable()
    spans = obs.spans()
    by_seq = {s.seq: s for s in spans}

    def chain(s):
        names = []
        while s.parent in by_seq:
            s = by_seq[s.parent]
            names.append(s.name)
        return names

    feed = next(s for s in spans if s.name == "frame.feed")
    assert feed.sid == "nest" and feed.parent == -1
    # the FEED's tick runs after its frame: at the end of the event-loop
    # turn (a root span), or inside the READ that needed its hop first
    tick = next(s for s in spans if s.name == "tick.feed")
    assert chain(tick) in ([], ["frame.read"])
    for inner in ("readback", "deliver"):
        s = next(s for s in spans if s.name == inner and "tick.feed" in chain(s))
        assert chain(s)[:3] == ["collect", "pump_all", "tick.feed"]
        assert chain(s)[3:] == chain(tick)
    for inner in ("dispatch", "wait_ready"):
        s = next(s for s in spans if s.name == inner and "tick.feed" in chain(s))
        assert chain(s) == ["pump_all", "tick.feed", *chain(tick)]
    for s in spans:  # children lie inside their parents
        p = by_seq.get(s.parent)
        if p is not None:
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    assert {s.name for s in spans} <= set(obs.SPAN_NAMES)
    (hop,) = obs.hop_times()
    assert hop.sid == "nest"
    parts = hop.ingest + hop.wait + hop.step + hop.collect + hop.unread
    assert parts == hop.server > 0
    assert min(hop[1:]) >= 0


def test_loop_wait_is_recorded_while_idle(clean, gw):
    obs.enable()
    with GatewayClient(*gw.address) as c:
        c.stats()  # the loop wakes, answers, and waits again: recorded
        time.sleep(0.05)
        c.stats()  # ends that wait
    obs.disable()
    waits = [s for s in obs.spans() if s.name == "loop_wait"]
    assert waits and max(s.end_ns - s.start_ns for s in waits) > 40e6
    assert all(s.parent == -1 for s in waits)


class Clock:
    now = 0

    def perf_counter_ns(self):
        return self.now


def test_hop_timeline_matches_a_scripted_schedule(clean, monkeypatch):
    clock = Clock()
    monkeypatch.setattr(obs, "time", clock)
    ledger = obs.HopLedger(capacity=2)

    def frame(name, t0, t1, body):
        clock.now = t0
        with obs.span(name, "s"):
            body()
            clock.now = t1

    def at(t, fn, *args):
        clock.now = t
        fn(*args)

    obs.enable()
    frame("frame.feed", 0, 3, lambda: at(2, ledger.fed, 0, 0, 1))  # hop 1
    frame("frame.feed", 10, 11, lambda: at(11, ledger.fed, 0, 1, 3))  # hops 2-3
    ledger.delivered(0, 0, 2, dispatch_ns=12, ready_ns=15, collect_ns=16)
    frame("frame.read", 20, 22, lambda: ledger.read(0))
    ledger.delivered(0, 2, 1, dispatch_ns=30, ready_ns=34, collect_ns=35)
    # hop 4 was never fed while recording: it has no timeline
    ledger.delivered(0, 3, 1, dispatch_ns=40, ready_ns=41, collect_ns=42)
    frame("frame.read", 50, 53, lambda: ledger.read(0))
    got = [h[1:] for h in obs.hop_times()]
    #        ingest wait step collect unread server
    assert got == [(2, 10, 3, 1, 6, 22),
                   (1, 1, 3, 1, 6, 12),
                   (1, 19, 4, 1, 18, 43)]
    for h in obs.hop_times():
        assert h.ingest + h.wait + h.step + h.collect + h.unread == h.server
        assert h.sid == "s"
    summary = obs.summary()
    assert summary["hops_kept"] == 3
    assert summary["hop_ms"]["server"]["p50"] == pytest.approx(22e-6)


def test_stats_carry_the_trace_key(clean, gw):
    stats = _feed_read(gw, "stats", 1)["trace"]
    assert stats["recording"] is False and stats["hop_ms"] == {}
    assert stats["ticks"]["feed"] >= 1 and stats["ticks"]["heartbeat"] >= 1
    (shard,) = stats["steps"]
    assert shard["steps"] >= 1 and shard["hops_stepped"] >= 1
    assert shard["lanes_offered"] == 2 * shard["steps"]  # capacity 2, K=1
    obs.enable()
    stats = _feed_read(gw, "stats-on", 1)["trace"]
    obs.disable()
    assert stats["recording"] is True and stats["hops_kept"] == 1
    assert set(stats["hop_ms"]) == set(obs.HOP_PARTS)


@pytest.mark.parametrize("kw", [dict(backend="xla"),
                                dict(backend="pallas", max_hops_per_step=2)],
                         ids=["xla", "pallas-k2"])
def test_step_stages_carry_named_scopes(params, kw):
    k = kw.get("max_hops_per_step", 1)
    hops = jnp.zeros((2, HOP) if k == 1 else (2, k, HOP))
    lanes = jnp.zeros((2,), bool if k == 1 else jnp.int32)
    lowered = make_stream_hop(params, CFG, **kw).lower(
        init_stream(params, CFG, 2), hops, lanes)
    names = re.findall(r'op_name="([^"]*)"',
                       lowered.as_text(dialect="hlo", debug_info=True))
    scopes = {part for n in names for part in n.split("/")}
    assert set(STAGES) <= scopes
