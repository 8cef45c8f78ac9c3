"""Elastic-pool tests (serve/elastic_pool).

The elastic pool's contract: capacity is a LADDER, not a constant — the pool
grows on attach-would-overflow and shrinks after sustained low occupancy —
and resizing is *invisible to audio*: under any interleaving of
attach/detach/feed/read/resize, every surviving session's output is
BIT-IDENTICAL to the same feeds through a fixed-capacity ``SessionPool`` at
the top tier, on both hop backends and with the double-buffered ingestion
pipeline in flight.

The churn property test is the elastic analogue of PR 1's
``test_churn_is_bit_identical_to_solo``; ``tests/soak.py`` checks the
structural invariants (bookkeeping, ring conservation, backpressure bound,
latency-record continuity) after every op.
"""

import dataclasses
import functools
import itertools

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import tftnn as tft
from repro.serve import (
    ElasticSessionPool,
    PoolFullError,
    SessionError,
    SessionPool,
    ShardedSessionPool,
    ShardFullError,
    make_stream_hop,
)
from soak import SoakChecker, check_pool_invariants, run_soak


def small_cfg() -> tft.TFTConfig:
    return dataclasses.replace(
        tft.tftnn_config(),
        n_fft=64,
        hop=16,
        freq_bins=32,
        channels=8,
        att_dim=8,
        num_heads=2,
        gru_hidden=8,
        dilation_rates=(1, 2),
    )


CFG = small_cfg()
PARAMS = tft.init_tft(jax.random.PRNGKey(0), CFG)
HOP = CFG.hop
TIERS = (2, 3, 5)  # small ladder: two boundaries, top tier = reference size
MAX_HOPS = 20  # audio budget per churn stream


@functools.lru_cache(maxsize=None)
def shared_step(backend: str):
    """ONE compiled hop step per backend for the whole module — jit caches
    per batch shape, so every tier/pool in these tests reuses it."""
    return make_stream_hop(PARAMS, CFG, backend=backend)


def _audio(seed: int, hops: int) -> np.ndarray:
    return np.asarray(
        0.3 * jax.random.normal(jax.random.PRNGKey(seed), (hops * HOP,)), np.float32
    )


def _pools(backend: str, inflight: int):
    """(elastic, fixed-reference-at-top-tier) pair sharing one compiled step."""
    ref = SessionPool(
        PARAMS, CFG, capacity=TIERS[-1], backend=backend, inflight=inflight,
        step_fn=shared_step(backend),
    )
    ep = ElasticSessionPool(
        PARAMS, CFG, TIERS, backend=backend, inflight=inflight,
        shrink_patience=3, step_fn=shared_step(backend),
    )
    return ep, ref


def _run_churn(ops, backend: str, inflight: int) -> int:
    """Apply an encoded op sequence to an elastic pool and a fixed reference
    in lockstep, asserting bit-identity at every read/detach. Returns the
    number of resizes that actually happened (callers assert coverage)."""
    ep, ref = _pools(backend, inflight)
    check_e, check_r = SoakChecker(), SoakChecker()
    streams = []  # [elastic handle, ref handle, audio, cursor]
    seeds = itertools.count(1000)
    for code, arg in ops:
        op = code % 6
        if op == 0 and ref.num_active < ref.capacity:
            streams.append(
                [ep.attach(), ref.attach(), _audio(next(seeds), MAX_HOPS), 0]
            )
        elif op == 1 and streams:  # ragged feed to BOTH pools
            s = streams[arg % len(streams)]
            chunk = s[2][s[3] : s[3] + 1 + arg % (3 * HOP)]
            s[3] += chunk.size
            if chunk.size:
                ep.feed(s[0], chunk)
                ref.feed(s[1], chunk)
        elif op == 2:
            ep.pump()
            ref.pump()
        elif op == 3 and streams:  # read: outputs must match bit for bit
            s = streams[arg % len(streams)]
            np.testing.assert_array_equal(ep.read(s[0]), ref.read(s[1]))
        elif op == 4 and streams:  # detach: unread tails must match too
            s = streams.pop(arg % len(streams))
            np.testing.assert_array_equal(ep.detach(s[0]), ref.detach(s[1]))
        elif op == 5:  # explicit resize to any tier with room
            fits = [t for t in TIERS if t >= ep.num_active]
            ep.resize_to(fits[arg % len(fits)])
        check_e.check(ep)
        check_r.check(ref)
    ep.pump()
    ref.pump()
    for s in streams:  # every survivor: identical audio AND accounting
        assert s[0].stats.hops == s[1].stats.hops
        np.testing.assert_array_equal(ep.detach(s[0]), ref.detach(s[1]))
    return ep.grow_count + ep.shrink_count


# -- the churn property: resizing is invisible to audio ----------------------


OPS = st.lists(
    st.tuples(st.integers(min_value=0, max_value=5),
              st.integers(min_value=0, max_value=2**16)),
    min_size=4,
    max_size=14,
)


@pytest.mark.parametrize("inflight", [1, 2])
@settings(max_examples=4, deadline=None)
@given(ops=OPS)
def test_churn_bit_identical_to_fixed_pool_xla(inflight, ops):
    """Randomized attach/detach/feed/read/resize churn on the xla backend:
    every surviving session bit-matches the fixed top-tier reference."""
    _run_churn(ops, "xla", inflight)


@pytest.mark.parametrize("inflight", [1, 2])
@settings(max_examples=2, deadline=None)
@given(ops=OPS)
def test_churn_bit_identical_to_fixed_pool_pallas(inflight, ops):
    """Same property through the deploy-compiled pallas backend (interpret
    mode off-TPU) — fewer examples, the kernels are emulated on CPU."""
    _run_churn(ops, "pallas", inflight)


def test_churn_with_forced_resizes_every_boundary():
    """A deterministic sequence that provably crosses every tier boundary in
    both directions (the hypothesis sweeps may or may not) stays bit-exact."""
    ops = (
        [(0, 0)] * 2 + [(1, i) for i in range(2)] + [(2, 0)]
        + [(0, 0)] * 3  # -> 5 sessions: grows 2->3->5
        + [(1, i) for i in range(5)] + [(2, 0)]
        + [(4, 1)] * 4  # detach back down to 1 survivor
        + [(2, 0)] * 8  # idle pumps: lazy shrinker walks the ladder down
        + [(1, 0), (2, 0)]
    )
    resizes = _run_churn(ops, "xla", 1)
    assert resizes >= 3  # at least both grows and one shrink happened


# -- ladder / watermark / hysteresis unit behaviour ---------------------------


def test_tier_ladder_validation():
    for bad in [(), (4, 4), (8, 4), (0, 4), (1, 2)]:
        with pytest.raises(ValueError):
            ElasticSessionPool(PARAMS, CFG, bad, step_fn=shared_step("xla"))
    with pytest.raises(ValueError):
        ElasticSessionPool(PARAMS, CFG, TIERS, shrink_fraction=0.0,
                           step_fn=shared_step("xla"))
    with pytest.raises(ValueError):
        ElasticSessionPool(PARAMS, CFG, TIERS, shrink_patience=0,
                           step_fn=shared_step("xla"))


def test_grow_on_attach_overflow_and_counters():
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, step_fn=shared_step("xla"))
    assert ep.capacity == 2 and ep.max_capacity == 5
    handles = [ep.attach() for _ in range(5)]
    assert ep.capacity == 5
    assert ep.grow_count == 2 and ep.shrink_count == 0
    assert ep.resize_log == [(2, 3), (3, 5)]
    assert len(ep.resize_seconds) == 2 and all(t >= 0 for t in ep.resize_seconds)
    check_pool_invariants(ep)
    for h in handles:
        ep.detach(h)


def test_shrink_needs_sustained_low_occupancy():
    """Hysteresis: occupancy below the watermark shrinks only after
    ``shrink_patience`` consecutive heartbeats, and a busy blip resets the
    counter — a pool oscillating at a boundary never thrashes."""
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, shrink_patience=3,
                            step_fn=shared_step("xla"))
    hs = [ep.attach() for _ in range(4)]  # tier 5
    assert ep.capacity == 5
    keep = hs[0]
    for h in hs[1:]:
        ep.detach(h)  # occupancy 1 <= 0.5 * 3: shrink-eligible
    ep.pump()
    ep.pump()
    assert ep.capacity == 5  # patience (3) not yet exhausted
    blip = [ep.attach(), ep.attach()]  # busy blip...
    ep.pump()  # ...resets the low-occupancy streak
    for h in blip:
        ep.detach(h)
    ep.pump()
    ep.pump()
    assert ep.capacity == 5  # streak restarted from zero
    ep.pump()  # third consecutive low heartbeat: NOW it shrinks
    assert ep.capacity == 3 and ep.shrink_count == 1
    ep.detach(keep)


def test_resize_restarts_shrink_hysteresis():
    """A streak of low-occupancy heartbeats accumulated at the OLD tier must
    not count toward shrinking the new one — every resize resets patience."""
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, shrink_patience=3,
                            step_fn=shared_step("xla"))
    keep = ep.attach()
    ep.pump()
    ep.pump()  # streak 2 of 3 at tier 2 (1 active <= 0.5 * ... not eligible
    # at the bottom tier; force a streak at tier 3 instead)
    ep.resize_to(3)
    ep.pump()
    ep.pump()  # streak 2 of 3 at tier 3
    burst = [ep.attach() for _ in range(4)]  # grow 3 -> 5
    assert ep.capacity == 5
    for h in burst:
        ep.detach(h)
    ep.pump()  # first eligible heartbeat at tier 5: streak restarted at 1...
    ep.pump()
    assert ep.capacity == 5  # ...so patience 3 is NOT yet exhausted
    ep.pump()
    assert ep.capacity == 3  # third heartbeat at THIS tier shrinks
    ep.detach(keep)


def test_resize_to_validation_and_roundtrip():
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, step_fn=shared_step("xla"))
    with pytest.raises(ValueError):
        ep.resize_to(4)  # not on the ladder
    hs = [ep.attach() for _ in range(3)]
    with pytest.raises(ValueError):
        ep.resize_to(2)  # 3 sessions live
    ep.resize_to(5)
    assert ep.capacity == 5
    ep.resize_to(3)  # explicit shrink back: allowed, sessions fit
    assert ep.capacity == 3
    for h in hs:
        ep.detach(h)


def test_latency_and_stats_continuity_across_resize():
    """The pool-wide step-latency record and per-session stats must span a
    resize unbroken (the ticket carries stats; the list object carries
    latency)."""
    aud = _audio(7, 12)
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, step_fn=shared_step("xla"))
    s = ep.attach()
    ep.feed(s, aud[: 6 * HOP])
    ep.pump()
    steps_before = len(ep.step_seconds)
    hops_before = s.stats.hops
    assert steps_before > 0 and hops_before == 6
    ep.resize_to(5)
    assert len(ep.step_seconds) == steps_before  # carried, not reset
    assert s.stats.hops == hops_before
    ep.feed(s, aud[6 * HOP :])
    ep.pump()
    assert len(ep.step_seconds) > steps_before
    assert s.stats.hops == 12
    assert ep.latency_percentiles()[50] > 0
    assert "resizes" in ep.report() or ep.resize_seconds
    ep.detach(s)


def test_prewarm_compiles_and_serves():
    aud = _audio(9, 8)
    ref = SessionPool(PARAMS, CFG, capacity=TIERS[-1], step_fn=shared_step("xla"))
    r = ref.attach()
    ref.feed(r, aud)
    ref.pump()
    want = ref.detach(r)
    ep = ElasticSessionPool(PARAMS, CFG, TIERS, step_fn=shared_step("xla"))
    ep.prewarm()
    s = ep.attach()
    ep.feed(s, aud)
    ep.pump()
    np.testing.assert_array_equal(ep.detach(s), want)


# -- PR 3 gap: pool mutation between dispatch() and collect() -----------------


@pytest.mark.parametrize("inflight", [1, 2])
def test_resize_between_dispatch_and_read(inflight):
    """An explicit resize right after dispatch() must drain the pending
    pipeline before migrating — no orphaned step, no corrupted audio."""
    aud = _audio(11, 10)
    ep, ref = _pools("xla", inflight)
    r = ref.attach()
    ref.feed(r, aud)
    ref.pump()
    want = ref.detach(r)
    s = ep.attach()
    ep.feed(s, aud)
    assert ep.dispatch() == 1
    ep.resize_to(5)  # mid-pipeline mutation
    check_pool_invariants(ep)
    ep.pump()
    np.testing.assert_array_equal(ep.detach(s), want)


@pytest.mark.parametrize("inflight", [1, 2])
def test_grow_triggered_between_dispatch_and_collect(inflight):
    """attach() that overflows the tier WHILE a step is in flight grows
    safely (the resize collects the pipeline first)."""
    aud = _audio(13, 10)
    ep, ref = _pools("xla", inflight)
    r = ref.attach()
    ref.feed(r, aud)
    ref.pump()
    want = ref.detach(r)
    s = ep.attach()
    extra = [ep.attach()]  # tier 2 now full
    ep.feed(s, aud)
    assert ep.dispatch() == 1
    extra.append(ep.attach())  # overflow -> grow with the step in flight
    assert ep.capacity == 3
    check_pool_invariants(ep)
    ep.pump()
    np.testing.assert_array_equal(ep.detach(s), want)
    for h in extra:
        ep.detach(h)


# -- error-path regression: messages must report the numbers ------------------


def test_elastic_full_reports_ladder():
    ep = ElasticSessionPool(PARAMS, CFG, (2, 3), step_fn=shared_step("xla"))
    hs = [ep.attach() for _ in range(3)]
    with pytest.raises(PoolFullError) as exc:
        ep.attach()
    msg = str(exc.value)
    assert "capacity=3" in msg and "active=3" in msg and "tiers=(2, 3)" in msg
    for h in hs:
        ep.detach(h)
    with pytest.raises(SessionError):
        ep.detach(hs[0])  # double detach still a SessionError


def test_fixed_pool_full_reports_capacity_and_occupancy():
    pool = SessionPool(PARAMS, CFG, capacity=2, step_fn=shared_step("xla"))
    pool.attach()
    pool.attach()
    with pytest.raises(PoolFullError) as exc:
        pool.attach()
    msg = str(exc.value)
    assert "capacity=2" in msg and "active=2" in msg


# -- elastic shards behind the router ----------------------------------------


def _sids_for_shard(ring, shard: int, n: int):
    out, i = [], 0
    while len(out) < n:
        sid = f"probe-{i}"
        if ring.route(sid) == shard:
            out.append(sid)
        i += 1
    return out


def test_elastic_shard_grows_instead_of_shard_full():
    """A hot shard climbs its ladder where a fixed shard would raise
    ShardFullError; the error only fires once its TOP tier is full."""
    pool = ShardedSessionPool(PARAMS, CFG, 0, shards=2, tiers=(2, 3))
    sids0 = _sids_for_shard(pool._ring, 0, 4)
    for sid in sids0[:3]:
        pool.attach(sid)  # third attach grows shard 0: no ShardFullError
    stats = pool.shard_stats()
    assert stats[0]["tier"] == 3 and stats[0]["active"] == 3
    assert stats[0]["grows"] == 1
    with pytest.raises(ShardFullError) as exc:
        pool.attach(sids0[3])  # top tier full, shard 1 has room
    msg = str(exc.value)
    assert "capacity=3" in msg and "active=3" in msg and "tiers=(2, 3)" in msg
    check_pool_invariants(pool)


def test_elastic_shard_audio_bit_identical():
    aud = _audio(17, 9)
    ref = SessionPool(PARAMS, CFG, capacity=TIERS[-1], step_fn=shared_step("xla"))
    r = ref.attach()
    ref.feed(r, aud)
    ref.pump()
    want = ref.detach(r)
    pool = ShardedSessionPool(PARAMS, CFG, 0, shards=2, tiers=TIERS)
    handles = [pool.attach(f"c-{i}") for i in range(7)]  # forces growth
    pool.feed(handles[0], aud)
    pool.pump_all()
    np.testing.assert_array_equal(pool.detach(handles[0]), want)
    for h in handles[1:]:
        pool.detach(h)


def test_rebalance_shrinks_elastic_donor():
    pool = ShardedSessionPool(PARAMS, CFG, 0, shards=2, tiers=(3, 5))
    sids0 = _sids_for_shard(pool._ring, 0, 4)
    for sid in sids0:
        pool.attach(sid)  # 4th attach grows shard 0 to tier 5
    assert pool.shard_stats()[0]["tier"] == 5
    moved = pool.rebalance()  # levels to 2/2...
    assert moved == 2
    stats = pool.shard_stats()
    # ...and the drained donor returned down its ladder (2 sessions < tier 3)
    assert stats[0]["tier"] == 3 and stats[0]["shrinks"] >= 1
    check_pool_invariants(pool)


def test_pump_all_gives_elastic_shards_the_shrink_heartbeat():
    """Regression: the router's serving loop (pump_all), not just a
    standalone pool's pump(), must tick the lazy shrinker — a shard grown
    hot and then drained returns down its ladder without an explicit
    rebalance()."""
    pool = ShardedSessionPool(PARAMS, CFG, 0, shards=2, tiers=(2, 3),
                              shrink_patience=1)
    sids0 = _sids_for_shard(pool._ring, 0, 3)
    handles = [pool.attach(sid) for sid in sids0]  # shard 0 grows to tier 3
    assert pool.shard_stats()[0]["tier"] == 3
    for h in handles[1:]:
        pool.detach(h)  # occupancy 1 <= 0.5 * 2: shrink-eligible
    pool.feed(handles[0], _audio(23, 2))
    pool.pump_all()
    assert pool.shard_stats()[0]["tier"] == 2
    assert pool.shard_stats()[0]["shrinks"] >= 1
    pool.detach(handles[0])


def test_import_session_grows_full_elastic_pool():
    aud = _audio(19, 8)
    src = SessionPool(PARAMS, CFG, capacity=2, step_fn=shared_step("xla"))
    s = src.attach()
    src.feed(s, aud[: 4 * HOP])
    src.pump()
    ticket = src.export_session(s)
    dst = ElasticSessionPool(PARAMS, CFG, (2, 3), step_fn=shared_step("xla"))
    fillers = [dst.attach(), dst.attach()]  # tier 2 full
    h = dst.import_session(ticket)  # grows instead of PoolFullError
    assert dst.capacity == 3
    dst.feed(h, aud[4 * HOP :])
    dst.pump()
    ref = SessionPool(PARAMS, CFG, capacity=TIERS[-1], step_fn=shared_step("xla"))
    r = ref.attach()
    ref.feed(r, aud)
    ref.pump()
    # the ticket's unread output travels with the session: one detach
    # returns the pre-migration AND post-migration audio
    np.testing.assert_array_equal(dst.detach(h), ref.detach(r))
    for f in fillers:
        dst.detach(f)


# -- soak: invariants under mixed churn ---------------------------------------


def test_soak_elastic_pool_invariants():
    ep = ElasticSessionPool(
        PARAMS, CFG, TIERS, inflight=2, max_unread_hops=3, shrink_patience=2,
        step_fn=shared_step("xla"),
    )
    counts = run_soak(
        ep, lambda rnd: _audio(rnd.randrange(10_000), 2)[: rnd.randrange(1, 3 * HOP)],
        n_ops=50, seed=3, max_live=6,
    )
    assert counts["attach"] > 0 and counts["feed"] > 0 and counts["pump"] > 0
    assert ep.num_active == 0
