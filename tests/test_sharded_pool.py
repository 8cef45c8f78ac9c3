"""Sharded-pool tests (serve/sharded_pool).

The router's contract: a session id always routes to the same shard, ids
spread across shards, and sharding is *invisible* to audio — a session's
output through a ShardedSessionPool (any shard count, even after migration)
is bit-identical to the same feeds through a plain SessionPool.

These run on the single real CPU device: shards beyond the device count
round-robin onto it, which exercises the full routing/migration machinery
without faked devices (conftest policy).
"""

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import tftnn as tft
from repro.serve import (
    HashRing,
    PoolFullError,
    SessionError,
    SessionPool,
    ShardedSessionPool,
    ShardFullError,
)
from soak import check_pool_invariants, run_soak


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


def _audio(seed: int, hops: int) -> np.ndarray:
    return np.asarray(
        0.3 * jax.random.normal(jax.random.PRNGKey(seed), (hops * HOP,)), np.float32
    )


def _run_plain(audio: np.ndarray, capacity: int = 2) -> np.ndarray:
    pool = SessionPool(PARAMS, CFG, capacity=capacity)
    s = pool.attach()
    pool.feed(s, audio)
    pool.pump()
    return pool.detach(s)


def _sids_for_shard(ring: HashRing, shard: int, n: int):
    """First n session ids (probe-0, probe-1, ...) that hash to `shard`."""
    out, i = [], 0
    while len(out) < n:
        sid = f"probe-{i}"
        if ring.route(sid) == shard:
            out.append(sid)
        i += 1
    return out


# -- routing -----------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.text(min_size=1, max_size=40), st.integers(min_value=1, max_value=16))
def test_same_sid_always_same_shard(sid, n_shards):
    """Routing is a pure function of (session id, shard count): repeated
    calls and independent ring instances agree."""
    a, b = HashRing(n_shards), HashRing(n_shards)
    assert a.route(sid) == a.route(sid) == b.route(sid)
    assert 0 <= a.route(sid) < n_shards


def test_sessions_spread_across_shards():
    """500 ids over 4 shards: every shard gets a share within loose bounds
    (consistent hashing with 64 vnodes is not uniform, but not degenerate)."""
    ring = HashRing(4)
    counts = np.zeros(4, int)
    for i in range(500):
        counts[ring.route(f"user-{i}")] += 1
    assert counts.sum() == 500
    assert counts.min() >= 0.3 * 500 / 4  # no starved shard
    assert counts.max() <= 2.5 * 500 / 4  # no shard hogs the keyspace


def test_ring_growth_reshuffles_few_keys():
    """Growing N -> N+1 shards should remap a minority of the keyspace —
    the property that makes the hashing 'consistent'."""
    old, new = HashRing(4), HashRing(5)
    keys = [f"user-{i}" for i in range(500)]
    moved = sum(old.route(k) != new.route(k) for k in keys)
    # ideal is ~1/5 of keys; allow generous slack, but far below "all"
    assert moved <= 0.45 * len(keys)


# -- sharding is invisible to audio ------------------------------------------


def test_one_shard_bit_identical_to_plain_pool():
    """Acceptance: a 1-shard ShardedSessionPool is BIT-IDENTICAL to a plain
    SessionPool for the same feeds."""
    audio = _audio(3, 12)
    ref = _run_plain(audio)
    pool = ShardedSessionPool(PARAMS, CFG, 2, shards=1)
    h = pool.attach("client-a")
    pool.feed(h, audio)
    pool.pump_all()
    got = pool.detach(h)
    np.testing.assert_array_equal(got, ref)


def test_multi_shard_bit_identical_per_session():
    """Every session in a 3-shard pool emits the same bits as a plain pool
    run of its own audio (slot masking isolates streams; routing only moves
    them between identical pools)."""
    ref = {i: _run_plain(_audio(100 + i, 8)) for i in range(5)}
    pool = ShardedSessionPool(PARAMS, CFG, 5, shards=3)  # room for hash skew
    handles = {i: pool.attach(f"sess-{i}") for i in range(5)}
    assert len({h.shard for h in handles.values()}) > 1  # actually sharded
    for i, h in handles.items():
        pool.feed(h, _audio(100 + i, 8))
    pool.pump_all()
    for i, h in handles.items():
        np.testing.assert_array_equal(pool.detach(h), ref[i])


def test_feed_read_by_raw_session_id():
    """attach/feed/read/detach also route by raw id (no handle needed)."""
    audio = _audio(7, 6)
    pool = ShardedSessionPool(PARAMS, CFG, 2, shards=2)
    pool.attach("by-id")
    pool.feed("by-id", audio)
    pool.pump_all()
    got = pool.detach("by-id")
    np.testing.assert_array_equal(got, _run_plain(audio))
    with pytest.raises(SessionError):
        pool.feed("by-id", audio)  # detached
    with pytest.raises(SessionError):
        pool.read("never-attached")


# -- shard-full vs pool-full --------------------------------------------------


def test_shard_full_vs_pool_full():
    pool = ShardedSessionPool(PARAMS, CFG, 2, shards=2)
    ring = pool._ring
    sids0 = _sids_for_shard(ring, 0, 3)
    sids1 = _sids_for_shard(ring, 1, 2)

    pool.attach(sids0[0])
    pool.attach(sids0[1])
    # home shard 0 full, shard 1 empty: ShardFullError (a PoolFullError too),
    # and the message reports the shard's capacity and occupancy
    with pytest.raises(ShardFullError) as exc:
        pool.attach(sids0[2])
    assert "capacity=2" in str(exc.value) and "active=2" in str(exc.value)
    assert pool.num_active == 2  # failed attach left no residue

    pool.attach(sids1[0])
    pool.attach(sids1[1])
    # every shard full: plain PoolFullError, NOT the shard-level subclass,
    # reporting fleet-wide capacity and occupancy
    with pytest.raises(PoolFullError) as exc:
        pool.attach(sids0[2])
    assert not isinstance(exc.value, ShardFullError)
    assert "capacity=4" in str(exc.value) and "active=4" in str(exc.value)

    # duplicate id is a SessionError regardless of capacity
    with pytest.raises(SessionError):
        pool.attach(sids0[0])


def test_rebalance_on_full_migrates_and_attaches():
    """With rebalance_on_full, a full home shard sheds one session (which
    resumes bit-for-bit) instead of refusing the attach."""
    audio = _audio(9, 10)
    pool = ShardedSessionPool(PARAMS, CFG, 2, shards=2)
    ring = pool._ring
    sids0 = _sids_for_shard(ring, 0, 3)

    victim = pool.attach(sids0[0])
    pool.feed(victim, audio[: 4 * HOP])  # mid-stream when migrated
    pool.pump_all()
    pool.attach(sids0[1])
    h = pool.attach(sids0[2], rebalance_on_full=True)
    assert h.shard == 0  # newcomer lands on its hash home
    assert victim.shard == 1  # someone was migrated off it
    assert pool.num_active == 3

    pool.feed(victim, audio[4 * HOP :])  # stream continues on the new shard
    pool.pump_all()
    np.testing.assert_array_equal(pool.detach(victim), _run_plain(audio))


def test_explicit_rebalance_levels_loads():
    pool = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    ring = pool._ring
    for sid in _sids_for_shard(ring, 0, 4):
        pool.attach(sid)
    loads = [s["active"] for s in pool.shard_stats()]
    assert loads == [4, 0]
    moved = pool.rebalance()
    loads = [s["active"] for s in pool.shard_stats()]
    assert moved == 2 and sorted(loads) == [2, 2]
    assert pool.rebalance() == 0  # already balanced: idempotent


# -- dispatch/collect seam -----------------------------------------------------


def test_dispatch_collect_equivalent_to_step():
    """The async split the router uses must produce the same bits as the
    blocking step() path."""
    audio = _audio(13, 9)
    ref = _run_plain(audio)
    pool = SessionPool(PARAMS, CFG, capacity=2)
    s = pool.attach()
    pool.feed(s, audio)
    while pool.dispatch():
        pool.collect()
    assert pool.collect() == 0  # idempotent when nothing is in flight
    np.testing.assert_array_equal(pool.detach(s), ref)


def test_read_folds_in_flight_dispatch():
    """read() after a dispatch() (no explicit collect) must still deliver
    that step's output — no lost audio at the async seam."""
    audio = _audio(17, 3)
    pool = SessionPool(PARAMS, CFG, capacity=1)
    s = pool.attach()
    pool.feed(s, audio[:HOP])
    assert pool.dispatch() == 1
    got = [pool.read(s)]
    pool.feed(s, audio[HOP:])
    pool.pump()
    got.append(pool.detach(s))
    np.testing.assert_array_equal(np.concatenate(got), _run_plain(audio, capacity=1))


def test_soak_sharded_churn_invariants():
    """Mixed churn through the router (auto-routed ids), with per-shard and
    router-level invariants checked after every op."""
    pool = ShardedSessionPool(PARAMS, CFG, 3, shards=2, max_unread_hops=2)

    def audio_fn(rnd):
        return _audio(rnd.randrange(10_000), 2)[: rnd.randrange(1, 3 * HOP)]

    counts = run_soak(pool, audio_fn, n_ops=50, seed=2, max_live=5)
    assert counts["attach"] > 0 and counts["feed"] > 0 and counts["pump"] > 0
    assert pool.num_active == 0
    check_pool_invariants(pool)


def test_shard_stats_counters():
    pool = ShardedSessionPool(PARAMS, CFG, 2, shards=2)
    h = pool.attach("stats")
    pool.feed(h, _audio(19, 4))
    stats = pool.shard_stats()
    assert len(stats) == 2
    assert sum(s["active"] for s in stats) == 1
    assert sum(s["backlog_hops"] for s in stats) == 4  # queued, not yet pumped
    pool.pump_all()
    stats = pool.shard_stats()
    assert sum(s["backlog_hops"] for s in stats) == 0
    assert sum(s["hops"] for s in stats) == 4
    pool.detach(h)


@pytest.mark.parametrize(
    "kw",
    [
        dict(),
        dict(hops_per_step=4),
        dict(hops_per_step=4, adaptive=True),
        dict(hops_per_step=2, ingest_ring=4, finite_guard=True),
        dict(tiers=(2, 4)),
    ],
    ids=["k1", "k4", "adaptive", "ring-guard", "elastic"],
)
def test_prewarm_leaves_nothing_to_compile(kw):
    """After ``prewarm()`` serving compiles nothing: every jitted program a
    shard dispatches already has its executable for the shapes it sees."""
    from repro.serve import session_server

    pool = ShardedSessionPool(PARAMS, CFG, 4, shards=2, **kw)
    pool.prewarm()
    jitted = [session_server._finite_slots, session_server._ring_write]
    steps = pool._shared[jax.local_devices()[0]][1]
    before = dict(steps)
    sizes = [f._cache_size() for f in jitted + list(before.values())]

    handles = [pool.attach(f"s{i}") for i in range(3)]
    for i, h in enumerate(handles):
        pool.feed(h, _audio(i, 9)[: 9 * HOP - 5])
    pool.pump_all()
    for h in handles:
        pool.read(h)
        pool.detach(h)

    assert steps == before
    assert [f._cache_size() for f in jitted + list(before.values())] == sizes
