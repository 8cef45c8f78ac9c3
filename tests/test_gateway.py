"""Gateway tests (serve/gateway): the fabric's socket front door.

Everything here crosses a REAL localhost TCP boundary — ``GatewayThread``
runs the asyncio server + pump loop on its own thread, ``GatewayClient``
speaks the framed protocol from the test thread. The headline contract:
the network is invisible to audio. A gateway-served session's output is
bit-identical to the same feed schedule through an in-process
``SessionPool``, including across a mid-stream shard failover and across a
severed-and-reconnected client connection.
"""

import dataclasses
import random
import socket
import struct
import time

import jax
import numpy as np
import pytest

from repro.models import tftnn as tft
from repro.serve import (
    FaultPlan,
    SessionError,
    SessionPoisonedError,
    SessionPool,
    ShardedSessionPool,
)
from repro.serve.gateway import (
    GatewayClient,
    GatewayThread,
    MAX_FRAME_BYTES,
    MSG_ATTACH,
    MSG_AUDIO,
    MSG_FEED,
    MSG_READ,
)
from chaos import run_chaos_gateway


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
        0.3 * jax.random.normal(jax.random.PRNGKey(seed), (hops * HOP,)),
        np.float32,
    )


def _reference(audio: np.ndarray) -> np.ndarray:
    pool = SessionPool(PARAMS, CFG, capacity=2)
    s = pool.attach()
    pool.feed(s, audio)
    pool.pump()
    return pool.detach(s)


@pytest.fixture
def gw():
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    g = GatewayThread(sp, pump_interval=0.002)
    yield g
    g.stop()


def _feed_jittery(client, audio, rnd):
    pos = 0
    while pos < audio.size:
        n = int(rnd.integers(0, 3 * HOP + 1))
        client.feed(audio[pos : pos + n])
        pos += n


def test_gateway_stream_bit_identical_to_inprocess(gw):
    """Socket chunks in, bit-identical enhanced audio out."""
    audio = _audio(1, 10)
    expect = (audio.size // HOP) * HOP
    with GatewayClient(*gw.address) as c:
        sid = c.attach()
        assert sid
        _feed_jittery(c, audio, np.random.default_rng(0))
        out = c.read_until(expect)
        tail = c.detach()
    got = np.concatenate([out, tail])
    assert np.array_equal(got, _reference(audio)[: got.size])
    assert got.size == expect


def test_gateway_two_clients_interleaved(gw):
    """Two connections multiplex onto the pool without cross-talk."""
    a1, a2 = _audio(2, 8), _audio(3, 8)
    e1, e2 = (a1.size // HOP) * HOP, (a2.size // HOP) * HOP
    c1 = GatewayClient(*gw.address)
    c2 = GatewayClient(*gw.address)
    c1.attach("alice")
    c2.attach("bob")
    rnd = np.random.default_rng(1)
    p1 = p2 = 0
    while p1 < a1.size or p2 < a2.size:
        n1 = int(rnd.integers(0, 2 * HOP)) if p1 < a1.size else 0
        n2 = int(rnd.integers(0, 2 * HOP)) if p2 < a2.size else 0
        c1.feed(a1[p1 : p1 + n1])
        c2.feed(a2[p2 : p2 + n2])
        p1, p2 = p1 + n1, p2 + n2
    o1 = c1.read_until(e1)
    o2 = c2.read_until(e2)
    assert np.array_equal(o1, _reference(a1)[:e1])
    assert np.array_equal(o2, _reference(a2)[:e2])
    c1.close()
    c2.close()


def test_gateway_failover_mid_stream_bit_exact(gw):
    """A shard dies while the client streams; the audio never notices."""
    audio = _audio(4, 12)
    expect = (audio.size // HOP) * HOP
    with GatewayClient(*gw.address) as c:
        sid = c.attach("failover-user")
        rnd = np.random.default_rng(2)
        pos = 0
        killed = False
        while pos < audio.size:
            n = int(rnd.integers(1, 3 * HOP))
            c.feed(audio[pos : pos + n])
            pos += n
            if not killed and pos > audio.size // 2:
                gw.call(lambda p: p.kill_shard(p.route(sid)))
                killed = True
        assert killed
        got = c.read_until(expect)
        stats = c.stats()
    assert np.array_equal(got, _reference(audio)[:expect])
    assert stats["sessions_failed_over"] >= 1
    assert any(not s["alive"] for s in stats["shards"])


def test_gateway_drop_reconnect_adopts_session(gw):
    """Severed connection, same id re-attached: nothing lost, bit-exact."""
    audio = _audio(5, 10)
    expect = (audio.size // HOP) * HOP
    c1 = GatewayClient(*gw.address)
    sid = c1.attach("roamer")
    c1.feed(audio[: 5 * HOP])
    c1.drop()  # no DETACH: the session is orphaned, keeps streaming
    c2 = GatewayClient(*gw.address)
    assert c2.attach("roamer") == sid
    c2.feed(audio[5 * HOP :])
    got = c2.read_until(expect)
    assert np.array_equal(got, _reference(audio)[:expect])
    c2.close()


def test_gateway_duplicate_attach_rejected(gw):
    """An id live on another connection cannot be stolen."""
    c1 = GatewayClient(*gw.address)
    c1.attach("owner")
    c2 = GatewayClient(*gw.address)
    with pytest.raises(SessionError, match="another live connection"):
        c2.attach("owner")
    # the rejected connection stays usable
    assert c2.attach("someone-else")
    c2.close()
    c1.close()


def test_gateway_lost_session_fails_loud_then_recovers(gw):
    """Destructive shard loss: the client hears about it, then re-attaches."""
    audio = _audio(6, 6)
    with GatewayClient(*gw.address) as c:
        sid = c.attach("doomed")
        c.feed(audio)
        gw.call(lambda p: p.kill_shard(p.route(sid), lose_state=True))
        with pytest.raises(SessionError, match="lost"):
            c.read()
        stats = c.stats()
        assert sid in stats["lost_session_ids"]
        assert stats["sessions_lost"] >= 1
        # bounded loss, not a poisoned connection: a fresh stream works
        assert c.attach("doomed") == "doomed"
        c.feed(audio)
        expect = (audio.size // HOP) * HOP
        assert np.array_equal(c.read_until(expect), _reference(audio)[:expect])


def test_gateway_protocol_errors_keep_connection_alive(gw):
    with GatewayClient(*gw.address) as c:
        with pytest.raises(SessionError, match="ATTACH first"):
            c.read()
        c.attach()
        with pytest.raises(SessionError, match="not float32"):
            c._request(2, b"abc")  # 3 bytes: not a float32 array
        # double attach on one connection is refused
        with pytest.raises(SessionError, match="already serves"):
            c._request(MSG_ATTACH, b"second")
        audio = _audio(7, 4)
        c.feed(audio)
        expect = (audio.size // HOP) * HOP
        assert np.array_equal(c.read_until(expect), _reference(audio)[:expect])


def test_gateway_chaos_kills_and_drops(gw):
    """The full chaos harness over sockets: kills + drops, all bit-exact."""
    audios = {f"chaos-{i}": _audio(20 + i, 6 + i) for i in range(3)}
    result = run_chaos_gateway(
        gw,
        audios,
        _reference,
        seed=4,
        rounds=16,
        kill_every=6,
        restart_after=2,
        drop_every=5,
    )
    assert result["kills"] >= 1
    assert result["drops"] >= 2
    assert result["lost"] == set()


def _frames_in_one_turn(g, frames):
    """Serve ``(type, payload, sid)`` frames in one event-loop turn (one
    ``call`` on the gateway thread); returns their reply payloads."""

    def serve(_pool):
        replies = []
        for msg_type, payload, sid in frames:
            rtype, body, _ = g.gateway._dispatch_msg(msg_type, payload, sid)
            assert rtype in (MSG_AUDIO, 0x81)  # AUDIO, or ATTACHED
            replies.append(body)
        return replies

    return g.call(serve)


def _tick_counts(g):
    gw = g.gateway
    return g.call(lambda pool: (gw.feed_frames, gw.feed_ticks,
                                [s["steps"] for s in pool.shard_stats()]))


def test_feeds_of_one_turn_share_one_tick():
    """FEEDs of N sessions handled in one event-loop turn are stepped by
    one deferred tick: each shard steps ceil(max hops / K) times."""
    k = 2
    # two slots a shard, as _reference's pool has; the ids route two a shard
    sp = ShardedSessionPool(PARAMS, CFG, 2, shards=2, hops_per_step=k)
    # a heartbeat that never fires during the test: only FEEDs tick
    g = GatewayThread(sp, pump_interval=3600.0)
    try:
        hops = {"s0": 3, "s1": 6, "s2": 5, "s3": 2}
        audio = {sid: _audio(40 + i, n) for i, (sid, n) in enumerate(hops.items())}
        _frames_in_one_turn(
            g, [(MSG_ATTACH, sid.encode(), None) for sid in hops])
        frames0, ticks0, steps0 = _tick_counts(g)
        _frames_in_one_turn(
            g, [(MSG_FEED, audio[sid].tobytes(), sid) for sid in hops])
        frames1, ticks1, steps1 = _tick_counts(g)
        assert frames1 - frames0 == len(hops)
        assert ticks1 - ticks0 == 1
        assert (frames1 - ticks1) - (frames0 - ticks0) == len(hops) - 1
        shard_of = g.call(lambda p: {sid: p.lookup(sid).shard for sid in hops})
        assert sorted(shard_of.values()) == [0, 0, 1, 1]
        for i, (s0, s1) in enumerate(zip(steps0, steps1)):
            mine = [n for sid, n in hops.items() if shard_of[sid] == i]
            assert s1 - s0 == -(-max(mine) // k)
        outs = _frames_in_one_turn(
            g, [(MSG_READ, b"", sid) for sid in hops])
        for sid, body in zip(hops, outs):
            got = np.frombuffer(body, np.float32)
            assert got.size == hops[sid] * HOP
            assert np.array_equal(got, _reference(audio[sid]))
    finally:
        g.stop()


def test_read_before_deferred_tick_gets_its_hops():
    """A READ handled before the turn's deferred tick runs it first (its
    session holds whole, unstepped hops) and gets every hop, bit-exact; a
    READ of a session with no backlog leaves the tick pending."""
    sp = ShardedSessionPool(PARAMS, CFG, 2, shards=2)
    g = GatewayThread(sp, pump_interval=3600.0)
    try:
        audio = _audio(50, 4)
        _frames_in_one_turn(
            g, [(MSG_ATTACH, b"fed", None), (MSG_ATTACH, b"idle", None)])
        _, ticks0, _ = _tick_counts(g)

        def feed_then_read(_pool):
            gw = g.gateway
            gw._dispatch_msg(MSG_FEED, audio.tobytes(), "fed")
            _, idle_out, _ = gw._dispatch_msg(MSG_READ, b"", "idle")
            pending_after_idle_read = gw._pending_tick is not None
            _, out, _ = gw._dispatch_msg(MSG_READ, b"", "fed")
            return idle_out, pending_after_idle_read, out, gw._pending_tick

        idle_out, pending, out, left = g.call(feed_then_read)
        assert idle_out == b"" and pending
        assert left is None  # the READ ran the tick; none is left to run
        got = np.frombuffer(out, np.float32)
        assert got.size == audio.size
        assert np.array_equal(got, _reference(audio))
        _, ticks1, _ = _tick_counts(g)
        assert ticks1 - ticks0 == 1
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# protocol hostility: seeded fuzz of malformed frames + hostile payloads.
# The contract under attack is containment — one bad connection may die, but
# the server, every other connection, and every other session live on.
# ---------------------------------------------------------------------------

_HDR = struct.Struct("<IB")


def _hostile_attacks(rnd: random.Random):
    """One hostile connection's worth of attack blobs.

    Each entry is ``(blob, expect_reply)`` — truncated frames never get an
    answer (the server is still waiting for the rest), so the driver only
    blocks on a reply where the protocol owes one.
    """
    menu = [
        # unknown message type with a garbage payload -> typed ERROR
        lambda: (
            _HDR.pack(24, rnd.randrange(0x06, 0x7F)) + rnd.randbytes(24),
            True,
        ),
        # ATTACH with invalid UTF-8 -> typed ERROR, connection stays usable
        lambda: (_HDR.pack(4, MSG_ATTACH) + b"\xff\xfe\xfd\xfc", True),
        # FEED before any ATTACH -> typed ERROR
        lambda: (_HDR.pack(8, MSG_FEED) + bytes(8), True),
        # declared length past the frame cap -> ERROR, then the gateway
        # drops the connection (the byte stream cannot be re-synchronized)
        lambda: (
            _HDR.pack(MAX_FRAME_BYTES + 1 + rnd.randrange(1 << 20), MSG_FEED),
            True,
        ),
        # truncated header: a few bytes, then the client vanishes
        lambda: (_HDR.pack(64, MSG_FEED)[: rnd.randrange(1, 5)], False),
        # truncated payload: header promises 100 bytes, delivers fewer
        lambda: (
            _HDR.pack(100, MSG_FEED) + rnd.randbytes(rnd.randrange(100)),
            False,
        ),
        # pure line noise (whatever length it decodes to, it never arrives)
        lambda: (rnd.randbytes(rnd.randrange(1, 48)), False),
    ]
    return [rnd.choice(menu)() for _ in range(rnd.randrange(1, 4))]


def _raw_assault(addr, attacks) -> int:
    """Fire attack blobs from a raw socket; count frames answered."""
    answered = 0
    try:
        with socket.create_connection(addr, timeout=2.0) as s:
            for blob, expect_reply in attacks:
                try:
                    s.sendall(blob)
                except OSError:
                    break  # server already dropped us: contained, move on
                if not expect_reply:
                    continue
                s.settimeout(1.0)
                try:
                    if s.recv(1 << 16):
                        answered += 1
                except (TimeoutError, OSError):
                    break
    except OSError:
        pass
    return answered


def test_gateway_hostile_frame_fuzz(gw):
    """Seeded malformed-frame storm: the server answers or drops each bad
    connection, never dies, and a healthy concurrent stream is bit-exact."""
    rnd = random.Random(1234)
    audio = _audio(30, 12)
    expect = (audio.size // HOP) * HOP
    answered = 0
    with GatewayClient(*gw.address) as healthy:
        healthy.attach("healthy")
        pos = 0
        for round_no in range(12):  # interleave: stream a little, attack
            n = int(rnd.randrange(0, 3 * HOP + 1))
            if pos < audio.size:
                healthy.feed(audio[pos : pos + n])
                pos += n
            answered += _raw_assault(gw.address, _hostile_attacks(rnd))
        if pos < audio.size:
            healthy.feed(audio[pos:])
        got = healthy.read_until(expect)
        stats = healthy.stats()
    assert answered >= 1, "no hostile frame was ever answered"
    assert np.array_equal(got, _reference(audio)[:expect])
    # the oversize-length attacks were rejected without killing the server
    assert stats["frames_rejected"] >= 1
    assert stats["active"] >= 0  # STATS round-trips: the gateway is alive
    with GatewayClient(*gw.address) as c:  # and still accepts fresh clients
        assert c.attach("post-storm")


def test_gateway_nan_feed_quarantined_bystander_bit_exact():
    """A hostile client feeds NaNs; the finite guard quarantines only that
    session — the bystander's stream is bit-exact and the id is reusable."""
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2, finite_guard=True)
    g = GatewayThread(sp, pump_interval=0.002)
    try:
        audio = _audio(31, 10)
        expect = (audio.size // HOP) * HOP
        with GatewayClient(*g.address) as good, GatewayClient(*g.address) as evil:
            good.attach("bystander")
            evil.attach("evil")
            good.feed(audio[: 5 * HOP])
            evil.feed(np.full(3 * HOP, np.nan, np.float32))
            poisoned = False
            for _ in range(200):  # the pump loop quarantines asynchronously
                try:
                    evil.read()
                except SessionPoisonedError as e:
                    assert e.good_hops == 0  # poisoned from the first hop
                    poisoned = True
                    break
                time.sleep(0.01)
            assert poisoned, "NaN feed was never quarantined"
            good.feed(audio[5 * HOP :])
            got = good.read_until(expect)
            assert np.array_equal(got, _reference(audio)[:expect])
            assert np.isfinite(got).all()
            stats = good.stats()
            assert stats["sessions_poisoned"] >= 1
            assert stats["sessions_quarantined"] >= 1
            # quarantine unbinds the id: the evil client can start fresh
            assert evil.attach("evil") == "evil"
            evil.feed(audio[: 2 * HOP])
            fresh = evil.read_until(2 * HOP)
            assert np.array_equal(fresh, _reference(audio)[: 2 * HOP])
    finally:
        g.stop()


def test_gateway_fault_plan_frame_corruption_contained():
    """Server-side injected frame corruption (the FaultPlan's hostile-client
    stand-in): every mangled frame is answered or harmless, a retrying
    client still lands a bit-exact stream."""
    plan = FaultPlan(3, corrupt_rate=0.0, max_corruptions=8)
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    g = GatewayThread(sp, pump_interval=0.002, faults=plan)
    try:
        audio = _audio(32, 10)
        expect = (audio.size // HOP) * HOP
        rnd = random.Random(7)
        with GatewayClient(*g.address) as c:
            c.attach("fuzzed")  # attach while disarmed: the id stays clean
            plan.corrupt_rate = 0.4
            pos = 0
            while pos < audio.size:
                # odd sample counts make every corruption mode detectable
                # (half or +1 byte of a 4n-byte payload, n odd, is never a
                # whole float32 array) — so a lost feed is always re-sent
                n = min(rnd.randrange(1, 3 * HOP, 2), audio.size - pos)
                for _ in range(20):
                    try:
                        c.feed(audio[pos : pos + n])
                        break
                    except SessionError:
                        continue  # mangled frame: the feed never landed
                else:
                    pytest.fail("feed never survived the corruption storm")
                pos += n
            plan.corrupt_rate = 0.0
            got = c.read_until(expect)
        assert plan.injected["corrupt_frames"] >= 1, "storm never fired"
        assert np.array_equal(got, _reference(audio)[:expect])
    finally:
        g.stop()


def test_gateway_orphan_ttl_reaps():
    """An orphan past its TTL is detached by the pump loop."""
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    g = GatewayThread(sp, pump_interval=0.002, orphan_ttl=3)
    try:
        c = GatewayClient(*g.address)
        c.attach("ephemeral")
        c.drop()
        deadline = 200
        while g.gateway.orphans_reaped == 0 and deadline:
            deadline -= 1
            import time

            time.sleep(0.01)
        assert g.gateway.orphans_reaped == 1
        assert g.call(lambda p: p.num_active) == 0
        # the id is attachable again — as a FRESH session
        c2 = GatewayClient(*g.address)
        assert c2.attach("ephemeral") == "ephemeral"
        c2.close()
    finally:
        g.stop()


def test_gateway_stops_with_client_still_connected():
    """Python >= 3.12's ``Server.wait_closed()`` waits for every connection:
    ``stop()`` must close open clients itself instead of hanging."""
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    g = GatewayThread(sp, pump_interval=0.002, call_timeout=10.0)
    c = GatewayClient(*g.address, reconnect=False)
    c.attach("lingering")
    c.feed(_audio(3, 2))
    t0 = time.monotonic()
    g.stop()
    assert time.monotonic() - t0 < 5.0
    assert not g._thread.is_alive()
    with pytest.raises((ConnectionError, OSError)):
        c.read()
    c.close()


class _PumpBoom(RuntimeError):
    pass


def test_gateway_dead_heartbeat_fails_requests_and_stop():
    """A heartbeat that raises is not silent: requests get its error at
    once (no client waits out its deadline) and ``stop()`` re-raises it.
    The failing tick is the one a FEED asked for: the FEED itself is acked
    as queued, and the first request after it gets the error."""
    sp = ShardedSessionPool(PARAMS, CFG, 4, shards=2)
    # the timer's first tick runs at start, before the fault; no other
    g = GatewayThread(sp, pump_interval=3600.0, call_timeout=10.0)
    c = GatewayClient(*g.address, timeout=10.0)
    c.attach("victim")

    def boom():
        raise _PumpBoom("device step failed")

    g.call(lambda p: setattr(p, "pump_all", boom))
    t0 = time.monotonic()
    c.feed(_audio(4, 1))  # acked once queued; its deferred tick fails
    with pytest.raises(SessionError, match="device step failed"):
        c.read()
    assert time.monotonic() - t0 < 5.0
    with pytest.raises(_PumpBoom):
        g.stop()
    assert not g._thread.is_alive()
    c.close()
