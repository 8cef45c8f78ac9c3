"""Load generator: the clients of one run, in a process that never loads JAX.

Started by ``bench/run.py`` once the gateway listens. It speaks the
gateway's framing (``u32 length | u8 type | payload``, little-endian, as
documented in the program's ``serve/gateway.py``) over one localhost TCP
connection per seat, with asyncio, so that no client thread competes for the
serving process's interpreter lock. The traffic comes from ``traffic.py``
and the audio from ``synth.py``, both pure functions of the seed.

Protocol with the parent: the first line on stdout is
``WINDOW <t0> <t1>`` in ``time.monotonic()`` seconds (the system-wide clock
on Linux, so the parent reads the same clock); then the generator runs,
drains, detaches, writes ``result.json`` and ``streams.npz`` (the audio
received by every stream) into ``--out``, prints how late it ran,
and exits 0.

Run alone:  python bench/loadgen.py --host H --port P --traffic F --seed N
            --seconds S --out DIR
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import struct
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import hops  # noqa: E402
import synth  # noqa: E402
import traffic  # noqa: E402

HEADER = struct.Struct("<IB")
ATTACH, FEED, READ, DETACH = 1, 2, 3, 4
ATTACHED, AUDIO, DETACHED, BUSY, POISONED, DEGRADED, ERROR = (
    0x81, 0x82, 0x83, 0x85, 0x86, 0x87, 0xFF)
START_LEAD_S = 0.2  # between announcing the window and the first stream
DRAIN_S = 60.0  # longest wait after the window for hops due in it
LAG_PROBE_S = 0.005
STALL_S = 0.02  # a probe this late is listed with its time


class ServerError(RuntimeError):
    """The gateway answered a request with an error frame."""


class Conn:
    def __init__(self, reader, writer):
        self.reader, self.writer = reader, writer

    async def request(self, msg: int, payload: bytes = b""):
        self.writer.write(HEADER.pack(len(payload), msg) + payload)
        await self.writer.drain()
        length, rtype = HEADER.unpack(await self.reader.readexactly(HEADER.size))
        body = await self.reader.readexactly(length)
        if rtype in (ERROR, POISONED):
            raise ServerError(f"{rtype:#x}: {body[:200]!r}")
        return rtype, body


class Record:
    """What one stream fed and received, with the times."""

    def __init__(self, stream):
        self.stream = stream
        self.feed_due, self.feed_cum = [], []
        self.recv_t, self.recv_n, self.recv_cum = [], [], []
        self.audio = []
        self.fed = 0
        self.received = 0
        self.refused = False
        self.error = ""
        self.lateness = []  # per paced FEED: send time - due time

    def got(self, t: float, body: bytes) -> None:
        n = len(body) // 4
        if n:
            self.received += n
            self.recv_t.append(t)
            self.recv_n.append(n)
            self.recv_cum.append(self.received)
            self.audio.append(np.frombuffer(body, np.float32))


class Generator:
    def __init__(self, args, mix):
        self.args, self.mix = args, mix
        self.hop = args.hop
        self.bank = synth.bank(args.seed, mix["sample_rate"])
        self.plan = traffic.plan(mix, args.seed, self.bank.size)
        self.phase = traffic.seat_phases(mix, args.seed)
        self.records = []
        self.lag = []
        self.stalls = []  # (seconds into the window, ms late)

    async def seat(self, s: int, conn: Conn, t_start: float) -> None:
        mix, rng = self.mix, np.random.default_rng([self.args.seed, 0x5A, s])
        cs = mix["chunk_samples"]
        start = t_start + self.phase[s]
        for st in self.plan[s]:
            if (start if mix["paced"] else time.monotonic()) >= self.t1:
                return
            rec = Record(st)
            self.records.append(rec)
            n_chunks = math.ceil(st.samples / cs)
            due = traffic.chunk_due(mix, start, n_chunks, rng) if mix["paced"] else None
            if mix["paced"]:
                await self.sleep_until(start)
            try:
                rtype, _ = await conn.request(ATTACH, f"{s}.{st.index}".encode())
                if rtype == BUSY:
                    rec.refused = True
                    await asyncio.sleep(0.05)  # the gateway's retry hint is 50 ms
                    rec.feed_due = list(due) if due is not None else [time.monotonic()]
                    rec.feed_cum = list(np.minimum(np.arange(1, n_chunks + 1) * cs, st.samples)) \
                        if due is not None else [st.samples]
                else:
                    await self.stream(conn, rec, due, n_chunks)
            except (ServerError, ConnectionError, asyncio.IncompleteReadError) as e:
                rec.error = repr(e)[:300]
                return
            start = due[-1] if mix["paced"] else time.monotonic()

    async def stream(self, conn: Conn, rec: Record, due, n_chunks: int) -> None:
        st, cs = rec.stream, self.mix["chunk_samples"]
        for i in range(n_chunks):
            if due is not None:
                await self.sleep_until(due[i])
                if due[i] >= self.t1:
                    break
                d = float(due[i])
            else:
                d = time.monotonic()
                if d >= self.t1:
                    break
            # one chunk at a time: a whole long call would stall the loop
            chunk = synth.stream(self.bank, st.offset + i * cs, min(cs, st.samples - i * cs))
            t_send = time.monotonic()
            await conn.request(FEED, chunk.tobytes())
            rec.fed += chunk.size
            rec.feed_due.append(d)
            rec.feed_cum.append(rec.fed)
            if due is not None and self.t0 <= d < self.t1:
                rec.lateness.append(t_send - d)
            _, body = await conn.request(READ)
            rec.got(time.monotonic(), body)
        want = rec.fed // self.hop * self.hop
        while rec.received < want and time.monotonic() < self.t1 + DRAIN_S:
            _, body = await conn.request(READ)
            if body:
                rec.got(time.monotonic(), body)
            else:
                await asyncio.sleep(0.002)
        _, body = await conn.request(DETACH)
        rec.got(time.monotonic(), body)

    async def sleep_until(self, t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            await asyncio.sleep(dt)

    async def lag_probe(self) -> None:
        while time.monotonic() < self.t1:
            t = time.monotonic()
            await asyncio.sleep(LAG_PROBE_S)
            late = time.monotonic() - t - LAG_PROBE_S
            if t >= self.t0:
                self.lag.append(late)
                if late > STALL_S:
                    self.stalls.append((t - self.t0, late * 1e3))

    async def main(self) -> None:
        a, mix = self.args, self.mix
        conns = []
        for _ in range(mix["sessions"]):
            r, w = await asyncio.open_connection(a.host, a.port)
            conns.append(Conn(r, w))
        t_start = time.monotonic() + START_LEAD_S
        self.t0 = t_start + mix["warmup_s"]
        self.t1 = self.t0 + a.seconds
        print(f"WINDOW {self.t0!r} {self.t1!r}", flush=True)
        tasks = [asyncio.ensure_future(self.seat(s, c, t_start)) for s, c in enumerate(conns)]
        probe = asyncio.ensure_future(self.lag_probe())
        await asyncio.gather(*tasks, probe)
        self.t_end = time.monotonic()
        for c in conns:
            c.writer.close()
        for c in conns:
            try:
                await c.writer.wait_closed()
            except ConnectionError:
                pass

    def summary(self) -> dict:
        t0, t1, hop = self.t0, self.t1, self.hop
        att = fail = delivered_hops = 0
        lat = []
        delivered = 0
        streams = []
        late = []
        for rec in self.records:
            due, done = hops.hop_times(rec.feed_due, rec.feed_cum, rec.recv_t, rec.recv_cum, hop)
            if rec.refused:
                done = np.full_like(due, np.nan)
            w = hops.window_stats(due, done, t0, t1, self.t_end)
            att += w["attempted"]
            fail += w["failed"]
            lat.append(w["latency_s"])
            delivered += hops.delivered_in_window(rec.recv_t, rec.recv_n, t0, t1)
            delivered_hops += int(np.sum((done >= t0) & (done < t1)))
            late += rec.lateness
            streams.append({"seat": rec.stream.seat, "index": rec.stream.index,
                            "offset": rec.stream.offset, "fed": rec.fed,
                            "received": rec.received, "refused": rec.refused,
                            "error": rec.error,
                            "undelivered_hops": int(np.isnan(done).sum()) if not rec.refused else 0})
        lat = np.concatenate(lat) if lat else np.zeros(0)
        q = lambda v, p: float(np.percentile(v, p)) * 1e3 if len(v) else 0.0  # noqa: E731
        return {
            "window": [t0, t1], "t_end": self.t_end, "hop": hop,
            "attempted": att, "failed": fail,
            "latency_ms": (lat * 1e3).tolist(),
            "delivered_samples_in_window": delivered,
            "hops_delivered_in_window": delivered_hops,
            "streams": streams,
            "loop_lag_ms": {"p50": q(self.lag, 50), "p99": q(self.lag, 99),
                            "max": max(self.lag, default=0.0) * 1e3, "n": len(self.lag)},
            "loop_stalls": self.stalls,
            "send_lateness_ms": {"p50": q(late, 50), "p99": q(late, 99),
                                 "max": max(late, default=0.0) * 1e3, "n": len(late)},
        }

    def write(self, out: Path) -> dict:
        s = self.summary()
        (out / "result.json").write_text(json.dumps(s))
        audio = {f"{r.stream.seat}.{r.stream.index}": np.concatenate(r.audio) if r.audio
                 else np.zeros(0, np.float32) for r in self.records}
        np.savez(out / "streams.npz", **audio)
        return s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="benchmark load generator (no JAX)")
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True, help="traffic mix JSON file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--hop", type=int, required=True)
    ap.add_argument("--out", required=True, help="directory for the results")
    args = ap.parse_args(argv)
    mix = json.loads(Path(args.traffic).read_text())
    gen = Generator(args, mix)
    asyncio.run(gen.main())
    s = gen.write(Path(args.out))
    lag, late = s["loop_lag_ms"], s["send_lateness_ms"]
    print(f"loadgen: event-loop lag p50={lag['p50']:.3f} ms p99={lag['p99']:.3f} ms "
          f"max={lag['max']:.3f} ms; paced sends late by p50={late['p50']:.3f} ms "
          f"p99={late['p99']:.3f} ms max={late['max']:.3f} ms over {late['n']} sends; "
          f"stalls over {STALL_S * 1e3:.0f} ms (s into window, ms): "
          f"{[(round(t, 2), round(ms, 1)) for t, ms in s['loop_stalls']]}; "
          f"streams={len(s['streams'])} attempted={s['attempted']} failed={s['failed']}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
