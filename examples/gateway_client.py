"""Cross-process serving fabric demo: real network clients, real failures.

The other serving examples drive pools in-process; this one crosses the
fabric's actual boundary. A ``GatewayThread`` runs the asyncio socket
gateway (its own pump loop, shard health checks every tick) over a 2-shard
``ShardedSessionPool``, and every client below is a real TCP connection
speaking the framed streaming protocol:

- two clients stream jittery variable-sized chunks concurrently,
- a shard is KILLED mid-stream — its sessions fail over as wire tickets
  and the audio keeps flowing,
- one client's connection is severed without detaching; a new connection
  re-attaches the same session id and resumes with nothing lost.

At the end, every stream is verified against a solo in-process pool that
never saw a network or a failure — bit-identical for the in-thread gateway,
within ``CONNECT_MIN_SI_SNR_DB`` for an external one — and the gateway's
failover metrics are printed.

Run:  PYTHONPATH=src python examples/gateway_client.py
Or serve a standalone gateway and connect from another terminal/process:

  PYTHONPATH=src python -m repro.launch.serve --task gateway --reduced --port 7861
  PYTHONPATH=src python examples/gateway_client.py --connect 127.0.0.1:7861

With ``--connect`` this process keeps JAX on the CPU: the gateway process
owns the accelerator, and a chip belongs to one process at a time.
"""

import argparse

import jax
import numpy as np

ap = argparse.ArgumentParser()
ap.add_argument("--connect", default="",
                help="host:port of a running --task gateway; default spins "
                "up an in-thread gateway (and can then inject failures)")
args = ap.parse_args()
if args.connect:
    jax.config.update("jax_platforms", "cpu")  # before JAX picks a device

from repro.audio.metrics import si_snr_db  # noqa: E402
from repro.audio.synthetic import batch_for_step  # noqa: E402
from repro.launch.serve import reduced_cfg  # noqa: E402
from repro.models import tftnn as tft  # noqa: E402
from repro.serve import SessionPool, ShardedSessionPool  # noqa: E402
from repro.serve.gateway import GatewayClient, GatewayThread  # noqa: E402

# An external gateway computes on its own device, possibly a TPU, where an
# f32 matmul defaults to one bf16 pass; this CPU reference runs full f32.
# Only that arithmetic may separate the two; a lost or repeated hop costs
# tens of dB.
CONNECT_MIN_SI_SNR_DB = 20.0

# the launcher's --reduced trunk with its PRNGKey(0) weights
cfg = reduced_cfg(tft.tftnn_config())
params = tft.init_tft(jax.random.PRNGKey(0), cfg)
hop = cfg.hop

noisy, _ = batch_for_step(1, 0, batch=2, num_samples=4000)
audio = np.asarray(noisy, np.float32)
n_out = (audio.shape[1] // hop) * hop

gw = None
if args.connect:
    host, _, port = args.connect.rpartition(":")
    address = (host, int(port))
    print(f"connecting to external gateway at {host}:{port}")
else:
    pool = ShardedSessionPool(params, cfg, 4, shards=2)
    gw = GatewayThread(pool, pump_interval=0.002)
    address = gw.address
    print(f"in-thread gateway listening on {address[0]}:{address[1]} "
          f"(2 shards x 4 slots)")

alice = GatewayClient(*address)
bob = GatewayClient(*address)
alice.attach("alice")
bob.attach("bob")
print("alice and bob attached over TCP")

rnd = np.random.default_rng(0)
pos = [0, 0]
killed = False
dropped = False
while min(pos) < audio.shape[1]:
    for i, client in enumerate((alice, bob)):
        n = int(rnd.integers(0, 3 * hop))  # jitter: dribbles, blobs, silence
        chunk = audio[i, pos[i] : pos[i] + n]
        client.feed(chunk)
        pos[i] += chunk.size
    if gw is not None and not killed and min(pos) > audio.shape[1] // 3:
        victim = gw.call(lambda p: p.route("alice"))
        gw.call(lambda p: p.kill_shard(victim))
        print(f"killed shard {victim} mid-stream (alice lives there) — "
              "sessions fail over as wire tickets")
        killed = True
    if not dropped and min(pos) > 2 * audio.shape[1] // 3:
        bob.drop()  # vanish without detaching: the session is orphaned
        bob = GatewayClient(*address)
        assert bob.attach("bob") == "bob"  # adoption: same id, same stream
        print("bob's connection dropped and re-attached; stream adopted")
        dropped = True

out_alice = np.concatenate([alice.read_until(n_out), alice.detach()])[:n_out]
out_bob = np.concatenate([bob.read_until(n_out), bob.detach()])[:n_out]

stats = alice.stats() if alice.session_id else None
alice.close()
bob.close()

# ground truth: a solo in-process pool, no network, no failures
solo = SessionPool(params, cfg, capacity=2)
for i, (name, got) in enumerate([("alice", out_alice), ("bob", out_bob)]):
    s = solo.attach()
    solo.feed(s, audio[i])
    solo.pump()
    want = solo.detach(s)[:n_out]
    if gw is not None:
        match = np.array_equal(got, want)
        print(f"{name}: {got.size} samples over TCP, bit-identical to "
              f"in-process: {match}")
    else:
        db = float(si_snr_db(got, want))
        match = db >= CONNECT_MIN_SI_SNR_DB
        print(f"{name}: {got.size} samples over TCP, SI-SNR vs in-process "
              f"CPU reference {db:.1f} dB (>= {CONNECT_MIN_SI_SNR_DB} dB: "
              f"{match})")
    assert match, f"{name}'s stream diverged crossing the fabric"

if gw is not None:
    final = gw.call(lambda p: {
        "sessions_failed_over": p.sessions_failed_over,
        "sessions_lost": p.sessions_lost,
        "dead_shards": p.dead_shards,
        "failovers_per_shard": [s["shard_failovers"] for s in p.shard_stats()],
        "pump_ticks": gw.gateway.pump_ticks,
    })
    print(f"fabric metrics: {final}")
    assert final["sessions_failed_over"] >= 1
    gw.stop()
print("OK: the network (and a dead shard) are invisible to the audio")
