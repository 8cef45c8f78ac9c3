"""Wire-format tests (serve/wire): the cross-process ticket contract.

Three layers of proof, cheapest first:

1. **Golden fixture** — ``tests/fixtures/session_ticket_v1.bin`` is a
   committed version-1 encoding of a hand-built ticket. Decoding it must
   yield exactly ``golden_ticket()`` and re-encoding must reproduce the
   file byte-for-byte: any unversioned format drift fails here before it
   can corrupt a real migration. Regenerate (after a deliberate,
   version-bumped change) with ``python tests/test_wire.py``.
2. **Property round-trip** — hypothesis drives random tickets (state
   shapes, float32 and fp10-grid leaves, empty/full rings, both parked
   states) through encode→decode and asserts bit-exactness leaf by leaf.
3. **End-to-end** — a live session exported from one pool crosses the
   wire as bytes and resumes in another pool bit-identically to a session
   that never migrated.
"""

import dataclasses
import pathlib
import struct
import zlib

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.quant import FP10, quantize
from repro.models import tftnn as tft
from repro.serve import (
    SessionPool,
    SessionStats,
    SessionTicket,
    StreamState,
    WIRE_VERSION,
    WireFormatError,
    decode_ticket,
    encode_ticket,
)

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "session_ticket_v1.bin"


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


def _assert_tickets_bit_exact(a: SessionTicket, b: SessionTicket) -> None:
    """Every leaf of ``b`` matches ``a``: dtype, shape, and bytes."""
    la, ta = jax.tree_util.tree_flatten(
        (a.state, a.pending_in, a.unread_out)
    )
    lb, tb = jax.tree_util.tree_flatten(
        (b.state, b.pending_in, b.unread_out)
    )
    assert ta == tb, "tree structure changed across the wire"
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype
        assert x.shape == y.shape
        assert x.tobytes() == y.tobytes()
    assert a.stats == b.stats
    assert a.parked == b.parked


# -- golden fixture ----------------------------------------------------------


def golden_ticket() -> SessionTicket:
    """The hand-built ticket pinned by the committed fixture.

    Deliberately synthetic (deterministic arange/linspace leaves, no model
    execution) so the fixture only moves when the FORMAT moves, never when
    model init or pool internals do.
    """
    n_fft, fp, hid = 16, 5, 4

    def ramp(shape, offset=0.0):
        n = int(np.prod(shape))
        return (np.linspace(-1.0, 1.0, n, dtype=np.float32) + np.float32(offset)).reshape(shape)

    state = StreamState(
        analysis=ramp((n_fft,)),
        synthesis=ramp((n_fft,), 0.25),
        wsum=ramp((n_fft,), 0.5),
        model={
            "block0": ramp((fp, hid), 1.0),
            "block1": ramp((fp, hid), -1.0),
        },
    )
    return SessionTicket(
        state=state,
        pending_in=np.arange(7, dtype=np.float32) * np.float32(0.125),
        unread_out=np.arange(12, dtype=np.float32) * np.float32(-0.0625),
        stats=SessionStats(
            hops=42, samples_in=672, samples_out=640, proc_seconds=0.03125
        ),
        parked=True,
    )


def test_golden_fixture_decodes_bit_exact():
    data = FIXTURE.read_bytes()
    ticket = decode_ticket(data)
    _assert_tickets_bit_exact(golden_ticket(), ticket)


def test_golden_fixture_reencodes_byte_identical():
    data = FIXTURE.read_bytes()
    assert encode_ticket(decode_ticket(data)) == data
    # and the in-memory builder lands on the same bytes: deterministic encode
    assert encode_ticket(golden_ticket()) == data


def test_golden_fixture_header_fields():
    data = FIXTURE.read_bytes()
    assert data[:4] == b"RTKT"
    version, flags = struct.unpack("<HH", data[4:8])
    assert version == WIRE_VERSION == 1
    assert flags == 0


# -- property round-trip -----------------------------------------------------

def _leaf(shape, seed, fp10):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    if fp10:  # the paper's deployment grid — what quantized-path leaves hold
        x = np.asarray(quantize(x, FP10), np.float32)
    return x


@settings(max_examples=25, deadline=None)
@given(
    n_fft=st.integers(min_value=1, max_value=24),
    fp=st.integers(min_value=1, max_value=6),
    hid=st.integers(min_value=1, max_value=6),
    n_blocks=st.integers(min_value=1, max_value=3),
    pending=st.integers(min_value=0, max_value=40),
    unread=st.integers(min_value=0, max_value=40),
    parked=st.booleans(),
    fp10=st.booleans(),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_roundtrip_is_bit_exact(
    n_fft, fp, hid, n_blocks, pending, unread, parked, fp10, seed
):
    ticket = SessionTicket(
        state=StreamState(
            analysis=_leaf((n_fft,), seed, fp10),
            synthesis=_leaf((n_fft,), seed + 1, fp10),
            wsum=_leaf((n_fft,), seed + 2, fp10),
            model={
                f"block{i}": _leaf((fp, hid), seed + 3 + i, fp10)
                for i in range(n_blocks)
            },
        ),
        pending_in=_leaf((pending,), seed + 99, fp10),
        unread_out=_leaf((unread,), seed + 100, fp10),
        stats=SessionStats(
            hops=seed % 1000,
            samples_in=seed % 7777,
            samples_out=seed % 6666,
            proc_seconds=float(seed % 100) / 64.0,
        ),
        parked=parked,
    )
    blob = encode_ticket(ticket)
    back = decode_ticket(blob)
    _assert_tickets_bit_exact(ticket, back)
    # deterministic: the decoded ticket re-encodes to the same bytes
    assert encode_ticket(back) == blob


def test_roundtrip_preserves_nonfinite_and_negative_zero():
    ticket = golden_ticket()
    ticket.pending_in = np.array(
        [np.inf, -np.inf, np.nan, -0.0, np.float32(1e-45)], np.float32
    )
    back = decode_ticket(encode_ticket(ticket))
    assert back.pending_in.tobytes() == ticket.pending_in.tobytes()


# -- malformed bytes ---------------------------------------------------------


def test_rejects_bad_magic():
    data = bytearray(encode_ticket(golden_ticket()))
    data[:4] = b"NOPE"
    with pytest.raises(WireFormatError, match="magic"):
        decode_ticket(bytes(data))


def test_rejects_wrong_version():
    data = bytearray(encode_ticket(golden_ticket()))
    data[4:6] = struct.pack("<H", WIRE_VERSION + 1)
    with pytest.raises(WireFormatError, match="version"):
        decode_ticket(bytes(data))


def test_rejects_truncation_everywhere():
    data = encode_ticket(golden_ticket())
    for cut in (0, 3, 7, 11, len(data) // 2, len(data) - 1):
        with pytest.raises(WireFormatError):
            decode_ticket(data[:cut])


def test_rejects_corrupted_body():
    data = bytearray(encode_ticket(golden_ticket()))
    data[len(data) // 2] ^= 0xFF
    with pytest.raises(WireFormatError, match="checksum"):
        decode_ticket(bytes(data))


def test_rejects_trailing_garbage():
    data = encode_ticket(golden_ticket())
    # keep the crc valid: append after re-wrapping body + junk
    body = data[8:-4] + b"\x00"
    evil = data[:8] + body + struct.pack("<I", zlib.crc32(body))
    with pytest.raises(WireFormatError):
        decode_ticket(evil)


def test_rejects_unknown_dataclass_name():
    data = encode_ticket(golden_ticket())
    body = bytearray(data[8:-4])
    # the first dataclass tag is the ticket itself: tag 9 + str "SessionTicket"
    idx = body.find(b"SessionTicket")
    assert idx > 0
    body[idx : idx + len(b"SessionTicket")] = b"EvilDataklass"
    evil = data[:8] + bytes(body) + struct.pack("<I", zlib.crc32(bytes(body)))
    with pytest.raises(WireFormatError, match="unknown dataclass|bad fields"):
        decode_ticket(evil)


def test_encode_rejects_non_ticket():
    with pytest.raises(WireFormatError):
        encode_ticket({"not": "a ticket"})


# -- end-to-end: a live session crosses the wire -----------------------------


def test_exported_session_resumes_across_the_wire():
    cfg = small_cfg()
    params = tft.init_tft(jax.random.PRNGKey(0), cfg)
    hop = cfg.hop
    audio = np.asarray(
        0.3 * jax.random.normal(jax.random.PRNGKey(7), (12 * hop,)), np.float32
    )

    ref_pool = SessionPool(params, cfg, capacity=2)
    s = ref_pool.attach()
    ref_pool.feed(s, audio)
    ref_pool.pump()
    ref = ref_pool.detach(s)

    src = SessionPool(params, cfg, capacity=2)
    a = src.attach()
    src.feed(a, audio[: 5 * hop])
    src.pump()
    first = src.read(a)
    blob = encode_ticket(src.export_session(a))  # ...process boundary...
    # (export_session detaches: the source slot is already free)

    dst = SessionPool(params, cfg, capacity=2)
    b = dst.import_session(decode_ticket(blob))
    dst.feed(b, audio[5 * hop :])
    dst.pump()
    rest = dst.detach(b)

    out = np.concatenate([first, rest])
    assert np.array_equal(out, ref)


if __name__ == "__main__":
    # deliberate format changes only: bump WIRE_VERSION, then regenerate
    FIXTURE.parent.mkdir(parents=True, exist_ok=True)
    FIXTURE.write_bytes(encode_ticket(golden_ticket()))
    print(f"wrote {FIXTURE} ({FIXTURE.stat().st_size} bytes)")
