"""Messenger tier: handshake, dispatch, auth, loss, injection.

ref test model: src/test/msgr/test_msgr.cc (MessengerTest) — client/
server pairs exercising delivery, policies, reconnect and fault
injection on localhost sockets.
"""

import asyncio
import zlib

import pytest

from ceph_tpu.encoding import denc
from ceph_tpu.msg import (
    MODE_SECURE, AuthError, Dispatcher, Keyring, Message, Messenger,
    Policy, register,
)
from ceph_tpu.msg import messenger as msgr_mod
from ceph_tpu.msg.messenger import (
    BANNER, CHUNK, PERF, TAG_MSG, ConnectionError_, EntityAddr,
)
from ceph_tpu.utils import tracing


@register
class MPing(Message):
    TYPE = 900
    FIELDS = [("x", "u64"), ("note", "str")]


@register
class MData(Message):
    TYPE = 901
    FIELDS = [("oid", "str"), ("data", "blob"), ("osds", "list:s32")]


class Collector(Dispatcher):
    def __init__(self):
        self.got = []
        self.resets = 0
        self.event = asyncio.Event()

    async def ms_dispatch(self, msg):
        self.got.append(msg)
        self.event.set()
        return True

    async def ms_handle_reset(self, conn):
        self.resets += 1


async def _wait(pred, timeout=5.0):
    t0 = asyncio.get_event_loop().time()
    while not pred():
        if asyncio.get_event_loop().time() - t0 > timeout:
            raise TimeoutError
        await asyncio.sleep(0.01)


def run(coro):
    return asyncio.run(coro)


def _keyring(*names):
    kr = Keyring()
    for n in names:
        kr.add(n)
    return kr


def test_basic_roundtrip_with_auth():
    async def go():
        kr = _keyring("osd.1", "client.a")
        server = Messenger("osd.1", keyring=kr)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a", keyring=kr)
        await client.send_message(
            MData(oid="obj1", data=b"\x01\x02", osds=[3, -1]), addr,
            "osd.1")
        await _wait(lambda: sink.got)
        m = sink.got[0]
        assert isinstance(m, MData)
        assert (m.oid, m.data, m.osds) == ("obj1", b"\x01\x02", [3, -1])
        assert m.src == "client.a"
        # reply over the incoming connection
        reply_sink = Collector()
        client.add_dispatcher(reply_sink)
        await m.conn.send_message(MPing(x=7, note="reply"))
        await _wait(lambda: reply_sink.got)
        assert reply_sink.got[0].x == 7
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_auth_rejects_wrong_key():
    async def go():
        server = Messenger("mon.a", keyring=_keyring("mon.a", "client.x"))
        await server.bind()
        bad = Messenger("client.x", keyring=_keyring("mon.a", "client.x"))
        # tamper: different secret than the server's for client.x
        bad.keyring.add("client.x")
        with pytest.raises((AuthError, ConnectionError_, OSError,
                            asyncio.IncompleteReadError)):
            await bad.send_message(MPing(x=1, note=""), server.addr,
                                   "mon.a")
        await bad.shutdown()
        await server.shutdown()
    run(go())


def test_unknown_entity_rejected():
    async def go():
        server = Messenger("mon.a", keyring=_keyring("mon.a"))
        await server.bind()
        kr = _keyring("mon.a")
        kr.add("client.ghost")
        ghost = Messenger("client.ghost", keyring=kr)
        with pytest.raises((AuthError, ConnectionError_, OSError,
                            asyncio.IncompleteReadError)):
            await ghost.send_message(MPing(x=1, note=""), server.addr,
                                     "mon.a")
        await ghost.shutdown()
        await server.shutdown()
    run(go())


def test_secure_mode_frames():
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr, mode=MODE_SECURE)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr, mode=MODE_SECURE)
        for i in range(5):
            await client.send_message(MPing(x=i, note="s"), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 5)
        assert [m.x for m in sink.got] == list(range(5))
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_secure_mode_no_plaintext_on_wire():
    """Secure mode is ENCRYPTION, not just integrity (VERDICT r3
    Missing #7): a distinctive payload must never appear in the bytes
    written to either socket; in crc mode it must (sanity check that
    the tap works)."""
    def tap(msgr, captured):
        orig_handshake = msgr._client_handshake_inner

        async def wrapped(reader, writer, addr, peer_name):
            orig_write = writer.write

            def spy(data):
                captured.append(bytes(data))
                return orig_write(data)
            writer.write = spy
            return await orig_handshake(reader, writer, addr, peer_name)
        msgr._client_handshake_inner = wrapped

    async def go(mode):
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr, mode=mode)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr, mode=mode)
        captured: list[bytes] = []
        tap(client, captured)
        marker = b"TOP-SECRET-PAYLOAD-0123456789"
        await client.send_message(
            MData(oid="o", data=marker, osds=[1]), addr, "osd.1")
        await _wait(lambda: sink.got)
        assert sink.got[0].data == marker
        wire = b"".join(captured)
        await client.shutdown()
        await server.shutdown()
        return marker in wire

    assert run(go(MODE_SECURE)) is False, "plaintext leaked in secure mode"
    from ceph_tpu.msg.messenger import MODE_CRC
    assert run(go(MODE_CRC)) is True, "wire tap failed to observe frames"


def test_secure_mode_survives_rekey():
    """Sessions must keep flowing across in-band key rotations (the
    cephx ticket-rotation analog)."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr, mode=MODE_SECURE,
                           rekey_frames=3)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr, mode=MODE_SECURE,
                           rekey_frames=3)
        for i in range(20):
            await client.send_message(MPing(x=i, note="r"), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 20)
        assert [m.x for m in sink.got] == list(range(20))
        conn = next(iter(client.conns.values()))
        assert conn._tx_epoch >= 5, "rekey never happened"
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_secure_mode_rejects_tampered_frames():
    """Flipping one ciphertext bit must kill the frame (AEAD tag)."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr, mode=MODE_SECURE)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr, mode=MODE_SECURE)
        await client.send_message(MPing(x=1, note="a"), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        conn = next(iter(client.conns.values()))
        orig_write = conn.writer.write

        def corrupt(data):
            b = bytearray(data)
            if len(b) > 20:
                b[-1] ^= 0x40          # flip a ciphertext/tag bit
            return orig_write(bytes(b))
        conn.writer.write = corrupt
        try:
            await conn.send_message(MPing(x=2, note="b"))
        except ConnectionError_:
            pass
        await asyncio.sleep(0.3)
        assert len(sink.got) == 1, "tampered frame was dispatched"
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_lossless_replay_exactly_once_under_injection():
    """Injected socket failures on a lossless peer link: every message
    still arrives, in order, exactly once (the qa thrash invariant)."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr)
        server.set_policy("osd", Policy.lossless_peer())
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr,
                           inject_socket_failures=12, seed=7)
        client.set_policy("osd", Policy.lossless_peer())
        n = 40
        for i in range(n):
            # injected failures surface as reconnect+replay inside
            await client.send_message(MPing(x=i, note="inj"), addr,
                                      "osd.1")
        client.inject_socket_failures = 0
        await _wait(lambda: len(sink.got) >= n, timeout=15)
        xs = [m.x for m in sink.got]
        assert xs == sorted(set(xs)), "duplicates or reordering"
        assert xs == list(range(n))
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_lossy_connection_raises_on_failure():
    async def go():
        kr = _keyring("client.a", "osd.1")
        server = Messenger("osd.1", keyring=kr)
        server.add_dispatcher(Collector())
        addr = await server.bind()
        client = Messenger("client.a", keyring=kr,
                           inject_socket_failures=1, seed=3)
        with pytest.raises(ConnectionError_):
            for _ in range(50):
                await client.send_message(MPing(x=0, note=""), addr,
                                          "osd.1")
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_throttled_dispatch_delivers_all():
    async def go():
        kr = _keyring("client.a", "osd.1")
        server = Messenger("osd.1", keyring=kr,
                           default_policy=Policy(lossy=True,
                                                 throttler_bytes=256))
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a", keyring=kr)
        for i in range(20):
            await client.send_message(
                MData(oid=f"o{i}", data=b"x" * 100, osds=[]), addr,
                "osd.1")
        await _wait(lambda: len(sink.got) == 20)
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_no_auth_mode():
    async def go():
        server = Messenger("mon.a")       # no keyring: auth disabled
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        await client.send_message(MPing(x=3, note="open"), addr, "mon.a")
        await _wait(lambda: sink.got)
        assert sink.got[0].x == 3
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_message_registry_duplicate_type_rejected():
    with pytest.raises(ValueError):
        @register
        class Clash(Message):
            TYPE = 900
            FIELDS = []


def test_auth_mode_mismatch_fails_fast():
    async def go():
        server = Messenger("mon.a")               # no auth
        await server.bind()
        kr = _keyring("mon.a", "client.a")
        client = Messenger("client.a", keyring=kr)  # auth required
        with pytest.raises((AuthError, ConnectionError_, OSError,
                            asyncio.IncompleteReadError)):
            await client.send_message(MPing(x=1, note=""), server.addr,
                                      "mon.a")
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_secure_mode_requires_keyring():
    with pytest.raises(ValueError):
        Messenger("osd.0", mode=MODE_SECURE)


def test_lossless_resumes_after_reader_side_abort():
    """A conn killed from the reader path must not silently lose later
    messages (the fresh handshake must inherit seq + unacked)."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr)
        server.set_policy("osd", Policy.lossless_peer())
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr)
        client.set_policy("osd", Policy.lossless_peer())
        await client.send_message(MPing(x=1, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        # simulate a reader-side failure: abort the live connection
        conn = client.conns[addr]
        conn._abort()
        await client.send_message(MPing(x=2, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 2)
        assert [m.x for m in sink.got] == [1, 2]
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_concurrent_first_sends_single_connection():
    """Racing first sends must share one connection + session."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr)
        server.set_policy("osd", Policy.lossless_peer())
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr)
        client.set_policy("osd", Policy.lossless_peer())
        await asyncio.gather(*[
            client.send_message(MPing(x=i, note="race"), addr, "osd.1")
            for i in range(10)])
        await _wait(lambda: len(sink.got) == 10)
        assert sorted(m.x for m in sink.got) == list(range(10))
        assert len(client.conns) == 1
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_crc_vs_secure_mode_mismatch_fails_fast():
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr, mode=MODE_SECURE)
        await server.bind()
        client = Messenger("osd.0", keyring=kr)   # MODE_CRC
        with pytest.raises((AuthError, ConnectionError_, OSError,
                            asyncio.IncompleteReadError)):
            await client.send_message(MPing(x=1, note=""), server.addr,
                                      "osd.1")
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_key_rotation_reauths_live_secure_session():
    """AuthMonitor key rotation (round 18): both ends hold the new
    secret, so the in-band REKEY session-ticket verifies and traffic
    continues on the live session — no reconnect, no reset."""
    async def go():
        master = _keyring("osd.0", "osd.1")
        kr_srv = master.copy_for("osd.0", "osd.1")
        kr_cli = master.copy_for("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr_srv, mode=MODE_SECURE)
        server.set_policy("osd", Policy.lossless_peer())
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr_cli, mode=MODE_SECURE)
        client.set_policy("osd", Policy.lossless_peer())
        reply = Collector()
        client.add_dispatcher(reply)
        await client.send_message(MPing(x=1, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        conn = client.conns[addr]
        epoch0 = conn._tx_epoch
        # paxos commits the rotation: every keyring copy gets the new
        # secret, each messenger re-keys the entity's live sessions
        newkey = master.generate_key()
        kr_srv.set_key("osd.0", newkey)
        kr_cli.set_key("osd.0", newkey)
        await _wait(lambda: conn._tx_epoch > epoch0)
        for i in range(2, 6):
            await client.send_message(MPing(x=i, note=""), addr,
                                      "osd.1")
        await _wait(lambda: len(sink.got) == 5)
        assert [m.x for m in sink.got] == [1, 2, 3, 4, 5]
        assert sink.resets == 0 and reply.resets == 0
        assert not conn.closed
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_key_rotation_skew_fences_session():
    """Only ONE side saw the rotation: the REKEY ticket no longer
    proves possession of the peer's notion of the secret, so the peer
    fences the session instead of silently relabeling epochs. The
    reconnect then fails full mutual auth (keys genuinely differ)."""
    async def go():
        master = _keyring("osd.0", "osd.1")
        kr_srv = master.copy_for("osd.0", "osd.1")
        kr_cli = master.copy_for("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr_srv, mode=MODE_SECURE)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr_cli, mode=MODE_SECURE)
        await client.send_message(MPing(x=1, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        conn = client.conns[addr]
        # rotation skew: the client rotates, the server never hears
        kr_cli.set_key("osd.0", master.generate_key())
        await _wait(lambda: sink.resets >= 1)
        await _wait(lambda: conn.closed)
        with pytest.raises((AuthError, ConnectionError_, OSError,
                            asyncio.IncompleteReadError)):
            await client.send_message(MPing(x=2, note=""), addr,
                                      "osd.1")
            # at-least-once may mask the dead socket on the first
            # write; a second send forces the failed re-handshake
            await client.send_message(MPing(x=3, note=""), addr,
                                      "osd.1")
        assert len(sink.got) == 1
        await client.shutdown()
        await server.shutdown()
    run(go())


# -- a payload crosses without being copied (PR 36) --------------------------
#
# Raw socket peers speak the no-auth handshake by hand, so these tests
# see the bytes a Messenger writes and choose how the bytes it reads
# arrive.

MIB4 = 4 << 20
# framing round a body: len u32 | tag u8 | seq u64 | body | crc32 u32
FRAMING = 4 + 9 + 4


def _body_overhead() -> int:
    """Bytes of an ``MData(oid="o", osds=[i])`` body besides its data."""
    return len(MData(oid="o", data=b"", osds=[0]).encode())


def _frame(seq: int, msg: Message) -> bytes:
    """The frame as the parent wrote it: one buffer, one crc."""
    msg.seq = seq
    wire = bytes([TAG_MSG]) + seq.to_bytes(8, "little") + msg.encode()
    return len(wire).to_bytes(4, "little") + wire + \
        zlib.crc32(wire).to_bytes(4, "little")


def _payload(n: int, salt: int = 0) -> bytes:
    return bytes((i * 131 + salt) & 0xFF for i in range(257)) \
        * (n // 257 + 1)


def _client_hello(name: str = "client.raw") -> bytes:
    nb = name.encode()
    return BANNER + bytes([0]) + len(nb).to_bytes(2, "little") + nb + \
        (7).to_bytes(8, "little") + b"\x00" * 16


async def _raw_server(on_bytes):
    """A listening peer that answers the no-auth handshake and hands
    everything after it to ``on_bytes(data)``."""
    async def serve(reader, writer):
        writer.write(BANNER + bytes([0]))
        await reader.readexactly(len(BANNER) + 1)
        nlen = int.from_bytes(await reader.readexactly(2), "little")
        await reader.readexactly(nlen + 8 + 16)
        writer.write(b"NA")
        await writer.drain()
        while data := await reader.read(1 << 20):
            on_bytes(data)
        writer.close()
    return await asyncio.start_server(serve, "127.0.0.1", 0)


@pytest.mark.parametrize("size", [2, MIB4], ids=["small", "4MiB"])
def test_wire_bytes_are_the_parents(size):
    """What a raw peer reads is ``len | tag | seq | body | crc32``,
    byte for byte, whether the frame was joined or gathered."""
    async def go():
        got = bytearray()
        srv = await _raw_server(got.extend)
        host, port = srv.sockets[0].getsockname()[:2]
        client = Messenger("client.a")
        data = _payload(size)[:size]
        gathered = PERF.tx_frames_gathered
        await client.send_message(MData(oid="o", data=data, osds=[4, -1]),
                                  EntityAddr(host, port), "osd.raw")
        want = _frame(1, MData(oid="o", data=data, osds=[4, -1]))
        await _wait(lambda: len(got) >= len(want), timeout=10)
        assert bytes(got) == want
        assert PERF.tx_frames_gathered - gathered == \
            (1 if size >= denc.REF_MIN else 0)
        await client.shutdown()
        srv.close()
    run(go())


_MAX_FRAME = 1 << 20


@pytest.mark.parametrize("wire_len", [
    "data0", "data1", CHUNK - 1, CHUNK, CHUNK + 1, MIB4 + FRAMING,
    "max_frame"])
def test_roundtrip_at_body_sizes(wire_len):
    """Bodies round the reader's boundaries: the least, one on each
    side of a whole chunk, a bulk payload, and the longest allowed."""
    async def go():
        max_frame = 64 << 20
        if wire_len == "max_frame":
            max_frame = _MAX_FRAME
            n = max_frame - 9 - _body_overhead()
        elif isinstance(wire_len, str):
            n = int(wire_len[4:])
        else:
            n = wire_len - FRAMING - _body_overhead()
        server = Messenger("osd.1", max_frame=max_frame)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        data = _payload(n, 3)[:n]
        for i in range(3):      # the frame after it starts where it ends
            await client.send_message(
                MData(oid="o", data=data, osds=[i]), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 3, timeout=20)
        assert [m.osds for m in sink.got] == [[0], [1], [2]]
        assert all(m.data == data for m in sink.got)
        assert sink.resets == 0
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_frame_over_max_frame_refused_before_allocation(monkeypatch):
    """A declared length past ``max_frame`` resets the connection
    before a buffer of that length exists."""
    made = []

    def spy(*a):
        if a and isinstance(a[0], int):
            made.append(a[0])
        return bytearray(*a)
    monkeypatch.setattr(msgr_mod, "bytearray", spy, raising=False)

    async def go():
        server = Messenger("osd.1", max_frame=_MAX_FRAME)
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        n = _MAX_FRAME - 9 - _body_overhead() + 1
        try:
            await client.send_message(
                MData(oid="o", data=_payload(n)[:n], osds=[0]), addr,
                "osd.1")
        except ConnectionError_:
            pass                    # the reset may beat the last bytes
        await _wait(lambda: sink.resets == 1)
        assert sink.got == []
        assert made and max(made) <= CHUNK, made
        await client.shutdown()
        await server.shutdown()
    run(go())


async def _raw_client(addr, first: bytes = b""):
    """Connect and say the client's half of the no-auth handshake,
    ``first`` straight behind it in the same write."""
    reader, writer = await asyncio.open_connection(addr.host, addr.port)
    writer.write(_client_hello() + first)
    await writer.drain()
    await reader.readexactly(len(BANNER) + 1 + 2)
    return reader, writer


@pytest.mark.parametrize("how", ["dribble", "burst"])
def test_frames_decode_however_they_arrive(how):
    """A few bytes at a time, or two hundred frames in one write."""
    async def go():
        server = Messenger("osd.1")
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        if how == "dribble":
            # small ones, one that fills the chunk's tail and one that
            # gets a buffer of its own, each cut anywhere
            sizes = [0, 5, 40_000, 70_000, 1, 300_000, 17]
        else:
            sizes = [i % 50 for i in range(200)]
        msgs = [MData(oid=f"o{i}", data=_payload(n, i)[:n], osds=[i])
                for i, n in enumerate(sizes)]
        blob = b"".join(_frame(i + 1, m) for i, m in enumerate(msgs))
        reader, writer = await _raw_client(addr)
        if how == "dribble":
            off = 0
            while off < len(blob):
                # long frames: bigger bites, still never a whole one
                step = 3 if len(sink.got) in (0, 1, 4, 6) else 4093
                writer.write(blob[off:off + step])
                off += step
                await asyncio.sleep(0)
        else:
            writer.write(blob)
        await writer.drain()
        await _wait(lambda: len(sink.got) == len(msgs), timeout=30)
        assert [(m.oid, m.data, m.osds) for m in sink.got] == \
            [(m.oid, m.data, m.osds) for m in msgs]
        writer.close()
        await server.shutdown()
    run(go())


def test_corrupt_trailer_on_gathered_frame_resets():
    async def go():
        server = Messenger("osd.1")
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        await client.send_message(MPing(x=1, note="a"), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        conn = client.conns[addr]
        orig = conn.writer.writelines
        seen = []

        def corrupt(pieces):
            pieces = list(pieces)
            seen.append(len(pieces))
            pieces[-1] = bytes(b ^ 0x40 for b in pieces[-1])
            return orig(pieces)
        conn.writer.writelines = corrupt
        try:
            await conn.send_message(
                MData(oid="o", data=_payload(MIB4), osds=[]))
        except ConnectionError_:
            pass
        await _wait(lambda: sink.resets == 1, timeout=10)
        assert seen and seen[0] >= 4, "the frame was not gathered"
        assert len(sink.got) == 1, "corrupt frame was dispatched"
        await client.shutdown()
        await server.shutdown()
    run(go())


@pytest.mark.parametrize("where", ["before_write", "mid_frame"])
def test_lossless_replay_resends_segmented_message(where):
    """A reset while a gathered 4 MiB frame goes out: the session's
    replay queue holds its segments and resends them intact, once, in
    order."""
    async def go():
        kr = _keyring("osd.0", "osd.1")
        server = Messenger("osd.1", keyring=kr)
        server.set_policy("osd", Policy.lossless_peer())
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("osd.0", keyring=kr)
        client.set_policy("osd", Policy.lossless_peer())
        await client.send_message(MPing(x=1, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 1)
        conn = client.conns[addr]
        armed = [True]
        if where == "before_write":
            def fail_once():
                fire, armed[0] = armed[0], False
                return fire
            client._inject_failure = fail_once
        else:
            orig = conn.writer.writelines

            def cut(pieces):        # head and a run out, then the reset
                orig(list(pieces)[:2])
                conn.writer.transport.abort()
            conn.writer.writelines = cut
        data = _payload(MIB4, 9)[:MIB4]
        await client.send_message(
            MData(oid="big", data=data, osds=[2]), addr, "osd.1")
        await client.send_message(MPing(x=3, note=""), addr, "osd.1")
        await _wait(lambda: len(sink.got) >= 3, timeout=20)
        await asyncio.sleep(0.2)                # a duplicate would follow
        assert [type(m).__name__ for m in sink.got] == \
            ["MPing", "MData", "MPing"]
        assert sink.got[1].data == data and sink.got[1].osds == [2]
        assert client.conns[addr] is not conn, "no reset was injected"
        await client.shutdown()
        await server.shutdown()
    run(go())


@pytest.mark.parametrize("kind", ["bytearray", "writable_view",
                                  "readonly_view_of_bytearray"])
def test_mutable_blob_is_copied_at_send(kind):
    """Only an immutable blob is referenced: whatever the caller can
    still change arrives as it was when ``send_message`` was called."""
    async def go():
        server = Messenger("osd.1")
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        buf = bytearray(b"a" * MIB4)
        data = {"bytearray": buf, "writable_view": memoryview(buf),
                "readonly_view_of_bytearray":
                    memoryview(buf).toreadonly()}[kind]
        gathered = PERF.tx_frames_gathered
        await client.send_message(MData(oid="o", data=data, osds=[]),
                                  addr, "osd.1")
        buf[:] = b"b" * MIB4        # part of the frame is still unsent
        await _wait(lambda: sink.got, timeout=10)
        assert sink.got[0].data == b"a" * MIB4
        assert PERF.tx_frames_gathered == gathered
        await client.shutdown()
        await server.shutdown()
    run(go())


@pytest.mark.parametrize("size,count", [(1 << 20, 48), (100, 40_000)],
                         ids=["1MiB", "100B"])
def test_slow_dispatcher_pauses_reading(size, count):
    """While dispatch is stuck the receiver holds a bounded backlog,
    not whatever a fast peer can send."""
    class Stuck(Collector):
        def __init__(self):
            super().__init__()
            self.go_on = asyncio.Event()

        async def ms_dispatch(self, msg):
            await self.go_on.wait()
            return await super().ms_dispatch(msg)

    async def go():
        server = Messenger("osd.1")
        sink = Stuck()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        data = _payload(size)[:size]
        sent = [0]

        async def flood():
            for i in range(count):
                await client.send_message(
                    MData(oid="o", data=data, osds=[i]), addr, "osd.1")
                sent[0] += 1
        task = asyncio.ensure_future(flood())
        await _wait(lambda: server._accepted)
        wire = next(iter(server._accepted)).reader
        worst_bytes = worst_frames = 0
        for _ in range(60):
            await asyncio.sleep(0.01)
            worst_bytes = max(worst_bytes, wire._queued)
            worst_frames = max(worst_frames, len(wire._frames))
        # under one chunk of completed frames, plus what one more read
        # can complete: a chunk of small ones or one long frame
        assert worst_bytes < CHUNK + max(CHUNK, size + 100), worst_bytes
        assert worst_frames <= max(2, 2 * CHUNK // size), worst_frames
        if size * count > 32 << 20:     # more than socket buffers hold
            assert sent[0] < count, "the sender never felt the backlog"
        sink.go_on.set()
        await task
        await _wait(lambda: len(sink.got) == count, timeout=60)
        assert [m.osds[0] for m in sink.got] == list(range(count))
        await client.shutdown()
        await server.shutdown()
    run(go())


@pytest.mark.parametrize("size", [10, MIB4], ids=["small", "4MiB"])
def test_first_frame_behind_handshake_is_not_lost(size):
    """The client may send straight after its hello: those bytes sit
    in the chunk the handshake was read from (all of it, for 4 MiB)."""
    async def go():
        server = Messenger("osd.1")
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        data = _payload(size, 5)[:size]
        first = _frame(1, MData(oid="first", data=data, osds=[1])) + \
            _frame(2, MPing(x=2, note="second"))
        reader, writer = await _raw_client(addr, first)
        await _wait(lambda: len(sink.got) == 2, timeout=10)
        assert (sink.got[0].oid, sink.got[0].data) == ("first", data)
        assert sink.got[1].x == 2
        assert sink.got[0].src == "client.raw"
        writer.close()
        await server.shutdown()
    run(go())


def test_tally_reads_what_happened():
    """One 4 MiB and ten small messages: one gathered frame, under 1%
    of the bytes copied to build frames, the payload received in
    place but for the chunk it started in; ``segs`` on ``msg.send``."""
    async def go():
        server = Messenger("osd.1")
        sink = Collector()
        server.add_dispatcher(sink)
        addr = await server.bind()
        client = Messenger("client.a")
        client.tracer = tracing.Tracer("client.a", {})
        before = PERF.dump()
        big = MData(oid="big", data=_payload(MIB4)[:MIB4], osds=[])
        big.trace_id, big.parent_span_id = tracing.new_trace_id(), 1
        await client.send_message(big, addr, "osd.1")
        for i in range(10):
            await client.send_message(MPing(x=i, note="s"), addr, "osd.1")
        await _wait(lambda: len(sink.got) == 11, timeout=10)
        d = {k: v - before[k] for k, v in PERF.dump().items()}
        assert d["tx_frames"] == d["rx_frames"] == 11
        assert d["tx_frames_gathered"] == 1
        assert d["tx_bytes"] == d["rx_bytes"] > MIB4
        assert d["tx_bytes_joined"] < d["tx_bytes"] // 100
        assert d["rx_bytes_in_place"] >= MIB4 - CHUNK
        sends = [s for s in client.tracer.dump()["spans"]
                 if s["name"] == "msg.send"]
        assert len(sends) == 1 and sends[0]["tags"]["segs"] == 3
        assert sends[0]["tags"]["bytes"] == 9 + len(big.encode())
        await client.shutdown()
        await server.shutdown()
    run(go())


def test_encoder_references_only_large_immutable_blobs():
    """The encoder's choice, from the value's type and length alone;
    the bytes are the same either way, sections included."""
    big, small = _payload(denc.REF_MIN)[:denc.REF_MIN], b"x" * 100
    cases = {"bytes": big, "view_of_bytes": memoryview(big),
             "short": small, "bytearray": bytearray(big),
             "under": big[:-1]}
    for name, blob in cases.items():
        e = denc.Encoder()
        with e.start(2):
            e.u32(7).blob(blob).string("tail")
        e.u8(1)
        flat = denc.Encoder()
        with flat.start(2):
            flat.u32(7)
            flat.raw(len(blob).to_bytes(4, "little")).raw(bytes(blob))
            flat.string("tail")
        flat.u8(1)
        segs, referenced = e.segments()
        assert b"".join(segs) == e.tobytes() == flat.tobytes(), name
        assert len(e) == len(flat.tobytes())
        if name in ("bytes", "view_of_bytes"):
            assert len(segs) == 3 and segs[1] is blob, name
            assert referenced == len(blob), name
        else:
            assert len(segs) == 1 and referenced == 0, name
