"""Async messenger: connections, dispatch, policies — msgr2-lite.

ref: src/msg/async/AsyncMessenger.{h,cc} + ProtocolV2.{h,cc}. Same
architecture mapped onto asyncio instead of epoll threads:

- ``Messenger`` owns a listening socket plus a connection table keyed by
  peer address; ``Dispatcher``s get ms_dispatch/ms_handle_reset
  callbacks (ref: src/msg/Dispatcher.h).
- The wire protocol performs a banner + cephx-lite auth exchange, then
  length-prefixed frames carrying MSG/ACK/KEEPALIVE tags with a crc32
  trailer ('crc' mode) or an HMAC trailer ('secure' mode)
  (ref: ProtocolV2 banner/auth frames, crc vs secure modes):
  ``len u32 | tag u8 | seq u64 | body | crc32 u32``, ``len`` counting
  tag, seq and body.
- A bulk payload is not copied in user space between the message's
  field and the socket, in either direction (``_Wire``). Sending: the
  encoder *references* a blob that is immutable (``bytes``, or a
  read-only ``memoryview`` of one) and at least ``denc.REF_MIN`` long,
  and copies everything else (a ``bytearray``, a writable view, a short
  blob), so only an object nobody can change is ever held after
  ``send_message`` returns; the crc runs over the pieces and one
  ``writelines`` (``sendmsg``) gathers them. A frame with nothing
  referenced goes out as one joined buffer through ``write``; so does
  every secure-mode frame (the seal makes a new ciphertext anyway).
  Receiving: the socket is read into a reusable chunk, small frames
  are cut out of it many to a ``recv``, and the rest of a frame that
  is longer than what arrived of it lands by ``recv_into`` in a buffer
  of the frame's own length; the body handed to ``Message.decode`` is
  a view of that frame. Which path a frame takes is read from the
  value's type and length alone.
- ``Policy`` decides lossy vs lossless: lossless client connections
  keep unacked messages and resend them after a reconnect (the
  stateful-session half of ProtocolV2's reconnect/replay); lossy
  connections drop state on failure (ref: Messenger::Policy).
- Fault injection: ``inject_socket_failures=N`` kills roughly one in N
  frame sends/receives (ref: 'ms inject socket failures' config used by
  the qa suites).

The reference's throttles (Policy::throttler_bytes) become a bytes
semaphore gating dispatch of incoming messages.
"""

from __future__ import annotations

import asyncio
import hmac
import random
import struct
import time
import traceback
import zlib
from collections import deque
from dataclasses import dataclass

from ceph_tpu.msg.auth import Authenticator, AuthError, Keyring
from ceph_tpu.msg.message import Message
from ceph_tpu.utils import tracing
from ceph_tpu.utils.logging import get_logger

log = get_logger("ms")
_clock = time.perf_counter_ns

BANNER = b"ceph_tpu msgr2.1\n"

TAG_MSG = 1
TAG_ACK = 2
TAG_KEEPALIVE = 3
TAG_REKEY = 4   # secure mode: sender announces its next tx key epoch

MODE_CRC = 1
MODE_SECURE = 2

# the reader's reusable buffer, and the most one recv asks for: what
# the selector transport's own recv asks for; small frames are cut out
# of it many to a recv
CHUNK = 256 << 10
# a frame that has not wholly arrived and is longer than this gets a
# buffer of its own length for the rest to land in; a shorter one goes
# on filling the chunk (it is copied out whole either way)
_IN_PLACE_MIN = 32 << 10
_FRAME_LEN = struct.Struct("<I").unpack_from
_TAG_SEQ = struct.Struct("<BQ").unpack_from     # a frame's first 9 bytes


class _Tally:
    """How often the copy-free paths engage, process-wide and read as
    ``crush.mapper.PERF`` is (``PERF.dump()``): the gather share of
    the bytes sent is ``1 - tx_bytes_joined / tx_bytes``, the in-place
    share of the bytes received ``rx_bytes_in_place / rx_bytes``.

    Plain ints, bumped on every frame by the event loop's thread (a
    process's messengers share its one loop; a second loop in a second
    thread could lose an increment, nothing worse): the five locked
    ``PerfCounters.inc`` a frame they would be cost 3-4 us of a small
    frame's ~40 (PERF.md §6, PR 36).

    tx_frames           frames handed to a socket
    tx_bytes            their bytes, length prefix and trailer included
    tx_bytes_joined     bytes copied in user space to build them: all
                        of a joined frame, and of a gathered one
                        everything but the blobs the encoder referenced
    tx_frames_gathered  frames sent as their pieces in one writelines
                        (a blob was referenced)
    rx_frames           frames read off a socket
    rx_bytes            bytes read off sockets once the handshake ended
    rx_bytes_in_place   of them, those recv_into put directly into the
                        buffer of their frame's own length
    """

    __slots__ = ("tx_frames", "tx_bytes", "tx_bytes_joined",
                 "tx_frames_gathered", "rx_frames", "rx_bytes",
                 "rx_bytes_in_place")

    def __init__(self) -> None:
        for key in self.__slots__:
            setattr(self, key, 0)

    def dump(self) -> dict[str, int]:
        return {key: getattr(self, key) for key in self.__slots__}


PERF = _Tally()


class ConnectionError_(Exception):
    pass


@dataclass(frozen=True)
class EntityAddr:
    """ref: src/msg/msg_types.h entity_addr_t (host:port; the nonce that
    distinguishes daemon restarts is the messenger's session id)."""

    host: str
    port: int

    def __str__(self) -> str:
        return f"{self.host}:{self.port}"


@dataclass
class Policy:
    """ref: Messenger::Policy — lossy connections are dropped on error
    (client->osd); lossless ones resend (osd->osd, mon peers)."""

    lossy: bool = True
    throttler_bytes: int = 0     # 0 = unthrottled

    @classmethod
    def lossless_peer(cls) -> "Policy":
        return cls(lossy=False)


class Throttle:
    """Byte-budget gate (ref: src/common/Throttle.{h,cc})."""

    def __init__(self, limit: int):
        self.limit = limit
        self._used = 0
        self._cond = asyncio.Condition()

    async def acquire(self, n: int) -> None:
        if not self.limit:
            return
        n = min(n, self.limit)
        async with self._cond:
            while self._used + n > self.limit:
                await self._cond.wait()
            self._used += n

    async def release(self, n: int) -> None:
        if not self.limit:
            return
        n = min(n, self.limit)
        async with self._cond:
            self._used -= n
            self._cond.notify_all()


class _Session:
    """Per-peer-address lossless session state shared by every TCP
    connection to that peer (ref: ProtocolV2 session cookies/out_queue:
    the logical session outlives individual sockets)."""

    def __init__(self) -> None:
        self.out_seq = 0
        # (seq, the body as Encoder.segments gave it)
        self.unacked: list[tuple[int, tuple]] = []


class _Wire(asyncio.streams.FlowControlMixin, asyncio.BufferedProtocol):
    """One socket, both directions: the handshake's reads, frames read
    in place, writes with the transport's flow control (``drain`` is
    ``StreamWriter``'s, over the same ``FlowControlMixin``). It stands
    where ``StreamReader``/``StreamWriter`` stood (a ``Connection``'s
    ``reader`` and ``writer`` are one ``_Wire``) and owns its buffers.

    The transport reads into ``get_buffer()``: the free end of a
    reusable chunk, or, once a frame longer than ``_IN_PLACE_MIN`` has
    arrived in part, the rest of a ``bytearray`` of that frame's own
    length (checked against ``max_frame`` first). ``buffer_updated``
    cuts the chunk's complete frames out as ``bytes`` (the chunk is
    reused, and a ``blob_view`` field may outlive the read) and queues
    them for ``read_frame``; a frame that filled its own buffer is
    queued as that buffer. Reading pauses while ``CHUNK`` bytes or more
    of completed frames wait for the connection's reader loop, so a
    fast peer cannot queue frames without bound; what arrived behind
    the handshake's last read is simply the chunk's first frames.
    """

    def __init__(self, on_accept=None):
        super().__init__()
        self._on_accept = on_accept     # the listening side's handler
        self._task: asyncio.Task | None = None
        self.transport: asyncio.Transport | None = None
        self._chunk = memoryview(bytearray(CHUNK))
        self._r = self._w = 0           # the chunk's unread bytes
        self._big: memoryview | None = None   # a frame filling in place
        self._big_w = 0
        self._frames: deque = deque()   # complete, not yet read
        self._queued = 0                # their bytes
        self._trailer = -1              # bytes after a frame; -1: handshake
        self._max_frame = 0
        self._exc: Exception | None = None
        self._reading = True
        self._waiter: asyncio.Future | None = None    # the one reader

    # -- transport callbacks -----------------------------------------------
    def connection_made(self, transport) -> None:
        self.transport = transport
        self.write = transport.write    # not a wrapper: a call less
        if self._on_accept is not None:
            self._task = asyncio.ensure_future(self._on_accept(self))

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._big is not None:
            return self._big[self._big_w:]
        return self._chunk[self._w:]    # never full: _parse leaves room

    def buffer_updated(self, nbytes: int) -> None:
        big = self._big
        if big is not None:
            PERF.rx_bytes += nbytes
            PERF.rx_bytes_in_place += nbytes
            self._big_w += nbytes
            if self._big_w < len(big):
                return
            self._big = None
            self._queue(big.obj)
        else:
            self._w += nbytes
            if self._trailer < 0:               # handshake
                if self._w == CHUNK and not self._compact():
                    self._set_reading(False)    # readexactly resumes
                self._wake()
                return
            PERF.rx_bytes += nbytes
            self._parse()
        if self._frames:
            self._wake()

    def eof_received(self) -> bool:
        self._fail(ConnectionError_("connection closed by peer"))
        return False                    # nothing more to say either

    def connection_lost(self, exc) -> None:
        self._fail(exc or ConnectionError_("connection lost"))
        super().connection_lost(exc)    # wakes whoever drains

    # -- reading -----------------------------------------------------------
    def _fail(self, exc: Exception) -> None:
        if self._exc is None:
            self._exc = exc
        self._wake()

    def _wake(self) -> None:
        w = self._waiter
        if w is not None and not w.done():
            w.set_result(None)

    async def _wait(self) -> None:
        self._waiter = asyncio.get_running_loop().create_future()
        try:
            await self._waiter
        finally:
            self._waiter = None

    def _set_reading(self, on: bool) -> None:
        if on == self._reading or (on and self._exc is not None):
            return
        self._reading = on
        if on:
            self.transport.resume_reading()
        else:
            self.transport.pause_reading()

    def _compact(self) -> bool:
        """Move the chunk's unread bytes to its start; was there room
        to gain?"""
        r, n = self._r, self._w - self._r
        if not r:
            return False
        self._chunk[:n] = self._chunk[r:r + n]      # memmove
        self._r, self._w = 0, n
        return True

    def _queue(self, frame: bytes | bytearray) -> None:
        self._frames.append(frame)
        self._queued += len(frame)
        PERF.rx_frames += 1
        # a reader that waits takes the frame before the loop polls the
        # socket again: pausing for it would be two epoll_ctl for nothing
        if self._queued >= CHUNK and self._waiter is None:
            self._set_reading(False)

    def _parse(self) -> None:
        """Cut the chunk's complete frames out and leave the next read
        room for the rest of the one that is not: in a buffer of its
        own if it is long, else at the chunk's end."""
        mv, r, w, t = self._chunk, self._r, self._w, self._trailer
        while w - r >= 4:
            ln, = _FRAME_LEN(mv, r)
            if ln < 9 or ln > self._max_frame:
                # before any buffer of that length exists
                self._set_reading(False)
                self._fail(ConnectionError_(f"bad frame length {ln}"))
                break
            end = r + 4 + ln + t
            if end <= w:
                self._queue(bytes(mv[r + 4:end]))
                r = end
                continue
            if ln + t > _IN_PLACE_MIN:
                self._big = memoryview(bytearray(ln + t))
                self._big_w = w - r - 4
                self._big[:self._big_w] = mv[r + 4:w]
                w = r
            break
        if r == w:
            self._r = self._w = 0
        else:
            self._r = r
            if r + 4 + _IN_PLACE_MIN > CHUNK:
                self._compact()

    def start_frames(self, trailer: int, max_frame: int) -> None:
        """The handshake is over: what the chunk holds, and all that
        follows, is frames with ``trailer`` bytes after each."""
        self._trailer, self._max_frame = trailer, max_frame
        PERF.rx_bytes += self._w - self._r
        self._parse()
        if self._queued < CHUNK:
            self._set_reading(True)

    async def readexactly(self, n: int) -> bytes:
        """A read of the handshake (before ``start_frames``)."""
        if n > CHUNK:
            raise ConnectionError_(f"handshake read of {n} bytes")
        while self._w - self._r < n:
            if self._exc is not None:
                raise self._exc
            if self._r + n > CHUNK:
                self._compact()
            self._set_reading(True)
            await self._wait()
        out = bytes(self._chunk[self._r:self._r + n])
        self._r += n
        if self._r == self._w:
            self._r = self._w = 0
        return out

    async def read_frame(self) -> bytes | bytearray:
        """The next frame without its length prefix (``tag | seq |
        body | trailer``): ``bytes`` if it was cut out of the chunk,
        the ``bytearray`` it landed in if it had one. Frames that were
        complete before the connection failed are still delivered."""
        while not self._frames:
            if self._exc is not None:
                raise self._exc
            await self._wait()
        frame = self._frames.popleft()
        self._queued -= len(frame)
        if not self._reading and self._queued < CHUNK:
            self._set_reading(True)
        return frame

    # -- writing: write() is the transport's --------------------------------
    def writelines(self, pieces) -> None:
        # write() drops what is written to a closed transport and
        # drain() then raises; the transport's writelines() has no
        # such guard and would call into a socket that is gone
        if not self.transport.is_closing():
            self.transport.writelines(pieces)

    async def drain(self) -> None:
        """``StreamWriter.drain``: wait while the transport's buffer is
        over its high-water mark; raise once the connection is lost."""
        if self.transport.is_closing():
            await asyncio.sleep(0)      # let connection_lost() be called
        await self._drain_helper()

    def close(self) -> None:
        if self.transport is not None:
            self.transport.close()


class Connection:
    """One established session (ref: AsyncConnection). Owned by a
    Messenger; users only call send_message / close."""

    def __init__(self, msgr: "Messenger", reader, writer,
                 peer_name: str, peer_addr: EntityAddr | None,
                 auth: Authenticator | None, policy: Policy,
                 peer_session: int = 0):
        self.msgr = msgr
        self.reader = reader
        self.writer = writer
        self.peer_name = peer_name
        self.peer_addr = peer_addr        # set for outgoing connections
        self.peer_session = peer_session  # peer's messenger instance nonce
        self.auth = auth
        self.policy = policy
        self.out_seq = 0
        self.in_seq = 0
        # lossless replay queue: (seq, the body's segments and count)
        self.unacked: list[tuple[int, tuple]] = []
        # outgoing lossless conns share per-peer-address session state
        # (seq counter + replay queue) across reconnects
        self.session: "_Session | None" = None
        self.closed = False
        self._send_lock = asyncio.Lock()
        self._reader_task: asyncio.Task | None = None
        # secure mode: AEAD key epochs, one per direction. The client
        # side of the socket encrypts with direction byte 0, the server
        # side with 1 (the epoch key is shared, the nonce is not).
        self.is_client = peer_addr is not None
        self._tx_epoch = 0
        self._rx_epoch = 0
        self._tx_frames = 0
        # both ends of the last frame's integrity check (or open), for
        # the ``msg.recv`` section the reader loop emits once the
        # decoded message says whose op the frame belonged to
        self._rx_t0 = self._rx_t1 = 0
        # the handshake is over: what follows it, the part already in
        # the reader's chunk too (the peer may send straight behind
        # its last handshake bytes), is frames
        reader.start_frames(0 if self._secure() else 4, msgr.max_frame)

    def _secure(self) -> bool:
        return self.msgr.mode == MODE_SECURE and self.auth is not None

    # -- framing -----------------------------------------------------------
    async def _send_frame(self, tag: int, seq: int, body=((), 0),
                          ctx: Message | None = None) -> None:
        """``body``: the buffers it is made of and how many of their
        bytes the encoder referenced, as ``Encoder.segments`` gives
        them (nothing for an ACK). Any referenced: the frame is
        gathered, not joined. ``ctx``: the message the frame carries,
        where the caller has it — whose op the ``msg.send`` section
        belongs to."""
        inj = self.msgr.faults
        if inj is not None:
            act = inj.on_frame(self.msgr.name, self.peer_name)
            if act == "drop":          # one-way blackhole: swallow
                return
            if act == "cut":           # partition: like a socket reset
                self._abort()
                raise ConnectionError_("injected partition (send)")
        if self.msgr._inject_failure():
            self._abort()
            raise ConnectionError_("injected socket failure (send)")
        # msg.send: trailer or seal, and the socket.send the transport
        # does inline inside write(); drain() may await and stays out
        try:
            with tracing.section("msg.send", ctx, self.msgr.tracer,
                                 self.msgr.name) as sec:
                head = tag.to_bytes(1, "little") + \
                    seq.to_bytes(8, "little")
                segs, referenced = body
                if self._secure():
                    # AEAD: header authenticated as AAD, body encrypted;
                    # no separate trailer (the GCM tag rides in the
                    # ciphertext). The seal makes a new buffer anyway,
                    # so a secure frame is always one joined write
                    ct = self.auth.seal(0 if self.is_client else 1,
                                        self._tx_epoch, tag, seq, head,
                                        b"".join(segs))
                    n = 9 + len(ct)
                    pieces = (n.to_bytes(4, "little"), head, ct)
                    sent, referenced = 4 + n, 0
                else:
                    crc, n = zlib.crc32(head), 9
                    for seg in segs:
                        crc = zlib.crc32(seg, crc)
                        n += len(seg)
                    pieces = (n.to_bytes(4, "little"), head, *segs,
                              crc.to_bytes(4, "little"))
                    sent = 8 + n
                if sec:
                    sec.tag("bytes", n).tag("segs", len(segs))
                if referenced:
                    self.writer.writelines(pieces)      # one sendmsg
                    PERF.tx_frames_gathered += 1
                else:
                    self.writer.write(b"".join(pieces))
                PERF.tx_frames += 1
                PERF.tx_bytes += sent
                PERF.tx_bytes_joined += sent - referenced
            await self.writer.drain()
        except (ConnectionError, OSError) as e:
            self._abort()
            raise ConnectionError_(str(e)) from e

    async def _recv_frame(self) -> tuple[int, int, bytes | memoryview]:
        try:
            frame = await self.reader.read_frame()
        except (ConnectionError, OSError) as e:
            raise ConnectionError_(str(e)) from e
        if self.msgr._inject_failure():
            self._abort()
            raise ConnectionError_("injected socket failure (recv)")
        self._rx_t0 = _clock()
        # views from here on: a slice of the frame is a copy of it
        mv = memoryview(frame)
        if not mv.readonly:
            mv = mv.toreadonly()
        tag, seq = _TAG_SEQ(mv)
        if self._secure():
            try:
                body = self.auth.open(
                    0 if not self.is_client else 1, self._rx_epoch,
                    tag, seq, mv[:9], mv[9:])
            except AuthError as e:
                raise ConnectionError_(str(e)) from e
        else:
            end = len(mv) - 4
            if not hmac.compare_digest(
                    zlib.crc32(mv[:end]).to_bytes(4, "little"), mv[end:]):
                raise ConnectionError_("frame integrity check failed")
            body = mv[9:end]
        self._rx_t1 = _clock()
        return tag, seq, body

    def _rekey_material(self, new_epoch: int
                        ) -> tuple[bytes, bytes | None]:
        """REKEY frame body + the secret to install after sending.

        Round 18 (rotation re-auth): the body carries the announced
        epoch PLUS a session-ticket — a MAC under the CURRENT keyring
        secret of this connection's authenticating entity (the client
        side's name: that's whose key both handshake directions used).
        The receiver verifies it against its own keyring, so a key
        rotation re-proves possession on the live session instead of
        just relabeling epochs. Appended after the legacy 4-byte
        epoch, zero-fill discipline: an old peer reads the epoch and
        ignores the tail. Falls back to the ticketless legacy body
        when the entity's key is gone (a racing revoke — the fence is
        already in flight)."""
        ep = new_epoch.to_bytes(4, "little")
        entity = self.msgr.name if self.is_client else self.peer_name
        kr = self.msgr.keyring
        try:
            secret = kr.get(entity) if kr is not None else None
        except Exception:
            secret = None
        if secret is None:
            return ep, None
        return ep + self.auth.rekey_ticket(secret, new_epoch), secret

    async def _maybe_rekey(self) -> None:
        """In-band tx-key rotation (the cephx ticket-renewal analog):
        after ms_rekey_frames frames, announce epoch+1 under the old
        key, then switch. The receiver flips its rx epoch on the REKEY
        frame; TCP ordering makes the cutover exact."""
        n = self.msgr.rekey_frames
        if not self._secure() or not n or self._tx_frames < n:
            return
        new_epoch = self._tx_epoch + 1
        body, secret = self._rekey_material(new_epoch)
        await self._send_frame(TAG_REKEY, 0, ((body,), 0))
        if secret is not None:
            self.auth.install_secret(0 if self.is_client else 1,
                                     secret, new_epoch)
        self._tx_epoch = new_epoch
        self._tx_frames = 0

    # -- public ------------------------------------------------------------
    async def send_message(self, msg: Message) -> None:
        """Queue-and-send with at-least-once semantics on outgoing
        lossless connections (resent after reconnect until acked).
        Server-side (accepted) connections cannot reconnect — a failed
        send raises so the caller knows the reply was lost and the peer
        must re-request (ref: OSD replies on reset client sessions).

        Message-level fault shaping (sim/faults.py) runs BEFORE the
        send lock and the seq assignment: a delayed/reordered message
        is overtaken by later sends and still gets an in-order seq, so
        the receiver's dedup machinery stays coherent; a duplicated
        message goes out twice under distinct seqs (end-to-end reqid
        dedup makes it exactly-once)."""
        inj = self.msgr.faults
        if inj is not None and \
                await inj.on_message(self.msgr.name, self.peer_name):
            await self._send_message_once(msg)    # injected duplicate
        await self._send_message_once(msg)

    async def _send_message_once(self, msg: Message) -> None:
        async with self._send_lock:
            sess = self.session
            if sess is not None:
                sess.out_seq += 1
                seq = sess.out_seq
            else:
                self.out_seq += 1
                seq = self.out_seq
            msg.seq = seq
            with tracing.section("msg.encode", msg, self.msgr.tracer,
                                 self.msgr.name) as sec:
                body = msg.encode_segments()
                if sec:
                    sec.tag("type", type(msg).__name__).tag(
                        "bytes", sum(map(len, body[0])))
            if not self.policy.lossy:
                (sess.unacked if sess is not None
                 else self.unacked).append((seq, body))
            try:
                await self._maybe_rekey()
                self._tx_frames += 1
                await self._send_frame(TAG_MSG, seq, body, msg)
            except ConnectionError_:
                if self.policy.lossy or sess is None:
                    raise
                await self.msgr._reconnect_and_replay(self.peer_addr,
                                                      self.peer_name)

    async def _ack(self, seq: int) -> None:
        # under _send_lock: in secure mode the reader task's ACKs must
        # serialize with send_message's rekey cutover, or an ACK sealed
        # under the old epoch can hit the wire AFTER the REKEY frame
        # and fail decryption on a peer that already flipped rx_epoch
        async with self._send_lock:
            await self._send_frame(TAG_ACK, seq)

    def _handle_ack(self, seq: int) -> None:
        if self.session is not None:
            self.session.unacked = [
                (s, b) for s, b in self.session.unacked if s > seq]
        else:
            self.unacked = [(s, b) for s, b in self.unacked if s > seq]

    def _abort(self) -> None:
        self.closed = True
        try:
            self.writer.close()
        except Exception:
            pass

    async def force_rekey(self) -> None:
        """Rotate this connection's tx frame key NOW (the AuthMonitor
        rotation hook): announce epoch+1 under the old key, then
        switch — exactly Connection._maybe_rekey without the frame-
        count gate. No-op outside secure mode (crc frames carry no
        key)."""
        if not self._secure() or self.closed:
            return
        async with self._send_lock:
            new_epoch = self._tx_epoch + 1
            body, secret = self._rekey_material(new_epoch)
            try:
                await self._send_frame(TAG_REKEY, 0, ((body,), 0))
            except ConnectionError_:
                return               # dead conn: nothing left to rekey
            if secret is not None:
                self.auth.install_secret(0 if self.is_client else 1,
                                         secret, new_epoch)
            self._tx_epoch = new_epoch
            self._tx_frames = 0

    async def close(self) -> None:
        self._abort()
        if self._reader_task:
            self._reader_task.cancel()


class Dispatcher:
    """ref: src/msg/Dispatcher.h — implement in daemons."""

    async def ms_dispatch(self, msg: Message) -> bool:
        raise NotImplementedError

    async def ms_handle_reset(self, conn: Connection) -> None:
        pass


class Messenger:
    """ref: Messenger::create + AsyncMessenger. One per daemon."""

    def __init__(self, name: str, keyring: Keyring | None = None,
                 mode: int = MODE_CRC,
                 default_policy: Policy | None = None,
                 inject_socket_failures: int = 0,
                 max_frame: int = 64 << 20,
                 seed: int | None = None,
                 rekey_frames: int = 4096):
        self.name = name                  # entity name, e.g. "osd.3"
        self.keyring = keyring
        if mode == MODE_SECURE and keyring is None:
            raise ValueError("secure mode requires a keyring "
                             "(frame MACs need a session key)")
        self.mode = mode
        # secure mode: rotate each connection's tx key after this many
        # frames (0 = never); see Connection._maybe_rekey
        self.rekey_frames = rekey_frames
        self.handshake_timeout = 5.0
        self.policy = default_policy or Policy()
        self.peer_policies: dict[str, Policy] = {}  # entity type -> policy
        self.max_frame = max_frame
        self.inject_socket_failures = inject_socket_failures
        # richer per-peer-pair fault table (sim/faults.FaultInjector):
        # partitions/drops/delays/dup/reorder, installed at runtime
        self.faults = None
        # the owning daemon's utils.tracing.Tracer (it sets it): where
        # the msg.* sections of sampled ops are kept; without one they
        # exist only while a profiler session captures
        self.tracer = None
        self._rng = random.Random(seed)
        # instance nonce: distinguishes this daemon incarnation so peers
        # reset replay-dedup state after a restart (ref: entity_addr_t
        # nonce + ProtocolV2 session cookies)
        self.session_id = random.SystemRandom().getrandbits(63)
        # lossless replay dedup survives TCP reconnects: peer name ->
        # [peer session_id, last delivered seq]
        self._peer_in_seq: dict[str, list[int]] = {}
        self.dispatchers: list[Dispatcher] = []
        self.conns: dict[EntityAddr, Connection] = {}
        # peer name -> live connections: the 10k-session fix for the
        # connection-table scans key events used to do (key_rotated/
        # key_revoked iterated EVERY connection per event — O(sessions)
        # per auth change). Maintained at attach/accept/close.
        self._by_peer: dict[str, set[Connection]] = {}
        self._sessions: dict[EntityAddr, _Session] = {}
        self._conn_locks: dict[EntityAddr, asyncio.Lock] = {}
        self._server: asyncio.AbstractServer | None = None
        self.addr: EntityAddr | None = None
        self.throttle: Throttle | None = None
        self._accepted: set[Connection] = set()
        # AuthMonitor lifecycle: a live keyring notifies us on
        # rotation (re-key live sessions) and revocation (fence)
        if keyring is not None:
            keyring.add_observer(self)

    # -- setup -------------------------------------------------------------
    def add_dispatcher(self, d: Dispatcher) -> None:
        self.dispatchers.append(d)

    def set_policy(self, entity_type: str, policy: Policy) -> None:
        """Per-peer-type policy (ref: Messenger::set_policy)."""
        self.peer_policies[entity_type] = policy

    def _policy_for(self, peer_name: str) -> Policy:
        etype = peer_name.split(".", 1)[0]
        return self.peer_policies.get(etype, self.policy)

    def _restore_in_seq(self, conn: Connection) -> None:
        """Lossless replay dedup across TCP reconnects: the same peer
        incarnation resumes at its last delivered seq; a restarted peer
        (new session id) starts fresh."""
        if conn.policy.lossy:
            return
        state = self._peer_in_seq.get(conn.peer_name)
        if state is None or state[0] != conn.peer_session:
            state = [conn.peer_session, 0]
            self._peer_in_seq[conn.peer_name] = state
        conn.in_seq = state[1]

    def _banner_flags(self) -> int:
        return (1 if self.keyring is not None else 0) | \
            (2 if self.mode == MODE_SECURE else 0)

    def _inject_failure(self) -> bool:
        n = self.inject_socket_failures
        return bool(n) and self._rng.randrange(n) == 0

    # -- key lifecycle (Keyring observer; ref: cephx ticket rotation /
    # session killing on auth removal) ------------------------------------
    def _index_conn(self, conn: Connection) -> None:
        self._by_peer.setdefault(conn.peer_name, set()).add(conn)

    def _unindex_conn(self, conn: Connection) -> None:
        peers = self._by_peer.get(conn.peer_name)
        if peers is not None:
            peers.discard(conn)
            if not peers:
                self._by_peer.pop(conn.peer_name, None)

    def _conns_of(self, name: str) -> list[Connection]:
        return [c for c in self._by_peer.get(name, ())
                if not c.closed]

    def key_rotated(self, name: str) -> None:
        """The entity's secret changed: bump the frame-key epoch on its
        live sessions (in-band REKEY; new handshakes pick up the new
        secret from the keyring automatically). Rotating OUR OWN key
        re-keys every connection we originate."""
        conns = list(self.conns.values()) + list(self._accepted) \
            if name == self.name else self._conns_of(name)
        for conn in conns:
            asyncio.ensure_future(conn.force_rekey())

    def key_revoked(self, name: str) -> None:
        """The entity's key is GONE: fence it — drop its open sessions
        and their replay state. Handshakes for it now fail at the
        keyring lookup, so the entity cannot come back until a new key
        is provisioned. Our own key revoked = we are fenced: every
        session drops."""
        if name == self.name:
            victims = list(self.conns.items()) + \
                [(None, c) for c in self._accepted]
        else:
            victims = [(a, c) for a, c in self.conns.items()
                       if c.peer_name == name] + \
                [(None, c) for c in self._accepted
                 if c.peer_name == name]
        for addr, conn in victims:
            if addr is not None:
                self.conns.pop(addr, None)
                self._sessions.pop(addr, None)
            asyncio.ensure_future(conn.close())
        if name != self.name:
            self._peer_in_seq.pop(name, None)

    async def bind(self, host: str = "127.0.0.1",
                   port: int = 0) -> EntityAddr:
        self._server = await asyncio.get_running_loop().create_server(
            lambda: _Wire(self._accept), host, port)
        sock = self._server.sockets[0]
        self.addr = EntityAddr(*sock.getsockname()[:2])
        if self.policy.throttler_bytes:
            self.throttle = Throttle(self.policy.throttler_bytes)
        return self.addr

    # -- handshake ---------------------------------------------------------
    async def _accept(self, wire: _Wire) -> None:
        try:
            conn = await asyncio.wait_for(
                self._server_handshake(wire, wire),
                timeout=self.handshake_timeout)
        except (AuthError, ConnectionError_, ConnectionError, OSError,
                asyncio.TimeoutError) as e:
            log.dout(5, f"accept failed: {e}")
            wire.close()
            return
        self._accepted.add(conn)
        self._index_conn(conn)
        conn._reader_task = asyncio.ensure_future(self._reader_loop(conn))

    async def _server_handshake(self, reader, writer) -> Connection:
        # banner carries auth+mode flags so a mismatch fails fast
        # instead of deadlocking/desyncing mid-stream
        writer.write(BANNER + bytes([self._banner_flags()]))
        await writer.drain()
        if await reader.readexactly(len(BANNER)) != BANNER:
            raise ConnectionError_("bad banner")
        peer_flags = (await reader.readexactly(1))[0]
        if peer_flags != self._banner_flags():
            raise AuthError("auth/mode mismatch with peer")
        # client hello: name + session id + nonce
        nlen = int.from_bytes(await reader.readexactly(2), "little")
        peer_name = (await reader.readexactly(nlen)).decode()
        peer_session = int.from_bytes(await reader.readexactly(8), "little")
        client_nonce = await reader.readexactly(16)
        auth = None
        if self.keyring is not None:
            auth = Authenticator(self.name, self.keyring.get(peer_name))
            # send our nonce + server proof
            proof = auth.server_respond(client_nonce)
            writer.write(auth.nonce + proof)
            await writer.drain()
            client_proof = await reader.readexactly(32)
            auth.verify_client(client_nonce, client_proof)
            writer.write(b"OK")
        else:
            writer.write(b"NA")
        await writer.drain()
        conn = Connection(self, reader, writer, peer_name, None, auth,
                          self._policy_for(peer_name),
                          peer_session=peer_session)
        self._restore_in_seq(conn)
        return conn

    async def _client_handshake(self, addr: EntityAddr,
                                peer_name: str) -> Connection:
        if self.faults is not None and \
                self.faults.blocks_connect(self.name, peer_name):
            # partitioned pair: the SYN never lands
            raise ConnectionError_(
                f"injected partition: {self.name} -> {peer_name}")
        _, wire = await asyncio.get_running_loop().create_connection(
            _Wire, addr.host, addr.port)
        try:
            return await asyncio.wait_for(
                self._client_handshake_inner(wire, wire, addr,
                                             peer_name),
                timeout=self.handshake_timeout)
        except BaseException:
            wire.close()
            raise

    async def _client_handshake_inner(self, reader, writer,
                                      addr: EntityAddr,
                                      peer_name: str) -> Connection:
        if await reader.readexactly(len(BANNER)) != BANNER:
            raise ConnectionError_("bad banner")
        peer_flags = (await reader.readexactly(1))[0]
        if peer_flags != self._banner_flags():
            raise AuthError("auth/mode mismatch with peer")
        writer.write(BANNER + bytes([self._banner_flags()]))
        name_b = self.name.encode()
        hello = len(name_b).to_bytes(2, "little") + name_b + \
            self.session_id.to_bytes(8, "little")
        auth = None
        if self.keyring is not None:
            auth = Authenticator(self.name, self.keyring.get(self.name))
            writer.write(hello + auth.nonce)
            await writer.drain()
            server_nonce = await reader.readexactly(16)
            server_proof = await reader.readexactly(32)
            auth.verify_server(server_nonce, server_proof)
            writer.write(auth.client_prove(server_nonce))
            await writer.drain()
        else:
            writer.write(hello + b"\x00" * 16)
            await writer.drain()
        status = await reader.readexactly(2)
        if status not in (b"OK", b"NA"):
            raise AuthError("handshake rejected")
        return Connection(self, reader, writer, peer_name, addr, auth,
                          self._policy_for(peer_name))

    # -- connection table --------------------------------------------------
    def _attach(self, addr: EntityAddr, conn: Connection) -> None:
        if not conn.policy.lossy:
            conn.session = self._sessions.setdefault(addr, _Session())
        self.conns[addr] = conn
        self._index_conn(conn)
        conn._reader_task = asyncio.ensure_future(self._reader_loop(conn))

    async def connect(self, addr: EntityAddr,
                      peer_name: str = "?") -> Connection:
        conn = self.conns.get(addr)
        if conn is not None and not conn.closed:
            return conn
        lock = self._conn_locks.setdefault(addr, asyncio.Lock())
        async with lock:
            conn = self.conns.get(addr)
            if conn is not None and not conn.closed:
                return conn
            if conn is not None and not conn.policy.lossy:
                # the logical session (seq + unacked) outlives sockets:
                # resume it so the peer's dedup state stays coherent
                await self._reconnect_locked(addr, conn.peer_name)
                return self.conns[addr]
            conn = await self._client_handshake(addr, peer_name)
            self._attach(addr, conn)
            return conn

    async def send_message(self, msg: Message, addr: EntityAddr,
                           peer_name: str = "?") -> None:
        conn = await self.connect(addr, peer_name)
        await conn.send_message(msg)

    async def _reconnect_and_replay(self, addr: EntityAddr,
                                    peer_name: str) -> None:
        lock = self._conn_locks.setdefault(addr, asyncio.Lock())
        async with lock:
            await self._reconnect_locked(addr, peer_name)

    async def _reconnect_locked(self, addr: EntityAddr,
                                peer_name: str) -> None:
        """Lossless reconnect: fresh socket, same session; replay the
        session's unacked queue in order (ref: ProtocolV2 session
        reconnect + out_queue replay). Acks prune the queue between
        attempts, so retries shrink under fault injection."""
        sess = self._sessions.setdefault(addr, _Session())
        for attempt in range(40):
            conn = self.conns.get(addr)
            if conn is None or conn.closed:
                try:
                    conn = await self._client_handshake(addr, peer_name)
                except (ConnectionError_, ConnectionError, OSError):
                    await asyncio.sleep(0.05 * (attempt + 1))
                    continue
                self._attach(addr, conn)
            try:
                # under the connection's send lock: replay on a LIVE
                # conn must serialize with send_message's secure-mode
                # rekey cutover (same reasoning as Connection._ack), or
                # a replayed frame sealed under the old epoch can land
                # after the REKEY frame and kill the session
                async with conn._send_lock:
                    for seq, body in list(sess.unacked):
                        await conn._send_frame(TAG_MSG, seq, body)
                return
            except ConnectionError_:
                continue
        raise ConnectionError_(
            f"reconnect to {addr} failed after retries")

    # -- dispatch ----------------------------------------------------------
    async def _reader_loop(self, conn: Connection) -> None:
        try:
            await self._reader_loop_inner(conn)
        finally:
            self._accepted.discard(conn)
            self._unindex_conn(conn)

    async def _reader_loop_inner(self, conn: Connection) -> None:
        while not conn.closed:
            try:
                tag, seq, body = await conn._recv_frame()
                rx_t0, rx_t1 = conn._rx_t0, conn._rx_t1
            except asyncio.CancelledError:
                return
            except Exception:           # ConnectionError_ or corrupt peer
                conn._abort()
                for d in self.dispatchers:
                    await d.ms_handle_reset(conn)
                return
            if tag == TAG_ACK:
                conn._handle_ack(seq)
                if tracing.capturing():   # no message: nobody's op
                    tracing.emit_section(
                        "msg.recv", rx_t0, _clock(), None,
                        self.tracer, self.name, {"type": "ack"})
                continue
            if tag == TAG_KEEPALIVE:
                continue
            if tag == TAG_REKEY:
                epoch = int.from_bytes(body[:4], "little")
                if conn._secure() and len(body) >= 36:
                    # session-ticket re-auth (round 18): the announcer
                    # must prove it holds the entity's CURRENT secret
                    # per OUR keyring. Mismatch = rotation skew or a
                    # revoked key — fence; the reconnect path runs
                    # full mutual auth against whatever keys then hold
                    entity = self.name if conn.is_client \
                        else conn.peer_name
                    secret = None
                    try:
                        secret = self.keyring.get(entity) \
                            if self.keyring is not None else None
                    except Exception:
                        secret = None
                    ok = secret is not None and hmac.compare_digest(
                        conn.auth.rekey_ticket(secret, epoch),
                        bytes(body[4:36]))
                    if not ok:
                        log.dout(1, f"rekey ticket from "
                                    f"{conn.peer_name} failed "
                                    f"verification: fencing session")
                        conn._abort()
                        for d in self.dispatchers:
                            await d.ms_handle_reset(conn)
                        return
                    conn.auth.install_secret(
                        1 if conn.is_client else 0, secret, epoch)
                conn._rx_epoch = epoch
                continue
            if not conn.policy.lossy:
                # ack even duplicates so a replaying peer can prune
                try:
                    await conn._ack(seq)
                except ConnectionError_:
                    pass
            if seq <= conn.in_seq:
                continue        # duplicate after replay
            conn.in_seq = seq
            if not conn.policy.lossy:
                state = self._peer_in_seq.get(conn.peer_name)
                if state is not None and state[0] == conn.peer_session:
                    state[1] = seq
            t_dec = _clock()
            try:
                msg = Message.decode(body)
            except Exception as e:
                log.dout(1, f"undecodable message from {conn.peer_name}: {e}")
                continue
            if tracing.wanted(msg, self.tracer):
                # the frame was checked and decoded before the message
                # could say whose op it is: both sections after the fact
                tags = {"type": type(msg).__name__, "bytes": len(body)}
                tracing.emit_section("msg.recv", rx_t0, rx_t1, msg,
                                     self.tracer, self.name, tags)
                tracing.emit_section("msg.decode", t_dec, _clock(), msg,
                                     self.tracer, self.name, tags)
            msg.src = conn.peer_name
            msg.conn = conn
            if self.throttle:
                await self.throttle.acquire(len(body))
            try:
                handled = False
                for d in self.dispatchers:
                    if await d.ms_dispatch(msg):
                        handled = True
                        break
                if not handled:
                    log.dout(10, f"unhandled {msg!r} from {conn.peer_name}")
            except Exception:
                log.error(f"dispatch of {type(msg).__name__} failed: "
                          f"{traceback.format_exc()}")
            finally:
                if self.throttle:
                    await self.throttle.release(len(body))

    # -- teardown ----------------------------------------------------------
    async def shutdown(self) -> None:
        if self.keyring is not None:
            self.keyring.remove_observer(self)
        if self._server:
            self._server.close()           # stop accepting first
        for conn in list(self.conns.values()) + list(self._accepted):
            await conn.close()
        self.conns.clear()
        self._accepted.clear()
        if self._server:
            # Python 3.12 wait_closed blocks until every handler's
            # transport is gone; bound it — sockets are already closed
            try:
                await asyncio.wait_for(self._server.wait_closed(),
                                       timeout=0.5)
            except Exception:
                pass
