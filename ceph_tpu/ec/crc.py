"""CRC32 as GF(2) linear algebra — the fused checksum+encode plan.

The EC write path stamps every shard with a write-time ``_hcrc``
(zlib.crc32 of the shard bytes; the hinfo analog scrub-repair uses to
LOCATE a corrupt shard). Historically that was three separate host-side
``zlib.crc32`` sites in ``osd/ec_pg.py``; this module lets the checksum
ride the SAME device program as the encode, so checksum+encode is one
kernel launch per stripe batch.

The decomposition (all facts pinned by tests/test_ec_agg.py):

- ``raw(m) = zlib.crc32(m, 0xffffffff) ^ 0xffffffff`` is the init-free
  CRC state machine. It is **linear over GF(2)** in the message bits
  (``raw(a ^ b) = raw(a) ^ raw(b)`` for equal lengths), and
  ``zlib.crc32(m) = raw(m) ^ zlib.crc32(b"\\0" * len(m))`` — the
  init/final-xor affine part depends only on the length.
- For a fixed row length C, ``raw`` of one row is a (32 x 8C) GF(2)
  matrix ``G_C`` applied to the row's bits: ON DEVICE this is one int8
  matmul per stripe batch (``(rows, 8C) @ (8C, 32) mod 2``), landing on
  the MXU right next to the encode matmul — the fused pass emits a
  uint32 row-CRC per shard row of the batch (data AND parity rows).
- Rows concatenate through the fixed 32x32 "append C zero bytes"
  operator ``M_C``: ``raw(A || B) = M_C(raw(A)) ^ raw(B)``, and
  ``M_2C = M_C o M_C``. The fold over a write's ``count`` rows is
  pairwise: log2(count) levels of a few 32-bit host ops on the
  device-produced row CRCs, vectorized across rows and shards — the
  O(bytes) work stays on device, in the encode program.

Everything here is host-side plan construction (numpy + zlib), cached
per chunk size, exactly like the bit-matrix expansion in gf/tables.py.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

_M32 = 0xFFFFFFFF


def raw_crc(data: bytes, state: int = 0) -> int:
    """The init-free CRC32 state machine (zlib pre/post-inverts
    internally; this peels that off). Linear over GF(2) in the message
    bits at state 0; composes: ``raw(a + b) = raw(b, raw(a))``."""
    return zlib.crc32(data, state ^ _M32) ^ _M32


@functools.lru_cache(maxsize=1)
def _byte_table() -> np.ndarray:
    """(256,) uint64: raw CRC of each single-byte message."""
    return np.array([raw_crc(bytes([x])) for x in range(256)],
                    dtype=np.uint64)


def _zero_byte_update(state: np.ndarray) -> np.ndarray:
    """Advance raw CRC state(s) by one zero message byte (vectorized)."""
    t = _byte_table()
    s = np.asarray(state, dtype=np.uint64)
    return (s >> np.uint64(8)) ^ t[(s & np.uint64(0xFF)).astype(np.int64)]


@functools.lru_cache(maxsize=8)
def row_crc_matrix(chunk_size: int) -> np.ndarray:
    """(8C, 32) int8 GF(2) matrix: bits of a C-byte row (LSB-first per
    byte, matching gf.ops.unpack_bits) -> bits of the row's raw CRC.

    Row 8p+b is the 32-bit contribution of byte position p, bit b —
    built by walking the single-byte table backward through the
    zero-byte-append operator (position p is followed by C-1-p zero
    bytes in the row's state machine)."""
    C = int(chunk_size)
    contrib = np.zeros((C, 8), dtype=np.uint64)
    contrib[C - 1] = _byte_table()[[1 << b for b in range(8)]]
    for p in range(C - 2, -1, -1):
        contrib[p] = _zero_byte_update(contrib[p + 1])
    bits = (contrib[:, :, None] >> np.arange(32, dtype=np.uint64)) \
        & np.uint64(1)
    return bits.reshape(8 * C, 32).astype(np.int8)


_DEVICE_ROW_CRC_CACHE: dict[int, object] = {}


def device_row_crcs(rows: np.ndarray) -> np.ndarray:
    """ONE batched device CRC job: (R, C) uint8 rows -> (R,) uint32
    raw row CRCs.

    The standalone twin of the fused encode+crc pass — same 8-bit-plane
    GF(2) matmul against ``row_crc_matrix(C)`` (plane b multiplies
    ``G[b::8]``), jitted once per chunk size and accounted through
    devmon as ``scrub_crc``. Deep scrub uses it to turn a whole
    chunk-map sweep's per-object ``zlib.crc32`` calls into O(batches)
    device launches; the per-shard fold back to zlib-equal values is
    :func:`shard_crc32` (O(rows) host work)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.utils.devmon import devmon as _devmon

    arr = np.ascontiguousarray(rows, dtype=np.uint8)
    if arr.ndim != 2:
        raise ValueError("device_row_crcs wants a (rows, C) batch")
    C = int(arr.shape[1])
    # pow2-pad the row axis (same discipline as the EC aggregators):
    # scrub batches arrive at arbitrary per-PG row counts, and an
    # unpadded launch would compile one program per count — padding
    # bounds the jit cache at O(log max_rows) shapes per chunk size
    R = int(arr.shape[0])
    padded = 1 << (R - 1).bit_length() if R > 1 else 1
    if padded != R:
        arr = np.concatenate(
            [arr, np.zeros((padded - R, C), dtype=np.uint8)])
    fn = _DEVICE_ROW_CRC_CACHE.get(C)
    if fn is None:
        G = jnp.asarray(row_crc_matrix(C))                # (8C, 32) i8

        def _kern(d):
            # bit-plane at a time keeps the matmul operand at
            # batch-bytes size (the naive 8C bit expansion is 8x)
            acc = jnp.zeros((d.shape[0], 32), dtype=jnp.int32)
            for b in range(8):
                plane = ((d >> jnp.uint8(b)) &
                         jnp.uint8(1)).astype(jnp.int8)
                acc = acc + jnp.matmul(
                    plane, G[b::8, :],
                    preferred_element_type=jnp.int32)
            bit32 = (acc & 1).astype(jnp.uint32)
            weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
            return jnp.sum(bit32 * weights[None, :], axis=1,
                           dtype=jnp.uint32)

        fn = _DEVICE_ROW_CRC_CACHE[C] = jax.jit(_kern)
    out = _devmon().jit_call("scrub_crc", (C, tuple(arr.shape)),
                             fn, arr)
    return np.asarray(out)[:R]


def _apply_cols(cols: np.ndarray, state) -> np.ndarray:
    """Apply a 32x32 GF(2) operator (given as its 32 basis-column
    images) to every state of an array (or to one int)."""
    s = np.asarray(state, dtype=np.uint64)
    j = np.arange(32, dtype=np.uint64)
    bits = ((s[..., None] >> j) & np.uint64(1)).astype(bool)
    return np.bitwise_xor.reduce(
        np.where(bits, cols, np.uint64(0)), axis=-1)


@functools.lru_cache(maxsize=128)
def _shift_columns(length: int) -> np.ndarray:
    """(32,) uint32-valued columns of M_length, the 'append ``length``
    zero bytes' operator on raw CRC states: column j = M_length applied
    to basis 2^j. Square-and-multiply (ref: crc32_combine): an even
    length is the square of its half, an odd one a zero byte more."""
    if length == 0:
        return np.array([1 << j for j in range(32)], dtype=np.uint64)
    if length % 2:
        return _zero_byte_update(_shift_columns(length - 1))
    half = _shift_columns(length // 2)
    return _apply_cols(half, half)


def combine_row_crcs(row_crcs: np.ndarray, chunk_size: int) -> np.ndarray:
    """Fold per-row raw CRCs into per-shard raw CRCs.

    ``row_crcs``: (..., count) uint32 — count C-byte rows per shard, in
    concatenation order. Returns (...) uint64-valued raw CRC of each
    shard's count*C bytes. A pairwise fold, log2(count) levels of a few
    vectorized 32-bit host ops each (``raw(A || B) = M_len(B)(raw(A)) ^
    raw(B)``, neighbours of equal length at every level) — the O(bytes)
    part already ran on device."""
    rc = np.asarray(row_crcs, dtype=np.uint64)
    count = rc.shape[-1]
    if count == 0:
        return np.zeros(rc.shape[:-1], dtype=np.uint64)
    lead = (1 << (count - 1).bit_length()) - count
    if lead:
        # leading zero rows leave a raw CRC as it is (state 0 stays 0)
        rc = np.concatenate(
            [np.zeros(rc.shape[:-1] + (lead,), dtype=np.uint64), rc],
            axis=-1)
    block = int(chunk_size)         # bytes a state stands for
    while rc.shape[-1] > 1:
        rc = _apply_cols(_shift_columns(block), rc[..., 0::2]) \
            ^ rc[..., 1::2]
        block *= 2
    return rc[..., 0]


@functools.lru_cache(maxsize=64)
def _zero_crc(length: int) -> int:
    """zlib.crc32 of `length` zero bytes — the affine (init/final-xor)
    part of the checksum, a function of the length alone. O(log length)
    through :func:`_shift_columns` — materializing a length-sized zero
    buffer here would re-introduce the O(bytes) host work the fused
    path exists to offload."""
    # the pre-inverted init register, run through `length` zero bytes
    return int(_apply_cols(_shift_columns(int(length)), _M32)) ^ _M32


def shard_crc32(row_crcs: np.ndarray, chunk_size: int) -> np.ndarray:
    """Device-produced row CRCs -> zlib.crc32-equal per-shard values.

    ``row_crcs``: (..., count) uint32 from the fused pass. Returns
    (...) values equal to ``zlib.crc32`` of each shard's bytes."""
    rc = np.asarray(row_crcs, dtype=np.uint64)
    lin = combine_row_crcs(rc, chunk_size)
    return lin ^ np.uint64(_zero_crc(rc.shape[-1] * int(chunk_size)))


def hcrc_attrs(shards, row_crcs=None,
               chunk_size: int | None = None) -> list[bytes]:
    """The ONE producer of the ``_hcrc`` shard attribute (4 bytes LE),
    for all of a write's shards at once.

    Consumes the fused kernel's per-row CRC output when the caller has
    one (``row_crcs``: (len(shards), count) uint32, a row per shard,
    ``chunk_size`` required: one fold for all of them), and falls back
    to host-side ``zlib.crc32`` of each shard's bytes otherwise — both
    producers are pinned byte-for-byte equal by test."""
    if row_crcs is not None:
        if not chunk_size:
            raise ValueError(
                "row_crcs needs the chunk size to combine")
        vals = shard_crc32(np.asarray(row_crcs), chunk_size)
    else:
        vals = [zlib.crc32(s) for s in shards]
    return [int(v).to_bytes(4, "little") for v in vals]


def hcrc_attr(shard_bytes: bytes, row_crcs=None,
              chunk_size: int | None = None) -> bytes:
    """One shard's ``_hcrc``: :func:`hcrc_attrs` of a single shard
    (``row_crcs``: (count,) uint32 for this shard)."""
    return hcrc_attrs(
        [shard_bytes],
        None if row_crcs is None else np.asarray(row_crcs)[None, :],
        chunk_size)[0]
