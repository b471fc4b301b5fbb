"""Plain reference for the erasure code: jerasure's ``reed_sol_van`` over
GF(2^8) (polynomial 0x11d), in numpy with no table of the program's.

``coding_matrix(k, m)`` follows reed_sol.c: the extended Vandermonde
matrix of k+m rows, brought by column operations to the form whose top
k rows are the identity, then scaled so that the first coding row and
the first column of the coding rows are all ones. ``shards`` cuts an
object into stripes of k chunks of ``stripe_unit`` bytes the way
ECUtil's stripe_info_t does and returns the k+m shard byte strings an
OSD keeps; ``reconstruct`` gives a lost data shard back from k others.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.lru_cache(maxsize=None)
def _log_exp():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    v = 1
    for i in range(255):
        exp[i] = v
        log[v] = i
        v <<= 1
        if v & 0x100:
            v ^= POLY
    exp[255:510] = exp[:255]
    return log, exp


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    log, exp = _log_exp()
    return int(exp[log[a] + log[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    log, exp = _log_exp()
    return int(exp[255 - log[a]])


@functools.lru_cache(maxsize=None)
def _mul_table() -> np.ndarray:
    """256 x 256 products, so that c * bytes is one take."""
    log, exp = _log_exp()
    t = exp[log[:, None] + log[None, :]].astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    return t


def _extended_vandermonde(rows: int, cols: int) -> list[list[int]]:
    v = [[0] * cols for _ in range(rows)]
    v[0][0] = 1
    if rows == 1:
        return v
    v[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(cols):
            v[i][j] = acc
            acc = gf_mul(acc, i)
    return v


@functools.lru_cache(maxsize=None)
def coding_matrix(k: int, m: int) -> np.ndarray:
    """reed_sol_vandermonde_coding_matrix(k, m, 8) -> (m, k) uint8."""
    rows, cols = k + m, k
    d = _extended_vandermonde(rows, cols)
    for i in range(1, cols):
        # a row at or below i with a non-zero in column i goes to row i
        j = next(r for r in range(i, rows) if d[r][i])
        if j != i:
            d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:                      # scale column i
            inv = gf_inv(d[i][i])
            for r in range(rows):
                d[r][i] = gf_mul(d[r][i], inv)
        for j in range(cols):                 # clear the rest of row i
            e = d[i][j]
            if j != i and e:
                for r in range(rows):
                    d[r][j] ^= gf_mul(e, d[r][i])
    for j in range(cols):                     # first coding row: ones
        e = d[cols][j]
        if e != 1:
            inv = gf_inv(e)
            for r in range(cols, rows):
                d[r][j] = gf_mul(d[r][j], inv)
    for r in range(cols + 1, rows):           # first column: ones
        e = d[r][0]
        if e != 1:
            inv = gf_inv(e)
            for j in range(cols):
                d[r][j] = gf_mul(d[r][j], inv)
    for i in range(cols):
        if d[i] != [int(i == j) for j in range(cols)]:
            raise AssertionError("the top of the matrix is not the identity")
    return np.array(d[cols:], dtype=np.uint8)


def _combine(coeffs, rows: np.ndarray) -> np.ndarray:
    """sum_j coeffs[j] * rows[j] over GF(2^8), bytewise."""
    table = _mul_table()
    acc = np.zeros(rows.shape[1], dtype=np.uint8)
    for c, row in zip(coeffs, rows):
        if c:
            acc ^= table[int(c)][row]
    return acc


def data_shards(payload: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """(k, shard_len) uint8: chunk j of every stripe, in stripe order.
    The last stripe is padded with zeros to its full width."""
    width = k * stripe_unit
    buf = np.frombuffer(payload, dtype=np.uint8)
    pad = -len(buf) % width
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, dtype=np.uint8)])
    return np.ascontiguousarray(
        buf.reshape(-1, k, stripe_unit).transpose(1, 0, 2)
    ).reshape(k, -1)


def shards(payload: bytes, k: int, m: int, stripe_unit: int) -> list[bytes]:
    """The k+m shards of ``payload`` as the pool's OSDs store them."""
    data = data_shards(payload, k, stripe_unit)
    mat = coding_matrix(k, m)
    return [row.tobytes() for row in data] + \
        [_combine(mat[i], data).tobytes() for i in range(m)]


def _invert(mat: list[list[int]]) -> list[list[int]]:
    n = len(mat)
    a = [row[:] + [int(i == j) for j in range(n)]
         for i, row in enumerate(mat)]
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r][col])
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(v, inv) for v in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                e = a[r][col]
                a[r] = [v ^ gf_mul(e, p) for v, p in zip(a[r], a[col])]
    return [row[n:] for row in a]


def reconstruct(have: dict[int, bytes], want: int, k: int, m: int) -> bytes:
    """Shard ``want`` from any k of the others (``have``: position ->
    bytes), by inverting the rows of the generator that are in hand."""
    ids = sorted(have)[:k]
    gen = [[int(i == j) for j in range(k)] for i in range(k)] + \
        coding_matrix(k, m).tolist()
    inv = _invert([gen[i] for i in ids])
    rows = np.stack([np.frombuffer(have[i], dtype=np.uint8) for i in ids])
    if want < k:
        coeffs = inv[want]
    else:
        coeffs = [0] * k
        for j in range(k):
            for t in range(k):
                coeffs[t] ^= gf_mul(gen[want][j], inv[j][t])
    return _combine(coeffs, rows).tobytes()


def assemble(data: list[bytes], k: int, stripe_unit: int, size: int) -> bytes:
    """The object's bytes back from its k data shards."""
    arr = np.stack([np.frombuffer(d, dtype=np.uint8) for d in data])
    out = arr.reshape(k, -1, stripe_unit).transpose(1, 0, 2).reshape(-1)
    return out[:size].tobytes()
