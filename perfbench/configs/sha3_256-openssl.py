"""Client and plain reference of ``sha3_256-openssl``.

Requests are SHA3-256 digests of random messages; the reference is
``hashlib``.  The control is the reference with the one guarantee the
configuration states broken: FIPS 202's domain suffix.  It hashes with
the pre-standard Keccak padding (suffix ``0x01`` in place of ``0x06``),
computed by the plain numpy Keccak-f[1600] below.
"""

from __future__ import annotations

import hashlib

import numpy as np

RATE_BYTES = 136


class Client:
    op = "sha3_256"

    def __init__(self, config: dict, seed: int, rng):
        self.config = config
        r = rng(seed, "messages")
        self.longest = max(config["message_bytes"])
        self.pool = r.bytes((1 << 20) + self.longest)

    def engine_options(self) -> dict:
        return {}

    def message(self, index: int, size: int) -> bytes:
        span = len(self.pool) - self.longest
        off = (index * 2654435761) % span
        return self.pool[off:off + size]

    def payload(self, index: int, size: int) -> bytes:
        return self.message(index, size)

    def geometry(self, size: int) -> dict:
        return {"message_bytes": size}

    def expected(self, items) -> list:
        return [hashlib.sha3_256(self.message(i, n)).digest()
                for i, n in items]

    def control(self, items) -> list:
        return keccak_256([self.message(i, n) for i, n in items],
                          domain=0x01)


# ---------------------------------------------------------------------------
# Plain Keccak, 64-bit lanes in numpy, vectorised over messages
# ---------------------------------------------------------------------------

def _round_constants() -> list:
    out, lfsr = [], 1
    for _ in range(24):
        rc = 0
        for j in range(7):
            if lfsr & 1:
                rc |= 1 << ((1 << j) - 1)
            lfsr = ((lfsr << 1) ^ 0x71) & 0xFF if lfsr & 0x80 else lfsr << 1
        out.append(rc)
    return out


def _rotations() -> dict:
    rot, x, y = {(0, 0): 0}, 1, 0
    for t in range(24):
        rot[(x, y)] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    return rot


RC = [np.uint64(c) for c in _round_constants()]
ROT = _rotations()


def _rotl(v: np.ndarray, r: int) -> np.ndarray:
    if r == 0:
        return v
    return (v << np.uint64(r)) | (v >> np.uint64(64 - r))


def keccak_f1600(a: list) -> list:
    """One permutation of B states, ``a[x + 5 y]`` a (B,) uint64 lane."""
    for rc in RC:
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        b = [None] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl(
                    a[x + 5 * y] ^ d[x], ROT[(x, y)])
        a = [b[x + 5 * y] ^ (~b[(x + 1) % 5 + 5 * y]
                             & b[(x + 2) % 5 + 5 * y])
             for y in range(5) for x in range(5)]
        a[0] = a[0] ^ rc
    return a


def _pad(message: bytes, domain: int) -> bytes:
    n = RATE_BYTES - len(message) % RATE_BYTES
    tail = bytearray(n)
    tail[0] ^= domain
    tail[-1] ^= 0x80
    return message + bytes(tail)


def keccak_256(messages: list, *, domain: int) -> list:
    """256-bit digests at rate 136 with padding suffix ``domain``
    (``0x06`` is SHA3-256, ``0x01`` the pre-standard Keccak-256)."""
    out = [None] * len(messages)
    by_len: dict = {}
    for i, m in enumerate(messages):
        by_len.setdefault(len(_pad(m, domain)), []).append(i)
    for padded_len, idx in by_len.items():
        rows = np.frombuffer(b"".join(_pad(messages[i], domain)
                                      for i in idx), np.uint8)
        rows = rows.reshape(len(idx), padded_len).view("<u8")
        a = [np.zeros(len(idx), np.uint64) for _ in range(25)]
        for blk in range(padded_len // RATE_BYTES):
            for lane in range(RATE_BYTES // 8):
                a[lane] = a[lane] ^ rows[:, blk * 17 + lane]
            a = keccak_f1600(a)
        state = np.stack(a[:4], axis=1).astype("<u8")
        for k, i in enumerate(idx):
            out[i] = state[k].tobytes()
    return out
