"""Client and plain reference of ``tls13-aes128gcm-sessions``.

Each request seals one TLS 1.3 record (RFC 8446 sections 5.2-5.3) of
one of ``sessions`` connections, each with its own write key and static
write IV (section 7.3).  The inner plaintext is the content and its
one-byte content type, the AAD is the five-byte record header, and the
nonce is the session's IV XOR the record's sequence number (its index,
unique within the session).  Record ``i`` belongs to a session drawn
from a Zipf(``session_skew``) law over the sessions' ranks, by a hash
of ``i`` and the seed, so the draw is a function of the record alone.
The keys, the IVs, the ranks' order and the records come from the
seed.  The reference is the ``cryptography`` package's AES-GCM, one
instance per session key.  The control breaks key isolation: it seals
every record under session 0's key.
"""

from __future__ import annotations

import numpy as np
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

from repro.serve.batching import encode_aead_record

APPLICATION_DATA = 0x17
LEGACY_VERSION = b"\x03\x03"
TAG_BYTES = 16
MASK64 = (1 << 64) - 1


def _mix64(v: int) -> int:
    """SplitMix64's finaliser: a well-spread 64-bit hash of ``v``."""
    v = (v + 0x9E3779B97F4A7C15) & MASK64
    v = ((v ^ (v >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    v = ((v ^ (v >> 27)) * 0x94D049BB133111EB) & MASK64
    return v ^ (v >> 31)


class Client:
    op = "gcm_seal"

    def __init__(self, config: dict, seed: int, rng):
        self.config = config
        n = int(config["sessions"])
        r = rng(seed, "sessions")
        keys, ivs = r.bytes(16 * n), r.bytes(12 * n)
        self.keys = tuple(keys[16 * i:16 * i + 16] for i in range(n))
        self.ivs = tuple(ivs[12 * i:12 * i + 12] for i in range(n))
        weights = np.arange(1, n + 1, dtype=np.float64) ** -float(
            config["session_skew"])
        self.cdf = np.cumsum(weights) / weights.sum()
        self.order = r.permutation(n)
        self.salt = int(r.integers(0, 1 << 63))
        self.longest = max(config["record_bytes"])
        self.pool = rng(seed, "records").bytes((1 << 20) + self.longest)
        self._aead: dict = {}

    def engine_options(self) -> dict:
        return {"aead_keys": self.keys}

    def session(self, index: int) -> int:
        u = _mix64(index ^ self.salt) / float(1 << 64)
        rank = min(int(np.searchsorted(self.cdf, u, side="right")),
                   len(self.keys) - 1)
        return int(self.order[rank])

    def content(self, index: int, size: int) -> bytes:
        span = len(self.pool) - self.longest
        off = (index * 2654435761) % span
        return self.pool[off:off + size]

    def record(self, index: int, size: int) -> tuple:
        """(nonce, inner plaintext, AAD, session) of record ``index``."""
        s = self.session(index)
        pt = self.content(index, size) + bytes([APPLICATION_DATA])
        header = (bytes([APPLICATION_DATA]) + LEGACY_VERSION
                  + (len(pt) + TAG_BYTES).to_bytes(2, "big"))
        nonce = bytes(a ^ b for a, b in zip(self.ivs[s],
                                            index.to_bytes(12, "big")))
        return nonce, pt, header, s

    def payload(self, index: int, size: int) -> bytes:
        nonce, pt, header, s = self.record(index, size)
        return encode_aead_record(nonce, pt, header, session=s)

    def geometry(self, size: int) -> dict:
        return {"pt_len": size + 1, "aad_len": 5}

    def _seal(self, key_session: int, nonce: bytes, pt: bytes,
              aad: bytes) -> bytes:
        aead = self._aead.get(key_session)
        if aead is None:
            aead = self._aead[key_session] = AESGCM(self.keys[key_session])
        return aead.encrypt(nonce, pt, aad)

    def expected(self, items) -> list:
        out = []
        for i, n in items:
            nonce, pt, aad, s = self.record(i, n)
            out.append(self._seal(s, nonce, pt, aad))
        return out

    def control(self, items) -> list:
        out = []
        for i, n in items:
            nonce, pt, aad, _ = self.record(i, n)
            out.append(self._seal(0, nonce, pt, aad))
        return out
