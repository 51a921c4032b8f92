"""Client and plain reference of ``tls13-aes128gcm``.

Each request seals one TLS 1.3 record (RFC 8446 sections 5.2-5.3):
the inner plaintext is the content and its one-byte content type, the
AAD is the five-byte record header, and the nonce is the static write
IV XOR the record's sequence number.  The write key is the
configuration's (one key serves the engine for its life); the IV and
the records come from the seed.  The reference is the
``cryptography`` package's AES-GCM.  The control breaks the nonce
guarantee: it seals every record under the static IV, as if the
sequence number were never mixed in.
"""

from __future__ import annotations

from cryptography.hazmat.primitives.ciphers.aead import AESGCM

from repro.serve.batching import encode_aead_record

APPLICATION_DATA = 0x17
LEGACY_VERSION = b"\x03\x03"
TAG_BYTES = 16


class Client:
    op = "gcm_seal"

    def __init__(self, config: dict, seed: int, rng):
        self.config = config
        self.key = bytes.fromhex(config["write_key_hex"])
        self.iv = rng(seed, "iv").bytes(12)
        self.longest = max(config["record_bytes"])
        self.pool = rng(seed, "records").bytes((1 << 20) + self.longest)

    def engine_options(self) -> dict:
        return {"aead_key": self.key}

    def content(self, index: int, size: int) -> bytes:
        span = len(self.pool) - self.longest
        off = (index * 2654435761) % span
        return self.pool[off:off + size]

    def nonce(self, seq: int) -> bytes:
        return bytes(a ^ b for a, b in zip(self.iv, seq.to_bytes(12, "big")))

    def record(self, index: int, size: int) -> tuple:
        """(nonce, inner plaintext, AAD) of record ``index``."""
        pt = self.content(index, size) + bytes([APPLICATION_DATA])
        header = (bytes([APPLICATION_DATA]) + LEGACY_VERSION
                  + (len(pt) + TAG_BYTES).to_bytes(2, "big"))
        return self.nonce(index), pt, header

    def payload(self, index: int, size: int) -> bytes:
        return encode_aead_record(*self.record(index, size))

    def geometry(self, size: int) -> dict:
        return {"pt_len": size + 1, "aad_len": 5}

    def expected(self, items) -> list:
        aead = AESGCM(self.key)
        return [aead.encrypt(*self.record(i, n)) for i, n in items]

    def control(self, items) -> list:
        aead = AESGCM(self.key)
        out = []
        for i, n in items:
            _, pt, aad = self.record(i, n)
            out.append(aead.encrypt(self.iv, pt, aad))
        return out
