"""Bytes that one AES-128-GCM seal moves, whatever computes it.

In: the 12-byte nonce, the AAD and the plaintext.  Out: the ciphertext
(as long as the plaintext) and the 16-byte tag.  The key schedule is
per engine, not per record, and is not counted.
"""

NONCE_BYTES = 12
TAG_BYTES = 16


def request_bytes(*, pt_len: int, aad_len: int) -> int:
    return NONCE_BYTES + aad_len + pt_len + pt_len + TAG_BYTES
