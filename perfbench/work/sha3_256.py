"""Bytes that one SHA3-256 request's work moves, whatever computes it.

Each sponge block is one Keccak-f[1600]: the 200-byte state read and
the 200-byte state written.  A message of ``n`` bytes absorbs
``(n + 1 + 135) // 136`` blocks of the 136-byte rate (pad10*1 always
adds at least the domain byte), and the digest is squeezed from the
last state, so no further permutation runs.
"""

RATE_BYTES = 136
STATE_BYTES = 200


def blocks(message_bytes: int) -> int:
    return (message_bytes + 1 + RATE_BYTES - 1) // RATE_BYTES


def request_bytes(*, message_bytes: int) -> int:
    return blocks(message_bytes) * 2 * STATE_BYTES
