"""Weight semirings: the algebra a crossbar pass accumulates in.

The paper's AND-OR crossbar computes ``out[o] = SUM_k w[o,k] * x[idx[o,k]]``
— but nothing about the datapath fixes *which* (+, ×) that is.  Machine
learning workloads want the real field (MoE gate scalars multiply, partial
sums add); cryptographic linear layers want finite fields: Keccak's θ and
AES's MixColumns are crossbars whose "multiply-add" is carry-free XOR
accumulation of GF(2)/GF(2^8) products.  This module makes the choice a
first-class, pluggable property of a plan:

* ``Semiring`` — a named ``(add, mul, zero, one)`` bundle with the extra
  hooks the execution backends need (reduction along the select axis, the
  dtype weights materialise in, whether a dense integer contraction can
  emulate the accumulation with a mod-2 fold).

* ``REAL``   — today's behaviour: f32/int multiply-add.  The default on
  every plan; all pre-semiring code paths are the REAL instances of the
  generic ones.

* ``GF2``    — the two-element field: add = XOR, mul = AND, carriers are
  0/1 integers.  Key property exploited by every matmul backend: a sum of
  0/1 products reduced **mod 2** *is* the XOR accumulation, so GF2 plans
  run on the same MXU contraction as REAL plans plus one cheap parity
  fold at emission.

* ``GF2_8``  — the AES field GF(2^8) with the Rijndael polynomial
  x^8+x^4+x^3+x+1 (0x11B): add = byte XOR, mul = the xtime-chain
  polynomial product.  Multiplication by a *constant* is GF(2)-linear, so
  a GF2_8-weighted plan over n bytes "lifts" to an unweighted GF2 plan
  over 8n bits (each byte weight w becomes the 8x8 bit matrix
  ``M_w[b, j] = bit b of w·2^j``); ``crossbar.apply_plan`` uses exactly
  that lift to run MixColumns on the ordinary bit-exact crossbar.

Semiring objects are interned singletons: identity comparison and
``name`` are both stable cache-key material (plan memo, compiled-schedule
LRU, pinned static cache, fixed-latency fingerprints all key on it — two
plans sharing idx/weight arrays under different semirings must never
collide).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# The Rijndael reduction polynomial x^8 + x^4 + x^3 + x + 1.
AES_POLY = 0x11B


# ---------------------------------------------------------------------------
# GF(2^8) arithmetic (vectorised, branch-free, numpy- and jax-compatible)
# ---------------------------------------------------------------------------

def gf2_8_xtime(a):
    """Multiply by x (i.e. 2) in GF(2^8): shift, conditionally reduce."""
    a = a.astype(jnp.int32) if isinstance(a, jax.Array) else \
        np.asarray(a, np.int32)
    return ((a << 1) ^ ((a >> 7) * (AES_POLY & 0xFF))) & 0xFF


def gf2_8_mul(a, b):
    """Elementwise GF(2^8) product via the xtime chain (8 fixed steps).

    Works on numpy arrays, python ints, and traced jax arrays alike;
    branch-free (fixed latency) in all cases.  Broadcasting follows the
    operands'.
    """
    if isinstance(a, jax.Array) or isinstance(b, jax.Array):
        a = jnp.asarray(a, jnp.int32)
        b = jnp.asarray(b, jnp.int32)
        where = jnp.where
    else:
        a = np.asarray(a, np.int32)
        b = np.asarray(b, np.int32)
        where = np.where
    acc = a * 0
    for i in range(8):
        acc = acc ^ where(((b >> i) & 1) != 0, a, 0)
        a = gf2_8_xtime(a)
    return acc


def gf2_8_pow(a: int, e: int) -> int:
    """Scalar GF(2^8) exponentiation (host-side table generation)."""
    acc, base = 1, a & 0xFF
    while e:
        if e & 1:
            acc = int(gf2_8_mul(np.int32(acc), np.int32(base)))
        base = int(gf2_8_mul(np.int32(base), np.int32(base)))
        e >>= 1
    return acc


def gf2_8_inv(a: int) -> int:
    """Multiplicative inverse in GF(2^8) (0 maps to 0, per AES S-box)."""
    return 0 if a == 0 else gf2_8_pow(a, 254)


@functools.lru_cache(maxsize=None)
def gf2_8_bit_matrix_table() -> np.ndarray:
    """(256, 8, 8) int8: ``T[w, b, j]`` = bit ``b`` of ``w · 2^j``.

    The GF(2)-linear representation of multiplication by each constant:
    ``(w·x)_b = XOR_j T[w, b, j] · x_j``.  This is the lookup the
    GF2_8 -> GF2 plan lift is built from.
    """
    w = np.arange(256, dtype=np.int32)
    cols = np.empty((8, 256), np.int32)
    cur = w.copy()
    for j in range(8):
        cols[j] = cur                      # w * 2^j
        cur = gf2_8_xtime(cur)
    # T[w, b, j] = bit b of cols[j, w]
    bits = (cols[:, :, None] >> np.arange(8)) & 1      # (j, w, b)
    return bits.transpose(1, 2, 0).astype(np.int8)     # (w, b, j)


# ---------------------------------------------------------------------------
# GF(2^k) arithmetic for arbitrary widths (the GHASH axis)
# ---------------------------------------------------------------------------
#
# Everything below parameterises the GF(2^8) machinery by (width, poly).
# Widths up to 31 carry elements as ordinary int32 scalars; wider fields
# (GHASH's GF(2^128)) carry elements as little-endian 8-bit LIMB arrays
# — a trailing axis of ``width // 8`` int32 values in [0, 256) — because
# no JAX integer dtype holds them.  Limb order follows bit order: limb
# ``r`` holds field bits ``8r .. 8r+7`` (coefficient of x^(8r+b) at bit
# ``b``), so packing/unpacking is a pure reshape at the bit level.

# Default reduction polynomials per width.  Only the field *ring*
# structure matters for the lift algebra (mul-by-constant is GF(2)-
# linear over any modulus); 0x87 is GHASH's x^128 + x^7 + x^2 + x + 1.
DEFAULT_POLYS = {
    4: 0x13,                    # x^4 + x + 1
    8: AES_POLY,                # x^8 + x^4 + x^3 + x + 1 (Rijndael)
    16: 0x1100B,                # x^16 + x^12 + x^3 + x + 1
    128: (1 << 128) | 0x87,     # x^128 + x^7 + x^2 + x + 1 (GHASH)
}


def _limb_count(width: int) -> int:
    """Limbs for a wide width (0 for scalar-carried widths <= 31)."""
    return 0 if width <= 31 else width // 8


def gf2k_xtime(a, width: int, poly: int):
    """Multiply by x in GF(2^width), scalar carriers (width <= 31)."""
    mask = (1 << width) - 1
    if isinstance(a, jax.Array):
        a = a.astype(jnp.int32)
    else:
        a = np.asarray(a, np.int32)
    return ((a << 1) ^ (((a >> (width - 1)) & 1) * (poly & mask))) & mask


def gf2k_mul(a, b, width: int, poly: int):
    """Elementwise GF(2^width) product, scalar carriers (width <= 31).

    Branch-free xtime chain (``width`` fixed steps); numpy, python int,
    and traced jax operands all work, broadcasting follows the operands.
    """
    if isinstance(a, jax.Array) or isinstance(b, jax.Array):
        a = jnp.asarray(a, jnp.int32)
        b = jnp.asarray(b, jnp.int32)
        where = jnp.where
    else:
        a = np.asarray(a, np.int32)
        b = np.asarray(b, np.int32)
        where = np.where
    acc = a * 0
    for i in range(width):
        acc = acc ^ where(((b >> i) & 1) != 0, a, 0)
        a = gf2k_xtime(a, width, poly)
    return acc


def _poly_limbs(poly: int, limbs: int) -> np.ndarray:
    """The low ``limbs`` bytes of the reduction polynomial (the part
    XORed in on overflow), little-endian limb order."""
    return np.asarray([(poly >> (8 * r)) & 0xFF for r in range(limbs)],
                      np.int32)


def gf2k_xtime_limbs(a, width: int, poly: int):
    """Multiply by x for limbed carriers: per-limb shift with carry
    ripple, then conditional reduction when bit width-1 falls off."""
    limbs = width // 8
    if isinstance(a, jax.Array):
        xp, where = jnp, jnp.where
        a = a.astype(jnp.int32)
        pl = jnp.asarray(_poly_limbs(poly, limbs))
    else:
        xp, where = np, np.where
        a = np.asarray(a, np.int32)
        pl = _poly_limbs(poly, limbs)
    carry = (a >> 7) & 1
    shifted = (a << 1) & 0xFF
    shifted = xp.concatenate(
        [shifted[..., :1],
         shifted[..., 1:] | carry[..., :-1]], axis=-1)
    overflow = carry[..., -1:]
    return shifted ^ where(overflow != 0, pl, 0)


def gf2k_mul_limbs(a, b, width: int, poly: int):
    """Elementwise GF(2^width) product over limbed carriers.

    ``a``/``b``: (..., width//8) int32 byte limbs; broadcasting follows
    the leading axes.  ``width`` fixed xtime steps — host-side table
    and weight-fold use only, never a payload hot path.
    """
    if isinstance(a, jax.Array) or isinstance(b, jax.Array):
        # One compiled program, not ``width`` x 8 eager dispatches.
        return _gf2k_mul_limbs_jit(jnp.asarray(a, jnp.int32),
                                   jnp.asarray(b, jnp.int32), width, poly)
    return _gf2k_mul_limbs(np.asarray(a, np.int32), np.asarray(b, np.int32),
                           width, poly)


def _gf2k_mul_limbs(a, b, width: int, poly: int):
    acc = (a * 0 + b * 0)   # zeros of the broadcast shape
    cur = a + acc
    where = jnp.where if isinstance(acc, jax.Array) else np.where
    for bit in range(width):
        r, s = divmod(bit, 8)
        bbit = (b[..., r] >> s) & 1
        acc = acc ^ where(bbit[..., None] != 0, cur, 0)
        cur = gf2k_xtime_limbs(cur, width, poly)
    return acc


_gf2k_mul_limbs_jit = jax.jit(_gf2k_mul_limbs, static_argnums=(2, 3))


def gf2k_to_limbs(v: int, width: int) -> np.ndarray:
    """Python int -> little-endian byte-limb vector (host helper)."""
    limbs = max(1, width // 8)
    return np.asarray([(v >> (8 * r)) & 0xFF for r in range(limbs)],
                      np.int32)


def gf2k_from_limbs(limbs_vec) -> int:
    """Byte-limb vector -> python int (host helper)."""
    return sum(int(l) << (8 * r) for r, l in enumerate(np.asarray(limbs_vec)))


def gf2k_mul_int(a: int, b: int, width: int, poly: int) -> int:
    """Exact python-int GF(2^width) product — the host-side oracle the
    differential tests compare every lowering against."""
    mask = (1 << width) - 1
    a &= mask
    b &= mask
    acc = 0
    while b:
        if b & 1:
            acc ^= a
        b >>= 1
        a <<= 1
        if a >> width:
            a ^= poly
    return acc & mask


@functools.lru_cache(maxsize=8)
def gf2k_tile_table(width: int, poly: int) -> np.ndarray:
    """(256, width, width + 8·(L-1)) int8 tiled bit-lift table.

    ``E[v, b, m]`` = bit ``b`` of ``v · x^m mod P`` for 8-bit tile
    values ``v``.  A full constant ``w = Σ_t limb_t · x^(8t)`` has bit
    matrix ``M_w[b, j] = XOR_t E[limb_t, b, j + 8t]`` — the 8-bit-tile
    decomposition that keeps the table 256 rows regardless of width
    (a dense (2^128, ...) table being somewhat impractical).  For
    width 8 this is exactly ``gf2_8_bit_matrix_table``.
    """
    limbs = max(1, width // 8 if width > 31 else (width + 7) // 8)
    n_cols = width + 8 * (limbs - 1)
    out = np.empty((256, width, n_cols), np.int8)
    if width <= 31:
        cur = np.arange(256, dtype=np.int32) & ((1 << width) - 1)
        for m in range(n_cols):
            out[:, :, m] = (cur[:, None] >> np.arange(width)) & 1
            cur = gf2k_xtime(cur, width, poly)
    else:
        cur = np.zeros((256, width // 8), np.int32)
        cur[:, 0] = np.arange(256)
        shifts = np.arange(8)
        for m in range(n_cols):
            bits = (cur[:, :, None] >> shifts) & 1     # (256, L, 8)
            out[:, :, m] = bits.reshape(256, width)
            cur = gf2k_xtime_limbs(cur, width, poly)
    return out


# ---------------------------------------------------------------------------
# The Semiring bundle
# ---------------------------------------------------------------------------

def _xor_reduce(x: Array, axis: int) -> Array:
    """XOR fold along ``axis`` (log-depth, branch-free)."""
    n = x.shape[axis]
    if n == 0:
        return jnp.zeros(x.shape[:axis] + x.shape[axis + 1:], x.dtype)
    while n > 1:
        half = n // 2
        lo = jax.lax.slice_in_dim(x, 0, half, axis=axis)
        hi = jax.lax.slice_in_dim(x, half, 2 * half, axis=axis)
        rest = jax.lax.slice_in_dim(x, 2 * half, n, axis=axis)
        x = jnp.concatenate([lo ^ hi, rest], axis=axis)
        n = x.shape[axis]
    return jnp.squeeze(x, axis=axis)


@dataclasses.dataclass(frozen=True, eq=False)
class Semiring:
    """A named (add, mul, zero, one) with backend execution hooks.

    Attributes:
      name:   stable identity for cache keys / fingerprints / repr.
      add/mul: elementwise jnp ops (broadcasting).
      zero/one: python scalars (additive / multiplicative identities).
      weight_dtype: dtype weights materialise in (f32 for REAL, int32
        for the finite fields — carriers are exact small integers).
      integer_carrier: True when payloads/weights must be integers.
      mod2_fold: True when a dense integer/f32 sum-of-products equals
        the semiring accumulation after a mod-2 fold (GF2's parity
        trick; the MXU path for both finite fields via the bit lift).
      carrier_mask: bitmask of the carrier set for finite fields (GF2:
        1, GF2_8: 0xFF; None for REAL) — pure-routing lowerings fold
        picked values with it so every lowering agrees even for
        payloads outside the carrier range.
    """

    name: str
    add: Callable[[Array, Array], Array]
    mul: Callable[[Array, Array], Array]
    zero: int
    one: int
    weight_dtype: jnp.dtype
    integer_carrier: bool = False
    mod2_fold: bool = False
    carrier_mask: int | None = None
    # GF(2^width) family metadata (0/None for REAL).  ``limbs`` > 0
    # marks a wide field whose elements ride as (..., limbs) int32
    # byte-limb arrays instead of scalars; ``poly`` is the reduction
    # polynomial the bit lift tiles decompose.
    width: int = 0
    poly: int | None = None
    limbs: int = 0

    def __repr__(self) -> str:
        return f"Semiring({self.name!r})"

    @property
    def is_gf2k(self) -> bool:
        """True for every GF(2^width) member with width >= 2 — the plans
        the crossbar executes through the GF(2) bit lift."""
        return self.width >= 2

    def reduce(self, x: Array, axis: int) -> Array:
        """Fold ``add`` along ``axis`` (the crossbar's select axis)."""
        if self.name == "real":
            return jnp.sum(x, axis=axis)
        return _xor_reduce(x, axis)

    def ones(self, shape, like=None) -> Array:
        del like
        if self.limbs:
            # Wide fields: the multiplicative identity is the limb
            # vector [1, 0, ..., 0], not a scalar fill.
            w = jnp.zeros(tuple(shape) + (self.limbs,), self.weight_dtype)
            return w.at[..., 0].set(1)
        return jnp.full(shape, self.one, self.weight_dtype)

    def cast_weights(self, w: Array) -> Array:
        return jnp.asarray(w).astype(self.weight_dtype)


REAL = Semiring(
    name="real", add=lambda a, b: a + b, mul=lambda a, b: a * b,
    zero=0, one=1, weight_dtype=jnp.float32)

GF2 = Semiring(
    name="gf2", add=jnp.bitwise_xor, mul=jnp.bitwise_and,
    zero=0, one=1, weight_dtype=jnp.int32,
    integer_carrier=True, mod2_fold=True, carrier_mask=1, width=1)

GF2_8 = Semiring(
    name="gf2_8", add=jnp.bitwise_xor, mul=gf2_8_mul,
    zero=0, one=1, weight_dtype=jnp.int32,
    integer_carrier=True, carrier_mask=0xFF, width=8, poly=AES_POLY)

_BY_NAME = {s.name: s for s in (REAL, GF2, GF2_8)}


@functools.lru_cache(maxsize=None)
def gf2_k(width: int, poly: int | None = None) -> Semiring:
    """The interned GF(2^width) semiring (default polynomial per width).

    Widths 2..31 carry elements/weights as int32 scalars and flow
    through every existing plan path; wider widths (multiples of 8 up
    to 128 — GHASH's GF(2^128)) carry them as (..., width//8) byte-limb
    arrays and execute exclusively through the tiled GF(2) bit lift.
    ``gf2_k(8)`` with the Rijndael polynomial IS ``GF2_8`` and
    ``gf2_k(1)`` is ``GF2`` — one interning for the whole family, so
    identity comparison and cache keys stay sound.
    """
    if width == 1:
        return GF2
    if poly is None:
        poly = DEFAULT_POLYS.get(width)
        if poly is None:
            raise ValueError(
                f"no default polynomial for width {width}; pass poly=")
    if poly >> width == 0 or poly >> (width + 1):
        raise ValueError(
            f"polynomial {poly:#x} is not degree-{width}")
    if width == 8 and poly == AES_POLY:
        return GF2_8
    if width <= 31:
        sr = Semiring(
            name=f"gf2_{width}" + (
                "" if poly == DEFAULT_POLYS.get(width) else f"_p{poly:x}"),
            add=jnp.bitwise_xor,
            mul=functools.partial(gf2k_mul, width=width, poly=poly),
            zero=0, one=1, weight_dtype=jnp.int32,
            integer_carrier=True, carrier_mask=(1 << width) - 1,
            width=width, poly=poly)
    else:
        if width > 128 or width % 8:
            raise ValueError(
                f"wide GF(2^k) widths must be multiples of 8 up to 128, "
                f"got {width}")
        sr = Semiring(
            name=f"gf2_{width}" + (
                "" if poly == DEFAULT_POLYS.get(width) else f"_p{poly:x}"),
            add=jnp.bitwise_xor,
            mul=functools.partial(gf2k_mul_limbs, width=width, poly=poly),
            zero=0, one=1, weight_dtype=jnp.int32,
            integer_carrier=True, width=width, poly=poly,
            limbs=width // 8)
    _BY_NAME.setdefault(sr.name, sr)
    return sr


def get(name: str) -> Semiring:
    """Look a semiring up by its stable name."""
    try:
        return _BY_NAME[name]
    except KeyError:
        pass
    # Family members materialise on demand: "gf2_16" parses to
    # gf2_k(16) with the default polynomial, so fingerprints and
    # serialised plans round-trip without pre-registration.
    if name.startswith("gf2_"):
        try:
            width = int(name.split("_")[1])
        except (IndexError, ValueError):
            width = -1
        if width > 1 and DEFAULT_POLYS.get(width) is not None:
            return gf2_k(width)
    raise ValueError(
        f"unknown semiring {name!r} (have {sorted(_BY_NAME)})")


def join(s1: Semiring, s2: Semiring, *, neutral1: bool = False,
         neutral2: bool = False) -> Semiring:
    """The common semiring of two plans being combined.

    Equal semirings join to themselves.  An *unweighted* plan still
    carrying the REAL default is semiring-neutral — pure routing has the
    same meaning in every semiring — and adopts the other operand's
    (``neutralN`` flags declare that property per operand).  Anything
    else is a real algebra mismatch and raises.
    """
    if s1 is s2:
        return s1
    if s1 is REAL and neutral1:
        return s2
    if s2 is REAL and neutral2:
        return s1
    raise ValueError(
        f"semiring mismatch: cannot combine plans over {s1.name!r} and "
        f"{s2.name!r}; reweight one side (plan_algebra.with_weights / "
        "with_semiring) so both agree")
