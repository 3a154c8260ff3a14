"""Floating-point formats and packed-integer codecs.

The paper's unit operates on IEEE754-like numbers (no NaN/Inf/subnormals) and
on HUB (Half-Unit-Biased) floating-point numbers [Hormigo & Villalba, IEEE TC
2016].  Both are carried here as *packed* int64 words with the layout

        [ sign(1) | exponent(e) | mantissa(m) ]

- Conventional decode:  (-1)^s * (1 + M/2^m)            * 2^(E - bias)
- HUB decode:           (-1)^s * (1 + M/2^m + 2^-(m+1)) * 2^(E - bias)
  (the extra 2^-(m+1) term is the Implicit LSB, always 1)
- E == 0 encodes exact zero in either format (subnormals unsupported,
  matching the paper's converters).

Encoding from binary64 uses round-to-nearest-even for the conventional format
and plain truncation for HUB (truncation *is* round-to-nearest for HUB).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

__all__ = [
    "FloatFormat", "HALF", "SINGLE", "DOUBLE",
    "encode_ieee", "decode_ieee", "encode_hub", "decode_hub",
    "pack_fields", "unpack_fields", "packed_is_zero", "frexp", "ldexp",
]

# Exponent ladders for the bitcast-free frexp/ldexp below: 2^(2^j) for
# j = 9..0 covers every float64 exponent (|e| < 2^10).
_LADDER = tuple(1 << j for j in range(9, -1, -1))


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """An IEEE754-like storage format: 1 sign, `exp_bits`, `man_bits`."""

    exp_bits: int
    man_bits: int
    name: str = ""

    @property
    def bias(self) -> int:
        return (1 << (self.exp_bits - 1)) - 1

    @property
    def max_exp_raw(self) -> int:
        # Largest *raw* exponent we emit; the all-ones code is avoided so the
        # packed space stays NaN/Inf-free (the converters saturate instead).
        return (1 << self.exp_bits) - 2

    @property
    def total_bits(self) -> int:
        return 1 + self.exp_bits + self.man_bits

    def __post_init__(self):
        # 64 fits: the sign bit may occupy bit 63 (int64 wraps are benign —
        # packed words are bit patterns, all field accesses go through masks).
        if self.total_bits > 64:
            raise ValueError("packed format must fit int64")


HALF = FloatFormat(5, 10, "half")
SINGLE = FloatFormat(8, 23, "single")
DOUBLE = FloatFormat(11, 52, "double")


def pack_fields(sign, exp_raw, man, fmt: FloatFormat):
    """Assemble packed int64 words from (sign, raw exponent, mantissa)."""
    sign = jnp.asarray(sign, jnp.int64)
    exp_raw = jnp.asarray(exp_raw, jnp.int64)
    man = jnp.asarray(man, jnp.int64)
    return (sign << (fmt.exp_bits + fmt.man_bits)) | (exp_raw << fmt.man_bits) | man


def unpack_fields(packed, fmt: FloatFormat):
    """Split packed words into (sign, raw exponent, mantissa)."""
    packed = jnp.asarray(packed, jnp.int64)
    man = packed & ((1 << fmt.man_bits) - 1)
    exp_raw = (packed >> fmt.man_bits) & ((1 << fmt.exp_bits) - 1)
    sign = (packed >> (fmt.exp_bits + fmt.man_bits)) & 1
    return sign, exp_raw, man


def packed_is_zero(packed, fmt: FloatFormat):
    """True where a packed word encodes ±0 (raw exponent field 0).

    Shared by both formats — E == 0 is the zero encoding for IEEE-like and
    HUB words alike (subnormals are unsupported).  Used by the complex
    datapath to detect exactly-real entries, for which the phase rotation
    is skipped as an exact identity (DESIGN.md §10).
    """
    packed = jnp.asarray(packed, jnp.int64)
    return ((packed >> fmt.man_bits) & ((1 << fmt.exp_bits) - 1)) == 0


def frexp(a):
    """``jnp.frexp`` for positive normal float64 ``a``, exactly, bitcast-free.

    ``jnp.frexp`` / ``jnp.ldexp`` / ``jnp.signbit`` bitcast float64 to
    int64, which the TPU compiler's x64 rewriter refuses.  Here ``a`` is
    scaled into [1/2, 2) by a ladder of power-of-two multiplies — each
    exact — counting the shifts.  Returns ``(f, e)`` with ``a = f·2^e``,
    ``f`` in [1/2, 1), ``e`` int32.
    """
    e = jnp.zeros(jnp.shape(a), jnp.int32)
    for k in _LADDER:
        big = a >= 2.0 ** k
        a = jnp.where(big, a * 2.0 ** -k, a)
        e = e + jnp.where(big, k, 0)
    for k in _LADDER:
        small = a < 2.0 ** -k
        a = jnp.where(small, a * 2.0 ** k, a)
        e = e - jnp.where(small, k, 0)
    up = a >= 1.0
    return jnp.where(up, a * 0.5, a), e + up


def ldexp(x, k):
    """``x · 2^k`` for int ``k`` with |k| < 1024, exactly, bitcast-free.

    One power-of-two multiply per set bit of |k|, largest first; every
    intermediate lies between ``x`` and the result, so none rounds.
    """
    neg = k < 0
    kk = jnp.abs(k)
    for j in _LADDER:
        f = jnp.where(neg, 2.0 ** -j, 2.0 ** j)
        x = jnp.where((kk & j) != 0, x * f, x)
    return x


def _split_finite(x):
    """x (float64) -> sign, unbiased exponent, significand in [1, 2).

    Zero maps to (sign, None-marker) via the `is_zero` mask returned.
    """
    x = jnp.asarray(x, jnp.float64)
    ax = jnp.abs(x)
    is_zero = ax == 0.0
    # the sign bit, -0.0 included (1/-0.0 = -inf)
    neg = jnp.where(is_zero, 1.0 / jnp.where(is_zero, x, 1.0), x) < 0
    sign = neg.astype(jnp.int64)
    # frexp: ax = f * 2^e with f in [0.5, 1)  ->  significand 2f in [1,2).
    f, e = frexp(jnp.where(is_zero, 1.0, ax))
    return sign, (e - 1).astype(jnp.int64), 2.0 * f, is_zero


def _significand_bits(sig, fmt: FloatFormat):
    """sig in [1, 2) -> (its top ``1 + man_bits`` bits, the dropped bits,
    the dropped width) as int64.

    Rounding is left to integer arithmetic: scaling by 2^52 and the
    integral conversion are exact, whereas the TPU's emulated float64
    ``rint``/``floor`` are not.
    """
    M = (sig * 2.0 ** 52).astype(jnp.int64)         # [2^52, 2^53)
    drop = 52 - fmt.man_bits
    return M >> drop, M & ((1 << drop) - 1), drop


def encode_ieee(x, fmt: FloatFormat):
    """binary64 -> packed conventional word (RNE; saturates, flushes to 0)."""
    sign, e, sig, is_zero = _split_finite(x)
    q, rem, drop = _significand_bits(sig, fmt)
    if drop:                                         # round to nearest even
        half = 1 << (drop - 1)
        q = q + ((rem > half) | ((rem == half) & ((q & 1) == 1))).astype(
            jnp.int64)
    man = q - (1 << fmt.man_bits)
    # Mantissa rounding may carry out (sig ~ 2.0).
    carry = man >> fmt.man_bits
    man = jnp.where(carry > 0, 0, man)
    e = e + carry
    exp_raw = e + fmt.bias
    underflow = exp_raw < 1
    overflow = exp_raw > fmt.max_exp_raw
    exp_raw = jnp.clip(exp_raw, 1, fmt.max_exp_raw)
    man = jnp.where(overflow, (1 << fmt.man_bits) - 1, man)
    packed = pack_fields(sign, exp_raw, man, fmt)
    return jnp.where(is_zero | underflow, sign << (fmt.exp_bits + fmt.man_bits), packed)


def decode_ieee(packed, fmt: FloatFormat):
    """packed conventional word -> binary64."""
    sign, exp_raw, man = unpack_fields(packed, fmt)
    sig = 1.0 + man.astype(jnp.float64) / np.float64(1 << fmt.man_bits)
    val = ldexp(sig, (exp_raw - fmt.bias).astype(jnp.int32))
    val = jnp.where(exp_raw == 0, 0.0, val)
    return jnp.where(sign == 1, -val, val)


def encode_hub(x, fmt: FloatFormat):
    """binary64 -> packed HUB word.

    Round-to-nearest for HUB is *truncation* of the mantissa field.
    """
    sign, e, sig, is_zero = _split_finite(x)
    q, _, _ = _significand_bits(sig, fmt)
    man = q - (1 << fmt.man_bits)                    # truncate == RN(HUB)
    man = jnp.clip(man, 0, (1 << fmt.man_bits) - 1)  # sig==2.0 cannot occur (frexp)
    exp_raw = e + fmt.bias
    underflow = exp_raw < 1
    overflow = exp_raw > fmt.max_exp_raw
    exp_raw = jnp.clip(exp_raw, 1, fmt.max_exp_raw)
    man = jnp.where(overflow, (1 << fmt.man_bits) - 1, man)
    packed = pack_fields(sign, exp_raw, man, fmt)
    return jnp.where(is_zero | underflow, sign << (fmt.exp_bits + fmt.man_bits), packed)


def decode_hub(packed, fmt: FloatFormat):
    """packed HUB word -> binary64 (includes the ILSB term 2^-(m+1))."""
    sign, exp_raw, man = unpack_fields(packed, fmt)
    scale = np.float64(1 << fmt.man_bits)
    sig = 1.0 + (man.astype(jnp.float64) + 0.5) / scale
    val = ldexp(sig, (exp_raw - fmt.bias).astype(jnp.int32))
    val = jnp.where(exp_raw == 0, 0.0, val)
    return jnp.where(sign == 1, -val, val)
