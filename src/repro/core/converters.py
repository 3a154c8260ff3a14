"""FP <-> block fixed-point converters (paper Secs. 3.1, 3.3, 4.1, 4.3).

Input converter (Figs. 2 / 5): two packed FP words -> two aligned N-bit
two's-complement significands sharing the larger exponent (block FP).
Output converter (Figs. 4 / 7): two rotated w-bit fixed-point values + the
common exponent -> two packed FP words (normalize, round, underflow flush).

Every paper variant is implemented:
  IEEE  : input alignment rounding 'rne' or 'trunc'  (Fig. 10: IEEERound/Trunc)
  HUB   : biased vs unbiased extension, identity ("1.0") detection
          (Fig. 10: HUBBasic / HUBunbias / HUBDetectI / HUBFull)

`N` may be a traced scalar so that bit-width sweeps share one compilation.
Internally significands use F = N-2 fraction bits; the CORDIC datapath width
is w = N+2 (two growth bits, Sec. 5.2).
"""
from __future__ import annotations

import jax.numpy as jnp

from .formats import FloatFormat, pack_fields, unpack_fields

__all__ = ["input_convert_ieee", "input_convert_hub",
           "output_convert_ieee", "output_convert_hub", "ilog2"]

_I64 = lambda v: jnp.asarray(v, jnp.int64)


def ilog2(a):
    """floor(log2(a)) for int64 a > 0, by an exact integer binary search.

    (A float64 ``frexp`` detour is exact only below 2^53, and its int64
    bitcast does not compile for the TPU.)
    """
    a = jnp.asarray(a, jnp.int64)
    r = jnp.zeros_like(a)
    for s in (32, 16, 8, 4, 2, 1):
        big = (a >> s) > 0
        a = jnp.where(big, a >> s, a)
        r = r + jnp.where(big, s, 0)
    return r


def _rshift_rne(v, sh):
    """Arithmetic right shift with round-to-nearest-even on the dropped bits."""
    sh = jnp.maximum(sh, 0)
    q = v >> sh
    rem = v - (q << sh)
    half = jnp.where(sh > 0, _I64(1) << jnp.maximum(sh - 1, 0), _I64(0))
    round_up = ((rem > half) | ((rem == half) & ((q & 1) == 1))) & (sh > 0)
    return q + round_up.astype(jnp.int64)


def _align(xfix, yfix, ex, ey, N, round_mode):
    """Shift the significand with the smaller exponent right by |ex - ey|.

    round_mode: 'rne' | 'trunc' (conventional) | 'hub' (truncation *is* RN).
    The shifter forces exact zero when the distance exceeds the word width.
    """
    d_xy = ex - ey
    x_is_low = d_xy < 0
    sh = jnp.abs(d_xy)
    lo = jnp.where(x_is_low, xfix, yfix)
    if round_mode == "rne":
        lo_sh = _rshift_rne(lo, sh)
    else:  # 'trunc' and 'hub': plain arithmetic shift
        lo_sh = lo >> jnp.minimum(sh, 62)
    lo_sh = jnp.where(sh >= N + 2, _I64(0), lo_sh)
    xout = jnp.where(x_is_low, lo_sh, xfix)
    yout = jnp.where(x_is_low, yfix, lo_sh)
    m_exp = jnp.maximum(ex, ey)
    return xout, yout, m_exp


def _expand_ieee(sign, exp_raw, man, fmt: FloatFormat, N):
    """Packed fields -> N-bit two's-complement significand (no alignment yet)."""
    is_zero = exp_raw == 0
    k_ext = N - 2 - fmt.man_bits  # appended zeros; requires N >= m + 2
    mag = ((_I64(1) << fmt.man_bits) | man) << k_ext
    mag = jnp.where(is_zero, 0, mag)
    return jnp.where(sign == 1, -mag, mag)


def input_convert_ieee(x_packed, y_packed, fmt: FloatFormat, N, rounding="rne"):
    """Conventional input converter (Fig. 2). rounding: 'rne' | 'trunc'."""
    sx, ex, mx = unpack_fields(x_packed, fmt)
    sy, ey, my = unpack_fields(y_packed, fmt)
    xf = _expand_ieee(sx, ex, mx, fmt, N)
    yf = _expand_ieee(sy, ey, my, fmt, N)
    return _align(xf, yf, ex, ey, N, rounding)


def _expand_hub(sign, exp_raw, man, fmt: FloatFormat, N,
                unbiased: bool, detect_identity: bool):
    """Packed HUB fields -> N-bit HUB significand (Fig. 5).

    Extension below the m explicit fraction bits (k = N-2-m bits):
      biased   : ILSB '1' then zeros                     ('1000...')
      unbiased : explicit-LSB then its inverse repeated  ('1000..'/'0111..')
      identity : exact 1.0 detected (exp==bias, man==0) -> all-zero extension,
                 so the fixed-point HUB word is 1.0 + 2^-(N-1) instead of
                 1.0 + 2^-(m+1).
    """
    is_zero = exp_raw == 0
    k = N - 2 - fmt.man_bits
    base = ((_I64(1) << fmt.man_bits) | man) << k
    km1 = jnp.maximum(k - 1, 0)
    top = _I64(1) << km1
    if unbiased:
        lsb = man & 1
        ext = jnp.where(lsb == 1, top, top - 1)
    else:
        ext = top
    ext = jnp.where(k > 0, ext, 0)
    if detect_identity:
        is_one = (exp_raw == fmt.bias) & (man == 0)
        ext = jnp.where(is_one, 0, ext)
    mag = base | ext
    mag = jnp.where(is_zero, 0, mag)
    # HUB negation: pure bit inversion (the ILSB absorbs the +1).
    return jnp.where(sign == 1, ~mag, mag)


def input_convert_hub(x_packed, y_packed, fmt: FloatFormat, N,
                      unbiased=True, detect_identity=True):
    """HUB input converter (Fig. 5)."""
    sx, ex, mx = unpack_fields(x_packed, fmt)
    sy, ey, my = unpack_fields(y_packed, fmt)
    xf = _expand_hub(sx, ex, mx, fmt, N, unbiased, detect_identity)
    yf = _expand_hub(sy, ey, my, fmt, N, unbiased, detect_identity)
    return _align(xf, yf, ex, ey, N, "hub")


def _saturate_pack(sign, exp_new, man, fmt: FloatFormat, flush_zero):
    overflow = exp_new > fmt.max_exp_raw
    exp_out = jnp.clip(exp_new, 1, fmt.max_exp_raw)
    man = jnp.where(overflow, (1 << fmt.man_bits) - 1, man)
    packed = pack_fields(sign, exp_out, man, fmt)
    underflow = (exp_new <= 0) | flush_zero
    return jnp.where(underflow, sign << (fmt.exp_bits + fmt.man_bits), packed)


def output_convert_ieee(v, m_exp, fmt: FloatFormat, N):
    """Conventional output converter (Fig. 4): normalize + RNE + exponent."""
    v = _I64(v)
    sign = (v < 0).astype(jnp.int64)
    a = jnp.abs(v)
    is_zero = a == 0
    a_safe = jnp.where(is_zero, 1, a)
    k = ilog2(a_safe)  # leading-one position
    m = fmt.man_bits
    # Keep m+1 significant bits with RNE on the discarded ones.
    down = jnp.maximum(k - m, 0)
    up = jnp.maximum(m - k, 0)
    q = _rshift_rne(a_safe, down) << up
    # Rounding may carry out: q == 2^(m+1).
    carry = q >> (m + 1)
    q = jnp.where(carry > 0, q >> 1, q)
    k = k + carry
    man = q - (_I64(1) << m)
    exp_new = m_exp + k - (N - 2)
    return _saturate_pack(sign, exp_new, man, fmt, is_zero)


def output_convert_hub(v, m_exp, fmt: FloatFormat, N, unbiased=True):
    """HUB output converter (Fig. 7): invert-negate, append ILSB, truncate.

    No sticky bit, no round-up adder, no mantissa-overflow path — truncation
    of a HUB word is round-to-nearest.
    """
    v = _I64(v)
    sign = (v < 0).astype(jnp.int64)
    stored = jnp.where(sign == 1, ~v, v)  # |value| stored part, >= 0
    A = (stored << 1) | 1                  # append the explicit ILSB
    k2 = ilog2(A)                          # A >= 1 always
    m = fmt.man_bits
    down = jnp.maximum(k2 - m, 0)
    up = jnp.maximum(m - k2, 0)
    hi = A >> down                         # truncation == RN for HUB
    if unbiased:
        # bits shifted in during left normalization: first = stored LSB,
        # rest = its inverse ('1000...' / '0111...'), Sec. 4.3.
        lsb = stored & 1
        upm1 = jnp.maximum(up - 1, 0)
        fill = jnp.where(lsb == 1, _I64(1) << upm1, (_I64(1) << upm1) - 1)
        fill = jnp.where(up > 0, fill, 0)
    else:
        fill = _I64(0)
    q = (hi << up) | fill
    man = q - (_I64(1) << m)
    exp_new = m_exp + (k2 - 1) - (N - 2)
    # The all-inverted zero (stored == 0 from v == -1) etc. round through the
    # normal path; true zero only via exponent underflow.
    return _saturate_pack(sign, exp_new, man, fmt, jnp.zeros_like(sign, bool))
