"""Dual-int32 lane emulation of the packed int64 Givens datapath.

Why this exists: the packed-word QRD kernels (`kernels/qrd_blocked.py`)
carry IEEE/HUB words as int64 lanes, and both Mosaic (TPU) and Triton
(GPU) reject 64-bit integer vector lanes — so the `cordic_pallas`
backend has been pinned to interpret mode since PR 1.  This module
re-expresses the entire unit (input converter -> CORDIC -> gain
compensation -> output converter, `repro.core.{converters,cordic,
givens}`) over *pairs of 32-bit lanes*, so the same kernels lower
through the hardware compilers.

Representation
--------------
A packed int64 word ``p`` is carried as two int32 lanes stacked on a
trailing axis of size 2::

    L[..., 0] = hi = int32(p >> 32)          # sign-carrying half
    L[..., 1] = lo = int32(p & 0xFFFFFFFF)   # bit pattern of the low half

(`kernels.cordic_givens.packed_to_lanes` / `lanes_to_packed` are the
host-side converters.)  Internally every primitive operates on a
``(hi, lo)`` tuple of **uint32** arrays — unsigned lanes make the
carry/borrow compares and the logical cross-lane shifts natural; the
sign only matters for arithmetic shifts and comparisons, which view the
high lane as int32.

Bit-exactness contract
----------------------
Every emulated primitive computes the exact low 64 bits of its int64
counterpart (two's complement is modular, so add/sub/mul agree between
signed and unsigned interpretations).  Shift amounts are clamped to
[0, 63]; the datapath masks any shift >= N + 2 to exact zero downstream
(`_align`), so the clamp can never be observed for supported N <= 50.
`ilog2` is an exact integer binary search (the int64 path detours
through float64 `frexp`, which Mosaic also rejects).  `LaneUnit` is
asserted bit-identical to `GivensUnit` by tests/test_packed_lanes.py.

Only static ``N`` / ``iters`` are supported (the kernel-resident case);
the traced-parameter sweep path stays on the int64 reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cordic
from repro.core.formats import FloatFormat

__all__ = ["LaneUnit", "lanes_stack", "lanes_unstack"]

_U32 = jnp.uint32
_I32 = jnp.int32
_M32 = 0xFFFFFFFF


# -- lane word construction ---------------------------------------------------

def u64(v: int):
    """Python int -> (hi, lo) uint32 scalar pair (two's complement)."""
    return (jnp.asarray((v >> 32) & _M32, _U32),
            jnp.asarray(v & _M32, _U32))


_ZERO = 0          # built lazily: u64 at trace time keeps constants local
_ONE = 1


def lanes_unstack(L):
    """Stacked int32 (..., 2) lane word -> (hi, lo) uint32 tuple."""
    return L[..., 0].astype(_U32), L[..., 1].astype(_U32)


def lanes_stack(pair):
    """(hi, lo) uint32 tuple -> stacked int32 (..., 2) lane word."""
    h, l = pair
    return jnp.stack([h.astype(_I32), l.astype(_I32)], axis=-1)


def _split(L):
    """Stacked (..., 2) lane word -> (hi, lo) tuple, dtype kept."""
    return L[..., 0], L[..., 1]


def _u32_pair(pair):
    return pair[0].astype(_U32), pair[1].astype(_U32)


def _i32_pair(pair):
    return pair[0].astype(_I32), pair[1].astype(_I32)


def from_i32(x):
    """Sign-extend an int32 array (small field values) to a lane pair."""
    x = jnp.asarray(x, _I32)
    return ((x >> 31).astype(_U32), x.astype(_U32))


def _low(x):
    """Nonnegative int32 array -> lane pair with zero high half."""
    x = jnp.asarray(x, _I32)
    return (jnp.zeros_like(x, _U32), x.astype(_U32))


# -- 64-bit integer primitives over (hi, lo) uint32 pairs ---------------------

def add64(a, b):
    ah, al = a
    bh, bl = b
    l = al + bl
    carry = (l < al).astype(_U32)
    return ah + bh + carry, l


def sub64(a, b):
    ah, al = a
    bh, bl = b
    borrow = (al < bl).astype(_U32)
    return ah - bh - borrow, al - bl


def not64(a):
    return ~a[0], ~a[1]


def neg64(a):
    return add64(not64(a), u64(1))


def and64(a, b):
    return a[0] & b[0], a[1] & b[1]


def or64(a, b):
    return a[0] | b[0], a[1] | b[1]


def xor64(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def eq64(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def ltu64(a, b):
    """Unsigned a < b."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def is_neg64(a):
    return a[0].astype(_I32) < 0


def where64(cond, a, b):
    return jnp.where(cond, a[0], b[0]), jnp.where(cond, a[1], b[1])


def _shift_norm(s):
    """Normalize a shift amount (python int or traced) to int32 in [0, 63]."""
    return jnp.clip(jnp.asarray(s, _I32), 0, 63)


def shl64(v, s):
    h, l = v
    s = _shift_norm(s)
    s_lo = jnp.minimum(s, 31)
    su = s_lo.astype(_U32)
    # cross = l >> (32 - s) for s in [1, 31], 0 for s == 0 — the two-step
    # shift avoids the undefined shift-by-32 at s == 0.
    cross = (l >> (31 - s_lo).astype(_U32)) >> _U32(1)
    h_small = (h << su) | cross
    l_small = l << su
    sb = jnp.clip(s - 32, 0, 31).astype(_U32)
    big = s >= 32
    return (jnp.where(big, l << sb, h_small),
            jnp.where(big, _U32(0), l_small))


def shr64(v, s):
    """Logical (zero-fill) right shift."""
    h, l = v
    s = _shift_norm(s)
    s_lo = jnp.minimum(s, 31)
    su = s_lo.astype(_U32)
    cross = (h << (31 - s_lo).astype(_U32)) << _U32(1)   # h << (32 - s)
    l_small = (l >> su) | cross
    h_small = h >> su
    sb = jnp.clip(s - 32, 0, 31).astype(_U32)
    big = s >= 32
    return (jnp.where(big, _U32(0), h_small),
            jnp.where(big, h >> sb, l_small))


def sar64(v, s):
    """Arithmetic (sign-fill) right shift."""
    h, l = v
    hs = h.astype(_I32)
    s = _shift_norm(s)
    s_lo = jnp.minimum(s, 31)
    su = s_lo.astype(_U32)
    cross = (h << (31 - s_lo).astype(_U32)) << _U32(1)
    l_small = (l >> su) | cross
    h_small = (hs >> s_lo).astype(_U32)
    sb = jnp.clip(s - 32, 0, 31)
    sign_fill = (hs >> 31).astype(_U32)
    big = s >= 32
    return (jnp.where(big, sign_fill, h_small),
            jnp.where(big, (hs >> sb).astype(_U32), l_small))


def _mul32x32(x, y):
    """Exact uint32 x uint32 -> (hi, lo) uint32 pair via 16-bit digits."""
    m16 = _U32(0xFFFF)
    x0, x1 = x & m16, x >> _U32(16)
    y0, y1 = y & m16, y >> _U32(16)
    p00 = x0 * y0
    p01 = x0 * y1
    p10 = x1 * y0
    p11 = x1 * y1
    mid = (p00 >> _U32(16)) + (p01 & m16) + (p10 & m16)   # < 2^18, no wrap
    lo = (p00 & m16) | ((mid & m16) << _U32(16))
    hi = p11 + (p01 >> _U32(16)) + (p10 >> _U32(16)) + (mid >> _U32(16))
    return hi, lo


def mul64(a, b):
    """Low 64 bits of the product (signed == unsigned mod 2^64)."""
    ah, al = a
    bh, bl = b
    hi, lo = _mul32x32(al, bl)
    cross = al * bh + ah * bl          # uint32 wrap keeps exactly the low 32
    return hi + cross, lo


def ilog2_32(u):
    """floor(log2(u)) for uint32 u >= 1 (0 for u == 0), pure integer."""
    r = jnp.where(u > _U32(0xFFFF), _I32(16), _I32(0))
    u = u >> r.astype(_U32)
    s = jnp.where(u > _U32(0xFF), _I32(8), _I32(0))
    u = u >> s.astype(_U32)
    r = r + s
    s = jnp.where(u > _U32(0xF), _I32(4), _I32(0))
    u = u >> s.astype(_U32)
    r = r + s
    s = jnp.where(u > _U32(0x3), _I32(2), _I32(0))
    u = u >> s.astype(_U32)
    r = r + s
    return r + jnp.where(u > _U32(0x1), _I32(1), _I32(0))


def ilog2_64(v):
    """floor(log2(v)) for a positive lane pair, int32 result."""
    h, l = v
    use_hi = h != 0
    k = ilog2_32(jnp.where(use_hi, h, l))
    return jnp.where(use_hi, k + 32, k)


def rshift_rne64(v, sh):
    """Arithmetic right shift with round-to-nearest-even on dropped bits.

    Lane mirror of `repro.core.converters._rshift_rne`; sh is clamped to
    [0, 63] (divergence beyond that is masked by the `_align` zero-force,
    identically to the int64 path's own undefined-shift masking).
    """
    sh = jnp.maximum(jnp.asarray(sh, _I32), 0)
    q = sar64(v, sh)
    rem = sub64(v, shl64(q, sh))
    half = shl64(u64(1), jnp.maximum(sh - 1, 0))
    half = where64(sh > 0, half, u64(0))
    round_up = ((ltu64(half, rem)
                 | (eq64(rem, half) & ((q[1] & _U32(1)) == 1)))
                & (sh > 0))
    return add64(q, (jnp.zeros_like(q[0]), round_up.astype(_U32)))


# -- converter datapath (lane mirror of repro.core.converters) ----------------

def _unpack(p, fmt: FloatFormat):
    man = and64(p, u64((1 << fmt.man_bits) - 1))
    exp_raw = (shr64(p, fmt.man_bits)[1]
               & _U32((1 << fmt.exp_bits) - 1)).astype(_I32)
    sign = (shr64(p, fmt.exp_bits + fmt.man_bits)[1] & _U32(1)).astype(_I32)
    return sign, exp_raw, man


def _align(xfix, yfix, ex, ey, N, round_mode):
    d_xy = ex - ey
    x_is_low = d_xy < 0
    sh = jnp.abs(d_xy)
    lo = where64(x_is_low, xfix, yfix)
    if round_mode == "rne":
        lo_sh = rshift_rne64(lo, sh)
    else:  # 'trunc' and 'hub': plain arithmetic shift
        lo_sh = sar64(lo, jnp.minimum(sh, 62))
    lo_sh = where64(sh >= N + 2, u64(0), lo_sh)
    xout = where64(x_is_low, lo_sh, xfix)
    yout = where64(x_is_low, yfix, lo_sh)
    return xout, yout, jnp.maximum(ex, ey)


def _expand_ieee(sign, exp_raw, man, fmt: FloatFormat, N):
    is_zero = exp_raw == 0
    k_ext = N - 2 - fmt.man_bits
    mag = shl64(or64(man, u64(1 << fmt.man_bits)), k_ext)
    mag = where64(is_zero, u64(0), mag)
    return where64(sign == 1, neg64(mag), mag)


def _expand_hub(sign, exp_raw, man, fmt: FloatFormat, N,
                unbiased: bool, detect_identity: bool):
    is_zero = exp_raw == 0
    k = N - 2 - fmt.man_bits          # static here (LaneUnit: static N only)
    base = shl64(or64(man, u64(1 << fmt.man_bits)), k)
    top = 1 << max(k - 1, 0)
    if unbiased:
        lsb = (man[1] & _U32(1)).astype(_I32)
        ext = where64(lsb == 1, u64(top), u64(top - 1))
    else:
        ext = u64(top)
    if k <= 0:
        ext = u64(0)
    if detect_identity:
        is_one = (exp_raw == fmt.bias) & eq64(man, u64(0))
        ext = where64(is_one, u64(0), ext)
    mag = or64(base, ext)
    mag = where64(is_zero, u64(0), mag)
    # HUB negation: pure bit inversion (the ILSB absorbs the +1).
    return where64(sign == 1, not64(mag), mag)


def _input_convert(xp, yp, cfg, N):
    fmt = cfg.fmt
    sx, ex, mx = _unpack(xp, fmt)
    sy, ey, my = _unpack(yp, fmt)
    if cfg.hub:
        xf = _expand_hub(sx, ex, mx, fmt, N, cfg.unbiased, cfg.detect_identity)
        yf = _expand_hub(sy, ey, my, fmt, N, cfg.unbiased, cfg.detect_identity)
        return _align(xf, yf, ex, ey, N, "hub")
    xf = _expand_ieee(sx, ex, mx, fmt, N)
    yf = _expand_ieee(sy, ey, my, fmt, N)
    return _align(xf, yf, ex, ey, N, cfg.input_rounding)


def _saturate_pack(sign, exp_new, man, fmt: FloatFormat, flush_zero):
    overflow = exp_new > fmt.max_exp_raw
    exp_out = jnp.clip(exp_new, 1, fmt.max_exp_raw)
    man = where64(overflow, u64((1 << fmt.man_bits) - 1), man)
    packed = or64(shl64(_low(sign), fmt.exp_bits + fmt.man_bits),
                  or64(shl64(_low(exp_out), fmt.man_bits), man))
    underflow = (exp_new <= 0) | flush_zero
    szero = shl64(_low(sign), fmt.exp_bits + fmt.man_bits)
    return where64(underflow, szero, packed)


def _output_ieee(v, m_exp, fmt: FloatFormat, N):
    neg = is_neg64(v)
    sign = neg.astype(_I32)
    a = where64(neg, neg64(v), v)
    is_zero = eq64(a, u64(0))
    a_safe = where64(is_zero, u64(1), a)
    k = ilog2_64(a_safe)
    m = fmt.man_bits
    down = jnp.maximum(k - m, 0)
    up = jnp.maximum(m - k, 0)
    q = shl64(rshift_rne64(a_safe, down), up)
    carry = (shr64(q, m + 1)[1]).astype(_I32)      # 0 or 1
    q = where64(carry > 0, sar64(q, 1), q)
    k = k + carry
    man = sub64(q, u64(1 << m))
    exp_new = m_exp + k - (N - 2)
    return _saturate_pack(sign, exp_new, man, fmt, is_zero)


def _output_hub(v, m_exp, fmt: FloatFormat, N, unbiased: bool):
    neg = is_neg64(v)
    sign = neg.astype(_I32)
    stored = where64(neg, not64(v), v)
    A = or64(shl64(stored, 1), u64(1))             # append the explicit ILSB
    k2 = ilog2_64(A)
    m = fmt.man_bits
    down = jnp.maximum(k2 - m, 0)
    up = jnp.maximum(m - k2, 0)
    hi = sar64(A, down)                            # truncation == RN for HUB
    if unbiased:
        lsb = (stored[1] & _U32(1)).astype(_I32)
        upm1 = jnp.maximum(up - 1, 0)
        fill = where64(lsb == 1, shl64(u64(1), upm1),
                       sub64(shl64(u64(1), upm1), u64(1)))
        fill = where64(up > 0, fill, u64(0))
    else:
        fill = u64(0)
    q = or64(shl64(hi, up), fill)
    man = sub64(q, u64(1 << m))
    exp_new = m_exp + (k2 - 1) - (N - 2)
    return _saturate_pack(sign, exp_new, man, fmt,
                          jnp.zeros_like(sign, bool))


def _output_convert(v, m_exp, cfg, N):
    if cfg.hub:
        return _output_hub(v, m_exp, cfg.fmt, N, cfg.unbiased)
    return _output_ieee(v, m_exp, cfg.fmt, N)


# -- CORDIC core (lane mirror of repro.core.cordic) ---------------------------

def _negate_fx(v, hub: bool):
    return not64(v) if hub else neg64(v)


def _carry_bit(y, i):
    """HUB carry-in: ILSB (1) at i == 0, else bit (i-1) of the pre-shift y."""
    bit = (sar64(y, jnp.maximum(i - 1, 0))[1] & _U32(1)).astype(_I32)
    return jnp.where(i == 0, _I32(1), bit)


def _microrotation(x, y, i, d_pos, hub: bool):
    ys = sar64(y, i)
    xs = sar64(x, i)
    if hub:
        cy = _carry_bit(y, i)
        cx = _carry_bit(x, i)
        x_sub = add64(add64(x, not64(ys)), _low(1 - cy))   # x - (y>>i)
        x_add = add64(add64(x, ys), _low(cy))              # x + (y>>i)
        y_add = add64(add64(y, xs), _low(cx))              # y + (x>>i)
        y_sub = add64(add64(y, not64(xs)), _low(1 - cx))   # y - (x>>i)
    else:
        x_sub = sub64(x, ys)
        x_add = add64(x, ys)
        y_add = add64(y, xs)
        y_sub = sub64(y, xs)
    return (where64(d_pos, x_sub, x_add), where64(d_pos, y_add, y_sub))


def _vectoring(x, y, iters, hub: bool):
    flip = is_neg64(x).astype(_I32)
    x = where64(flip == 1, _negate_fx(x, hub), x)
    y = where64(flip == 1, _negate_fx(y, hub), y)

    def body(i, carry):
        xh, xl, yh, yl, sh, sl = carry
        cx, cy, sig = (xh, xl), (yh, yl), (sh, sl)
        d_pos = is_neg64(cy)
        nx, ny = _microrotation(cx, cy, i, d_pos, hub)
        bit = (jnp.zeros_like(sh), d_pos.astype(_U32))
        sig = or64(sig, shl64(bit, i))
        return (*nx, *ny, *sig)

    z = jnp.zeros_like(x[0])
    # int32 loop bounds: under x64 a Python-int bound makes the counter
    # int64, which Mosaic cannot lower
    out = jax.lax.fori_loop(_I32(0), _I32(iters), body, (*x, *y, z, z))
    return ((out[0], out[1]), (out[2], out[3]), flip, (out[4], out[5]))


def _rotation(x, y, flip, sig, iters, hub: bool):
    x = where64(flip == 1, _negate_fx(x, hub), x)
    y = where64(flip == 1, _negate_fx(y, hub), y)

    def body(i, carry):
        xh, xl, yh, yl = carry
        d_pos = (shr64(sig, i)[1] & _U32(1)) == 1
        nx, ny = _microrotation((xh, xl), (yh, yl), i, d_pos, hub)
        return (*nx, *ny)

    out = jax.lax.fori_loop(_I32(0), _I32(iters), body, (*x, *y))
    return (out[0], out[1]), (out[2], out[3])


def _fixmul(v, comp: int, p: int, round_nearest: bool):
    """Lane mirror of `cordic.fixmul` with a static comp constant."""
    v_lo = and64(v, u64(0xFFFF))
    v_hi = sar64(v, 16)
    comp_p = u64(comp)
    acc = add64(mul64(v_hi, comp_p), sar64(mul64(v_lo, comp_p), 16))
    sh = p - 16
    if round_nearest:
        acc = add64(acc, u64(1 << (sh - 1)))
    return sar64(acc, sh)


def _apply_gain(x, y, iters: int, w: int, hub: bool):
    p = int(min(78 - w, 46))
    # The identical IEEE-double rounding as `cordic.gain_comp_constant`,
    # kept in numpy: the constant must be a Python int inside the kernel.
    inv_gain = np.float64(1.0) / np.float64(cordic.GAIN_TABLE[iters])
    comp = int(np.rint(inv_gain * np.exp2(np.float64(p))))
    rn = not hub
    return _fixmul(x, comp, p, rn), _fixmul(y, comp, p, rn)


# -- the unit -----------------------------------------------------------------

class LaneUnit:
    """Lane-pair mirror of `repro.core.givens.GivensUnit`.

    All methods operate on *stacked* lane words: int32 arrays with a
    trailing axis of size 2 holding the (hi, lo) halves of each packed
    int64 word.  The rotation state is ``(flip, sig)`` with ``flip`` an
    int32 0/1 array and ``sig`` a stacked lane word (the sigma bitmask
    may need up to iters <= 48 bits).  Bit-identical to `GivensUnit` on
    the int64 packing of the same words; static ``N`` / ``iters`` only.
    """

    def __init__(self, config):
        config.validate()
        self.cfg = config

    def vector_lanes(self, x, y):
        """`vector` on unstacked words: ``(hi, lo)`` int32 tuples in and out.

        The kernel-side entry point — no trailing lane axis, which Mosaic
        would lay out on the 128-wide vector lane dimension.  Returns
        ``(xr, yr, (flip, sig))`` with ``sig`` a ``(hi, lo)`` uint32 pair.
        """
        cfg = self.cfg
        N = cfg.n
        iters = cfg.resolved_iters()
        xf, yf, m_exp = _input_convert(_u32_pair(x), _u32_pair(y), cfg, N)
        xr, yr, flip, sig = _vectoring(xf, yf, iters, cfg.hub)
        xr, yr = _apply_gain(xr, yr, iters, N + 2, cfg.hub)
        return (_i32_pair(_output_convert(xr, m_exp, cfg, N)),
                _i32_pair(_output_convert(yr, m_exp, cfg, N)),
                (flip, sig))

    def rotate_lanes(self, x, y, state):
        """`rotate` on unstacked ``(hi, lo)`` int32 tuples; ``state`` as
        returned by `vector_lanes`."""
        cfg = self.cfg
        N = cfg.n
        iters = cfg.resolved_iters()
        flip, sig = state
        xf, yf, m_exp = _input_convert(_u32_pair(x), _u32_pair(y), cfg, N)
        xr, yr = _rotation(xf, yf, flip, sig, iters, cfg.hub)
        xr, yr = _apply_gain(xr, yr, iters, N + 2, cfg.hub)
        return (_i32_pair(_output_convert(xr, m_exp, cfg, N)),
                _i32_pair(_output_convert(yr, m_exp, cfg, N)))

    def vector(self, xp, yp):
        xr, yr, (flip, sig) = self.vector_lanes(_split(xp), _split(yp))
        return (lanes_stack(xr), lanes_stack(yr), (flip, lanes_stack(sig)))

    def rotate(self, xp, yp, state):
        flip, sig = state
        xr, yr = self.rotate_lanes(_split(xp), _split(yp),
                                   (flip, lanes_unstack(sig)))
        return lanes_stack(xr), lanes_stack(yr)

    def rotate_rows(self, row_x, row_y):
        """Rotate two stacked-lane rows (..., e, 2); vectoring on element 0."""
        rx0, ry0, (flip, sig) = self.vector(row_x[..., 0, :],
                                            row_y[..., 0, :])
        rx, ry = self.rotate(row_x[..., 1:, :], row_y[..., 1:, :],
                             (flip[..., None], sig[..., None, :]))
        return (jnp.concatenate([rx0[..., None, :], rx], axis=-2),
                jnp.concatenate([ry0[..., None, :], ry], axis=-2))
