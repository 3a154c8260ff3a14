"""Pure-jnp oracles for the Pallas CORDIC kernels.

These mirror the kernel arithmetic *operation for operation* (int32 lanes,
15x15-bit gain multiply) so tests can assert exact integer equality against
the kernels, shape-by-shape.  A second set of tests cross-checks these
oracles against the independent int64 implementation in `repro.core.cordic`.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.cordic import GAIN_TABLE

__all__ = ["vectoring_ref", "rotation_ref", "gain_mul_q30_ref"]


def gain_mul_q30_ref(v, comp: int):
    c_hi = comp >> 15
    c_lo = comp & 0x7FFF
    v_hi = v >> 15
    v_lo = v & 0x7FFF
    mid = v_hi * c_lo + v_lo * c_hi + ((v_lo * c_lo) >> 15) + (1 << 14)
    return v_hi * c_hi + (mid >> 15)


def _negate(v, hub):
    return ~v if hub else -v


def _micro(x, y, i, d_pos, hub):
    ys = y >> i
    xs = x >> i
    if hub:
        one = jnp.int32(1)
        cy = one if i == 0 else (y >> (i - 1)) & 1
        cx = one if i == 0 else (x >> (i - 1)) & 1
        x_sub = x + ~ys + (1 - cy)
        x_add = x + ys + cy
        y_add = y + xs + cx
        y_sub = y + ~xs + (1 - cx)
    else:
        x_sub = x - ys
        x_add = x + ys
        y_add = y + xs
        y_sub = y - xs
    return (jnp.where(d_pos, x_sub, x_add),
            jnp.where(d_pos, y_add, y_sub))


def _comp(iters: int) -> int:
    return int(np.rint(2.0 ** 30 / GAIN_TABLE[iters]))


def vectoring_ref(x, y, *, iters: int, hub: bool):
    """x, y: int32 arrays (any shape) -> (xr, yr, flip, sigma)."""
    x = jnp.asarray(x, jnp.int32)
    y = jnp.asarray(y, jnp.int32)
    flip = x < 0
    x = jnp.where(flip, _negate(x, hub), x)
    y = jnp.where(flip, _negate(y, hub), y)
    sig = jnp.zeros_like(x)
    for i in range(iters):
        d_pos = y < 0
        x, y = _micro(x, y, i, d_pos, hub)
        sig = sig | (d_pos.astype(jnp.int32) << i)
    comp = _comp(iters)
    return (gain_mul_q30_ref(x, comp), gain_mul_q30_ref(y, comp),
            flip.astype(jnp.int32), sig)


def rotation_ref(x, y, flip, sigma, *, iters: int, hub: bool):
    """x, y: int32 (B, L); flip/sigma: int32 broadcastable -> rotated pair."""
    x = jnp.asarray(x, jnp.int32)
    y = jnp.asarray(y, jnp.int32)
    fl = jnp.asarray(flip, jnp.int32) != 0
    sig = jnp.asarray(sigma, jnp.int32)
    x = jnp.where(fl, _negate(x, hub), x)
    y = jnp.where(fl, _negate(y, hub), y)
    for i in range(iters):
        d_pos = ((sig >> i) & 1) == 1
        x, y = _micro(x, y, i, d_pos, hub)
    comp = _comp(iters)
    return gain_mul_q30_ref(x, comp), gain_mul_q30_ref(y, comp)
