"""Pallas TPU kernels for the fixed-point CORDIC Givens rotator.

TPU adaptation of the paper's pipeline (DESIGN.md §2): the FPGA's
one-element-per-cycle pipeline becomes lane-parallel integer arithmetic on
the VPU.  Two kernels:

  vectoring kernel : a (TB, 1) tile of leading element pairs; each lane runs
                     the full micro-rotation recurrence and packs its sigma
                     direction bits into one int32 word (+ a flip bit).
                     "Compute the tiny control word once."
  rotation kernel  : a (TB, TL) tile of row elements; the per-row sigma words
                     (one int32 per row, VMEM (TB,1) column) broadcast across
                     the 128-lane axis and the recurrence replays in parallel.
                     "Broadcast it across the wide vector."

Datapath: int32, w = N + 2 bits (N <= 28; N = 26 is the paper's recommended
single-precision config).  The CORDIC gain is compensated in-kernel with a
15x15-bit partial-product multiply (Q30 constant) so every intermediate fits
int32 — the same reasoning as the paper's "compensation in the embedded
multipliers".

Both kernels carry a static `hub` flag switching the add/sub arithmetic to
Half-Unit-Biased semantics (negate-by-inversion + the Fig. 6 carry-in rule).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.cordic import GAIN_TABLE

__all__ = ["vectoring_call", "rotation_call", "fused_call",
           "fused_rotate_block", "fused_rotate_pairs", "fused_replay",
           "comp_q30", "x32_trace", "packed_to_lanes", "lanes_to_packed",
           "TILE_B", "TILE_L"]

TILE_B = 8     # sublane tile (int32 native tile is (8, 128))
TILE_L = 128   # lane tile


def x32_trace(enabled: bool = True):
    """Context for tracing a compiled kernel: x64 off inside it.

    The package turns x64 on globally (the host-side packed words are
    int64), so every Python int in an index map or a kernel body would
    trace as an int64 scalar — and Mosaic refuses 64-bit values ("failed
    to legalize operation 'func.return'", "64-bit types are not
    supported").  The 32-bit datapaths build their `pallas_call` inside
    this context, where those scalars are int32; their operands are
    32-bit by construction, so the arithmetic is unchanged.  The int64
    datapaths (interpret mode only) pass ``enabled=False``.
    """
    return jax.enable_x64(False) if enabled else contextlib.nullcontext()


def packed_to_lanes(p):
    """Packed int64 FP words -> stacked (hi, lo) int32 lane words (..., 2).

    The hi/lo split that makes the packed-word kernels compilable: Mosaic
    and Triton reject 64-bit integer lanes, so the compiled datapath
    (`repro.kernels.packed_lanes`) carries each word as two int32 lanes
    — ``[..., 0] = int32(p >> 32)``, ``[..., 1] = int32(p)``.  Exact
    (two's complement) and inverted by `lanes_to_packed`.
    """
    p = jnp.asarray(p, jnp.int64)
    return jnp.stack([(p >> 32).astype(jnp.int32), p.astype(jnp.int32)],
                     axis=-1)


def lanes_to_packed(L):
    """Stacked (hi, lo) int32 lane words (..., 2) -> packed int64 FP words."""
    hi = L[..., 0].astype(jnp.int64)
    lo = L[..., 1].astype(jnp.int64) & 0xFFFFFFFF
    return (hi << 32) | lo


def comp_q30(iters: int) -> int:
    """Gain compensation constant in Q30: round(2^30 / K(iters))."""
    return int(np.rint(2.0 ** 30 / GAIN_TABLE[iters]))


def _gain_mul_q30(v, comp: int):
    """(v * comp) >> 30, rounded to nearest, all partial products in int32.

    v: w-bit int32 (|v| < 2^29); comp: Q30 constant < 2^30.  Split both
    into 15-bit halves; the three low partial products are summed before
    one rounding shift (each < 2^30, the sum < 2^31).  Rounding, not
    truncation: a truncating shift biases every gain compensation toward
    -inf, and the bias accumulates over the m rotations of a column.
    """
    c_hi = comp >> 15
    c_lo = comp & 0x7FFF
    v_hi = v >> 15          # arithmetic: keeps the sign
    v_lo = v & 0x7FFF
    mid = v_hi * c_lo + v_lo * c_hi + ((v_lo * c_lo) >> 15) + (1 << 14)
    return v_hi * c_hi + (mid >> 15)


def _microrotation(x, y, i: int, d_pos, hub: bool):
    """x' = x - d*(y>>i), y' = y + d*(x>>i); d_pos lanes: d = +1."""
    ys = y >> i
    xs = x >> i
    if hub:
        cy = jnp.int32(1) if i == 0 else (y >> (i - 1)) & 1
        cx = jnp.int32(1) if i == 0 else (x >> (i - 1)) & 1
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


def _negate(v, hub: bool):
    return ~v if hub else -v


# ---------------------------------------------------------------------------
# Vectoring kernel
# ---------------------------------------------------------------------------
def _vectoring_kernel(x_ref, y_ref, xo_ref, yo_ref, flip_ref, sig_ref,
                      *, iters: int, hub: bool, comp: int):
    x = x_ref[...]
    y = y_ref[...]
    flip, sig = _vector_lead(x, y, iters=iters, hub=hub)
    # the leading pair's replay by its own sigma IS the vectoring result
    xo_ref[...], yo_ref[...] = fused_replay(x, y, flip, sig, iters=iters,
                                            hub=hub, comp=comp)
    flip_ref[...] = flip.astype(jnp.int32)
    sig_ref[...] = sig


def vectoring_call(x, y, *, iters: int, hub: bool, interpret: bool = True):
    """x, y: (B, 1) int32 block-FP significands -> (xr, yr, flip, sigma).

    B must be a multiple of TILE_B (ops.py pads).
    """
    B = x.shape[0]
    assert x.shape == (B, 1) and B % TILE_B == 0 and iters <= 30
    grid = (B // TILE_B,)
    spec = pl.BlockSpec((TILE_B, 1), lambda b: (b, 0))
    out_shape = [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 4
    kernel = functools.partial(_vectoring_kernel, iters=iters, hub=hub,
                               comp=comp_q30(iters))
    with x32_trace():
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[spec, spec],
            out_specs=[spec, spec, spec, spec],
            out_shape=out_shape,
            interpret=interpret,
        )(x, y)


# ---------------------------------------------------------------------------
# Rotation kernel
# ---------------------------------------------------------------------------
def _rotation_kernel(flip_ref, sig_ref, x_ref, y_ref, xo_ref, yo_ref,
                     *, iters: int, hub: bool, comp: int):
    # (TB, 1) control words broadcast across the lane axis
    xo_ref[...], yo_ref[...] = fused_replay(
        x_ref[...], y_ref[...], flip_ref[...], sig_ref[...], iters=iters,
        hub=hub, comp=comp)


def rotation_call(x, y, flip, sigma, *, iters: int, hub: bool,
                  interpret: bool = True, tile_l: int = TILE_L):
    """x, y: (B, L) int32; flip, sigma: (B, 1) int32 -> rotated (B, L)."""
    B, L = x.shape
    assert B % TILE_B == 0 and L % tile_l == 0 and iters <= 30
    grid = (B // TILE_B, L // tile_l)
    tile = pl.BlockSpec((TILE_B, tile_l), lambda b, l: (b, l))
    ctrl = pl.BlockSpec((TILE_B, 1), lambda b, l: (b, 0))
    out_shape = [jax.ShapeDtypeStruct((B, L), jnp.int32)] * 2
    kernel = functools.partial(_rotation_kernel, iters=iters, hub=hub,
                               comp=comp_q30(iters))
    with x32_trace():
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[ctrl, ctrl, tile, tile],
            out_specs=[tile, tile],
            out_shape=out_shape,
            interpret=interpret,
        )(flip, sigma, x, y)


# ---------------------------------------------------------------------------
# Fused kernel (beyond-paper §Perf iteration): vectoring + rotation in one
# pass.  The separate-kernel pipeline writes the rows to HBM between the
# phases; here each (TB, L) row block stays in VMEM — sigma is derived from
# the leading column and replayed over the whole block before a single
# write-back.  HBM traffic per element drops 2x (one read + one write).
# ---------------------------------------------------------------------------
def _vector_lead(xl, yl, *, iters: int, hub: bool):
    """Vectoring recurrence on the leading pairs -> (flip, sigma).

    ``flip`` is the boolean coarse π-flip, ``sigma`` the int32 packed
    direction bits (bit i == 1 ⇔ d_i = +1), both shaped like ``xl``.
    """
    flip = xl < 0
    xl = jnp.where(flip, _negate(xl, hub), xl)
    yl = jnp.where(flip, _negate(yl, hub), yl)
    sig = jnp.zeros_like(xl)
    for i in range(iters):
        d_pos = yl < 0
        xl, yl = _microrotation(xl, yl, i, d_pos, hub)
        sig = sig | (d_pos.astype(jnp.int32) << i)
    return flip, sig


def fused_replay(x, y, flip, sig, *, iters: int, hub: bool, comp: int):
    """Replay `(flip, sigma)` control words over two row blocks.

    x, y : int32 rows; flip (bool or 0/1 int32) and sig (int32) are
    broadcastable against them — a trailing singleton element axis
    broadcasts one control word across its row.  Replaying sigma on the
    pair that produced it reproduces the vectoring output bit for bit,
    and on any other column applies the exact same micro-rotation
    sequence — the trailing-panel update is therefore bit-identical to
    having rotated the full-width rows in one shot.
    """
    fb = flip != jnp.zeros_like(flip)     # no weak literal: bool or 0/1
    x = jnp.where(fb, _negate(x, hub), x)
    y = jnp.where(fb, _negate(y, hub), y)
    for i in range(iters):
        d_pos = ((sig >> i) & 1) == 1
        x, y = _microrotation(x, y, i, d_pos, hub)
    return _gain_mul_q30(x, comp), _gain_mul_q30(y, comp)


def fused_rotate_block(x, y, *, iters: int, hub: bool, comp: int):
    """Fused Givens step on two resident (TB, L) row blocks.

    Vectoring on the leading column derives the control words (flip +
    sigma), then the whole block — leading column included; its replay by
    its own sigma IS the vectoring result — rotates with the broadcast
    words and is gain-compensated.
    """
    flip, sig = _vector_lead(x[:, :1], y[:, :1], iters=iters, hub=hub)
    return fused_replay(x, y, flip, sig, iters=iters, hub=hub, comp=comp)


def fused_rotate_pairs(x, y, lead, *, iters: int, hub: bool, comp: int,
                       axis: int = -1):
    """Fused Givens step on a whole *pair axis* of resident row blocks.

    The kernel-resident building block of every blocked QR kernel
    (DESIGN.md §8, §14): ``x``/``y`` are pivot/target rows at *uniform*
    width e along the element ``axis`` — (P, TB, e) rows-first, or
    (P, e, rows, 128) batch-minor — and ``lead`` is the 0/1 one-hot of
    each pair's leading column (the annihilated entry's column) along
    it, (P, 1, e) or (P, e, rows, 128).  The leading pair is extracted by the
    one-hot contraction, vectoring derives one (flip, sigma) control
    word per (pair, matrix), and the replay broadcasts it across the
    whole e axis — every pair rotates in one shot.

    Column masking is the caller's job: lanes left of the leading column
    are rotated here too (uniform shape keeps the datapath wide) and must
    be restored from the inputs afterwards — they belong to earlier,
    already-annihilated columns, which the sequential path never touches.

    Replaying sigma on the leading column itself reproduces the vectoring
    result bit for bit (same micro-rotation sequence), so each pair's lanes
    at and right of `lead` match `fused_rotate_block` on the ragged slice
    exactly.

    Returns ``(rx, ry, flip, sig)`` — the rotated rows plus the int32
    control words (flip as 0/1), shaped like the rows with ``axis`` of
    size 1, which the panel kernels export for replay over trailing
    panels (`fused_replay`).
    """
    sel = lead.astype(x.dtype)
    # dtype-pinned sums: default integer accumulation widens to int64
    xl = jnp.sum(x * sel, axis=axis, keepdims=True, dtype=x.dtype)
    yl = jnp.sum(y * sel, axis=axis, keepdims=True, dtype=y.dtype)
    flip, sig = _vector_lead(xl, yl, iters=iters, hub=hub)
    rx, ry = fused_replay(x, y, flip, sig, iters=iters, hub=hub, comp=comp)
    return rx, ry, flip.astype(jnp.int32), sig


def _fused_kernel(x_ref, y_ref, xo_ref, yo_ref,
                  *, iters: int, hub: bool, comp: int):
    xo_ref[...], yo_ref[...] = fused_rotate_block(
        x_ref[...], y_ref[...], iters=iters, hub=hub, comp=comp)


def fused_call(x, y, *, iters: int, hub: bool, interpret: bool = True):
    """x, y: (B, L) int32 full rows (element 0 = leading pair) -> rotated."""
    B, L = x.shape
    assert B % TILE_B == 0 and iters <= 30
    grid = (B // TILE_B,)
    tile = pl.BlockSpec((TILE_B, L), lambda b: (b, 0))
    out_shape = [jax.ShapeDtypeStruct((B, L), jnp.int32)] * 2
    kernel = functools.partial(_fused_kernel, iters=iters, hub=hub,
                               comp=comp_q30(iters))
    with x32_trace():
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[tile, tile],
            out_specs=[tile, tile],
            out_shape=out_shape,
            interpret=interpret,
        )(x, y)
