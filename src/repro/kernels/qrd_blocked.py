"""Kernel-resident blocked QR: one `pallas_call` per triangularization.

The reference loop (`repro.core.qrd.qr_cordic`) launches one rotation per
schedule step from Python: every step reads the two packed rows from HBM,
runs the unit, and writes them back — 2·steps HBM passes over the working
set, plus per-step dispatch overhead.  The paper's FPGA never does this: the
control word is computed once per row pair and *replayed inside the
pipeline* (DESIGN.md §2, §5).  These kernels restore that property on the
TPU: the whole (batched) m×e working tile is staged into VMEM once, every
schedule step runs on the resident tile, and the result is written back
once.

One schedule machinery (`qr_call`), four datapaths:

`BlockFPPath` — int32 block-fixed-point significands (the TPU fast path)
    Significands quantized once, outside the kernel, with one shared
    exponent per (matrix, column) — Givens rotations only ever combine
    same-column elements of two rows, so per-column block-FP scaling is
    invariant under the whole schedule.  Arithmetic is the fused int32
    pipeline of `cordic_givens` (w ≤ 30 bits, Q30 gain compensation).

`LanePath` — bit-exact packed words on dual int32 lanes
    The packed FP words (`repro.core.formats`) carried as separate hi/lo
    int32 tiles and rotated by the emulated-64-bit `LaneUnit`
    (`repro.kernels.packed_lanes`) — no 64-bit types in the kernel, so it
    lowers through Mosaic.  Bit-identical to `PackedPath`.

`PackedPath` — bit-exact packed words on int64 lanes
    The same `GivensUnit` arithmetic as the reference loop, so (Q, R) are
    bit-identical to `qr_cordic`.  Interpret mode only: the semantic
    reference of the two compiled datapaths.

`ComplexPath` — three-rotation complex Givens on packed (re, im) pairs
    (DESIGN.md §10), int64 lanes with a trailing axis of size 2.
    Interpret mode only.

Schedule machinery.  Every schedule enters as (S, Pmax) stage tables held
in SMEM: pivot rows, target rows, leading columns, one row per stage.  A
`lax.fori_loop` walks the stages; each iteration reads its pairs' rows by
dynamic index, rotates all pairs of the stage at uniform width e in one
shot (the one-hot ``lead`` mask selects each pair's leading column, the
``active`` mask restores the lanes left of it), and stores the rows back.
The step-serial column-major order is the special case of one pair per
stage; the Sameh–Kuck wavefront (DESIGN.md §8) packs the disjoint pairs of
each stage along a pair axis, so sequential depth is the number of stages.
Padded pairs carry the out-of-range row ``m``: they read a clamped row and
store nothing.  The trace holds one stage body whatever the schedule
length.  ``table_layout`` selects how the tables travel: ``'split'``
(three operands) or ``'stacked'`` (one) — an autotuner dimension
(`repro.kernels.autotune`).

Tiled panel QR (DESIGN.md §14) reuses the machinery: ``qr_call(...,
export=True)`` factors one panel while exporting each step's (flip, sigma)
control words, and `replay_call` replays them over the trailing panels on
a (batch, panel) grid — the paper's compute-once/replay-everywhere
contract extended across kernel launches.  With the column-major schedule
every rotation is elementwise in the column axis once its control word is
fixed, so the split cannot change a single bit.

Layout.  Kernel operands are rows-first, ``(m, B, e)``: a dynamic row index
then addresses the leading (untiled) axis and one row of a batch tile is a
dense (TB, e) block.  Callers hand in rows-first arrays
(`repro.kernels.ops` transposes once around the call).  The batch axis is
padded up to a multiple of ``tile_b`` with all-zero matrices (exact
through every datapath) and sliced back.

VMEM budget (DESIGN.md §5, §8): one (m, tile_b, e) tile per operand and
result — int32 block-FP and each lane half: 2·m·tb·e·4 bytes; the stage
pair tensors add about one more tile.  A 64×128 augmented tile at tile_b=8
is 64·8·192·4 ≈ 393 KiB ·2, well inside the ~16 MiB scoped VMEM of a TPU
core; `autotune` searches tile_b under an explicit budget.  The exported
control words are (tile_b, S) int32 per word.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.givens import GivensConfig, GivensUnit
from .cordic_givens import (TILE_B, comp_q30, fused_replay,
                            fused_rotate_pairs, x32_trace)
from .packed_lanes import LaneUnit

__all__ = ["qr_call", "replay_call", "pad_to", "BlockFPPath", "LanePath",
           "PackedPath", "ComplexPath", "TILE_B", "TABLE_LAYOUTS",
           "HBM_PASSES_PER_QRD"]

TABLE_LAYOUTS = ("split", "stacked")

#: The kernel-resident HBM-traffic contract every call here honors: the
#: working tile is staged into VMEM once and written back once — two
#: passes over the (B, m, e) working set per decomposition, independent
#: of schedule length.  `repro.launch.perfmodel` builds the roofline's
#: memory term from this.
HBM_PASSES_PER_QRD = 2

_I32 = jnp.int32


def pad_to(x, mult, axis):
    """Zero-pad ``axis`` of ``x`` up to a multiple of ``mult``."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# Datapaths: how one stage of row pairs rotates, and how exported control
# words replay.  Rows arrive as one tuple entry per kernel tile (the lane
# path carries two: hi and lo), each (P, TB, e[, 2]); ``lead`` is the
# (P, 1, e) one-hot of each pair's leading column.  `stage` returns the
# rotated rows and the per-pair control words, (P, TB, 1) each.  `name`
# names the datapath's kernels in a trace: ``givens_qr_<name>`` and
# ``givens_replay_<name>``.
# ---------------------------------------------------------------------------
class BlockFPPath:
    """int32 block-FP significands: the fused int32 CORDIC pipeline."""

    name = "blockfp"
    wide = False
    word_dtype = jnp.int32

    def __init__(self, iters: int, hub: bool):
        assert iters <= 30
        self.kw = dict(iters=iters, hub=hub, comp=comp_q30(iters))

    def stage(self, X, Y, lead):
        rx, ry, flip, sig = fused_rotate_pairs(X[0], Y[0], lead, **self.kw)
        return (rx,), (ry,), (flip, sig)

    def replay(self, x, y, words):
        rx, ry = fused_replay(x[0], y[0], *words, **self.kw)
        return (rx,), (ry,)


class PackedPath:
    """int64 packed FP words through `GivensUnit` (interpret mode only)."""

    name = "packed"
    wide = True
    word_dtype = jnp.int64

    def __init__(self, cfg: GivensConfig):
        self.unit = GivensUnit(cfg)

    def stage(self, X, Y, lead):
        x, y = X[0], Y[0]
        sel = lead.astype(x.dtype)
        xl = jnp.sum(x * sel, axis=-1, keepdims=True)
        yl = jnp.sum(y * sel, axis=-1, keepdims=True)
        _, _, (flip, sig) = self.unit.vector(xl, yl)
        # Replaying sigma on the leading column reproduces the vectoring
        # output bit for bit, so the whole row rotates at uniform width.
        rx, ry = self.unit.rotate(x, y, (flip, sig))
        return (rx,), (ry,), (flip.astype(jnp.int64), sig)

    def replay(self, x, y, words):
        rx, ry = self.unit.rotate(x[0], y[0], words)
        return (rx,), (ry,)


class LanePath:
    """Packed FP words as separate (hi, lo) int32 tiles, `LaneUnit`
    arithmetic — bit-identical to `PackedPath`, and it compiles."""

    name = "lanes"
    wide = False

    def __init__(self, cfg: GivensConfig):
        self.unit = LaneUnit(cfg)

    def stage(self, X, Y, lead):
        sel = lead.astype(_I32)
        # one-hot contraction: exact in int32, it only selects a word
        xl = tuple(jnp.sum(h * sel, axis=-1, keepdims=True, dtype=_I32)
                   for h in X)
        yl = tuple(jnp.sum(h * sel, axis=-1, keepdims=True, dtype=_I32)
                   for h in Y)
        _, _, state = self.unit.vector_lanes(xl, yl)
        rx, ry = self.unit.rotate_lanes(X, Y, state)
        return rx, ry, ()


class ComplexPath:
    """Complex packed (re, im) words, three-rotation Givens (interpret
    mode only).

    Per pair: the phase control words come from vectoring on the lead
    (re, im) pairs and replay across the whole row at uniform width; the
    realized leads re-extracted from the phase-rotated rows drive the real
    Givens across both lanes.  The realized pivot lead is real, so its
    imaginary lane is forced to zero here (the target's lead is zeroed by
    `qr_call` like every datapath's).
    """

    name = "complex"
    wide = True

    def __init__(self, cfg: GivensConfig):
        self.unit = GivensUnit(cfg)

    def stage(self, X, Y, lead):
        unit = self.unit
        x, y = X[0], Y[0]
        sel = lead.astype(x.dtype)                   # (P, 1, e) 0/1

        def lead_of(v):
            return jnp.sum(v * sel, axis=-1, keepdims=True, dtype=v.dtype)

        def realize(re, im):
            _, st, sk = unit.phase_vector(lead_of(re), lead_of(im))
            return unit.phase_rotate(re, im, st, sk)

        pxr, pxi = realize(x[..., 0], x[..., 1])
        pyr, pyi = realize(y[..., 0], y[..., 1])
        _, _, st = unit.vector(lead_of(pxr), lead_of(pyr))
        rxr, ryr = unit.rotate(pxr, pyr, st)
        rxi, ryi = unit.rotate(pxi, pyi, st)
        rxi = jnp.where(lead, jnp.zeros_like(rxi), rxi)  # realized pivot
        return ((jnp.stack([rxr, rxi], axis=-1),),
                (jnp.stack([ryr, ryi], axis=-1),), ())


# ---------------------------------------------------------------------------
# Stage tables in SMEM
# ---------------------------------------------------------------------------
def _table_operands(tables, table_layout: str):
    """(S, Pmax) stage tables -> flat int32 SMEM operands and their specs."""
    if table_layout not in TABLE_LAYOUTS:
        raise ValueError(f"table_layout must be one of {TABLE_LAYOUTS}, "
                         f"got {table_layout!r}")
    flat = [jnp.asarray(t, _I32).reshape(-1) for t in tables]
    if table_layout == "stacked":
        flat = [jnp.concatenate(flat)]
    # whole-array blocks, the same on every grid cell
    return flat, [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in flat]


def _table_reader(tab_refs, S: int, Pmax: int, table_layout: str):
    """Kernel-side inverse of `_table_operands`: ``read(which, s, p)``."""
    if table_layout == "stacked":
        (t_ref,) = tab_refs
        return lambda which, s, p: t_ref[(which * S + s) * Pmax + p]
    return lambda which, s, p: tab_refs[which][s * Pmax + p]


def _check_compiles(dp, interpret: bool):
    if dp.wide and not interpret:
        raise NotImplementedError(
            f"{type(dp).__name__} carries int64 words, which no Pallas "
            "compiler lowers: it runs in interpret mode only")


def _bcast(mask, a):
    """Broadcast a (P, 1, e) mask over ``a``'s trailing datapath axes."""
    return mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))


# ---------------------------------------------------------------------------
# The factor kernel: every stage of the schedule on the resident tile
# ---------------------------------------------------------------------------
def _factor_kernel(*refs, dp, n_tab: int, n_tiles: int, S: int, Pmax: int,
                   table_layout: str, export: bool):
    tab_refs = refs[:n_tab]
    in_refs = refs[n_tab:n_tab + n_tiles]
    tiles = refs[n_tab + n_tiles:n_tab + 2 * n_tiles]
    word_refs = refs[n_tab + 2 * n_tiles:]
    for i_ref, o_ref in zip(in_refs, tiles):
        o_ref[...] = i_ref[...]
    m, TB, e = tiles[0].shape[:3]
    read = _table_reader(tab_refs, S, Pmax, table_layout)
    col_iota = jax.lax.broadcasted_iota(_I32, (1, 1, e), 2)
    step_iota = jax.lax.broadcasted_iota(_I32, (TB, S), 1)

    def gather(rows):
        # padded pairs (row m) read a clamped row and are never stored
        rows = [jnp.minimum(r, m - 1) for r in rows]
        return tuple(jnp.stack([t[r] for r in rows]) for t in tiles)

    def body(s, words):
        piv = [read(0, s, p) for p in range(Pmax)]
        tgt = [read(1, s, p) for p in range(Pmax)]
        col = [read(2, s, p) for p in range(Pmax)]
        X, Y = gather(piv), gather(tgt)
        lead = jnp.concatenate([col_iota == c for c in col])   # (P, 1, e)
        active = jnp.concatenate([col_iota >= c for c in col])
        rx, ry, w = dp.stage(X, Y, lead)
        # untouched left lanes, then the structural zero of the target
        rx = [jnp.where(_bcast(active, a), a, x) for a, x in zip(rx, X)]
        ry = [jnp.where(_bcast(lead, a), jnp.zeros_like(a),
                        jnp.where(_bcast(active, a), a, y))
              for a, y in zip(ry, Y)]
        for p in range(Pmax):
            def store(p=p):
                for t, a, b in zip(tiles, rx, ry):
                    t[piv[p]] = a[p]
                    t[tgt[p]] = b[p]
            if Pmax == 1:          # step tables carry no padding
                store()
            else:
                pl.when(piv[p] < m)(store)
        if not export:
            return words
        return tuple(jnp.where(step_iota == s, v[0], acc)
                     for v, acc in zip(w, words))

    init = tuple(jnp.zeros((TB, S), dp.word_dtype) for _ in word_refs)
    words = jax.lax.fori_loop(_I32(0), _I32(S), body, init)
    for ref, acc in zip(word_refs, words):
        ref[...] = acc


def qr_call(tiles, tables, dp, *, interpret: bool, tile_b: int = TILE_B,
            table_layout: str = "split", export: bool = False):
    """Run a stage schedule over rows-first working tiles in one kernel.

    Parameters
    ----------
    tiles : tuple of (m, B, e[, 2]) arrays
        The working matrices, rows first — one array per datapath tile
        (two for `LanePath`: hi, lo).  Ragged ``B`` is padded to a
        multiple of ``tile_b`` with zero matrices and sliced back.
    tables : (piv, tgt, col), each (S, Pmax) int
        Stage tables (`repro.kernels.ops.stage_tables` / `step_tables`):
        pivot rows, target rows, leading columns; padded pairs carry
        row ``m``.
    dp : BlockFPPath | LanePath | PackedPath | ComplexPath
        The datapath.
    interpret : bool
        Pallas interpret mode.  The int64 datapaths exist only there.
    export : bool
        Also return each step's control words, (B, S) per word, for
        `replay_call` (one pair per stage only: the panel factor).

    Returns
    -------
    tuple of rotated tiles (same shapes), followed by the exported words
    when ``export``.
    """
    _check_compiles(dp, interpret)
    S, Pmax = tables[0].shape
    assert not export or Pmax == 1
    B = tiles[0].shape[1]
    tiles = tuple(pad_to(t, tile_b, 1) for t in tiles)
    m, Bp, e = tiles[0].shape[:3]

    def spec(t):
        rest = t.shape[3:]
        return pl.BlockSpec((m, tile_b, e) + rest,
                            lambda b: (0, b, 0) + (0,) * len(rest))

    tab_ops, tab_specs = _table_operands(tables, table_layout)
    out_specs = [spec(t) for t in tiles]
    out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles]
    if export:                             # (flip, sigma) per step
        out_specs += [pl.BlockSpec((tile_b, S), lambda b: (b, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((Bp, S), dp.word_dtype)] * 2
    kernel = functools.partial(
        _factor_kernel, dp=dp, n_tab=len(tab_ops), n_tiles=len(tiles), S=S,
        Pmax=Pmax, table_layout=table_layout, export=export)
    with x32_trace(not dp.wide):
        out = pl.pallas_call(
            kernel, grid=(Bp // tile_b,),
            in_specs=[*tab_specs, *[spec(t) for t in tiles]],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name=f"givens_qr_{dp.name}",
        )(*tab_ops, *tiles)
    outs = tuple(o[:, :B] for o in out[:len(tiles)])
    return outs + tuple(w[:B] for w in out[len(tiles):])


# ---------------------------------------------------------------------------
# The replay kernel: exported control words over trailing panels
# ---------------------------------------------------------------------------
def _replay_kernel(piv_ref, tgt_ref, *refs, dp, S: int):
    *word_refs, t_in, t = refs
    t[...] = t_in[...]
    read = _table_reader((piv_ref, tgt_ref), S, 1, "split")
    words = tuple(r[...] for r in word_refs)         # (TB, S) each
    step_iota = jax.lax.broadcasted_iota(_I32, words[0].shape, 1)

    def body(s, carry):
        k, j = read(0, s, 0), read(1, s, 0)
        # column s of each word table, as a (TB, 1) lane-broadcast word
        w = tuple(jnp.sum(jnp.where(step_iota == s, W, jnp.zeros_like(W)),
                          axis=1, keepdims=True, dtype=W.dtype)
                  for W in words)
        (rx,), (ry,) = dp.replay((t[k],), (t[j],), w)
        t[k] = rx
        t[j] = ry
        return carry

    jax.lax.fori_loop(_I32(0), _I32(S), body, 0)


def replay_call(T, piv, tgt, words, dp, *, interpret: bool,
                tile_b: int = TILE_B):
    """Replay exported panel controls over the trailing panels.

    The grid is (batch tiles, trailing panels): each cell replays the
    full (S,) rotation set on one (mr, tile_b, pw) trailing block — the
    trailing-panel axis rides the Pallas grid, so wide trailing regions
    parallelize across cells instead of growing the resident tile.

    Parameters
    ----------
    T : (G, mr, B, pw)
        The trailing region, rows first, chunked into G panel-width tiles
        (zero-pad the last chunk; rotations are columnwise, so pad
        columns never feed back into real ones).
    piv, tgt : (S,) int — panel-local step row tables.
    words : tuple of (B, S) control words from ``qr_call(export=True)``.
    dp : BlockFPPath | PackedPath

    Returns
    -------
    (G, mr, B, pw) — the updated trailing region.
    """
    _check_compiles(dp, interpret)
    B = T.shape[2]
    T = pad_to(T, tile_b, 2)
    words = tuple(pad_to(w, tile_b, 0) for w in words)
    G, mr, Bp, pw = T.shape
    S = piv.shape[0]
    spec = pl.BlockSpec((pl.Squeezed(), mr, tile_b, pw),
                        lambda b, g: (g, 0, b, 0))
    wspec = pl.BlockSpec((tile_b, S), lambda b, g: (b, 0))
    tab_ops, tab_specs = _table_operands((piv[:, None], tgt[:, None]),
                                         "split")
    kernel = functools.partial(_replay_kernel, dp=dp, S=S)
    with x32_trace(not dp.wide):
        out = pl.pallas_call(
            kernel, grid=(Bp // tile_b, G),
            in_specs=[*tab_specs, *[wspec] * len(words), spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(T.shape, T.dtype),
            interpret=interpret,
            name=f"givens_replay_{dp.name}",
        )(*tab_ops, *words, T)
    return out[:, :, :B]
