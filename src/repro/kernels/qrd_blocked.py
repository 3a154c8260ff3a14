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

Layouts.  A dynamic row index always addresses the leading, untiled axis,
so loads and stores of a row keep the same structure in both layouts; what
differs is where the batch lies (`batch_minor` chooses, from the shape):

* rows-first, ``(m, B, e)``: one row of a batch tile is a dense
  ``(tile_b, e)`` block — the element axis on the 128 lanes, the batch on
  the sublanes.  A 64×128 panel fills its lanes; an 8×16 tile at
  ``tile_b = 8`` uses one (8, 128) vreg with 16 of 128 lanes live.
* batch-minor, ``(m, e, B)``: every element ``(r, c)`` of a batch tile is
  a dense ``(rows, 128)`` slab holding that element of ``rows · 128``
  matrices — at ``rows = 8`` one vreg holds the same element of 1024
  matrices.  The leading pair of a rotation is then one dense vreg per
  row, the vectoring runs on 1024 matrices per vreg, and the replay runs
  over ``e`` dense vregs per row.  The kernel sees the batch as
  ``(cells, rows, 128)``: ``tile_b`` rounds up to a multiple of 128
  matrices and the batch splits evenly over the cells it needs
  (`batch_tiles`; by default cells of at most 8 rows).

Batch-minor applies where the element axis is narrower than the lanes,
the block fits the VMEM budget, and a stage touches fewer vregs than
rows-first — enough matrices to fill the slabs — for the compiled int32
datapaths.  Everything else stays rows-first: wide tiles, a handful of
matrices, the int64 interpret-only datapaths, the panel factor
(``export=True``) and `replay_call`, whose panels already fill the
lanes.  Callers hand in arrays in the chosen layout
(`repro.kernels.ops` transposes once, in the encode, and back in the
decode).  The batch axis is padded with all-zero matrices (exact through
every datapath) and sliced back.

VMEM budget (DESIGN.md §5, §8): the model charges ``VMEM_BUFFERS`` copies
of a block of ``tile_b`` matrices at the datapath's bytes per element —
input and output, each double-buffered, and the rows a stage works on —
(`tile_vmem_bytes`) against `DEFAULT_VMEM_BUDGET`, half of a v5e core's
16 MiB of scoped VMEM.  An 8×16 block-FP tile of 1024 matrices
batch-minor is 6·8·16·4·1024 B = 3 MiB; a 64×192 one would be 288 MiB,
so wide tiles stay rows-first, where a 64×128 augmented tile at
tile_b=8 is 64·8·192·4 B = 384 KiB per copy.  `autotune` searches tile_b
under the same model (with a budget of its own).  The exported control
words are (tile_b, S) int32 per word.
"""
from __future__ import annotations

import functools
import math

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
           "HBM_PASSES_PER_QRD", "LANES", "batch_minor", "batch_tiles",
           "padded_batch", "tile_vmem_bytes"]

TABLE_LAYOUTS = ("split", "stacked")

#: Vector lanes and sublanes of a TPU int32 vreg: a batch-minor element
#: slab holds ``rows`` x `LANES` matrices, one vreg per `SUBLANES` rows.
LANES = 128
SUBLANES = 8

#: Default VMEM budget (bytes) of the tile model: half of a v5e core's
#: 16 MiB of scoped VMEM, the rest left to stage tables, spills and the
#: compiler's own buffers.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024

#: Block copies the VMEM model charges: input and output block, each
#: double-buffered by the pipeline, and about two more for the rows a
#: stage gathers, rotates and stores.
VMEM_BUFFERS = 6

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
# The layout rule and the batch tiling
# ---------------------------------------------------------------------------
def tile_vmem_bytes(m: int, e: int, itemsize: int, tile_b: int) -> int:
    """VMEM the model charges for a block of ``tile_b`` m×e matrices."""
    return VMEM_BUFFERS * m * e * itemsize * tile_b


def batch_tiles(batch: int, tile_b: int | None = None):
    """``(cells, rows)`` of a batch-minor tiling of ``batch`` matrices.

    Each of the ``cells`` grid cells holds ``rows`` lane rows of `LANES`
    matrices.  ``tile_b`` (matrices per cell) rounds up to whole lane
    rows; ``None`` takes cells of at most `SUBLANES` rows, one vreg per
    element.  The rows then spread evenly over the cells, so padding
    stays under one lane row per cell.
    """
    lane_rows = max(1, math.ceil(batch / LANES))
    cap = SUBLANES if tile_b is None else math.ceil(tile_b / LANES)
    cells = math.ceil(lane_rows / min(cap, lane_rows))
    return cells, math.ceil(lane_rows / cells)


def padded_batch(batch: int, tile_b: int | None, batch_minor: bool) -> int:
    """The batch a kernel call runs: ``batch`` padded to whole tiles."""
    if batch_minor:
        cells, rows = batch_tiles(batch, tile_b)
        return cells * rows * LANES
    tb = TILE_B if tile_b is None else tile_b
    return math.ceil(batch / tb) * tb


def batch_minor(m: int, e: int, batch: int, itemsize: int) -> bool:
    """The layout rule: True where ``batch`` m×e tiles run batch-minor.

    Batch-minor where the element axis is narrower than the lanes, the
    default block (`batch_tiles`) fits `DEFAULT_VMEM_BUDGET`, and a
    stage then touches fewer vregs: ``e`` per 8×128 slab of matrices,
    against one per `TILE_B` matrices rows-first.  A slab costs the same
    for one matrix as for 1024, so a handful of matrices stays
    rows-first.  ``itemsize`` is the datapath's bytes per element (4
    block-FP, 8 the lane pair).
    """
    if e >= LANES:
        return False
    cells, rows = batch_tiles(batch)
    if tile_vmem_bytes(m, e, itemsize, rows * LANES) > DEFAULT_VMEM_BUDGET:
        return False
    return e * cells * math.ceil(rows / SUBLANES) < math.ceil(batch / TILE_B)


# ---------------------------------------------------------------------------
# Datapaths: how one stage of row pairs rotates, and how exported control
# words replay.  Rows arrive as one tuple entry per kernel tile (the lane
# path carries two: hi and lo), each (P, TB, e[, 2]) rows-first or
# (P, e, rows, 128) batch-minor; ``axis`` is the element axis (2 or 1)
# and ``lead`` the one-hot of each pair's leading column along it,
# (P, 1, e) or (P, e, rows, 128).  `stage` returns the rotated rows and
# the per-pair control words, (P, TB, 1) rows-first.  `name` names the
# datapath's kernels in a trace: ``givens_qr_<name>`` (with a
# ``_batch_minor`` suffix in that layout) and ``givens_replay_<name>``;
# the compiled datapaths' `itemsize` is their bytes per element for the
# VMEM model.
# ---------------------------------------------------------------------------
class BlockFPPath:
    """int32 block-FP significands: the fused int32 CORDIC pipeline."""

    name = "blockfp"
    wide = False
    itemsize = 4
    word_dtype = jnp.int32

    def __init__(self, iters: int, hub: bool):
        assert iters <= 30
        self.kw = dict(iters=iters, hub=hub, comp=comp_q30(iters))

    def stage(self, X, Y, lead, axis):
        rx, ry, flip, sig = fused_rotate_pairs(X[0], Y[0], lead, axis=axis,
                                               **self.kw)
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

    def stage(self, X, Y, lead, axis):
        x, y = X[0], Y[0]
        sel = lead.astype(x.dtype)
        xl = jnp.sum(x * sel, axis=axis, keepdims=True)
        yl = jnp.sum(y * sel, axis=axis, keepdims=True)
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
    itemsize = 8                    # hi and lo: two int32 tiles

    def __init__(self, cfg: GivensConfig):
        self.unit = LaneUnit(cfg)

    def stage(self, X, Y, lead, axis):
        sel = lead.astype(_I32)
        # one-hot contraction: exact in int32, it only selects a word
        xl = tuple(jnp.sum(h * sel, axis=axis, keepdims=True, dtype=_I32)
                   for h in X)
        yl = tuple(jnp.sum(h * sel, axis=axis, keepdims=True, dtype=_I32)
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

    def stage(self, X, Y, lead, axis):
        unit = self.unit
        x, y = X[0], Y[0]
        sel = lead.astype(x.dtype)                   # (P, 1, e) 0/1

        def lead_of(v):
            return jnp.sum(v * sel, axis=axis, keepdims=True, dtype=v.dtype)

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
    """Broadcast a stage mask over ``a``'s trailing datapath axes."""
    return mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))


# ---------------------------------------------------------------------------
# The factor kernel: every stage of the schedule on the resident tile
# ---------------------------------------------------------------------------
def _factor_kernel(*refs, dp, n_tab: int, n_tiles: int, S: int, Pmax: int,
                   table_layout: str, export: bool, batch_minor: bool):
    tab_refs = refs[:n_tab]
    in_refs = refs[n_tab:n_tab + n_tiles]
    tiles = refs[n_tab + n_tiles:n_tab + 2 * n_tiles]
    word_refs = refs[n_tab + 2 * n_tiles:]
    for i_ref, o_ref in zip(in_refs, tiles):
        o_ref[...] = i_ref[...]
    m = tiles[0].shape[0]
    # the element axis of the stacked (P, ...) rows: (P, e, rows, 128)
    # batch-minor, (P, TB, e[, 2]) rows-first
    axis = 1 if batch_minor else 2
    e = tiles[0].shape[axis]
    # column masks: (1, 1, e) rows-first; batch-minor at full row shape,
    # (1, e, rows, 128), as Mosaic cannot broadcast a (.., 1, 1) vector
    # over both sublanes and lanes
    mask_shape = (1,) + tiles[0].shape[1:] if batch_minor else (1, 1, e)
    col_iota = jax.lax.broadcasted_iota(_I32, mask_shape, axis)
    read = _table_reader(tab_refs, S, Pmax, table_layout)

    def gather(rows):
        # padded pairs (row m) read a clamped row and are never stored
        rows = [jnp.minimum(r, m - 1) for r in rows]
        return tuple(jnp.stack([t[r] for r in rows]) for t in tiles)

    def body(s, words):
        piv = [read(0, s, p) for p in range(Pmax)]
        tgt = [read(1, s, p) for p in range(Pmax)]
        col = [read(2, s, p) for p in range(Pmax)]
        X, Y = gather(piv), gather(tgt)
        lead = jnp.concatenate([col_iota == c for c in col])
        active = jnp.concatenate([col_iota >= c for c in col])
        rx, ry, w = dp.stage(X, Y, lead, axis)
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
        step_iota = jax.lax.broadcasted_iota(_I32, words[0].shape, 1)
        return tuple(jnp.where(step_iota == s, v[0], acc)
                     for v, acc in zip(w, words))

    TB = tiles[0].shape[1]
    init = tuple(jnp.zeros((TB, S), dp.word_dtype) for _ in word_refs)
    words = jax.lax.fori_loop(_I32(0), _I32(S), body, init)
    for ref, acc in zip(word_refs, words):
        ref[...] = acc


def qr_call(tiles, tables, dp, *, interpret: bool, tile_b: int | None = None,
            table_layout: str = "split", export: bool = False,
            batch_minor: bool = False):
    """Run a stage schedule over the working tiles in one kernel.

    Parameters
    ----------
    tiles : tuple of (m, B, e[, 2]) arrays, or (m, e, B) when ``batch_minor``
        The working matrices — one array per datapath tile (two for
        `LanePath`: hi, lo).  ``B`` is padded with zero matrices to whole
        batch tiles and sliced back.
    tables : (piv, tgt, col), each (S, Pmax) int
        Stage tables (`repro.kernels.ops.stage_tables` / `step_tables`):
        pivot rows, target rows, leading columns; padded pairs carry
        row ``m``.
    dp : BlockFPPath | LanePath | PackedPath | ComplexPath
        The datapath.
    interpret : bool
        Pallas interpret mode.  The int64 datapaths exist only there.
    tile_b : int, optional
        Matrices per grid cell: rows-first, a multiple of the sublanes
        (``None``: `TILE_B`); batch-minor, rounded up to whole lane rows
        (`batch_tiles`; ``None``: up to 8 rows).
    export : bool
        Also return each step's control words, (B, S) per word, for
        `replay_call` (one pair per stage, rows-first only: the panel
        factor).
    batch_minor : bool
        The layout of ``tiles`` (`batch_minor` chooses it from the
        shape); only the compiled int32 datapaths run batch-minor.

    Returns
    -------
    tuple of rotated tiles (same shapes), followed by the exported words
    when ``export``.
    """
    _check_compiles(dp, interpret)
    S, Pmax = tables[0].shape
    assert not export or Pmax == 1
    assert not (batch_minor and (export or dp.wide))
    if batch_minor:
        m, e, B = tiles[0].shape
        cells, rows = batch_tiles(B, tile_b)
        tiles = tuple(pad_to(t, cells * rows * LANES, 2).reshape(
            m, e, cells, rows, LANES) for t in tiles)
        grid = (cells,)

        def spec(t):
            return pl.BlockSpec((m, e, pl.Squeezed(), rows, LANES),
                                lambda b: (0, 0, b, 0, 0))
    else:
        tile_b = TILE_B if tile_b is None else tile_b
        B = tiles[0].shape[1]
        tiles = tuple(pad_to(t, tile_b, 1) for t in tiles)
        m, Bp, e = tiles[0].shape[:3]
        grid = (Bp // tile_b,)

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
        Pmax=Pmax, table_layout=table_layout, export=export,
        batch_minor=batch_minor)
    suffix = "_batch_minor" if batch_minor else ""
    with x32_trace(not dp.wide):
        out = pl.pallas_call(
            kernel, grid=grid,
            in_specs=[*tab_specs, *[spec(t) for t in tiles]],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name=f"givens_qr_{dp.name}{suffix}",
        )(*tab_ops, *tiles)
    if batch_minor:
        return tuple(o.reshape(m, e, -1)[..., :B] for o in out)
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
                tile_b: int | None = None):
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
    tile_b : int, optional — matrices per grid cell (``None``: `TILE_B`).

    Returns
    -------
    (G, mr, B, pw) — the updated trailing region.
    """
    _check_compiles(dp, interpret)
    tile_b = TILE_B if tile_b is None else tile_b
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
