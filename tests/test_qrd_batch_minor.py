"""The batch-minor layout of the flat QR kernel (`qrd_blocked.qr_call`).

Many small matrices run with the batch on the lanes: every element of a
batch tile is a dense (rows, 128) slab of matrices.  The int32 recurrence
per (matrix, element) and the step order are those of the rows-first
kernel, so the results must be bit-identical to it, to the rows-first
panel route, and to a plain-jnp loop over `fused_rotate_pairs`.  Also
pinned: the layout rule, the batch tiling and `tile_b` rounding, and the
engine's ``padded_matrices`` counter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qrd as q
from repro.core.givens import GivensConfig, GivensUnit
from repro.kernels import autotune, ops
from repro.kernels import qrd_blocked as qb
from repro.kernels.cordic_givens import comp_q30, fused_rotate_pairs
from repro.qrd import QRDConfig, QRDEngine

ITERS = 24


def _jnp_reference(X, steps, hub):
    """Plain-jnp Givens loop over (B, m, e) int32 significands: one
    `fused_rotate_pairs` per step, the kernel's masks, no Pallas."""
    e = X.shape[-1]
    cols = jnp.arange(e)
    for k, j, c in steps:
        x, y = X[:, k], X[:, j]
        lead = (cols == c)[None, None]
        rx, ry, _, _ = fused_rotate_pairs(x[None], y[None], lead,
                                          iters=ITERS, hub=hub,
                                          comp=comp_q30(ITERS))
        active = cols >= c
        rx = jnp.where(active, rx[0], x)
        ry = jnp.where(cols == c, 0, jnp.where(active, ry[0], y))
        X = X.at[:, k].set(rx).at[:, j].set(ry)
    return X


def _significands(batch, m, n, seed):
    rng = np.random.default_rng(seed)
    W = q._augment(jnp.asarray(rng.standard_normal((batch, m, n))), True)
    X, ex = ops._blockfp_encode(W, 24)
    return W, X, ex


def _run(X, tables, dp, batch_minor, tile_b=None):
    """(B, m, e) significands through ``qr_call`` in either layout."""
    (out,) = qb.qr_call((ops._to_kernel(X, batch_minor),), tables, dp,
                        interpret=True, tile_b=tile_b,
                        batch_minor=batch_minor)
    return ops._from_kernel(out, batch_minor)


# One parametrised test: every case runs the batch-minor kernel and
# compares it bit for bit with its references.
_CASES = [(f"blockfp-{sched}-{'hub' if hub else 'ieee'}-B{batch}",
           ("blockfp", sched, hub, batch))
          for sched in ("col", "sameh_kuck") for hub in (False, True)
          for batch in (1, 273, 1124)]          # 1124: one tile + 100
_CASES += [("lanes-col-4x4-B273", ("lanes", "col", True, 273)),
           ("rls-block-n4-b4-B256", ("rls", "col", True, 256))]


@pytest.mark.parametrize("case", [c for _, c in _CASES],
                         ids=[i for i, _ in _CASES])
def test_batch_minor_bit_identical(case):
    kind, sched, hub, batch = case
    if kind == "lanes":
        # the lane pair (hi, lo) batch-minor against the int64 packed
        # words rows-first: two datapaths, two layouts, one answer
        cfg = GivensConfig(hub=hub)
        rng = np.random.default_rng(3)
        A = jnp.asarray(rng.standard_normal((batch, 4, 4)))
        P = GivensUnit(cfg).encode(q._augment(A, True))
        assert qb.batch_minor(4, 8, batch, qb.LanePath.itemsize)
        steps = q.givens_schedule(4, 4)
        lanes = ops.qr_packed(P, cfg=cfg, steps=steps, lanes=True)
        packed = ops.qr_packed(P, cfg=cfg, steps=steps, lanes=False)
        assert bool(jnp.all(lanes == packed))
        return

    dp = qb.BlockFPPath(ITERS, hub)
    if kind == "rls":
        n, blk = 4, 4
        steps = ops.rls_block_steps(n, blk)
        rng = np.random.default_rng(4)
        W = jnp.asarray(rng.standard_normal((batch, n + blk, n + 1)))
        X, ex = ops._blockfp_encode(W, 24)
        m = n + blk
        tables = ops.step_tables(steps, m)
    else:
        m = n = 8
        W, X, ex = _significands(batch, m, n, seed=batch)
        if sched == "col":
            steps = q.givens_schedule(m, n)
            tables = ops.step_tables(steps, m)
        else:
            stages = q.sameh_kuck_schedule(m, n)
            steps = tuple(s for st in stages for s in st)
            tables = ops.stage_tables(stages, m)

    got = _run(X, tables, dp, batch_minor=True)
    assert bool(jnp.all(got == _run(X, tables, dp, batch_minor=False)))
    assert bool(jnp.all(got == jax.jit(_jnp_reference, static_argnums=(1, 2))(
        X, tuple(steps), hub)))
    if sched == "col" and kind == "blockfp":
        # the rows-first panel route, trusted bit-identical to flat
        pan = ops.givens_block_apply_panel(W, n_cols=n, hub=hub, panel_n=4)
        assert bool(jnp.all(ops._blockfp_decode(got, ex, 24) == pan))
    # the public wrapper picks the layout by the rule, same bits
    flat = (ops.givens_block_apply(W, tuple(steps), hub=hub) if sched == "col"
            else ops.givens_block_apply_wavefront(W, stages, hub=hub))
    assert bool(jnp.all(flat == ops._blockfp_decode(got, ex, 24)))


# --------------------------------------------------------------------------
# The layout rule and the batch tiling
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,e,batch,itemsize,want", [
    (8, 16, 3276, 4, True),      # mimo.slot: 8x8 with Q, block-FP
    (8, 16, 273, 4, True),       # mimo.prb
    (4, 8, 4096, 8, True),       # 4x4 with Q on the lane pair
    (8, 5, 256, 4, True),        # RLS block update, n=4, block=4
    (8, 5, 131072, 4, True),     # a 2^17-slot fleet in block mode
    (64, 128, 64, 4, False),     # e fills the lanes: a 64x64 panel
    (64, 128, 4096, 4, False),
    (128, 33, 256, 4, False),    # TSQR leaf: over the VMEM budget
    (32, 64, 4096, 4, False),    # 32x32 with Q: over the VMEM budget
    (8, 16, 8, 4, False),        # a handful of matrices
    (8, 5, 1, 4, False),         # a single RLS state
], ids=["slot", "prb", "lanes-4x4", "rls-256", "rls-fleet", "panel-64",
        "panel-64-wide-batch", "tsqr-leaf", "flat-32x32", "batch-8",
        "rls-1"])
def test_layout_rule(m, e, batch, itemsize, want):
    assert qb.batch_minor(m, e, batch, itemsize) is want


def test_batch_tiles_default_fills_vregs():
    assert qb.batch_tiles(273) == (1, 3)         # one cell, three rows
    assert qb.batch_tiles(3276) == (4, 7)        # 26 rows over 4 cells
    assert qb.batch_tiles(4096) == (4, 8)        # full vregs
    assert qb.batch_tiles(1) == (1, 1)
    for batch in (1, 100, 273, 1124, 3276, 5000):
        cells, rows = qb.batch_tiles(batch)
        assert rows <= qb.SUBLANES
        # no more 8x128 slabs than the batch needs
        assert cells * -(-rows // qb.SUBLANES) == -(-batch // 1024)


@pytest.mark.parametrize("tile_b,want", [
    (8, (26, 1)),          # rounds up to one lane row per cell
    (128, (26, 1)),
    (129, (13, 2)),
    (1024, (4, 7)),        # eight rows asked, spread evenly: seven
    (4096, (1, 26)),       # more than the batch: one cell
], ids=["8", "128", "129", "1024", "4096"])
def test_tile_b_rounds_to_lane_rows(tile_b, want):
    assert qb.batch_tiles(3276, tile_b) == want
    cells, rows = want
    assert qb.padded_batch(3276, tile_b, True) == cells * rows * qb.LANES


def test_tile_b_rounding_runs_bit_identical():
    """A configured rows-first-sized tile_b is rounded, not rejected."""
    _, X, _ = _significands(300, 4, 4, seed=7)
    tables = ops.step_tables(q.givens_schedule(4, 4), 4)
    dp = qb.BlockFPPath(ITERS, True)
    ref = _run(X, tables, dp, batch_minor=False)
    for tile_b in (8, 200):
        assert bool(jnp.all(_run(X, tables, dp, True, tile_b) == ref))


def test_padded_batch_rows_first():
    assert qb.padded_batch(273, None, False) == 280
    assert qb.padded_batch(273, 16, False) == 288


def test_autotune_offers_lane_rows_for_batch_minor():
    assert autotune.candidate_tile_bs(3276, 8, 16, 4,
                                      vmem_budget=1 << 30) == (
        128, 256, 512, 1024, 2048)
    # the budget caps them: 6 copies of 8x16 int32 matrices, 3 KiB each
    assert autotune.candidate_tile_bs(3276, 8, 16, 4,
                                      vmem_budget=3 << 20) == (
        128, 256, 512, 1024)
    assert autotune.candidate_tile_bs(3276, 8, 16, 4, vmem_budget=1) == (
        128,)


# --------------------------------------------------------------------------
# The engine's padded_matrices counter
# --------------------------------------------------------------------------
@pytest.mark.parametrize("batch,per_call", [(273, 111), (3276, 308)],
                         ids=["prb", "slot"])
def test_engine_counts_padded_matrices(batch, per_call):
    eng = QRDEngine(QRDConfig(backend="blockfp_pallas", dtype="float64"))
    A = np.random.default_rng(5).standard_normal((batch, 8, 8))
    for _ in range(2):
        Q, R = eng(A)
    stats = eng.stats()
    assert stats["padded_matrices"] == 2 * per_call
    assert stats["matrices"] == 2 * batch
    _, Rr = np.linalg.qr(A)

    def positive(R):            # rows scaled so that diag(R) >= 0
        return R * np.sign(np.diagonal(R, axis1=-2, axis2=-1))[..., None]
    assert np.max(np.abs(positive(np.asarray(R)) - positive(Rr))) < 1e-4


def test_padded_matrices_rows_first_and_other_backends():
    A = np.random.default_rng(6).standard_normal((5, 4, 4))
    eng = QRDEngine(QRDConfig(backend="blockfp_pallas", dtype="float64"))
    eng(A)
    assert eng.stats()["padded_matrices"] == 3        # 5 -> one tile of 8
    eng = QRDEngine(QRDConfig(backend="cordic_pallas", dtype="float64"))
    eng(np.random.default_rng(6).standard_normal((300, 4, 4)))
    # int64 words interpreted run rows-first: 300 -> 38 tiles of 8
    assert eng.stats()["padded_matrices"] == 4
    eng = QRDEngine(QRDConfig(backend="jnp", dtype="float64"))
    eng(A)
    assert eng.stats()["padded_matrices"] == 0
