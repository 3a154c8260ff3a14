"""QR decomposition engines built on the Givens rotation unit.

The paper evaluates its rotator inside the pipelined QRD architecture of
[Muñoz & Hormigo, TCAS-II 2015]: an m x n input matrix is triangularized by
the column-major Givens schedule, and Q is obtained by augmenting the rows
with the identity — the exact setup behind the paper's "e = 8 elements per
row for 4x4 matrices" throughput accounting and the HUB identity-detection
feature (the 1.0 entries of I enter the unit as data).

Backends:
  'cordic'        the paper's unit, bit-accurate (GivensUnit; IEEE or HUB)
  'cordic_pallas' the same unit, kernel-resident: the whole triangularization
                  runs inside one Pallas kernel (DESIGN.md §5), bit-identical
                  to 'cordic'
  'blockfp_pallas' int32 block-fixed-point blocked kernel: quantize once,
                  rotate everything fixed-point in VMEM, decode once (the
                  TPU-compilable fast path; not bit-identical to 'cordic')
  'givens_float'  float Givens rotations (algorithmic baseline, any dtype)
  'jnp'           jnp.linalg.qr (LAPACK-style "Matlab qr" reference)
  'fixed'         the 32-bit fixed-point rotator of [20] (Fig. 11 baseline)

All backends are batched over a leading batch axis.  Schedules: the default
column-major order, or the Sameh–Kuck parallel pairing
(`sameh_kuck_schedule`) whose stages rotate disjoint row pairs — the order a
spatial/multi-unit implementation would use.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp

from . import cordic
from .givens import GivensConfig, GivensUnit

__all__ = ["qr_cordic", "qr_cordic_pallas", "qr_blockfp_pallas",
           "qr_cordic_panel", "qr_blockfp_panel",
           "qr_cordic_wavefront", "qr_blockfp_wavefront",
           "qr_cordic_complex", "qr_cordic_complex_pallas",
           "qr_cordic_complex_wavefront",
           "qr_givens_float", "qr_jnp", "qr_fixed", "qr_blocked_sharded",
           "QRDEngine", "snr_db", "givens_schedule", "sameh_kuck_schedule"]

#: Bound on the host-side schedule memoization.  Schedules are derived
#: per *tile* (the tiled layer never asks for a full tall-skinny m ~ 10k
#: schedule — that would be a multi-MB tuple per shape), so a small LRU
#: covers every shape a process realistically touches while capping
#: worst-case host memory (DESIGN.md §14).
SCHEDULE_CACHE_SIZE = 128


@lru_cache(maxsize=SCHEDULE_CACHE_SIZE)
def givens_schedule(m: int, n: int):
    """Column-major zeroing order for an m x n matrix (memoized).

    Returns
    -------
    tuple[(int, int, int), ...]
        ``(pivot_row, target_row, col)`` triples: entry ``(target_row,
        col)`` is annihilated against the diagonal row ``col``, one column
        at a time.  This is the order the reference loop and the blocked
        kernels share.  The tuple is hashable (a jit static) and cached
        per ``(m, n)``, so repeated engine calls reuse one object.
    """
    return tuple((k, j, k)
                 for k in range(min(m - 1, n))
                 for j in range(k + 1, m))


@lru_cache(maxsize=SCHEDULE_CACHE_SIZE)
def sameh_kuck_schedule(m: int, n: int):
    """Sameh–Kuck parallel pairing schedule [Sameh & Kuck, JACM 1978].

    Entry ``(r, c)`` is annihilated against the *adjacent* row ``r - 1`` at
    stage ``(m - 1 - r) + 2 c``; all rotations within a stage touch
    disjoint row pairs, so a spatial array of rotators (or the wavefront
    kernels' pair axis, DESIGN.md §8) executes each stage fully in
    parallel.  The stage count is ``min(m + n - 2, 2 m - 3)`` — the
    sequential depth of the wavefront path, vs ``len(givens_schedule)``
    dependent rotations for the step-serial path.

    Returns
    -------
    tuple[tuple[(int, int, int), ...], ...]
        One inner tuple of ``(pivot_row, target_row, col)`` triples per
        stage (hashable — usable as a jit static; memoized per
        ``(m, n)``).  Flatten for engines that consume a sequential order
        — within-stage rotations commute, so any flattening of the stage
        order gives identical results.
    """
    stages: dict[int, list] = {}
    for c in range(min(m - 1, n)):
        for r in range(m - 1, c, -1):
            stages.setdefault((m - 1 - r) + 2 * c, []).append((r - 1, r, c))
    return tuple(tuple(stages[t]) for t in sorted(stages))


def _split_qr(out, m, n, compute_q):
    """Split a decoded working matrix [R' | Qt] and force R's structure."""
    R = out[..., :n]
    tri = jnp.tril(jnp.ones((m, n), bool), -1)
    R = jnp.where(tri, 0.0, R)
    if not compute_q:
        return None, R
    Q = jnp.swapaxes(out[..., n:], -1, -2)
    return Q, R


# --------------------------------------------------------------------------
# Paper backend: the CORDIC unit over packed words, rows augmented with I.
# --------------------------------------------------------------------------
def _augment(A, compute_q):
    """Append the identity columns: rows of e = n + m elements (or e = n)."""
    if not compute_q:
        return A
    m = A.shape[-2]
    eye = jnp.broadcast_to(jnp.eye(m, dtype=jnp.float64), A.shape[:-1] + (m,))
    return jnp.concatenate([A, eye], axis=-1)


def qr_cordic(A, unit: GivensUnit, N=None, iters=None, compute_q=True,
              steps=None):
    """QRD of a batch of matrices with the paper's unit (reference loop).

    One `GivensUnit.rotate_rows` launch per schedule step: every step
    round-trips the two packed rows through host-level ops — the behavior
    the kernel-resident `qr_cordic_pallas` eliminates while staying
    bit-identical.

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices (converted to float64).
    unit : GivensUnit
        The configured rotator (IEEE or HUB datapath).
    N, iters : optional traced scalars
        Override the config's significand width / CORDIC depth (used by the
        paper's Fig. 9 sweeps); None takes the config defaults.
    compute_q : bool
        Augment the rows with the identity to accumulate Q^T (the paper's
        setup; the 1.0 entries enter the unit as data).
    steps : sequence[(int, int, int)], optional
        Rotation schedule; defaults to the column-major `givens_schedule`.

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``), with R's
    structural zeros forced (the systolic array never stores them).
    """
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    work = _augment(A, compute_q)
    P = unit.encode(work)
    if steps is None:
        steps = givens_schedule(m, n)
    for (k, j, col) in steps:
        # Leading pair at `col`; rotate every remaining element of both rows.
        row_x = P[..., k, col:]
        row_y = P[..., j, col:]
        rx, ry = unit.rotate_rows(row_x, row_y, N=N, iters=iters)
        # The zeroed entry is structural in the systolic array.
        ry = ry.at[..., 0].set(0)
        P = P.at[..., k, col:].set(rx)
        P = P.at[..., j, col:].set(ry)
    # decode() maps packed-zero to +/-0.0; re-zero explicitly for cleanliness
    out = unit.decode(P)
    return _split_qr(out, m, n, compute_q)


def qr_cordic_pallas(A, unit: GivensUnit, compute_q=True, steps=None,
                     interpret=None, tile_b=None):
    """Kernel-resident QRD: the whole triangularization in one Pallas call.

    Semantically `qr_cordic` with the Python loop moved *inside* the
    kernel: the working tile stays in VMEM across all schedule steps and
    the per-step converter dataflow runs in registers (DESIGN.md §5).
    (Q, R) are bit-identical to `qr_cordic` for the same `GivensConfig`
    (IEEE and HUB) — the kernel calls the same unit arithmetic.

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices (converted to float64).
    unit : GivensUnit
        The configured rotator; its frozen config is a static kernel
        parameter.
    steps : sequence[(int, int, int)], optional
        Schedule; defaults to column-major.  Pass a flattened
        `sameh_kuck_schedule` for the parallel-pairing order.
    interpret : bool, optional
        Forwarded to the kernel; None auto-selects (interpret on CPU).
    tile_b : int, optional
        Batch tile of the blocked kernel; None lets the kernel choose
        from the shape (or takes the engine's autotuned value when
        dispatched through `repro.qrd.QRDEngine`).

    Returns
    -------
    (Q, R) : float64 arrays, bit-identical to `qr_cordic`.
    """
    from repro.kernels import ops as _kops  # deferred: core must not
    # depend on the kernels package at import time
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    P = unit.encode(_augment(A, compute_q))
    if steps is None:
        steps = givens_schedule(m, n)
    Pout = _kops.qr_packed(P, cfg=unit.cfg, steps=tuple(steps),
                           interpret=interpret, tile_b=tile_b)
    out = unit.decode(Pout)
    return _split_qr(out, m, n, compute_q)


def qr_blockfp_pallas(A, compute_q=True, iters=24, hub=True, frac=24,
                      steps=None, interpret=None, tile_b=None):
    """Blocked QRD on the int32 block-fixed-point kernel (the fast path).

    The working matrix is quantized once to per-column block fixed point,
    every rotation step runs int32 inside one Pallas kernel, and a single
    decode at the end recovers floats — no per-step FP round-trips.  Not
    bit-identical to `qr_cordic` (Q30 gain, no per-step renormalization);
    accuracy is that of an F-fraction-bit fixed-point datapath per column,
    which for ``frac=24`` lands within a few dB of the packed path on
    well-scaled inputs.

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices.  ``frac=24`` supports m up to ~64 (two
        CORDIC growth bits + √m column-norm growth inside int32).
    iters, hub, frac : int, bool, int
        CORDIC depth, HUB/conventional arithmetic, fraction bits.
    steps : sequence[(int, int, int)], optional
        Schedule; defaults to column-major.

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    with jax.named_scope("encode"):
        work = _augment(A, compute_q)
    if steps is None:
        steps = givens_schedule(m, n)
    out = _kops.givens_block_apply(work, tuple(steps), iters=iters, hub=hub,
                                   frac=frac, interpret=interpret,
                                   tile_b=tile_b)
    with jax.named_scope("decode"):
        return _split_qr(out, m, n, compute_q)


def qr_cordic_panel(A, unit: GivensUnit, compute_q=True, panel_n=8,
                    interpret=None, tile_b=None):
    """Tiled panel QRD over packed words: production m at kernel speed.

    The scaling form of `qr_cordic_pallas` (DESIGN.md §14): the flat
    kernel unrolls the whole schedule into one straight-line body, which
    stops tracing beyond toy m; here the triangularization proceeds
    panel by panel with the rotation control words exported from each
    panel factorization and replayed over the trailing panels
    (`ops.qr_packed_panel`).  Column-major order is preserved exactly,
    so (Q, R) are **bit-identical** to `qr_cordic` / `qr_cordic_pallas`
    with the default schedule (IEEE and HUB).

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices (converted to float64).
    unit : GivensUnit
        The configured rotator.
    panel_n : int
        Panel width (autotuner dimension).

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    P = unit.encode(_augment(A, compute_q))
    Pout = _kops.qr_packed_panel(P, cfg=unit.cfg, n_cols=n, panel_n=panel_n,
                                 interpret=interpret, tile_b=tile_b)
    out = unit.decode(Pout)
    return _split_qr(out, m, n, compute_q)


def qr_blockfp_panel(A, compute_q=True, iters=24, hub=True, frac=24,
                     panel_n=8, interpret=None, tile_b=None):
    """Tiled panel QRD on the int32 block-FP datapath (the fast path).

    The scaling form of `qr_blockfp_pallas`: quantize once, sweep the
    panels with exported/replayed control words, decode once
    (`ops.givens_block_apply_panel`).  Bit-identical to
    `qr_blockfp_pallas` with the default schedule.  ``frac=24`` supports
    m ≤ 128 (2 CORDIC growth bits + √m column-norm growth inside int32).

    Parameters as `qr_blockfp_pallas` plus ``panel_n`` (panel width).

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    with jax.named_scope("encode"):
        work = _augment(A, compute_q)
    out = _kops.givens_block_apply_panel(work, n_cols=n, iters=iters, hub=hub,
                                         frac=frac, panel_n=panel_n,
                                         interpret=interpret, tile_b=tile_b)
    with jax.named_scope("decode"):
        return _split_qr(out, m, n, compute_q)


# --------------------------------------------------------------------------
# Complex datapath: three-rotation Givens on (re, im) lane pairs (§10).
# --------------------------------------------------------------------------
def _augment_complex(A, compute_q):
    """Append the (real) identity columns to a complex working matrix."""
    if not compute_q:
        return A
    m = A.shape[-2]
    eye = jnp.broadcast_to(jnp.eye(m, dtype=A.dtype), A.shape[:-1] + (m,))
    return jnp.concatenate([A, eye], axis=-1)


def _encode_complex(unit, C):
    """complex (..., m, e) -> packed (..., m, e, 2) re/im lane pairs."""
    return jnp.stack([unit.encode(C.real), unit.encode(C.imag)], axis=-1)


def _decode_complex(unit, P):
    """packed (..., m, e, 2) -> complex128 (..., m, e)."""
    out = unit.decode(P)
    return jax.lax.complex(out[..., 0], out[..., 1])


def _split_qr_complex(C, m, n, compute_q):
    """Split a decoded complex working matrix [R' | G] into (Q, R).

    The rotations accumulate the unitary G with ``G A = R``, so
    ``Q = G^H`` — the conjugate transpose, where the real datapath takes a
    plain transpose.
    """
    R = C[..., :n]
    tri = jnp.tril(jnp.ones((m, n), bool), -1)
    R = jnp.where(tri, jnp.zeros((), R.dtype), R)
    if not compute_q:
        return None, R
    Q = jnp.conj(jnp.swapaxes(C[..., n:], -1, -2))
    return Q, R


def qr_cordic_complex(A, unit: GivensUnit, N=None, iters=None, compute_q=True,
                      steps=None):
    """Complex QRD of a batch of matrices with the paper's unit.

    The complex counterpart of `qr_cordic`: every schedule step runs the
    three-rotation decomposition (`GivensUnit.rotate_rows_complex`) — two
    vectoring phase rotations realize the leading entries, then the real
    Givens of the real datapath replays across the re and im lanes.  R
    comes out with a real non-negative diagonal (the phases are rotated
    into Q), the standard convention of complex Givens QRD hardware.
    Purely-real inputs reproduce `qr_cordic` bit for bit (the phase
    rotations skip as exact identities).

    Parameters
    ----------
    A : (..., m, n) array_like, complex
        Batch of input matrices (converted to complex128).
    unit : GivensUnit
        The configured rotator (IEEE or HUB datapath).
    N, iters : optional traced scalars
        Override the config's significand width / CORDIC depth.
    compute_q : bool
        Augment the rows with the identity to accumulate the unitary G;
        ``Q = G^H``.
    steps : sequence[(int, int, int)], optional
        Rotation schedule; defaults to the column-major `givens_schedule`.

    Returns
    -------
    (Q, R) : complex128 arrays (Q is None when ``compute_q=False``), with
    R's structural zeros forced and its diagonal exactly real.
    """
    A = jnp.asarray(A, jnp.complex128)
    m, n = A.shape[-2], A.shape[-1]
    P = _encode_complex(unit, _augment_complex(A, compute_q))
    if steps is None:
        steps = givens_schedule(m, n)
    for (k, j, col) in steps:
        rx, ry = unit.rotate_rows_complex(P[..., k, col:, :],
                                          P[..., j, col:, :], N=N, iters=iters)
        P = P.at[..., k, col:, :].set(rx)
        P = P.at[..., j, col:, :].set(ry)
    out = _decode_complex(unit, P)
    return _split_qr_complex(out, m, n, compute_q)


def qr_cordic_complex_pallas(A, unit: GivensUnit, compute_q=True, steps=None,
                             interpret=None, tile_b=None):
    """Kernel-resident complex QRD: the triangularization in one Pallas call.

    `qr_cordic_complex` with the step loop moved inside the kernel — the
    (re, im) lane pairs ride along as a trailing axis of the resident
    tile, and each step runs the same three-rotation
    `GivensUnit.rotate_rows_complex` dataflow in registers.  (Q, R) are
    bit-identical to `qr_cordic_complex` for the same `GivensConfig`.

    Parameters as `qr_cordic_complex`; ``interpret`` is forwarded to the
    kernel (None auto-selects: interpret on CPU).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.complex128)
    m, n = A.shape[-2], A.shape[-1]
    P = _encode_complex(unit, _augment_complex(A, compute_q))
    if steps is None:
        steps = givens_schedule(m, n)
    Pout = _kops.qr_packed_complex(P, cfg=unit.cfg, steps=tuple(steps),
                                   interpret=interpret, tile_b=tile_b)
    return _split_qr_complex(_decode_complex(unit, Pout), m, n, compute_q)


def qr_cordic_complex_wavefront(A, unit: GivensUnit, compute_q=True,
                                stages=None, interpret=None, tile_b=None,
                                table_layout=None):
    """Wavefront kernel-resident complex QRD (one scan step per stage).

    The stage-parallel counterpart of `qr_cordic_complex_pallas`: every
    Sameh–Kuck stage's disjoint row pairs run the three-rotation
    decomposition in one shot along the pair axis, with the (re, im)
    lanes as an extra trailing axis and the per-pair column masks of the
    real wavefront path unchanged (DESIGN.md §8, §10).  Bit-identical to
    `qr_cordic_complex` on the flattened stage schedule.

    Parameters as `qr_cordic_wavefront`.
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.complex128)
    m, n = A.shape[-2], A.shape[-1]
    P = _encode_complex(unit, _augment_complex(A, compute_q))
    Pout = _kops.qr_packed_complex_wavefront(
        P, cfg=unit.cfg, stages=_as_stages(m, n, stages), interpret=interpret,
        tile_b=tile_b, table_layout=table_layout)
    return _split_qr_complex(_decode_complex(unit, Pout), m, n, compute_q)


def _as_stages(m, n, stages):
    """Normalize a stage schedule to a hashable tuple-of-tuples static."""
    if stages is None:
        return sameh_kuck_schedule(m, n)
    return tuple(tuple(st) for st in stages)


def qr_cordic_wavefront(A, unit: GivensUnit, compute_q=True, stages=None,
                        interpret=None, tile_b=None, table_layout=None):
    """Wavefront kernel-resident QRD: one scan step per Sameh–Kuck stage.

    The stage-parallel counterpart of `qr_cordic_pallas` (DESIGN.md §8):
    all rotations of a stage — their row pairs are disjoint by construction
    — run in one shot along a pair axis of the resident tile, so the
    sequential depth collapses from ``len(steps)`` dependent rotations to
    ``len(stages)`` loop iterations, and the trace holds one stage body
    instead of the whole unrolled schedule.  (Q, R) are bit-identical to
    `qr_cordic` on the flattened stage schedule (same `GivensUnit`
    arithmetic; within-stage rotations commute).

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices (converted to float64).
    unit : GivensUnit
        The configured rotator; its frozen config is a static kernel
        parameter.
    stages : sequence[sequence[(int, int, int)]], optional
        Stage schedule; defaults to ``sameh_kuck_schedule(m, n)``.  Every
        inner sequence's row pairs must be disjoint.

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    P = unit.encode(_augment(A, compute_q))
    Pout = _kops.qr_packed_wavefront(P, cfg=unit.cfg,
                                     stages=_as_stages(m, n, stages),
                                     interpret=interpret, tile_b=tile_b,
                                     table_layout=table_layout)
    out = unit.decode(Pout)
    return _split_qr(out, m, n, compute_q)


def qr_blockfp_wavefront(A, compute_q=True, iters=24, hub=True, frac=24,
                         stages=None, interpret=None, tile_b=None,
                         table_layout=None):
    """Wavefront blocked QRD on the int32 block-FP kernel (fastest path).

    `qr_blockfp_pallas` with the step-serial schedule replaced by the
    Sameh–Kuck stage tables: quantize once, rotate every stage's disjoint
    row pairs in one shot, decode once (DESIGN.md §8).  Bit-identical to
    `qr_blockfp_pallas` on the flattened stage schedule; accuracy is that
    of the F-fraction-bit block-FP datapath, as for the sequential path.

    Parameters
    ----------
    A : (..., m, n) array_like
        Batch of input matrices (``frac=24`` supports m up to ~64).
    stages : sequence[sequence[(int, int, int)]], optional
        Stage schedule; defaults to ``sameh_kuck_schedule(m, n)``.

    Returns
    -------
    (Q, R) : float64 arrays (Q is None when ``compute_q=False``).
    """
    from repro.kernels import ops as _kops
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    with jax.named_scope("encode"):
        work = _augment(A, compute_q)
    out = _kops.givens_block_apply_wavefront(
        work, _as_stages(m, n, stages), iters=iters, hub=hub, frac=frac,
        interpret=interpret, tile_b=tile_b, table_layout=table_layout)
    with jax.named_scope("decode"):
        return _split_qr(out, m, n, compute_q)


def qr_blocked_sharded(A, unit: GivensUnit, mesh, compute_q=True,
                       steps=None, interpret=None, schedule="col"):
    """Batch-sharded kernel-resident QRD (the tall-skinny scaling path).

    Legacy shim: since the API redesign (DESIGN.md §9) this is plain
    engine dispatch with a mesh-carrying config —
    ``repro.qrd.QRDEngine(backend='cordic_pallas', mesh=mesh)(A)`` — which
    places the leading batch axis of ``A`` across the mesh's data axes
    (`repro.launch.sharding.shard_qrd_batch`) and runs the kernel-resident
    QRD; under jit the per-device kernels each triangularize their local
    batch shard — QRD is embarrassingly parallel over the batch, so no
    collective is needed until the caller combines results.

    Parameters
    ----------
    A : (batch, m, n) array_like
    mesh : jax.sharding.Mesh
        Mesh with a "model" axis and one or more data axes (see
        `repro.launch.mesh`).
    schedule : str
        ``'col'`` runs the step-serial `qr_cordic_pallas`;
        ``'sameh_kuck'`` runs the wavefront `qr_cordic_wavefront` — each
        device's kernel rotates whole stages at once, and the stage index
        tables are replicated across the mesh
        (`repro.launch.sharding.qrd_stage_table_spec`).
    steps : tuple, optional
        Explicit step-serial schedule override (not expressible as an
        engine config; runs the direct sharded path).

    Returns
    -------
    (Q, R) with the same batch sharding as the input placement.
    """
    if schedule not in ("col", "sameh_kuck"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if steps is not None:
        if schedule == "sameh_kuck":
            raise ValueError("steps= is the step-serial schedule; the "
                             "wavefront path takes stage schedules — call "
                             "qr_cordic_wavefront(stages=...) directly")
        from repro.launch import sharding as _sh
        A = _sh.shard_qrd_batch(jnp.asarray(A, jnp.float64), mesh)
        return qr_cordic_pallas(A, unit, compute_q=compute_q, steps=steps,
                                interpret=interpret)
    from repro import qrd as _api
    cfg = _api.QRDConfig(backend="cordic_pallas", schedule=schedule,
                         givens=unit.cfg, interpret=interpret, mesh=mesh)
    return _shared_engine()._dispatch(A, compute_q, cfg)


def _shared_engine():
    """Module-level dispatch host for the legacy free-function shims.

    One bounded jitted-callable LRU shared by all legacy calls; the
    per-call config (including its mesh, keyed by identity) selects the
    actual backend.
    """
    global _SHARED_ENGINE
    if _SHARED_ENGINE is None:
        from repro import qrd as _api
        _SHARED_ENGINE = _api.QRDEngine()
    return _SHARED_ENGINE


_SHARED_ENGINE = None


# --------------------------------------------------------------------------
# Float Givens baseline (the algorithm, without the paper's arithmetic).
# --------------------------------------------------------------------------
def qr_givens_float(A, dtype=jnp.float32, compute_q=True):
    """Batched QR via float Givens rotations (same schedule as the unit).

    The algorithmic baseline: identical column-major schedule and
    augmented-identity Q accumulation, but plain `dtype` floating point
    instead of the paper's arithmetic.  Complex dtypes use the conjugate
    Givens rotation ``G = [[ā, b̄], [-b, a]] / r`` with ``r = √(|a|²+|b|²)``
    — unitary, annihilates b, and reduces exactly to the real rotation
    when the inputs are real (conjugation is the identity there, so the
    real path is unchanged bit for bit).  A: (..., m, n); returns (Q, R)
    in `dtype` (Q is None when ``compute_q=False``); for complex dtypes
    ``Q = G^H`` takes the conjugate transpose and R's diagonal is real
    non-negative.
    """
    dtype = jnp.dtype(dtype)
    A = jnp.asarray(A, dtype)
    m, n = A.shape[-2], A.shape[-1]
    if compute_q:
        eye = jnp.broadcast_to(jnp.eye(m, dtype=dtype), A.shape[:-1] + (m,))
        W = jnp.concatenate([A, eye], axis=-1)
    else:
        W = A
    for (k, j, col) in givens_schedule(m, n):
        a = W[..., k, col]
        b = W[..., j, col]
        r = jnp.sqrt(jnp.abs(a) ** 2 + jnp.abs(b) ** 2)
        safe = r > 0
        rs = jnp.where(safe, r, 1).astype(dtype)
        c = jnp.where(safe, jnp.conj(a) / rs, 1.0).astype(dtype)
        s = jnp.where(safe, jnp.conj(b) / rs, 0.0).astype(dtype)
        rk = c[..., None] * W[..., k, :] + s[..., None] * W[..., j, :]
        rj = (-jnp.conj(s)[..., None] * W[..., k, :]
              + jnp.conj(c)[..., None] * W[..., j, :])
        rj = rj.at[..., col].set(0)
        rk = rk.at[..., col].set(r.astype(dtype))
        W = W.at[..., k, :].set(rk)
        W = W.at[..., j, :].set(rj)
    R = W[..., :n]
    if not compute_q:
        return None, R
    Q = jnp.conj(jnp.swapaxes(W[..., n:], -1, -2))
    return Q, R


def qr_jnp(A, dtype=jnp.float32, compute_q=True):
    """LAPACK-style reference ("Matlab qr, single precision").

    A: (..., m, n); returns complete-mode (Q, R) from `jnp.linalg.qr` in
    `dtype` — the paper's comparison reference.  ``compute_q=False``
    returns ``(None, R)`` like every other backend (the registry exposes
    one uniform backend signature); under jit XLA dead-code-eliminates
    the unused Q factor.
    """
    Q, R = jnp.linalg.qr(jnp.asarray(A, dtype), mode="complete")
    return (Q if compute_q else None), R


# --------------------------------------------------------------------------
# Fixed-point rotator of [20] (Fig. 11 comparison): inputs pre-scaled by
# 2^-scale_exp into (-1, 1), W-bit datapath, CORDIC + gain compensation.
# --------------------------------------------------------------------------
def qr_fixed(A, width=32, iters=27, scale_exp=0, compute_q=True):
    """Batched QRD in pure fixed point (W-bit, F = width-2 fraction bits).

    The Fig. 11 baseline [20]: inputs are pre-scaled by 2^-scale_exp into
    (-1, 1) and quantized RNE to the F-bit grid; the whole decomposition
    runs in int64-carried W-bit two's complement with CORDIC + gain
    compensation.  A: (..., m, n); returns float64 (Q, R).
    """
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    if compute_q:
        eye = jnp.broadcast_to(jnp.eye(m, dtype=jnp.float64), A.shape[:-1] + (m,))
        W = jnp.concatenate([A, eye], axis=-1)
    else:
        W = A
    F = width - 2
    scale = jnp.exp2(jnp.asarray(F - scale_exp, jnp.float64))
    X = jnp.rint(W * scale).astype(jnp.int64)  # RNE quantization to the grid
    itv = jnp.asarray(iters, jnp.int64)
    wv = jnp.asarray(width + 2, jnp.int64)
    for (k, j, col) in givens_schedule(m, n):
        xl, yl, flip, sig = cordic.vectoring(X[..., k, col], X[..., j, col],
                                             itv, hub=False)
        xr, yr = cordic.rotation(X[..., k, col + 1:], X[..., j, col + 1:],
                                 flip[..., None], sig[..., None], itv, hub=False)
        xl, yl = cordic.apply_gain(xl, yl, itv, wv, hub=False)
        xr, yr = cordic.apply_gain(xr, yr, itv, wv, hub=False)
        X = X.at[..., k, col].set(xl)
        X = X.at[..., j, col].set(0)
        X = X.at[..., k, col + 1:].set(xr)
        X = X.at[..., j, col + 1:].set(yr)
    out = X.astype(jnp.float64) / scale
    R = out[..., :n]
    tri = jnp.tril(jnp.ones((m, n), bool), -1)
    R = jnp.where(tri, 0.0, R)
    if not compute_q:
        return None, R
    Q = jnp.swapaxes(out[..., n:], -1, -2)
    return Q, R


# --------------------------------------------------------------------------
# Engine facade + error metric
# --------------------------------------------------------------------------
@dataclasses.dataclass
class QRDEngine:
    """Backend-selectable batched QRD — legacy shim over `repro.qrd`.

    Since the API redesign (DESIGN.md §9) this dataclass is a thin facade
    over the registry-dispatched `repro.qrd.QRDEngine`: construction
    validates the backend/schedule against the registry, and every call
    rebuilds a `repro.qrd.QRDConfig` from the (mutable) fields, so field
    mutation between calls misses the jitted-callable cache rather than
    returning stale results.  New code should use `repro.qrd.QRDEngine`
    directly — it adds ``solve()`` (batched least squares), ``rls()``
    (streaming QRD-RLS) and mesh-sharded dispatch.

    Parameters
    ----------
    backend : str
        Any registered backend (`repro.qrd.available_backends()`); the
        built-ins are ``'jnp'`` (LAPACK reference), ``'givens_float'``
        (float Givens baseline), ``'cordic'`` (bit-accurate unit,
        reference loop), ``'cordic_pallas'`` (same unit, kernel-resident —
        (Q, R) bit-identical to ``'cordic'``), ``'blockfp_pallas'`` (int32
        block-fixed-point blocked kernel), ``'fixed'`` (32-bit fixed-point
        rotator of [20]).
    givens_config : GivensConfig
        Unit parameters for the ``'cordic'`` / ``'cordic_pallas'``
        backends; ``'blockfp_pallas'`` uses its ``hub`` flag and resolved
        iteration count.
    schedule : str
        ``'col'`` (column-major) or ``'sameh_kuck'`` (parallel pairing).
        With ``'sameh_kuck'`` the Pallas backends route onto the
        **wavefront datapath** (DESIGN.md §8); the ``'cordic'`` loop
        consumes the flattened stage order.
    fixed_width, fixed_iters, fixed_scale_exp : int
        Parameters of the ``'fixed'`` baseline.

    Call with ``engine(A, compute_q=...)`` where ``A`` is ``(..., m, n)``;
    returns ``(Q, R)`` float arrays (Q is None when ``compute_q=False``).
    The engine memoizes one jitted callable per ``(m, n, compute_q,
    config)`` in a *bounded* LRU (`repro.qrd.QRDEngine`), so churning
    many shapes evicts cold callables instead of growing without bound.
    """

    backend: str = "jnp"
    givens_config: GivensConfig = dataclasses.field(default_factory=GivensConfig)
    schedule: str = "col"
    fixed_width: int = 32
    fixed_iters: int = 27
    fixed_scale_exp: int = 0

    _BACKENDS = ("jnp", "givens_float", "cordic", "cordic_pallas",
                 "blockfp_pallas", "fixed")

    def _to_config(self):
        from repro import qrd as _api
        return _api.QRDConfig(backend=self.backend, schedule=self.schedule,
                              givens=self.givens_config,
                              fixed_width=self.fixed_width,
                              fixed_iters=self.fixed_iters,
                              fixed_scale_exp=self.fixed_scale_exp)

    def __post_init__(self):
        # fail at construction, not first call: bad backend/schedule names
        # and invalid unit configs should not surface deep inside a run
        from repro import qrd as _api
        self._engine = _api.QRDEngine(self._to_config())

    @property
    def _fn_cache(self):
        """The underlying bounded jitted-callable LRU (tests poke this)."""
        return self._engine._fn_cache

    def __call__(self, A, compute_q=True):
        return self._engine._dispatch(A, compute_q, self._to_config())

    def solve(self, A, b, return_residuals=False):
        """Batched least squares — see `repro.qrd.QRDEngine.solve`."""
        eng = self._engine
        eng.config = self._to_config()
        return eng.solve(A, b, return_residuals=return_residuals)

    def rls(self, n, lam=0.99, delta=1e-3, block=None):
        """Streaming QRD-RLS state — see `repro.qrd.QRDEngine.rls`."""
        eng = self._engine
        eng.config = self._to_config()
        return eng.rls(n, lam=lam, delta=delta, block=block)


def snr_db(A, Q, R):
    """Paper's error metric: SNR of the reconstruction B = Q @ R vs A, in dB.

    Computed in double precision; mean is taken over the batch by the caller
    (the paper reports the mean SNR of 10,000 matrices).
    """
    A = jnp.asarray(A, jnp.float64)
    B = jnp.matmul(jnp.asarray(Q, jnp.float64), jnp.asarray(R, jnp.float64))
    num = jnp.sum(A * A, axis=(-2, -1))
    den = jnp.sum((A - B) ** 2, axis=(-2, -1))
    return 10.0 * jnp.log10(num / jnp.maximum(den, 1e-300))
