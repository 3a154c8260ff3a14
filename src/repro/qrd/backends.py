"""Built-in backend registrations (the entries `QRDEngine._build` used to
hard-code as an if/elif chain).

Each builder closes over a resolved `QRDConfig` + static shape and returns
a jit-compatible ``(A) -> (Q, R)`` callable on the corresponding free
function in `repro.core.qrd` — the free functions stay the single source
of arithmetic truth, the registry only owns dispatch.  Importing this
module (it is imported by ``repro.qrd``) populates the registry;
third-party backends call `repro.qrd.register_backend` the same way.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import qrd as _q
from repro.core.givens import GivensUnit

from .registry import (BackendCapabilities, available_backends,
                       register_backend)

__all__ = ["register_builtin_backends", "batch_padding"]


def _flat_steps(config, m, n):
    """Schedule for step-serial backends: None = column-major default."""
    if config.schedule == "sameh_kuck":
        return tuple(s for st in _q.sameh_kuck_schedule(m, n) for s in st)
    return None


def _build_jnp(config, m, n, compute_q):
    dtype = jnp.dtype(config.dtype)
    return lambda A: _q.qr_jnp(A, dtype, compute_q=compute_q)


def _build_givens_float(config, m, n, compute_q):
    dtype = jnp.dtype(config.dtype)
    return lambda A: _q.qr_givens_float(A, dtype=dtype, compute_q=compute_q)


def _build_cordic(config, m, n, compute_q):
    unit = GivensUnit(config.givens)
    steps = _flat_steps(config, m, n)
    if config.is_complex():               # complex datapath (DESIGN.md §10)
        return lambda A: _q.qr_cordic_complex(A, unit, compute_q=compute_q,
                                              steps=steps)
    return lambda A: _q.qr_cordic(A, unit, compute_q=compute_q, steps=steps)


def _build_cordic_pallas(config, m, n, compute_q):
    unit = GivensUnit(config.givens)
    tile_b, layout = config.tile_b, config.table_layout
    if config.schedule == "sameh_kuck":   # wavefront datapath (DESIGN.md §8)
        stages = _q.sameh_kuck_schedule(m, n)
        if config.is_complex():
            return lambda A: _q.qr_cordic_complex_wavefront(
                A, unit, compute_q=compute_q, stages=stages,
                interpret=config.interpret, tile_b=tile_b,
                table_layout=layout)
        return lambda A: _q.qr_cordic_wavefront(
            A, unit, compute_q=compute_q, stages=stages,
            interpret=config.interpret, tile_b=tile_b, table_layout=layout)
    if config.is_complex():
        return lambda A: _q.qr_cordic_complex_pallas(
            A, unit, compute_q=compute_q, interpret=config.interpret,
            tile_b=tile_b)
    return lambda A: _q.qr_cordic_pallas(A, unit, compute_q=compute_q,
                                         interpret=config.interpret,
                                         tile_b=tile_b)


def _build_blockfp_pallas(config, m, n, compute_q):
    iters, hub, frac = (config.blockfp_iters(), config.blockfp_hub(),
                        config.frac)
    tile_b, layout = config.tile_b, config.table_layout
    if config.schedule == "sameh_kuck":
        stages = _q.sameh_kuck_schedule(m, n)
        return lambda A: _q.qr_blockfp_wavefront(
            A, compute_q=compute_q, iters=iters, hub=hub, frac=frac,
            stages=stages, interpret=config.interpret, tile_b=tile_b,
            table_layout=layout)
    return lambda A: _q.qr_blockfp_pallas(
        A, compute_q=compute_q, iters=iters, hub=hub, frac=frac,
        interpret=config.interpret, tile_b=tile_b)


def _build_fixed(config, m, n, compute_q):
    return lambda A: _q.qr_fixed(A, config.fixed_width, config.fixed_iters,
                                 config.fixed_scale_exp, compute_q=compute_q)


def batch_padding(config, m, n, compute_q, batch) -> int:
    """Zero matrices the flat Pallas kernel of ``config`` adds to a batch
    of ``batch`` m×n operands to fill its batch tiles.

    The kernel's layout follows from the shape as in `repro.kernels.ops`
    (`qrd_blocked.batch_minor`).  Other backends, and the tiled routes,
    which re-tile per panel and per tree level, count 0.
    """
    from repro.kernels import ops, qrd_blocked as qb
    from . import registry, tiled

    if config.backend == "blockfp_pallas":
        itemsize, wide = qb.BlockFPPath.itemsize, False
    elif config.backend == "cordic_pallas":    # int64 words interpreted
        itemsize = qb.LanePath.itemsize
        wide = config.is_complex() or ops.auto_interpret(config.interpret)
    else:
        return 0
    caps = registry.get_backend(config.backend).capabilities
    if tiled.resolve_route(config, m, n, caps) != "flat":
        return 0
    e = n + m if compute_q else n
    bm = not wide and qb.batch_minor(m, e, batch, itemsize)
    return qb.padded_batch(batch, config.tile_b, bm) - batch


def register_builtin_backends(overwrite=False):
    """Populate the registry with the six built-in backends (idempotent)."""
    entries = (
        ("jnp", _build_jnp, BackendCapabilities(
            bit_exact=False, wavefront=False, sharding=False,
            dtypes=("float16", "float32", "float64",
                    "complex64", "complex128"),
            description="jnp.linalg.qr Householder reference "
                        "(schedule-agnostic; 'sameh_kuck' degrades to it)")),
        ("givens_float", _build_givens_float, BackendCapabilities(
            bit_exact=False, wavefront=False, sharding=False,
            dtypes=("float16", "float32", "float64",
                    "complex64", "complex128"),
            description="float Givens baseline, column-major schedule "
                        "(complex via conjugate rotations)")),
        ("cordic", _build_cordic, BackendCapabilities(
            bit_exact=True, wavefront=False, sharding=True,
            dtypes=("float64", "complex128"),
            description="the paper's unit, host reference loop "
                        "('sameh_kuck' consumes the flattened stage order; "
                        "complex via the three-rotation decomposition)")),
        ("cordic_pallas", _build_cordic_pallas, BackendCapabilities(
            bit_exact=True, wavefront=True, sharding=True,
            dtypes=("float64", "complex128"),
            max_shape=(128, 128), supports_tiling=True,
            interpret_only=("complex", "tiling"),
            description="kernel-resident unit, bit-identical to 'cordic'; "
                        "'sameh_kuck' routes onto the wavefront datapath; "
                        "complex and tiled words are int64 (interpret "
                        "mode only)")),
        ("blockfp_pallas", _build_blockfp_pallas, BackendCapabilities(
            bit_exact=False, wavefront=True, sharding=True,
            max_shape=(128, 128), supports_tiling=True,
            description="int32 block-FP blocked kernel (fast TPU path)")),
        ("fixed", _build_fixed, BackendCapabilities(
            bit_exact=False, wavefront=False, sharding=False,
            description="32-bit fixed-point rotator of [20] "
                        "(Fig. 11 baseline; schedule-agnostic)")),
    )
    registered = available_backends()
    for name, builder, caps in entries:
        if overwrite or name not in registered:
            register_backend(name, builder, caps, overwrite=overwrite)


register_builtin_backends()
