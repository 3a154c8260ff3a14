"""Compiled-vs-interpret parity and ragged-batch padding contracts.

Two guarantees from DESIGN.md §11:

* **Compiled parity** — every Pallas path that can lower on this host's
  backend must produce *bit-identical* output with ``interpret=False``
  and ``interpret=True``.  On CPU-only hosts (no Mosaic/Triton target)
  these tests skip with an explicit reason rather than silently passing;
  ``chip_smoke.py`` at the repo root is the entry point that runs the
  main path compiled on a TPU (and fails where it finds none).

* **Ragged batches** — every ``*_call``-backed op pads the leading batch
  axis up to a multiple of ``tile_b`` and strips the padding afterward,
  so a batch of 5 with ``tile_b=4`` is bit-identical to the same batch
  with a tile that divides it exactly.  This runs everywhere (interpret
  mode included) and covers all six kernel entry points.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.givens import GivensConfig, GivensUnit
from repro.core.qrd import givens_schedule, sameh_kuck_schedule
from repro.kernels import ops

compiled = pytest.mark.skipif(
    not ops.compiled_backend_available(),
    reason="no compiled Pallas backend on "
           f"'{jax.default_backend()}' — interpret=False needs TPU/GPU")

CFG = GivensConfig(n=25, hub=True)
M = 4
STEPS = givens_schedule(M, M)
STAGES = sameh_kuck_schedule(M, M)


def _packed(batch, seed=0, cfg=CFG, m=M):
    rng = np.random.default_rng(seed)
    unit = GivensUnit(cfg)
    return unit.encode(jnp.asarray(rng.standard_normal((batch, m, m))))


def _cpacked(batch, seed=0, cfg=CFG, m=M):
    rng = np.random.default_rng(seed)
    unit = GivensUnit(cfg)
    z = rng.standard_normal((batch, m, m, 2))
    return unit.encode(jnp.asarray(z))


def _rows(batch, seed=0, m=M):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((batch, m, m)))


# --------------------------------------------------------------------------
# Compiled parity (skips on CPU with the reason above).
# --------------------------------------------------------------------------
@compiled
def test_blockfp_compiled_matches_interpret():
    W = _rows(8)
    ci = ops.givens_block_apply(W, STEPS, interpret=True)
    cc = ops.givens_block_apply(W, STEPS, interpret=False)
    assert bool(jnp.all(ci == cc))


@compiled
def test_blockfp_wavefront_compiled_matches_interpret():
    W = _rows(8, seed=1)
    ci = ops.givens_block_apply_wavefront(W, STAGES, interpret=True)
    cc = ops.givens_block_apply_wavefront(W, STAGES, interpret=False)
    assert bool(jnp.all(ci == cc))


@compiled
@pytest.mark.parametrize("lanes", [False, True])
def test_packed_compiled_matches_interpret(lanes):
    P = _packed(8, seed=2)
    ci = ops.qr_packed(P, cfg=CFG, steps=STEPS, lanes=lanes, interpret=True)
    cc = ops.qr_packed(P, cfg=CFG, steps=STEPS, lanes=lanes, interpret=False)
    assert bool(jnp.all(ci == cc))


@compiled
def test_packed_wavefront_compiled_matches_interpret():
    P = _packed(8, seed=3)
    ci = ops.qr_packed_wavefront(P, cfg=CFG, stages=STAGES, lanes=True,
                                 interpret=True)
    cc = ops.qr_packed_wavefront(P, cfg=CFG, stages=STAGES, lanes=True,
                                 interpret=False)
    assert bool(jnp.all(ci == cc))


# --------------------------------------------------------------------------
# Ragged batches: B=5 with tile_b=4 (pad+mask) vs tile_b=5 (exact fit).
# Runs on every host; interpret mode is resolved by the ops layer.
# --------------------------------------------------------------------------
B_RAGGED = 5


def test_ragged_qr_packed():
    P = _packed(B_RAGGED, seed=4)
    a = ops.qr_packed(P, cfg=CFG, steps=STEPS, tile_b=4)
    b = ops.qr_packed(P, cfg=CFG, steps=STEPS, tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


def test_ragged_qr_packed_lanes():
    P = _packed(B_RAGGED, seed=5)
    a = ops.qr_packed(P, cfg=CFG, steps=STEPS, lanes=True, tile_b=4)
    b = ops.qr_packed(P, cfg=CFG, steps=STEPS, lanes=True, tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


@pytest.mark.parametrize("layout", ["split", "stacked"])
def test_ragged_qr_packed_wavefront(layout):
    P = _packed(B_RAGGED, seed=6)
    a = ops.qr_packed_wavefront(P, cfg=CFG, stages=STAGES, tile_b=4,
                                lanes=True, table_layout=layout)
    b = ops.qr_packed_wavefront(P, cfg=CFG, stages=STAGES, tile_b=B_RAGGED,
                                lanes=True, table_layout=layout)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


def test_ragged_qr_packed_complex():
    P = _cpacked(B_RAGGED, seed=7)
    a = ops.qr_packed_complex(P, cfg=CFG, steps=STEPS, tile_b=4)
    b = ops.qr_packed_complex(P, cfg=CFG, steps=STEPS, tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


def test_ragged_qr_packed_complex_wavefront():
    P = _cpacked(B_RAGGED, seed=8)
    a = ops.qr_packed_complex_wavefront(P, cfg=CFG, stages=STAGES, tile_b=4)
    b = ops.qr_packed_complex_wavefront(P, cfg=CFG, stages=STAGES,
                                        tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


def test_ragged_blockfp():
    W = _rows(B_RAGGED, seed=9)
    a = ops.givens_block_apply(W, STEPS, tile_b=4)
    b = ops.givens_block_apply(W, STEPS, tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))


def test_ragged_blockfp_wavefront():
    W = _rows(B_RAGGED, seed=10)
    a = ops.givens_block_apply_wavefront(W, STAGES, tile_b=4)
    b = ops.givens_block_apply_wavefront(W, STAGES, tile_b=B_RAGGED)
    assert a.shape[0] == B_RAGGED
    assert bool(jnp.all(a == b))
