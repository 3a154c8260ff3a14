"""Wavefront datapath (DESIGN.md §8): stage-parallel blocked QR parity.

The contract: rotating every Sameh–Kuck stage in one shot along a pair
axis — full-width rows, per-pair column masks, gather/scatter by stage
index tables — changes the *order of evaluation*, never the arithmetic.
Within-stage rotations touch disjoint row pairs, so the packed wavefront
path must match `qr_cordic` on the flattened stage schedule bit for bit
(IEEE and HUB), and the int32 block-FP wavefront path must match the
step-serial blocked kernel on the same schedule.  The schedule itself is
checked as a property: every subdiagonal entry annihilated exactly once,
all within-stage pairs disjoint, depth = min(m + n − 2, 2m − 3).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (GivensConfig, GivensUnit, QRDEngine, givens_schedule,
                        qr_blockfp_pallas, qr_blockfp_wavefront, qr_cordic,
                        qr_cordic_wavefront, sameh_kuck_schedule, snr_db)

# Interpret-mode kernel compiles dominate this module's runtime
# (tens of seconds per pallas_call trace): full lane only.
pytestmark = pytest.mark.slow

RNG = np.random.default_rng(11)


def matrices(shape, r=4.0):
    mag = np.exp2(RNG.uniform(-r, r, size=shape))
    return RNG.choice([-1.0, 1.0], size=shape) * mag


def _flat(m, n):
    return tuple(s for stage in sameh_kuck_schedule(m, n) for s in stage)


def _assert_bit_exact(a, b):
    for u, v in zip(a, b):
        if u is None:
            assert v is None
            continue
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# odd batches stress TILE_B padding; non-square shapes stress the stage
# tables' Pmax padding (stages with fewer pairs than the widest stage)
@pytest.mark.parametrize("shape", [(5, 4, 4), (3, 6, 3), (2, 3, 5)])
@pytest.mark.parametrize("hub", [False, True])
def test_packed_wavefront_bit_exact(shape, hub):
    A = matrices(shape)
    m, n = shape[1:]
    unit = GivensUnit(GivensConfig(hub=hub, n=26))
    ref = qr_cordic(A, unit, steps=_flat(m, n))
    _assert_bit_exact(ref, qr_cordic_wavefront(A, unit))


def test_packed_wavefront_bit_exact_no_q():
    A = matrices((5, 4, 4))
    unit = GivensUnit(GivensConfig(hub=True, n=26))
    ref = qr_cordic(A, unit, compute_q=False, steps=_flat(4, 4))
    _assert_bit_exact(ref, qr_cordic_wavefront(A, unit, compute_q=False))


@pytest.mark.parametrize("shape", [(5, 4, 4), (3, 6, 3), (2, 3, 5)])
@pytest.mark.parametrize("hub", [False, True])
def test_blockfp_wavefront_matches_sequential(shape, hub):
    """Same quantize-once datapath, stage-parallel order: the wavefront
    block-FP path reproduces the step-serial blocked kernel on the
    flattened stage schedule (within-stage pairs are disjoint, and the
    pair-axis kernel replays the identical int32 recurrence), and stays a
    faithful QRD of the input."""
    A = matrices(shape)
    m, n = shape[1:]
    ref = qr_blockfp_pallas(A, steps=_flat(m, n), hub=hub)
    got = qr_blockfp_wavefront(A, hub=hub)
    for u, v in zip(ref, got):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=0.0, atol=0.0)
    assert float(jnp.mean(snr_db(A, *got))) > 90.0


def test_engine_sameh_kuck_routes_to_wavefront():
    """schedule='sameh_kuck' on the Pallas backends = the wavefront path,
    bit-identical to the reference loop on the flattened stage order."""
    A = matrices((4, 6, 4))
    cfg = GivensConfig(hub=True, n=26)
    ref = QRDEngine(backend="cordic", givens_config=cfg,
                    schedule="sameh_kuck")(A)
    got = QRDEngine(backend="cordic_pallas", givens_config=cfg,
                    schedule="sameh_kuck")(A)
    _assert_bit_exact(ref, got)
    Q, R = QRDEngine(backend="blockfp_pallas", givens_config=cfg,
                     schedule="sameh_kuck")(A)
    assert float(jnp.mean(snr_db(A, Q, R))) > 90.0
    assert np.all(np.tril(np.asarray(R), -1) == 0.0)


def test_engine_memoizes_schedules_and_jitted_callables():
    # schedule constructors are lru_cached: one tuple object per (m, n)
    assert sameh_kuck_schedule(6, 4) is sameh_kuck_schedule(6, 4)
    assert givens_schedule(6, 4) is givens_schedule(6, 4)
    eng = QRDEngine(backend="cordic_pallas",
                    givens_config=GivensConfig(hub=True, n=26),
                    schedule="sameh_kuck")
    A = matrices((2, 4, 4))
    eng(A)
    assert len(eng._fn_cache) == 1
    fn = next(iter(eng._fn_cache.values()))
    eng(matrices((2, 4, 4)))
    assert next(iter(eng._fn_cache.values())) is fn  # no rebuild, same shape
    eng(matrices((2, 3, 3)))
    assert len(eng._fn_cache) == 2               # one callable per (m, n)


def test_sharded_wavefront_tall_skinny_batch():
    from repro.core import qr_blocked_sharded
    from repro.launch.sharding import qrd_stage_table_spec

    assert qrd_stage_table_spec() == jax.sharding.PartitionSpec()
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    A = matrices((6, 8, 3), r=2.0)               # tall-skinny batch
    unit = GivensUnit(GivensConfig(hub=True, n=26))
    ref = qr_cordic(A, unit, steps=_flat(8, 3))
    _assert_bit_exact(ref, qr_blocked_sharded(A, unit, mesh,
                                              schedule="sameh_kuck"))
