"""Kernel-resident blocked QR vs the reference loop: bit-exact parity.

The contract under test (DESIGN.md §5): moving the whole triangularization
inside one Pallas kernel changes *where* the arithmetic runs, never *what*
it computes — `'cordic_pallas'` must match `qr_cordic` bit for bit on IEEE
and HUB configs, for every schedule, on shapes that stress the batch-tile
padding.  The int32 block-fixed-point fast path is held to accuracy (not
bit) parity, and the fused single-pass row kernel is checked against the
separate vectoring/rotation kernels on odd shapes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (GivensConfig, GivensUnit, QRDEngine, givens_schedule,
                        qr_blockfp_pallas, qr_cordic, qr_cordic_pallas,
                        sameh_kuck_schedule, snr_db)
from repro.kernels import ops

# Interpret-mode kernel compiles dominate this module's runtime
# (tens of seconds per pallas_call trace): full lane only.
pytestmark = pytest.mark.slow

RNG = np.random.default_rng(7)


def matrices(shape, r=4.0):
    mag = np.exp2(RNG.uniform(-r, r, size=shape))
    return RNG.choice([-1.0, 1.0], size=shape) * mag


def _assert_bit_exact(a, b):
    for u, v in zip(a, b):
        if u is None:
            assert v is None
            continue
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# shapes stress the TILE_B padding (odd batches) and non-square matrices
@pytest.mark.parametrize("shape", [(5, 4, 4), (3, 6, 3), (2, 3, 5)])
@pytest.mark.parametrize("hub", [False, True])
def test_cordic_pallas_bit_exact(shape, hub):
    A = matrices(shape)
    unit = GivensUnit(GivensConfig(hub=hub, n=26))
    _assert_bit_exact(qr_cordic(A, unit), qr_cordic_pallas(A, unit))


def test_cordic_pallas_bit_exact_no_q():
    A = matrices((5, 4, 4))
    unit = GivensUnit(GivensConfig(hub=True, n=26))
    _assert_bit_exact(qr_cordic(A, unit, compute_q=False),
                      qr_cordic_pallas(A, unit, compute_q=False))


def test_sameh_kuck_stages_disjoint_and_complete():
    for (m, n) in [(4, 4), (6, 3), (8, 8), (3, 5)]:
        stages = sameh_kuck_schedule(m, n)
        flat = [s for st in stages for s in st]
        # same rotation set as the column-major schedule
        assert {(j, c) for (_, j, c) in flat} == \
               {(j, c) for (_, j, c) in givens_schedule(m, n)}
        for stage in stages:  # within a stage all row pairs are disjoint
            rows = [r for (k, j, _) in stage for r in (k, j)]
            assert len(rows) == len(set(rows))
        # adjacent-row pairing: pivot is always target - 1
        assert all(k == j - 1 for (k, j, _) in flat)


def test_sameh_kuck_schedule_bit_exact_and_accurate():
    m, n = 6, 4
    A = matrices((3, m, n))
    sk = tuple(s for stage in sameh_kuck_schedule(m, n) for s in stage)
    unit = GivensUnit(GivensConfig(hub=True, n=26))
    ref = qr_cordic(A, unit, steps=sk)
    got = qr_cordic_pallas(A, unit, steps=sk)
    _assert_bit_exact(ref, got)
    assert float(jnp.mean(snr_db(A, *got))) > 115.0


def test_engine_backend_parity_and_schedule():
    A = matrices((4, 4, 4))
    cfg = GivensConfig(hub=True, n=26)
    ref = QRDEngine(backend="cordic", givens_config=cfg)(A)
    for sched in ("col", "sameh_kuck"):
        got = QRDEngine(backend="cordic_pallas", givens_config=cfg,
                        schedule=sched)(A)
        if sched == "col":
            _assert_bit_exact(ref, got)
        B = np.asarray(got[0]) @ np.asarray(got[1])
        assert np.allclose(B, A, rtol=1e-4, atol=1e-4)


def test_blockfp_accuracy_and_orthogonality():
    A = matrices((8, 4, 4))
    Q, R = qr_blockfp_pallas(A)
    assert float(jnp.mean(snr_db(A, Q, R))) > 90.0
    QtQ = np.swapaxes(np.asarray(Q), -1, -2) @ np.asarray(Q)
    assert np.max(np.abs(QtQ - np.eye(4))) < 1e-4
    assert np.all(np.tril(np.asarray(R), -1) == 0.0)


def test_blockfp_custom_steps_rls_block_update():
    """RLS block update: annihilate B stacked snapshot rows into R."""
    n, B = 5, 3
    R0 = np.triu(RNG.normal(size=(n, n))) + np.eye(n) * 3
    X = RNG.normal(size=(B, n))
    W = np.concatenate([R0, X], axis=0)[None]          # (1, n+B, n)
    steps = tuple((k, j, k) for k in range(n) for j in range(n, n + B))
    got = np.asarray(ops.givens_block_apply(W, steps, hub=True))[0]
    # float Givens reference on the same schedule
    ref = W[0].copy()
    for (k, j, col) in steps:
        a, b = ref[k, col], ref[j, col]
        r = np.hypot(a, b)
        c, s = (a / r, b / r) if r > 0 else (1.0, 0.0)
        rk = c * ref[k] + s * ref[j]
        rj = -s * ref[k] + c * ref[j]
        ref[k], ref[j] = rk, rj
    np.testing.assert_allclose(got[:n], ref[:n], atol=2e-5)
    assert np.max(np.abs(got[n:])) < 2e-5  # snapshot rows fully annihilated


@pytest.mark.parametrize("B,L", [(1, 3), (9, 129), (17, 64)])
@pytest.mark.parametrize("hub", [False, True])
def test_fused_vs_separate_kernels_odd_shapes(B, L, hub):
    """Fused single-pass kernel == separate vectoring+rotation kernels."""
    v = RNG.uniform(-1.9, 1.9, size=(2, B, L))
    X = np.rint(v * 2.0 ** 24).astype(np.int32)
    x, y = jnp.asarray(X[0]), jnp.asarray(X[1])
    a = ops.givens_rotate_rows_fixed(x, y, iters=24, hub=hub)
    b = ops.givens_rotate_rows_fused(x, y, iters=24, hub=hub)
    _assert_bit_exact(a, b)


def test_sharded_tall_skinny_batch():
    from repro.core import qr_blocked_sharded
    from repro.launch.sharding import qrd_batch_spec

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    spec = qrd_batch_spec(3, 6, mesh)
    assert spec == jax.sharding.PartitionSpec(("data",), None, None)
    A = matrices((6, 8, 3), r=2.0)                     # tall-skinny batch
    unit = GivensUnit(GivensConfig(hub=True, n=26))
    _assert_bit_exact(qr_cordic(A, unit), qr_blocked_sharded(A, unit, mesh))
