"""The plain references agree with the mathematics they stand for."""
import numpy as np

from bench.lib import spec

qr = spec.reference("qr_f64")


def test_qr_reference():
    rng = np.random.default_rng(1)
    A = rng.standard_normal((6, 8, 8))
    Q, R = qr.qr(A)
    np.testing.assert_allclose(Q @ R, A, atol=1e-12)
    np.testing.assert_allclose(np.swapaxes(Q, -1, -2) @ Q,
                               np.broadcast_to(np.eye(8), Q.shape),
                               atol=1e-12)
    assert np.all(np.diagonal(R, axis1=-2, axis2=-1) >= 0)
    assert np.allclose(np.tril(R, -1), 0)
    # another factorization of A, with row signs flipped, normalizes back
    s = np.array([1, -1, 1, 1, -1, -1, 1, -1.0])
    Q2, R2 = qr.positive_diag(Q * s, R * s[:, None])
    np.testing.assert_allclose(Q2, Q, atol=1e-15)
    np.testing.assert_allclose(R2, R, atol=1e-15)

