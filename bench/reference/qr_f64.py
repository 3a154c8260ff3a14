"""Plain float64 QR decomposition: the reference of the QRD cells.

NumPy's LAPACK QR on float64, with each R row's sign chosen so that
diag(R) >= 0 (Q's columns flip with it).  That makes (Q, R) unique for a
full-rank A, so two factorizations can be compared entry by entry.
"""
from __future__ import annotations

import numpy as np


def qr(A):
    """A (..., m, n) float64 -> (Q (..., m, m), R (..., m, n)), diag(R) >= 0."""
    A = np.asarray(A, np.float64)
    Q, R = np.linalg.qr(A, mode="complete")
    d = np.diagonal(R, axis1=-2, axis2=-1)
    s = np.where(d < 0, -1.0, 1.0)
    s = np.concatenate(
        [s, np.ones(s.shape[:-1] + (A.shape[-2] - s.shape[-1],))], axis=-1)
    return Q * s[..., None, :], R * s[..., :, None]


def positive_diag(Q, R):
    """The same normalization for another factorization of the same A."""
    d = np.diagonal(R, axis1=-2, axis2=-1)
    s = np.where(d < 0, -1.0, 1.0)
    s = np.concatenate(
        [s, np.ones(s.shape[:-1] + (R.shape[-2] - s.shape[-1],))], axis=-1)
    return Q * s[..., None, :], R * s[..., :, None]
