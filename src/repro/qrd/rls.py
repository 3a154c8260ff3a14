"""Streaming QRD-RLS state — the paper's adaptive-filtering application.

QRD-RLS never forms the (ill-conditioned) covariance matrix: the carried
state is the Cholesky-equivalent pair ``[R | z]`` of the forgetting-
factor-weighted data matrix, and every new snapshot ``(x, d)`` is
annihilated into it by exactly the Givens rotations the paper's unit
computes (vectoring on the leading pair, σ-replay across the row).  The
beamforming example used to hand-roll this loop; `RLSState` is the
library-grade replacement, with three update paths:

* ``mode='unit'`` — per-snapshot on the bit-accurate `GivensUnit`: the n
  pivot annihilations run inside one jitted ``lax.fori_loop`` over
  `GivensUnit.annihilate` (traced pivot column via the roll trick — one
  fixed shape, one compile, no per-rotation host round-trips);
* ``mode='block'`` — the kernel-resident path: ``block`` snapshots are
  stacked under ``[R | z]`` and annihilated by ONE blocked Pallas
  schedule (`repro.kernels.ops.givens_block_apply` on
  `ops.rls_block_steps`), with exponential forgetting telescoped exactly
  (state weighted λ^{b/2}, pending row i by λ^{(b-1-i)/2});
* ``mode='float'`` — plain f64 Givens loop (algorithmic baseline).

Weights come from the shared jit-safe back-substitution
(`repro.qrd.solve.back_substitute`) — the same triangular solve the
engine's `solve()` uses.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .solve import back_substitute

__all__ = ["RLSState", "validate_lam"]

_MODES = ("float", "unit", "block")


def validate_lam(lam, what="forgetting factor"):
    """Validate ``0 < lam <= 1`` (scalar or per-slot array) loudly.

    QRD-RLS with λ <= 0 silently destroys the carried factor (the √λ
    weighting zeroes — or, for negative λ, imaginarizes — R); λ > 1
    amplifies history without bound; NaN poisons the state on the first
    update.  Every entry point (`RLSState`, `QRDEngine.rls`,
    `repro.serve.RLSFleet`) funnels through here so no path accepts a
    non-positive λ.
    """
    raw = np.asarray(lam)
    if raw.dtype.kind == "c":
        # np.asarray(complex, float64) would silently discard the
        # imaginary part, letting e.g. 0.9+0.5j pass as 0.9.
        raise TypeError(f"{what} must be real, got complex {lam!r}")
    if raw.dtype.kind not in "fiu":
        raise TypeError(f"{what} must be numeric, got {raw.dtype}")
    # lint: allow[narrowing-cast] real/int-only here, complex rejected above
    arr = raw.astype(np.float64)
    if arr.size == 0:
        raise ValueError(f"{what} must be non-empty")
    if not np.all((arr > 0.0) & (arr <= 1.0)):
        raise ValueError(f"{what} must be in (0, 1], got {lam!r}")
    return arr


class RLSState:
    """Carried QRD-RLS state ``[R | z]`` with streaming updates.

    Parameters
    ----------
    n : int
        Filter length (size of the carried upper-triangular R).
    lam : float
        Forgetting factor λ in (0, 1].
    delta : float
        Initial diagonal loading: ``R0 = delta * I`` (cold-start
        regularization, standard QRD-RLS initialization).
    mode : str
        ``'float'`` | ``'unit'`` | ``'block'`` (see module docstring).
        Usually chosen by `repro.qrd.QRDEngine.rls` from the backend.
    unit : GivensUnit, required for ``mode='unit'``
        The bit-accurate rotator the updates run on.
    block, hub, iters, frac, interpret :
        Blocked-kernel parameters (``mode='block'``): snapshots per
        kernel launch and the block-FP datapath knobs of
        `repro.kernels.ops.givens_block_apply`.
    dtype : str
        ``'float64'`` (default) or ``'complex128'``.  Complex states
        carry complex ``[R | z]`` and rotate snapshots with unitary
        complex Givens — the three-rotation decomposition on the unit
        path (`GivensUnit.annihilate_complex`, DESIGN.md §10), conjugate
        rotations on the float path.  The blocked-kernel path has no
        complex datapath (``mode='block'`` with a complex dtype raises
        ``TypeError``).

    Attributes
    ----------
    R : (n, n) ndarray — carried triangular factor (dtype as configured).
    z : (n,) ndarray — carried rotated target vector.
    updates : int — snapshots absorbed (committed + pending).

    Notes
    -----
    In ``mode='block'`` snapshots accumulate in a pending buffer and are
    committed ``block`` at a time; `weights` reads the *committed* state
    (call `flush` first to force a partial block through the kernel).
    """

    def __init__(self, n, lam=0.99, delta=1e-3, *, mode="float", unit=None,
                 block=4, hub=True, iters=24, frac=24, interpret=None,
                 dtype="float64"):
        if mode not in _MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of {_MODES}")
        validate_lam(lam)
        if mode == "unit" and unit is None:
            raise ValueError("mode='unit' needs a GivensUnit")
        if dtype not in ("float64", "complex128"):
            raise ValueError(f"dtype must be 'float64' or 'complex128', "
                             f"got {dtype!r}")
        if dtype == "complex128":
            from repro.kernels.ops import check_complex_compiles
            check_complex_compiles("complex RLSState")
        if mode == "block" and dtype == "complex128":
            raise TypeError("the blocked-kernel RLS path has no complex "
                            "datapath; use mode='unit' or mode='float' for "
                            "complex QRD-RLS")
        self.n = int(n)
        self.lam = float(lam)
        self.mode = mode
        self.unit = unit
        self.block = int(block)
        self.dtype = np.dtype(dtype)
        self._blockfp = dict(hub=hub, iters=iters, frac=frac,
                             interpret=interpret)
        self.R = np.eye(self.n, dtype=self.dtype) * float(delta)
        self.z = np.zeros(self.n, dtype=self.dtype)
        self.updates = 0
        self._pending: list[np.ndarray] = []
        if mode == "unit":
            self._unit_update = jax.jit(self._make_unit_update())

    @property
    def is_complex(self):
        return self.dtype.kind == "c"

    # -- update paths ---------------------------------------------------------
    def _make_unit_update(self):
        unit, n = self.unit, self.n
        if self.is_complex:
            def update(P, prow):
                """Annihilate one packed complex snapshot into [R | z]."""
                def body(k, carry):
                    P, prow = carry
                    xk, prow = unit.annihilate_complex(P[k], prow, k)
                    return P.at[k].set(xk), prow
                P, _ = jax.lax.fori_loop(0, n, body, (P, prow))
                return P
            return update

        def update(P, prow):
            """Annihilate one packed snapshot row into packed [R | z]."""
            def body(k, carry):
                P, prow = carry
                xk, prow = unit.annihilate(P[k], prow, k)
                return P.at[k].set(xk), prow
            P, _ = jax.lax.fori_loop(0, n, body, (P, prow))
            return P

        return update

    def _encode(self, work):
        """float/complex ndarray -> packed words ((..., 2) lanes if complex).

        The complex lane packing is the shared `repro.core.qrd` codec —
        one source of truth for the (re, im) trailing-axis convention.
        """
        from repro.core.qrd import _encode_complex
        if self.is_complex:
            return _encode_complex(self.unit, jnp.asarray(work))
        return self.unit.encode(jnp.asarray(work))

    def _decode(self, P):
        from repro.core.qrd import _decode_complex
        if self.is_complex:
            return np.asarray(_decode_complex(self.unit, P))
        return np.asarray(self.unit.decode(P))

    def _work(self, weight):
        return np.concatenate([self.R, self.z[:, None]], axis=1) * weight

    def update(self, x, d):
        """Absorb one snapshot: rotate ``[x, d]`` into ``[√λ R | √λ z]``.

        Parameters
        ----------
        x : (n,) array_like — input/regressor snapshot.
        d : scalar — desired response.

        Returns
        -------
        self (for chaining).
        """
        x = np.asarray(x)
        if ((x.dtype.kind == "c" or np.asarray(d).dtype.kind == "c")
                and not self.is_complex):
            raise TypeError(
                "complex snapshot on a real-dtype RLS state (no silent "
                "real cast); create the state with dtype='complex128' — "
                "e.g. engine.rls() on a complex-dtype QRDConfig")
        row = np.concatenate([x.astype(self.dtype).ravel(),
                              [self.dtype.type(d)]])
        if row.shape[0] != self.n + 1:
            raise ValueError(f"snapshot length {row.shape[0] - 1} != n="
                             f"{self.n}")
        self.updates += 1
        if self.mode == "block":
            self._pending.append(row)
            if len(self._pending) >= self.block:
                self.flush()
            return self
        work = self._work(np.sqrt(self.lam))
        if self.mode == "unit":
            P = self._unit_update(self._encode(work), self._encode(row))
            out = self._decode(P)
        else:  # float: conjugate Givens (reduces to the real rotation
            #    for real dtypes — conjugation is the identity there)
            out = work
            for k in range(self.n):
                a, b = out[k, k], row[k]
                r = np.hypot(abs(a), abs(b))
                if r == 0.0:
                    continue
                c, s = np.conj(a) / r, np.conj(b) / r
                wk = c * out[k] + s * row
                row = -np.conj(s) * out[k] + np.conj(c) * row
                row[k] = 0.0
                out[k] = wk
                out[k, k] = r
        self.R, self.z = out[:, :self.n], out[:, self.n]
        return self

    def flush(self):
        """Commit pending snapshots through the blocked kernel (mode='block').

        One `givens_block_apply` launch annihilates all ``b`` stacked
        rows column-by-column against the carried state; the forgetting
        weights (state × λ^{b/2}, row i × λ^{(b-1-i)/2}) telescope to the
        per-snapshot recursion exactly.  No-op when nothing is pending.
        """
        b = len(self._pending)
        if b == 0:
            return self
        from repro.kernels import ops as kops
        lam_half = np.sqrt(self.lam)
        top = self._work(lam_half ** b)
        rows = np.stack([row * lam_half ** (b - 1 - i)
                         for i, row in enumerate(self._pending)])
        W = np.concatenate([top, rows], axis=0)[None]   # (1, n+b, n+1)
        steps = kops.rls_block_steps(self.n, b)
        Wp = np.asarray(kops.givens_block_apply(W, steps,
                                                **self._blockfp))[0]
        self.R, self.z = Wp[:self.n, :self.n], Wp[:self.n, self.n]
        self._pending = []
        return self

    # -- pure pytree export / import ------------------------------------------
    def to_arrays(self):
        """Export the full state as a pure array pytree.

        Block mode's partial-flush buffer used to live only as Python
        list state — invisible to checkpointing and to the fleet; here
        it is materialized as a fixed-shape ``(block, n+1)`` array (rows
        beyond ``pending_count`` are zero padding), so the export has a
        static structure suitable as a `repro.checkpoint` template and
        as the interop schema of `repro.serve.RLSFleet.import_state` /
        ``export_state``.

        Returns
        -------
        dict with keys ``R`` (n, n), ``z`` (n,), ``lam`` float64,
        ``updates`` int64, ``pending`` (block, n+1) — (0, n+1) for the
        unblocked modes — and ``pending_count`` int64.
        """
        cap = self.block if self.mode == "block" else 0
        pending = np.zeros((cap, self.n + 1), dtype=self.dtype)
        for i, row in enumerate(self._pending):
            pending[i] = row
        return {"R": self.R.copy(), "z": self.z.copy(),
                "lam": np.float64(self.lam),
                "updates": np.int64(self.updates),
                "pending": pending,
                "pending_count": np.int64(len(self._pending))}

    def from_arrays(self, arrays):
        """Load a `to_arrays` pytree into this (compatibly configured)
        state — the restore half of the pure export.

        The receiving state supplies the *configuration* (mode, unit,
        kernel knobs — none of which are arrays); `arrays` supplies the
        carried numbers.  Shapes, dtype kind and λ are validated;
        pending snapshots beyond the unblocked modes' empty buffer
        require ``mode='block'``.
        """
        R = np.asarray(arrays["R"])
        z = np.asarray(arrays["z"])
        if R.shape != (self.n, self.n) or z.shape != (self.n,):
            raise ValueError(f"state shape mismatch: R {R.shape}, z {z.shape}"
                             f" vs n={self.n}")
        if (R.dtype.kind == "c") != self.is_complex:
            raise TypeError(f"dtype kind mismatch: imported {R.dtype} into a "
                            f"{self.dtype} state (no silent cast)")
        count = int(arrays.get("pending_count", 0))
        pending = np.asarray(arrays.get("pending",
                                        np.zeros((0, self.n + 1),
                                                 dtype=self.dtype)))
        if count:
            if self.mode != "block":
                raise ValueError(f"{count} pending snapshot(s) in the import "
                                 f"but mode={self.mode!r} has no pending "
                                 "buffer (flush() the source first)")
            if count > pending.shape[0] or pending.shape[1:] != (self.n + 1,):
                raise ValueError(f"pending buffer {pending.shape} cannot hold "
                                 f"{count} rows of length {self.n + 1}")
        self.lam = float(validate_lam(np.asarray(arrays["lam"]).item()))
        self.R = R.astype(self.dtype).copy()
        self.z = z.astype(self.dtype).copy()
        self.updates = int(arrays["updates"])
        self._pending = [pending[i].astype(self.dtype).copy()
                         for i in range(count)]
        return self

    # -- readout --------------------------------------------------------------
    def weights(self, ridge=1e-12):
        """Back-substitute the carried ``R w = z`` for the filter weights.

        Parameters
        ----------
        ridge : float
            Diagonal loading added to R before the solve (guards the
            cold-started diagonal; matches the historical example's
            ``solve(R + 1e-12 I, z)``).

        Returns
        -------
        (n,) float64 ndarray.
        """
        R = self.R + ridge * np.eye(self.n) if ridge else self.R
        return np.asarray(back_substitute(jnp.asarray(R),
                                          jnp.asarray(self.z)))

    def predict(self, x):
        """Filter output ``xᵀ w`` for a snapshot ``x`` (complex for
        complex states)."""
        x = np.asarray(x)
        if x.dtype.kind == "c" and not self.is_complex:
            raise TypeError("complex snapshot on a real-dtype RLS state "
                            "(no silent real cast)")
        out = x.astype(self.dtype) @ self.weights()
        return complex(out) if self.is_complex else float(out)
