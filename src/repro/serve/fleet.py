"""`RLSFleet` — millions of concurrent QRD-RLS filter states as ONE pytree.

PR 3's `RLSState` is a single in-process object: one ``[R | z]`` pair,
one Python attribute per field.  A serving deployment (per-user
equalizers, beamforming channels) holds *millions* of such states and
updates thousands per second; looping over Python objects cannot keep
up, and neither can a pytree-of-objects (N separate small buffers).  The
fleet therefore stores all N states **struct-of-arrays**: one slot-major
array per field, so the whole fleet is a handful of large buffers and a
snapshot batch touches them with one gather → one vectorized
annihilation → one scatter.

`FleetState` (the carried pytree) is a NamedTuple of slot-major arrays:

* ``work``       (N, n, n+1) — the per-slot carried ``[R | z]``,
  float64 for the real datapaths and complex128 for the complex one.
  The carried domain is the *decoded* float domain (exactly as
  `RLSState` keeps it): the forgetting multiply ``√λ·[R | z]`` happens
  in float64 *before* the unit's input converter rounds, so storing the
  packed words instead would double-round the cold-start state and break
  bit-parity with the single-state reference.
* ``lam``        (N,)  float64 — per-slot forgetting factor λ.
* ``occupied``   (N,)  bool — slot occupancy mask.
* ``generation`` (N,)  int32 — bumped on every admit/evict so stale
  requests addressed to a recycled slot are detectable.
* ``updates``    (N,)  int32 — snapshots absorbed per slot.

The hot path is ONE jitted, **donated** step per batch of snapshots::

    fleet.update(slot_ids, X, d)     # (B,), (B, n), (B,)

which gathers the targeted rows, runs the existing `repro.qrd` RLS
annihilation paths vectorized over the batch — the bit-accurate
`GivensUnit.annihilate` / `annihilate_complex` recursion for the cordic
family, the kernel-resident ``givens_block_apply`` block path, or the
f64 conjugate-Givens loop — and scatters the results back **in place**:
``jax.jit(..., donate_argnums=0)`` hands the previous state's buffers to
XLA, so a steady-state serving loop performs zero per-step reallocation
(verified by ``is_deleted`` assertions in tests/test_serve_fleet.py).
Padded / stale batch entries carry the out-of-range sentinel slot id N
(gathers clip, scatters drop) plus a ``valid`` mask, so every batch
shape is fixed and one compilation serves the whole stream.

Because the vectorized paths run the *same* jitted element ops as the
single-state `RLSState`, an occupied fleet slot is **bit-identical** to
an independently driven `RLSState` on the IEEE, HUB and complex unit
paths — the acceptance contract of DESIGN.md §12.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

import typing

from repro.qrd.rls import validate_lam
from repro.qrd.solve import back_substitute

__all__ = ["FleetState", "RLSFleet", "validate_lam"]

_MODES = ("float", "unit", "block")


class FleetState(typing.NamedTuple):
    """Slot-major struct-of-arrays fleet state (a jit/donation-friendly
    pytree; see the module docstring for the per-field layout)."""

    work: jax.Array        # (N, n, n+1) float64 | complex128
    lam: jax.Array         # (N,) float64
    occupied: jax.Array    # (N,) bool
    generation: jax.Array  # (N,) int32
    updates: jax.Array     # (N,) int32


class RLSFleet:
    """N independent QRD-RLS filter states, updated as one batched pytree.

    Parameters
    ----------
    slots : int
        Fleet capacity N (slots are admitted/evicted individually; the
        buffers are allocated once, up front).
    n : int
        Filter length (size of each carried triangular R).
    mode : str
        ``'unit'`` (bit-accurate `GivensUnit` recursion — IEEE/HUB/
        complex), ``'block'`` (kernel-resident ``givens_block_apply``
        of ``block`` stacked snapshots per slot per call) or ``'float'``
        (f64 conjugate-Givens loop).  Usually chosen by
        `repro.qrd.QRDEngine.fleet` from the backend.
    unit : GivensUnit, required for ``mode='unit'``.
    lam, delta : float
        Default forgetting factor / cold-start diagonal loading applied
        by `admit` (λ can be overridden per admit — it is per-slot
        state).
    dtype : str
        ``'float64'`` or ``'complex128'`` (complex only on the unit and
        float modes, exactly as `RLSState`).
    block, hub, iters, frac, interpret :
        Blocked-kernel parameters (``mode='block'``).
    mesh : jax.sharding.Mesh, optional
        When set, every state leaf is placed with its slot axis sharded
        across the mesh's data axes (`repro.launch.sharding.shard_fleet`)
        — the fleet analogue of ``QRDConfig.mesh``.

    Notes
    -----
    The carried state lives in ``self.state`` (a `FleetState`); `update`
    *replaces* it with the donated-step output, so host references to a
    previous state observe deleted buffers — snapshot with
    `export_state` / checkpointing, not by aliasing ``fleet.state``.
    """

    def __init__(self, slots, n, *, mode="unit", unit=None, lam=0.99,
                 delta=1e-3, dtype="float64", block=4, hub=True, iters=24,
                 frac=24, interpret=None, mesh=None):
        if mode not in _MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of {_MODES}")
        if mode == "unit" and unit is None:
            raise ValueError("mode='unit' needs a GivensUnit")
        if dtype not in ("float64", "complex128"):
            raise ValueError(f"dtype must be 'float64' or 'complex128', "
                             f"got {dtype!r}")
        if dtype == "complex128":
            from repro.kernels.ops import check_complex_compiles
            check_complex_compiles("complex RLSFleet")
        if mode == "block" and dtype == "complex128":
            raise TypeError("the blocked-kernel RLS path has no complex "
                            "datapath; use mode='unit' or mode='float' for "
                            "complex QRD-RLS fleets")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        validate_lam(lam)
        self.slots = int(slots)
        self.n = int(n)
        self.mode = mode
        self.unit = unit
        self.lam = float(lam)
        self.delta = float(delta)
        self.dtype = np.dtype(dtype)
        self.block = int(block)
        self._blockfp = dict(hub=hub, iters=iters, frac=frac,
                             interpret=interpret)
        self.mesh = mesh
        N, width = self.slots, self.n + 1
        self.state = FleetState(
            work=jnp.zeros((N, self.n, width), dtype=self.dtype),
            lam=jnp.full((N,), self.lam, dtype=jnp.float64),
            occupied=jnp.zeros((N,), dtype=bool),
            generation=jnp.zeros((N,), dtype=jnp.int32),
            updates=jnp.zeros((N,), dtype=jnp.int32),
        )
        self._place()
        self._update_fn = jax.jit(self._make_step(), donate_argnums=(0,))
        self._weights_fn = jax.jit(self._make_weights())

    # -- introspection --------------------------------------------------------
    @property
    def is_complex(self):
        return self.dtype.kind == "c"

    @property
    def occupancy(self):
        """Occupied-slot count (host int)."""
        return int(np.asarray(self.state.occupied).sum())

    def __repr__(self):
        return (f"RLSFleet(slots={self.slots}, n={self.n}, "
                f"mode={self.mode!r}, dtype={self.dtype.name!r}, "
                f"occupied={self.occupancy})")

    def _place(self):
        if self.mesh is not None:
            from repro.launch.sharding import shard_fleet
            self.state = shard_fleet(self.state, self.mesh)

    # -- the donated batched step --------------------------------------------
    def _make_step(self):
        """Build the jitted step: gather → vectorized annihilate → scatter.

        All three paths share the wrapper: ``slot_ids`` may contain the
        sentinel N for padded entries (gather clips, scatter drops), and
        ``valid & occupied`` masks the write-back so invalid or evicted
        entries leave their slots bit-untouched.
        """
        n, mode = self.n, self.mode

        def gather(state, slot_ids):
            rows = jnp.take(state.work, slot_ids, axis=0, mode="clip")
            lam = jnp.take(state.lam, slot_ids, mode="clip")
            occ = jnp.take(state.occupied, slot_ids, mode="clip")
            return rows, lam, occ

        def scatter(state, slot_ids, rows, out, mask, count):
            new_rows = jnp.where(mask[:, None, None], out, rows)
            work = state.work.at[slot_ids].set(new_rows, mode="drop")
            inc = jnp.where(mask, jnp.int32(count), jnp.int32(0))
            updates = state.updates.at[slot_ids].add(inc, mode="drop")
            return state._replace(work=work, updates=updates)

        if mode == "unit":
            unit = self.unit
            if self.is_complex:
                from repro.core.qrd import _decode_complex, _encode_complex

                def annihilate(scaled, snap):
                    P = _encode_complex(unit, scaled)
                    prow = _encode_complex(unit, snap)

                    def body(k, carry):
                        P, prow = carry
                        xk, prow = unit.annihilate_complex(P[:, k], prow, k)
                        return P.at[:, k].set(xk), prow

                    P, _ = jax.lax.fori_loop(0, n, body, (P, prow))
                    return _decode_complex(unit, P)
            else:
                def annihilate(scaled, snap):
                    P = unit.encode(scaled)
                    prow = unit.encode(snap)

                    def body(k, carry):
                        P, prow = carry
                        xk, prow = unit.annihilate(P[:, k], prow, k)
                        return P.at[:, k].set(xk), prow

                    P, _ = jax.lax.fori_loop(0, n, body, (P, prow))
                    return unit.decode(P)
        elif mode == "float":
            def annihilate(scaled, snap):
                # Conjugate Givens, vectorized over the batch axis; the
                # conjugation is the identity for real dtypes, matching
                # RLSState's float path element for element.
                out, row = scaled, snap
                for k in range(n):
                    a, b = out[:, k, k], row[:, k]
                    r = jnp.hypot(jnp.abs(a), jnp.abs(b))
                    safe = r > 0.0
                    rs = jnp.where(safe, r, 1.0)
                    c = (jnp.conj(a) / rs)[:, None]
                    s = (jnp.conj(b) / rs)[:, None]
                    wk = c * out[:, k] + s * row
                    nrow = -jnp.conj(s) * out[:, k] + jnp.conj(c) * row
                    nrow = nrow.at[:, k].set(0.0)
                    wk = wk.at[:, k].set(r.astype(out.dtype))
                    out = out.at[:, k].set(
                        jnp.where(safe[:, None], wk, out[:, k]))
                    row = jnp.where(safe[:, None], nrow, row)
                return out

        if mode in ("unit", "float"):
            def step(state, slot_ids, X, d, valid):
                rows, lam, occ = gather(state, slot_ids)
                mask = valid & occ
                snap = jnp.concatenate(
                    [X, d[:, None]], axis=1).astype(state.work.dtype)
                scaled = rows * jnp.sqrt(lam)[:, None, None]
                out = annihilate(scaled, snap)
                return scatter(state, slot_ids, rows, out, mask, 1)

            return step

        # mode == 'block': k snapshots per slot per call, annihilated by
        # one kernel-resident blocked schedule with the forgetting
        # telescoped exactly as RLSState.flush does.
        blockfp, blk = self._blockfp, self.block

        def step(state, slot_ids, X, d, valid):
            from repro.kernels import ops as kops
            rows, lam, occ = gather(state, slot_ids)
            mask = valid & occ
            lam_half = jnp.sqrt(lam)
            top = rows * (lam_half ** blk)[:, None, None]
            exps = jnp.arange(blk - 1, -1, -1, dtype=jnp.float64)
            w_snap = lam_half[:, None] ** exps[None, :]
            snaps = jnp.concatenate(
                [X, d[..., None]], axis=-1).astype(state.work.dtype)
            snaps = snaps * w_snap[..., None]
            W = jnp.concatenate([top, snaps], axis=1)      # (B, n+blk, n+1)
            steps = kops.rls_block_steps(self.n, blk)
            Wp = kops.givens_block_apply(W, steps, **blockfp)
            return scatter(state, slot_ids, rows, Wp[:, :self.n, :],
                           mask, blk)

        return step

    def update(self, slot_ids, X, d, valid=None):
        """Absorb one snapshot batch: scatter ``(x, d)`` pairs into slots.

        Parameters
        ----------
        slot_ids : (B,) int array
            Target slot per snapshot.  Entries MUST be distinct within a
            batch (the scatter is unordered for duplicates — the server's
            batcher enforces this); padded entries use the sentinel
            ``fleet.slots`` and ``valid=False``.
        X : (B, n) array — or ``(B, block, n)`` in ``mode='block'``
            (``block`` stacked snapshots per slot per call).
        d : (B,) array — or ``(B, block)`` in ``mode='block'``.
        valid : (B,) bool, optional
            Mask of live entries (default: all valid).  Invalid entries
            and entries addressing unoccupied slots leave their slots
            bit-untouched and do not advance ``updates``.

        Returns
        -------
        self (for chaining).  The previous ``FleetState``'s buffers are
        donated to the step and must not be read afterwards.
        """
        slot_ids = jnp.asarray(slot_ids, dtype=jnp.int32)
        X = jnp.asarray(X)
        d = jnp.asarray(d)
        if ((X.dtype.kind == "c" or d.dtype.kind == "c")
                and not self.is_complex):
            raise TypeError(
                "complex snapshot batch on a real-dtype fleet (no silent "
                "real cast); create the fleet with dtype='complex128'")
        want = 3 if self.mode == "block" else 2
        if X.ndim != want or X.shape[-1] != self.n:
            raise ValueError(
                f"mode={self.mode!r} expects X of shape "
                f"{'(B, block, n)' if want == 3 else '(B, n)'} with "
                f"n={self.n}, got {X.shape}")
        if self.mode == "block" and X.shape[1] != self.block:
            raise ValueError(f"block fleet expects {self.block} snapshots "
                             f"per slot per call, got {X.shape[1]}")
        if d.shape != X.shape[:-1]:
            raise ValueError(f"d shape {d.shape} != {X.shape[:-1]}")
        if valid is None:
            valid = jnp.ones(slot_ids.shape, dtype=bool)
        else:
            valid = jnp.asarray(valid, dtype=bool)
        self.state = self._update_fn(self.state, slot_ids, X, d, valid)
        return self

    # -- slot lifecycle -------------------------------------------------------
    def _check_ids(self, slot_ids):
        ids = np.asarray(slot_ids, dtype=np.int64).ravel()
        if ids.size and (ids.min() < 0 or ids.max() >= self.slots):
            raise IndexError(f"slot ids out of range [0, {self.slots})")
        if np.unique(ids).size != ids.size:
            raise ValueError("duplicate slot ids")
        return ids

    def admit(self, count=None, slot_ids=None, *, lam=None, delta=None):
        """Admit filters into free slots: reset state, bump generation.

        Parameters
        ----------
        count : int — admit this many filters into the lowest free
            slots; or
        slot_ids : explicit free slot ids to admit into.
        lam : scalar or (B,) array, optional — per-slot forgetting
            factor (validated ``0 < lam <= 1``); defaults to the fleet's.
        delta : float, optional — cold-start diagonal loading.

        Returns
        -------
        (B,) int64 ndarray of admitted slot ids.
        """
        occ = np.asarray(self.state.occupied)
        if slot_ids is None:
            if count is None:
                raise ValueError("admit() needs count= or slot_ids=")
            free = np.flatnonzero(~occ)
            if count > free.size:
                raise RuntimeError(
                    f"fleet full: {count} slots requested, "
                    f"{free.size} free of {self.slots}")
            ids = free[:count]
        else:
            ids = self._check_ids(slot_ids)
            if occ[ids].any():
                busy = ids[occ[ids]][:8]
                raise ValueError(f"admit of occupied slot(s) {busy.tolist()}"
                                 " — evict first")
        lam_arr = validate_lam(self.lam if lam is None else lam)
        # validate_lam returns float64 (and rejects complex), so a bare
        # broadcast is dtype-safe here.
        lam_arr = np.broadcast_to(lam_arr, ids.shape)
        delta = self.delta if delta is None else float(delta)
        init = jnp.eye(self.n, self.n + 1, dtype=self.dtype) * delta
        rows = jnp.broadcast_to(init, (ids.size, self.n, self.n + 1))
        jids = jnp.asarray(ids)
        st = self.state
        # unique_indices: `free` slots are distinct by construction and
        # _check_ids raises on duplicate caller ids, so XLA may skip the
        # serialized-scatter fallback.
        self.state = FleetState(
            work=st.work.at[jids].set(rows, unique_indices=True),
            lam=st.lam.at[jids].set(jnp.asarray(lam_arr),
                                    unique_indices=True),
            occupied=st.occupied.at[jids].set(True, unique_indices=True),
            generation=st.generation.at[jids].add(1, unique_indices=True),
            updates=st.updates.at[jids].set(0, unique_indices=True),
        )
        self._place()
        return ids

    def evict(self, slot_ids):
        """Evict slots: clear occupancy, bump generation (state rows are
        left stale — admit overwrites them)."""
        ids = self._check_ids(slot_ids)
        occ = np.asarray(self.state.occupied)
        if not occ[ids].all():
            idle = ids[~occ[ids]][:8]
            raise ValueError(f"evict of unoccupied slot(s) {idle.tolist()}")
        jids = jnp.asarray(ids)
        st = self.state
        # unique_indices: _check_ids raises on duplicate ids.
        self.state = st._replace(
            occupied=st.occupied.at[jids].set(False, unique_indices=True),
            generation=st.generation.at[jids].add(1, unique_indices=True),
        )
        self._place()
        return ids

    def generation_of(self, slot_ids):
        """Host-side generation counters for `slot_ids` (stale-request
        detection)."""
        return np.asarray(self.state.generation)[
            self._check_ids(slot_ids)]

    # -- readout --------------------------------------------------------------
    def _make_weights(self):
        n = self.n

        def weights(work, slot_ids, ridge):
            rows = jnp.take(work, slot_ids, axis=0, mode="clip")
            R = rows[..., :n] + ridge * jnp.eye(n, dtype=rows.dtype)
            return back_substitute(R, rows[..., n])

        return weights

    def weights(self, slot_ids, ridge=1e-12):
        """Back-substitute ``R w = z`` for a batch of slots.

        Returns a ``(B, n)`` float64 (complex128) ndarray — bit-identical
        to `RLSState.weights` on each occupied slot.
        """
        ids = self._check_ids(slot_ids)
        return np.asarray(self._weights_fn(self.state.work, jnp.asarray(ids),
                                           ridge))

    def predict(self, slot_ids, X):
        """Filter outputs ``x_iᵀ w_i`` for one snapshot per slot."""
        X = np.asarray(X).astype(self.dtype)
        return np.einsum("bn,bn->b", X, self.weights(slot_ids))

    # -- single-state interop (RLSState.to_arrays schema) ---------------------
    def export_state(self, slot):
        """Export one slot as an `RLSState.from_arrays`-compatible pytree."""
        (slot,) = self._check_ids([slot])
        if not bool(np.asarray(self.state.occupied)[slot]):
            raise ValueError(f"slot {slot} is not occupied")
        row = np.asarray(self.state.work[slot])
        return {
            "R": row[:, :self.n].copy(),
            "z": row[:, self.n].copy(),
            "lam": np.float64(np.asarray(self.state.lam)[slot]),
            "updates": np.int64(np.asarray(self.state.updates)[slot]),
            "pending": np.zeros((0, self.n + 1), dtype=self.dtype),
            "pending_count": np.int64(0),
        }

    def import_state(self, slot, arrays):
        """Admit `arrays` (the `RLSState.to_arrays` schema) into a free slot.

        The donor state must have an empty pending buffer
        (``RLSState.flush()`` first) — the fleet has no per-slot pending;
        batching lives in the server's queue, not in device state.
        """
        if int(arrays.get("pending_count", 0)) != 0:
            raise ValueError("cannot import a state with pending snapshots; "
                             "call RLSState.flush() first")
        R = np.asarray(arrays["R"])
        z = np.asarray(arrays["z"])
        if R.shape != (self.n, self.n) or z.shape != (self.n,):
            raise ValueError(f"state shape mismatch: R {R.shape}, z {z.shape}"
                             f" vs fleet n={self.n}")
        (slot,) = self.admit(slot_ids=[slot], lam=float(arrays["lam"]))
        row = np.concatenate([R, z[:, None]], axis=1).astype(self.dtype)
        st = self.state
        # unique_indices: `slot` is a single admitted slot id.
        self.state = st._replace(
            work=st.work.at[slot].set(jnp.asarray(row),
                                      unique_indices=True),
            updates=st.updates.at[slot].set(
                jnp.int32(int(arrays["updates"])), unique_indices=True),
        )
        self._place()
        return slot

    # -- checkpoint interop ---------------------------------------------------
    def template(self):
        """A `FleetState` of the live structure/shapes/dtypes — the
        restore template for `repro.checkpoint.restore_pytree`."""
        return self.state

    def load_state(self, state: FleetState):
        """Replace the carried fleet state (checkpoint restore path)."""
        if jax.tree.structure(state) != jax.tree.structure(self.state):
            raise ValueError("restored pytree structure does not match")
        for new, cur in zip(jax.tree.leaves(state),
                            jax.tree.leaves(self.state)):
            if (tuple(new.shape) != tuple(cur.shape)
                    or np.dtype(new.dtype) != np.dtype(cur.dtype)):
                raise ValueError(
                    f"restored leaf {new.shape}/{new.dtype} does not match "
                    f"fleet {cur.shape}/{cur.dtype}")
        self.state = FleetState(*[jnp.asarray(l)
                                  for l in jax.tree.leaves(state)])
        self._place()
        return self
