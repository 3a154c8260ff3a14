"""The solver-grade QRD engine: registry-dispatched, problem-level API.

`QRDEngine` here is the canonical surface (DESIGN.md §9); the legacy
``repro.core.QRDEngine`` dataclass is a thin shim over it.  Three layers:

* **decompose** — ``engine(A)`` / ``engine.decompose(A)``: batched
  ``(Q, R)`` via the registered backend, one jitted callable per
  ``(m, n, compute_q, config)`` held in a *bounded* LRU (churning many
  shapes evicts cold callables instead of growing without bound; see the
  repo's lru_cache tracer-leak pitfall — the cache stores only jitted
  callables keyed by static shape, never arrays from inside a trace).
* **solve** — ``engine.solve(A, b)``: batched least squares via the
  Q-free augmented-column trick + `repro.qrd.solve.back_substitute`.
* **rls** — ``engine.rls(n)``: a streaming QRD-RLS state
  (`repro.qrd.rls.RLSState`) on the backend-appropriate update path.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro.kernels import autotune

from .config import QRDConfig
from .solve import lstsq_from_triangular

__all__ = ["QRDEngine"]


class QRDEngine:
    """Registry-dispatched batched QRD with problem-level methods.

    Parameters
    ----------
    config : QRDConfig, optional
        The problem configuration; defaults to ``QRDConfig()``.
    max_cache : int
        Bound on the jitted-callable LRU (distinct
        ``(m, n, compute_q, config)`` keys held at once); least-recently
        used entries are evicted beyond it.
    **overrides
        Field overrides applied on top of ``config`` — any `QRDConfig`
        field, e.g. ``backend='cordic_pallas'``, ``schedule='sameh_kuck'``,
        ``mesh=mesh``.  ``givens_config=`` is accepted as an alias for
        ``givens=`` (legacy spelling).

    Examples
    --------
    >>> eng = QRDEngine(backend='cordic_pallas',
    ...                 givens=GivensConfig(hub=True, n=26))
    >>> Q, R = eng(A)                      # decomposition
    >>> x = eng.solve(A, b)                # batched least squares
    >>> state = eng.rls(n)                 # streaming QRD-RLS
    """

    def __init__(self, config: QRDConfig | None = None, *, max_cache=32,
                 **overrides):
        if config is None:
            config = QRDConfig()
        if "givens_config" in overrides:
            overrides["givens"] = overrides.pop("givens_config")
        if overrides:
            config = config.replace(**overrides)
        self._spec = config.validate()   # raises early: bad backend/schedule
        self.config = config
        if max_cache < 1:
            raise ValueError("max_cache must be >= 1")
        self._max_cache = int(max_cache)
        self._fn_cache: OrderedDict = OrderedDict()

    # -- introspection --------------------------------------------------------
    @property
    def capabilities(self):
        """The configured backend's `BackendCapabilities`."""
        return self._spec.capabilities

    def __repr__(self):
        return (f"QRDEngine(backend={self.config.backend!r}, "
                f"schedule={self.config.schedule!r}, "
                f"cached={len(self._fn_cache)}/{self._max_cache})")

    # -- decomposition --------------------------------------------------------
    def _validate_operand(self, A, config: QRDConfig):
        """Validate the operand dtype against the backend's capabilities.

        Historically complex (and integer) operands were cast straight
        through ``jnp.asarray(..., float64)`` inside the backends — a
        complex matrix lost its imaginary part with nothing but a
        ``ComplexWarning`` from deep inside the cast.  Now:

        * bool/integer operands are promoted to float64 explicitly (an
          exact, documented promotion, as in ``np.linalg``);
        * complex operands require a complex-capable backend — otherwise
          ``TypeError`` names the backend and the complex-capable set —
          and are routed onto the complex datapath by upgrading the
          config's dtype to the matching complex dtype;
        * anything else (strings, objects) raises ``TypeError``.

        Returns the (possibly promoted) operand and the routing config.
        """
        A = jnp.asarray(A)
        kind = A.dtype.kind
        if kind in "biu":
            # lint: allow[narrowing-cast] bool/int -> float64 upcast only
            A = A.astype(jnp.float64)
        elif kind == "c":
            if not config.is_complex():
                from . import registry
                caps = registry.get_backend(config.backend).capabilities
                if not caps.supports_complex:
                    raise TypeError(
                        f"complex operand (dtype {A.dtype}) but backend "
                        f"{config.backend!r} has no complex datapath; "
                        "complex-capable backends: "
                        f"{', '.join(registry.complex_capable_backends())}."
                        "  Configure one with e.g. QRDConfig("
                        "backend='cordic', dtype='complex64'), or take "
                        "A.real explicitly if that was intended.")
                config = config.replace(dtype=A.dtype.name)
        elif kind != "f":
            raise TypeError(f"operand dtype {A.dtype} is not a real, "
                            "complex, or integer numeric dtype")
        return A, config

    @staticmethod
    def _resolve_tuned(config: QRDConfig, m: int, n: int) -> QRDConfig:
        """Fill tuned kernel parameters from the autotune cache.

        Only fires for the tunable Pallas backends, and only for fields
        the config left ``None`` (an explicit value always wins):
        ``tile_b``/``table_layout`` from the flat entry, and — when the
        shape routes onto a tiled datapath — ``panel_n``/``tile_m``
        from the ``/tiled-<route>`` entry (`autotune.tune_tiled`).
        Runs *before* jitted-callable cache-key formation so a cache
        entry appearing between calls misses the LRU instead of
        silently running the stale tile.  Cost on a tuned run is one
        ``os.stat`` (`repro.kernels.autotune.lookup` memoizes the file
        by mtime).
        """
        if config.backend not in autotune.TUNABLE_BACKENDS:
            return config
        if config.tile_b is None:
            hit = autotune.lookup(config.backend, config.schedule, m, n,
                                  config.dtype)
            if hit is not None:
                layout = (config.table_layout
                          if config.table_layout is not None
                          else hit.table_layout)
                config = config.replace(tile_b=hit.tile_b,
                                        table_layout=layout)
        if config.panel_n is None or config.tile_m is None:
            from . import registry, tiled
            caps = registry.get_backend(config.backend).capabilities
            if not caps.supports_tiling:
                return config
            try:
                route = tiled.resolve_route(config, m, n, caps)
            except ValueError:
                return config      # dispatch re-raises the clear error
            if route in ("panel", "tsqr"):
                hit = autotune.lookup(config.backend, "col", m, n,
                                      config.dtype, tiling=route)
                if hit is not None:
                    updates = {}
                    if config.panel_n is None and hit.panel_n is not None:
                        updates["panel_n"] = hit.panel_n
                    if config.tile_m is None and hit.tile_m is not None:
                        updates["tile_m"] = hit.tile_m
                    if updates:
                        config = config.replace(**updates)
        return config

    def _dispatch(self, A, compute_q, config: QRDConfig | None = None):
        """Registry dispatch with the bounded jitted-callable LRU.

        ``config`` defaults to the engine's own; the legacy shim passes a
        per-call config rebuilt from its mutable fields, so field
        mutation misses the cache instead of returning stale results.
        The operand dtype is validated against the backend capabilities
        first (`_validate_operand`) — complex operands route onto the
        complex datapath where capable and raise ``TypeError`` otherwise.
        `_resolve_tuned` then fills autotuned tile parameters before the
        cache key is formed.

        Shapes beyond the flat kernels' `BackendCapabilities.max_shape`
        route onto the tiled datapaths (`repro.qrd.tiled`): panel sweeps
        when the rows still fit one tile, TSQR tree reduction for
        tall-skinny operands.  `tiled.resolve_route` is deterministic in
        ``(m, n, config)`` — the cache key needs no route component —
        and raises a ``ValueError`` naming ``max_shape`` and the tiled
        alternatives when no route can hold the operand (instead of the
        opaque Pallas failure oversized shapes used to hit).  Note the
        TSQR route returns *economy* factors (``Q (m, n), R (n, n)``).
        """
        fn, A = self._callable(A, compute_q, config)
        return fn(A)

    def _callable(self, A, compute_q, config=None):
        """The jitted callable `_dispatch` runs on ``A``, and ``A`` as it
        is handed to it (validated, cast, placed on the mesh)."""
        if config is None:
            config = self.config
        A, config = self._validate_operand(A, config)
        if A.ndim < 2:
            raise ValueError(f"expected (..., m, n) operand, got {A.shape}")
        m, n = A.shape[-2], A.shape[-1]
        config = self._resolve_tuned(config, m, n)
        key = (m, n, bool(compute_q), config.cache_key())
        fn = self._fn_cache.pop(key, None)
        if fn is None:
            from . import tiled
            spec = config.validate()
            route = tiled.resolve_route(config, m, n, spec.capabilities)
            if route == "flat":
                fn = jax.jit(spec.builder(config, m, n, bool(compute_q)))
            else:
                fn = jax.jit(tiled.build_tiled(route, config, m, n,
                                               bool(compute_q),
                                               spec.capabilities))
        self._fn_cache[key] = fn           # (re-)insert as most-recent
        while len(self._fn_cache) > self._max_cache:
            self._fn_cache.popitem(last=False)
        if config.mesh is not None:
            from repro.launch.sharding import shard_qrd_batch
            work_dtype = (jnp.complex128 if config.is_complex()
                          else jnp.float64)
            A = shard_qrd_batch(jnp.asarray(A, work_dtype), config.mesh)
        return fn, A

    def lower(self, A, compute_q=True):
        """Ahead-of-time `jax.stages.Lowered` of the program ``engine(A)``
        runs — ``.compile().as_text()`` shows what the device executes
        (e.g. a ``tpu_custom_call`` per compiled Pallas kernel)."""
        fn, A = self._callable(A, compute_q)
        return fn.lower(A)

    def __call__(self, A, compute_q=True):
        """Batched QRD: ``A (..., m, n) -> (Q, R)`` (Q None w/o compute_q)."""
        return self._dispatch(A, compute_q)

    decompose = __call__

    # -- least squares --------------------------------------------------------
    def solve(self, A, b, return_residuals=False):
        """Batched least squares ``min_x ||A x - b||`` without forming Q.

        The engine triangularizes the augmented matrix ``[A | b]`` with
        ``compute_q=False`` — the appended column(s) come out as ``Qᵀ b``
        under the same rotations that reduce A — then back-substitutes
        (`repro.qrd.solve`).  Runs on whatever backend/schedule/mesh this
        engine is configured with; per-backend accuracy vs
        ``np.linalg.lstsq`` is documented in
        `repro.qrd.solve.SOLVE_TOLERANCES`.

        Complex systems (complex ``A``/``b``, or a complex-dtype config)
        run on the complex datapath of a complex-capable backend: the
        rotations triangularizing ``[A | b]`` are unitary, the appended
        columns come out as ``Q^H b``, and the conjugate-aware
        back-substitution recovers x; residual norms are the usual
        ``√Σ|·|²`` over the annihilated tail.

        Parameters
        ----------
        A : (..., m, n) array_like, with ``m >= n`` (full-rank for a
            finite solution, as with any non-pivoting QR solve).
        b : (..., m) or (..., m, k) array_like
            One RHS vector per matrix, or ``k`` stacked RHS columns.
        return_residuals : bool
            Also return the ``(..., k)`` residual two-norms
            ``||A x - b||`` — free with the augmented-column trick (the
            annihilated tail of the b column carries them).

        Returns
        -------
        x : (..., n) or (..., n, k) float64 — complex128 for complex
        problems — (matching ``b``), or ``(x, residuals)`` when
        ``return_residuals`` (residuals are always real).
        """
        A = jnp.asarray(A)
        b = jnp.asarray(b)
        if (self.config.is_complex() or A.dtype.kind == "c"
                or b.dtype.kind == "c"):
            work_dtype = jnp.complex128
        else:
            work_dtype = jnp.float64
        A = A.astype(work_dtype)
        b = b.astype(work_dtype)
        m, n = A.shape[-2], A.shape[-1]
        if m < n:
            raise ValueError(f"solve() needs m >= n (got {m} x {n}); "
                             "underdetermined systems have no unique "
                             "least-squares triangular solve")
        vec = b.ndim == A.ndim - 1
        B = b[..., None] if vec else b
        if B.ndim != A.ndim or B.shape[-2] != m:
            raise ValueError(f"b rows must match A rows: A {A.shape}, "
                             f"b {b.shape}")
        aug = jnp.concatenate([A, B], axis=-1)
        _, Raug = self._dispatch(aug, False)
        x, resid = lstsq_from_triangular(Raug, n)
        if vec:
            x, resid = x[..., 0], resid[..., 0]
        return (x, resid) if return_residuals else x

    # -- streaming RLS --------------------------------------------------------
    def rls(self, n, lam=0.99, delta=1e-3, block=None):
        """Create a streaming QRD-RLS state bound to this engine's backend.

        Parameters
        ----------
        n : int
            Filter length (columns of the carried R).
        lam : float
            Forgetting factor λ.
        delta : float
            Initial diagonal loading of R (regularizes the cold start).
        block : int, optional
            Update granularity.  ``None`` selects the backend's natural
            path: the cordic family updates per snapshot on the
            bit-accurate unit (`GivensUnit.annihilate` under one jitted
            scan), ``'blockfp_pallas'`` batches ``block=4`` snapshots per
            kernel-resident block annihilation, and the float backends
            use a plain f64 rotation loop.  An explicit ``block`` forces
            the blocked-kernel path on any backend.

        A complex-dtype config creates a **complex QRD-RLS** state
        (complex128 carried ``[R | z]``, snapshots rotated by the
        three-rotation decomposition on the unit path or conjugate
        Givens on the float path) — the adaptive-beamforming scenario on
        complex baseband snapshots.  The blocked-kernel path has no
        complex datapath; requesting it raises ``TypeError``.

        Returns
        -------
        `repro.qrd.rls.RLSState` — ``state.update(x, d)`` /
        ``state.weights()``.
        """
        from repro.core.givens import GivensUnit
        from .rls import RLSState, validate_lam

        validate_lam(lam)  # eagerly — before any mode routing can raise
        cfg = self.config
        dtype = "complex128" if cfg.is_complex() else "float64"
        if block is not None or cfg.backend == "blockfp_pallas":
            if cfg.is_complex():
                raise TypeError(
                    "the blocked-kernel RLS path has no complex datapath; "
                    "use the cordic family (mode='unit') or a float "
                    "backend for complex QRD-RLS")
            return RLSState(n, lam=lam, delta=delta, mode="block",
                            block=4 if block is None else int(block),
                            hub=cfg.blockfp_hub(), iters=cfg.blockfp_iters(),
                            frac=cfg.frac, interpret=cfg.interpret)
        if cfg.backend in ("cordic", "cordic_pallas"):
            return RLSState(n, lam=lam, delta=delta, mode="unit",
                            unit=GivensUnit(cfg.givens), dtype=dtype)
        return RLSState(n, lam=lam, delta=delta, mode="float", dtype=dtype)

    def fleet(self, slots, n, lam=0.99, delta=1e-3, block=None, mesh=None):
        """Create an `repro.serve.RLSFleet` bound to this engine's backend.

        The fleet analogue of `rls`: N independent streaming QRD-RLS
        states as one struct-of-arrays pytree updated by a single
        donated jitted step (`repro.serve.fleet`, DESIGN.md §12).  Mode
        routing mirrors `rls` exactly — the cordic family vectorizes the
        bit-accurate `GivensUnit` annihilation over slots (so fleet
        slots stay bit-identical to single `RLSState` objects), explicit
        ``block`` or ``'blockfp_pallas'`` selects the kernel-resident
        blocked path (real only), anything else the f64 rotation loop.

        Parameters
        ----------
        slots : int — fleet capacity N.
        n : int — filter length.
        lam, delta : defaults for `RLSFleet.admit` (λ is per-slot state
            and may be overridden per admit).
        block : int, optional — force the blocked-kernel path with this
            many stacked snapshots per slot per update call.
        mesh : jax.sharding.Mesh, optional — shard the slot axis across
            the mesh's data axes; defaults to ``config.mesh``.

        Returns
        -------
        `repro.serve.RLSFleet` — ``fleet.admit(k)`` /
        ``fleet.update(slot_ids, X, d)`` / ``fleet.weights(slot_ids)``.
        """
        from repro.core.givens import GivensUnit
        from repro.serve.fleet import RLSFleet

        from .rls import validate_lam

        validate_lam(lam)
        cfg = self.config
        mesh = cfg.mesh if mesh is None else mesh
        dtype = "complex128" if cfg.is_complex() else "float64"
        if block is not None or cfg.backend == "blockfp_pallas":
            if cfg.is_complex():
                raise TypeError(
                    "the blocked-kernel RLS path has no complex datapath; "
                    "use the cordic family (mode='unit') or a float "
                    "backend for complex QRD-RLS fleets")
            return RLSFleet(slots, n, lam=lam, delta=delta, mode="block",
                            block=4 if block is None else int(block),
                            hub=cfg.blockfp_hub(), iters=cfg.blockfp_iters(),
                            frac=cfg.frac, interpret=cfg.interpret,
                            mesh=mesh)
        if cfg.backend in ("cordic", "cordic_pallas"):
            return RLSFleet(slots, n, lam=lam, delta=delta, mode="unit",
                            unit=GivensUnit(cfg.givens), dtype=dtype,
                            mesh=mesh)
        return RLSFleet(slots, n, lam=lam, delta=delta, mode="float",
                        dtype=dtype, mesh=mesh)
