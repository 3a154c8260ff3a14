"""Tiled QRD routes: panel factorization + TSQR tree reduction (DESIGN.md §14).

The flat Pallas datapaths keep the whole augmented ``(m, n + m)`` tile
kernel-resident, which caps them at `BackendCapabilities.max_shape`
(VMEM).  This module supplies the two routes that lift that cap while
preserving the wavefront property — every rotation is *computed once*
(vectoring on the leading pair) and *replayed everywhere else* from its
``(flip, sigma)`` control words:

* **panel** — sweep the columns in ``panel_n``-wide panels.  Each panel
  is factorized by a kernel-resident step loop
  (`repro.kernels.qrd_blocked.qr_call` with ``export=True``) that
  exports the control words of every rotation; the trailing columns are
  updated by a replay kernel (`qrd_blocked.replay_call`) batched over
  *both* the matrix batch and the trailing-panel axis of the Pallas
  grid.  The panel schedule is the
  column-major flat schedule split at panel boundaries — the
  concatenation of the per-panel step tables *is*
  `repro.core.qrd.givens_schedule`, so the route is bit-identical to
  the flat reference ordering by construction (verified by
  ``tests/test_qrd_tiled.py``).  Rows still ride in one tile: m is
  bounded by ``max_shape[0]``; n is unbounded (columns stream through
  the grid).

* **tsqr** — the communication-avoiding tall-skinny route.  Rows are
  zero-padded to ``L * tile_m`` and split into L leaf tiles; every leaf
  is factorized by the panel driver as one batched launch, then a binary
  tree of ``(2n, n)`` stacked R-pair factorizations reduces the L leaf
  R factors to one.  Each tree level is again one batched launch —
  sharded over the mesh's data axes via
  `repro.launch.sharding.tsqr_node_spec` when ``config.mesh`` is set —
  so the critical path is ``ceil(log2 L)`` launches regardless of m.
  Returns the *economy* factors ``Q (m, n), R (n, n)`` (a full m x m Q
  would defeat the point at m = 10^4).  Q is recovered without ever
  materializing tree-level Qs at full height: each leaf carries an
  ``(n, n)`` composition factor B, updated per level from the economy Q
  of the node that consumed the leaf's R (top or bottom half, selected
  by a *static* owner/side index map), and the final
  ``Q = concat_l(Q_leaf[l] @ B[l])[:m]``.

Route selection (`resolve_route`) is deterministic in
``(m, n, config)`` — the engine's jitted-callable LRU key
``(m, n, compute_q, config.cache_key())`` therefore already
distinguishes routes.  ``tiling='auto'`` (or None) keeps every shape
that previously worked on the flat datapath unchanged
(``m, n <= FLAT_LIMIT``), so existing callers see identical bits and
identical cache behavior.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FLAT_LIMIT", "DEFAULT_PANEL_N", "resolve_route", "resolve_tiles",
           "build_tiled", "tsqr_host_reference"]

# auto routes shapes at or under this bound onto the flat datapath --
# small problems fit comfortably and all pre-tiling callers stay on
# byte-identical code paths (same kernels, same jit cache entries).
FLAT_LIMIT = 32

# default panel width when config.panel_n is None and the autotuner has
# no entry: 8 columns matches TILE_B-sized VMEM tiles on both datapaths.
DEFAULT_PANEL_N = 8


def _capacity_error(config, caps, m, n, detail):
    max_m, max_n = caps.max_shape
    return ValueError(
        f"operand {m}x{n} exceeds backend {config.backend!r} kernel "
        f"capacity max_shape={caps.max_shape} ({detail}); "
        f"tiled alternatives: tiling='panel' keeps m <= {max_m} rows "
        f"kernel-resident with unbounded columns, tiling='tsqr' handles "
        f"tall-skinny m > {max_m} with n <= {max_m // 2} (tree nodes "
        f"stack R pairs to 2n x n), and tiling='auto' selects between "
        f"them.  See DESIGN.md §14.")


def resolve_route(config, m, n, caps) -> str:
    """Pick the datapath route for an (m, n) problem: flat | panel | tsqr.

    Deterministic in ``(m, n, config)``.  Raises ``ValueError`` naming
    the backend's ``max_shape`` and the tiled alternative whenever the
    requested (or only available) route cannot hold the operand —
    previously such shapes died deep inside Pallas with an opaque VMEM
    or iota-shape error — and ``NotImplementedError`` when the route is
    tiled but the backend's tiled kernels are interpret-only and would
    be compiled (`QRDConfig.check_compiled`).
    """
    route = _route(config, m, n, caps)
    config.check_compiled(caps, route != "flat")
    return route


def _route(config, m, n, caps) -> str:
    tiling = "auto" if config.tiling is None else config.tiling

    # Backends without a tiled datapath (host references, float
    # baselines) have max_shape=None and always run flat.
    if not caps.supports_tiling:
        return "flat"
    # The complex datapath and the sameh_kuck wavefront ordering only
    # exist flat: the tiled routes replay the column-major schedule.
    forced_tiled = tiling in ("panel", "tsqr")
    if config.is_complex() or config.schedule == "sameh_kuck":
        which = ("complex datapath" if config.is_complex()
                 else "schedule='sameh_kuck'")
        if forced_tiled:
            raise ValueError(
                f"tiling={tiling!r} is only defined for the real "
                f"column-major datapath, but this config uses {which}; "
                "the tiled routes replay the flat column-major ordering "
                "(schedule='col')")
        if not caps.fits_flat(m, n):
            raise _capacity_error(
                config, caps, m, n,
                f"{which} runs flat only, and the whole augmented tile "
                "must fit VMEM")
        return "flat"

    max_m, max_n = caps.max_shape
    if tiling == "flat":
        if not caps.fits_flat(m, n):
            raise _capacity_error(
                config, caps, m, n,
                "tiling='flat' keeps the whole augmented tile "
                "kernel-resident")
        return "flat"
    if tiling == "panel":
        if m > max_m:
            raise _capacity_error(
                config, caps, m, n,
                "the panel route keeps all m rows kernel-resident")
        return "panel"
    if tiling == "tsqr":
        if 2 * n > max_m or n > max_n:
            raise _capacity_error(
                config, caps, m, n,
                "tsqr tree nodes stack R pairs to 2n x n tiles")
        return "tsqr"

    # -- auto -------------------------------------------------------------
    if m <= FLAT_LIMIT and n <= FLAT_LIMIT:
        return "flat"
    tsqr_ok = 2 * n <= max_m and n <= max_n
    if tsqr_ok and (m > max_m or m >= 4 * n):
        return "tsqr"          # over row capacity, or decisively tall-skinny
    if m <= max_m:
        return "panel"
    raise _capacity_error(
        config, caps, m, n,
        "m exceeds the row capacity and n is too wide for tsqr tree nodes")


def resolve_tiles(config, caps):
    """Resolve ``(tile_m, panel_n)``: explicit config values win, else the
    backend's row capacity and `DEFAULT_PANEL_N` (the engine fills tuned
    values into the config *before* this runs, so autotuned winners land
    here as if explicit)."""
    tile_m = config.tile_m if config.tile_m is not None else caps.max_shape[0]
    panel_n = config.panel_n if config.panel_n is not None else DEFAULT_PANEL_N
    return tile_m, panel_n


def _leaf_qr_fn(config, panel_n):
    """The batched small-QR primitive both tiled routes are built from:
    ``qr(X, compute_q) -> (Q, R)`` on the panel driver of the configured
    backend (full-shape factors, `repro.core.qrd._split_qr` contract)."""
    from repro.core import qrd as _q
    from repro.core.givens import GivensUnit

    if config.backend == "cordic_pallas":
        unit = GivensUnit(config.givens)

        def qr(X, cq):
            return _q.qr_cordic_panel(X, unit, compute_q=cq, panel_n=panel_n,
                                      interpret=config.interpret,
                                      tile_b=config.tile_b)
        return qr

    iters, hub, frac = (config.blockfp_iters(), config.blockfp_hub(),
                        config.frac)

    def qr(X, cq):
        return _q.qr_blockfp_panel(X, compute_q=cq, iters=iters, hub=hub,
                                   frac=frac, panel_n=panel_n,
                                   interpret=config.interpret,
                                   tile_b=config.tile_b)
    return qr


def build_tiled(route, config, m, n, compute_q, caps):
    """Builder for the tiled routes — same contract as a registry builder
    (``(A) -> (Q, R)``, jit-compatible), selected by `resolve_route`.

    ``route='panel'`` returns full factors like the flat datapath
    (``Q (m, m), R (m, n)``); ``route='tsqr'`` returns the economy
    factors (``Q (m, n), R (n, n)``) — at TSQR scale a full Q is the
    product the route exists to avoid.
    """
    tile_m, panel_n = resolve_tiles(config, caps)
    qr = _leaf_qr_fn(config, panel_n)
    if route == "panel":
        return lambda A: qr(A, compute_q)
    if route != "tsqr":
        raise ValueError(f"unknown tiled route {route!r}")
    mesh = config.mesh

    def fn(A):
        return _tsqr(A, leaf_qr=qr, tile_m=tile_m, compute_q=compute_q,
                     mesh=mesh)
    return fn


def _constrain_nodes(X, mesh):
    """In-jit analogue of `repro.launch.sharding.shard_tsqr_nodes`: a
    sharding *constraint* (placement hints are all a trace can express —
    ``device_put`` belongs outside jit)."""
    if mesh is None:
        return X
    from jax.sharding import NamedSharding

    from repro.launch.sharding import tsqr_node_spec
    spec = tsqr_node_spec(X.ndim, X.shape[0], mesh)
    return jax.lax.with_sharding_constraint(X, NamedSharding(mesh, spec))


def _tsqr(A, *, leaf_qr, tile_m, compute_q, mesh):
    """TSQR binary tree reduction over batched tall-skinny operands.

    Tree plan (pairings, owner/side maps) is static numpy — only the
    node factorizations and the (n, n) composition einsums trace.  Zero
    rows padding the last leaf ride through its factorization (columns
    of zeros rotate to zeros; the pad rows of Q are sliced off at the
    end) — the bit-exactness contract is against a host reference with
    the *same* padded tree (`tsqr_host_reference`): **R bit-identical**
    (it is produced entirely by the bit-exact rotation datapath), Q to
    float64-rounding tolerance (the composition is float matmul, whose
    summation order differs between XLA and host BLAS).
    """
    A = jnp.asarray(A, jnp.float64)
    m, n = A.shape[-2], A.shape[-1]
    batch = A.shape[:-2]
    Af = A.reshape((-1, m, n))
    B = Af.shape[0]
    L = -(-m // tile_m)
    pad = L * tile_m - m
    if pad:
        Af = jnp.pad(Af, ((0, 0), (0, pad), (0, 0)))

    nodes = _constrain_nodes(Af.reshape(B * L, tile_m, n), mesh)
    Qf, Rf = leaf_qr(nodes, compute_q)
    Rs = Rf[..., :n, :].reshape(B, L, n, n)
    if compute_q:
        Qleaf = Qf[..., :n].reshape(B, L, tile_m, n)   # economy leaf Q
        eye = jnp.eye(n, dtype=Qleaf.dtype)
        comp = jnp.broadcast_to(eye, (B, L, n, n))     # per-leaf B factors

    owner = np.arange(L)        # which live R-slot each leaf feeds (static)
    cur = L
    while cur > 1:
        pairs, odd = cur // 2, cur % 2
        stack = jnp.concatenate([Rs[:, 0:2 * pairs:2], Rs[:, 1:2 * pairs:2]],
                                axis=-2).reshape(B * pairs, 2 * n, n)
        Qn, Rn = leaf_qr(_constrain_nodes(stack, mesh), compute_q)
        new_Rs = Rn[..., :n, :].reshape(B, pairs, n, n)
        if odd:                 # unpaired last node carries to the next level
            new_Rs = jnp.concatenate([new_Rs, Rs[:, -1:]], axis=1)
        if compute_q:
            Qe = Qn[..., :n].reshape(B, pairs, 2 * n, n)
            # T-stack layout [top halves | bottom halves | I]; each leaf
            # selects its consumer node's half (or I when carried) by a
            # static index -- a gather, never a traced branch.
            T = jnp.concatenate(
                [Qe[:, :, :n, :], Qe[:, :, n:, :],
                 jnp.broadcast_to(eye, (B, 1, n, n))], axis=1)
            sel = np.where(owner < 2 * pairs,
                           owner // 2 + (owner % 2) * pairs, 2 * pairs)
            comp = jnp.einsum("blij,bljk->blik", comp, T[:, sel])
        owner = np.where(owner < 2 * pairs, owner // 2, pairs)
        Rs, cur = new_Rs, pairs + odd

    R = Rs[:, 0].reshape(batch + (n, n))
    if not compute_q:
        return None, R
    Q = jnp.einsum("blij,bljk->blik", Qleaf, comp)
    Q = Q.reshape(B, L * tile_m, n)[:, :m]
    return Q.reshape(batch + (m, n)), R


def tsqr_host_reference(A, node_qr, tile_m):
    """Host-loop TSQR oracle for the bit-exactness tests.

    Runs the *same* padded tree plan as `_tsqr` but factorizes every
    node one at a time through ``node_qr(X) -> (Q, R)`` (full-shape
    factors, e.g. `repro.core.qrd.qr_cordic` on the column-major
    schedule) — a completely independent execution path from the
    batched panel kernels, sharing only the rotation *ordering*.
    Returns economy ``(Q (m, n), R (n, n))``; R compares bitwise
    against the tsqr route, Q to float64-rounding tolerance (host BLAS
    and XLA matmuls sum in different orders).
    """
    A = np.asarray(A, np.float64)
    m, n = A.shape
    L = -(-m // tile_m)
    Af = np.zeros((L * tile_m, n))
    Af[:m] = A
    Qs, Rs = [], []
    for leaf in range(L):
        Q, R = node_qr(Af[leaf * tile_m:(leaf + 1) * tile_m])
        Qs.append(np.asarray(Q)[:, :n])
        Rs.append(np.asarray(R)[:n, :])
    comp = [np.eye(n) for _ in range(L)]
    owner = list(range(L))
    while len(Rs) > 1:
        pairs = len(Rs) // 2
        new_Rs, tops, bots = [], [], []
        for p in range(pairs):
            Q, R = node_qr(np.concatenate([Rs[2 * p], Rs[2 * p + 1]]))
            Qe = np.asarray(Q)[:, :n]
            new_Rs.append(np.asarray(R)[:n, :])
            tops.append(Qe[:n])
            bots.append(Qe[n:])
        if len(Rs) % 2:
            new_Rs.append(Rs[-1])
        for leaf in range(L):
            o = owner[leaf]
            if o < 2 * pairs:
                half = tops[o // 2] if o % 2 == 0 else bots[o // 2]
                comp[leaf] = comp[leaf] @ half
                owner[leaf] = o // 2
            else:
                owner[leaf] = pairs
        Rs = new_Rs
    Q = np.concatenate([Qs[leaf] @ comp[leaf] for leaf in range(L)])[:m]
    return Q, Rs[0]
