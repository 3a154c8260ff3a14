"""Tiled-route smoke: the ISSUE-10 acceptance shapes end-to-end.

CI entry point for the tiled lane (DESIGN.md §14).  Exercises the two
production shapes the tiled datapath exists for, through the public
engine (so routing, tuned-knob resolution, and the jit cache are all on
the hot path, not a kernel-level shortcut):

* **64x64 panel** — ``tiling='auto'`` must resolve to the panel route;
  full factors, reconstruction and orthogonality checked.
* **4096x32 TSQR** — ``'auto'`` must resolve to the tree route; the
  economy R is checked upper-triangular and against ``np.linalg.qr``
  up to row signs.
* **bit-identity probe** — a small packed TSQR against
  ``tiled.tsqr_host_reference``: R must match *bitwise* (the full-size
  parity matrix lives in the tier-1 suite; this keeps the contract
  armed in the lane that owns the shapes).
* **bench row sanity** — the committed BENCH_qrd.json must carry the
  ``tiled:{m}x{n}`` rows `check_bench_regression.REQUIRED_ROWS` pins.

    PYTHONPATH=src python -m benchmarks.tiled_smoke
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_qrd.json")


def main():
    import jax
    jax.config.update("jax_enable_x64", True)
    import repro.qrd as api
    from repro.qrd import tiled

    failures = []
    rng = np.random.default_rng(7)

    # 64x64 through the panel route: full Q, float-grade checks (the
    # block-FP datapath at frac=24 is ~1e-5-grade on 64-row columns).
    eng = api.QRDEngine(api.QRDConfig(backend="blockfp_pallas",
                                      dtype="float64"))
    caps = eng.capabilities
    route = tiled.resolve_route(eng.config, 64, 64, caps)
    A = rng.standard_normal((64, 64))
    Q, R = eng(A)
    recon = float(np.max(np.abs(np.asarray(Q) @ np.asarray(R) - A)))
    orth = float(np.max(np.abs(np.asarray(Q).T @ np.asarray(Q)
                               - np.eye(Q.shape[-1]))))
    ok = route == "panel" and recon < 2e-3 and orth < 1e-3
    print(f"{'ok ' if ok else 'FAIL'} 64x64 route={route} "
          f"recon={recon:.2e} orth={orth:.2e}")
    if not ok:
        failures.append("64x64 panel")

    # 4096x32 through the TSQR tree: economy R, sign-normalized vs LAPACK.
    route = tiled.resolve_route(eng.config, 4096, 32, caps)
    A = rng.standard_normal((4096, 32))
    _, R = eng(A, compute_q=False)
    R = np.asarray(R)
    Rref = np.linalg.qr(A, mode="r")
    tri = float(np.max(np.abs(np.tril(R, -1))))
    rerr = float(np.max(np.abs(np.abs(R) - np.abs(Rref))))
    tol = 1e-3 * float(np.max(np.abs(Rref)))
    ok = (route == "tsqr" and R.shape == (32, 32) and tri == 0.0
          and rerr < tol)
    print(f"{'ok ' if ok else 'FAIL'} 4096x32 route={route} "
          f"R{R.shape} |R|err={rerr:.2e} (tol {tol:.1e})")
    if not ok:
        failures.append("4096x32 tsqr")

    # Packed bit-identity probe: engine TSQR vs the host tree replay.
    import jax.numpy as jnp
    from repro.core import qrd as core_qrd
    from repro.core.givens import GivensConfig, GivensUnit
    peng = api.QRDEngine(api.QRDConfig(backend="cordic_pallas",
                                       tiling="tsqr", tile_m=12))
    Ap = rng.standard_normal((40, 4))
    _, Rt = peng(Ap, compute_q=False)
    unit = GivensUnit(GivensConfig())
    _, Rh = tiled.tsqr_host_reference(
        Ap, lambda X: core_qrd.qr_cordic(jnp.asarray(X), unit), tile_m=12)
    bit = bool(np.all(np.asarray(Rt) == Rh))
    print(f"{'ok ' if bit else 'FAIL'} packed tsqr 40x4 R bit-identical "
          f"to host tree: {bit}")
    if not bit:
        failures.append("packed tsqr bit-identity")

    # Bench row sanity: the committed baseline must measure the shapes.
    from benchmarks.check_bench_regression import REQUIRED_ROWS
    with open(_BENCH) as fh:
        rows = json.load(fh).get("results", {})
    for key in REQUIRED_ROWS:
        row = rows.get(key)
        ok = (row is not None and row.get("qrd_per_s")
              and row.get("roofline_fraction") is not None)
        print(f"{'ok ' if ok else 'FAIL'} BENCH_qrd.json[{key!r}]: "
              f"{'present with rate + roofline' if ok else 'missing/incomplete'}")
        if not ok:
            failures.append(f"bench row {key}")

    if failures:
        print(f"tiled_smoke: {len(failures)} failure(s): "
              f"{', '.join(failures)}", file=sys.stderr)
        return 1
    print("tiled_smoke: production shapes OK")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
