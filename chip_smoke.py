#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4   # four chips: the sharded fleet only

Phases, all through the entry points a user calls, at deployment size:

(a) flat batched QRD through `QRDEngine`: 4096 real 8x8 matrices with Q
    on ``blockfp_pallas`` (schedules ``col`` and ``sameh_kuck``) and on
    ``cordic_pallas`` (``col``, the dual-int32 lane datapath).  Block-FP
    is held to float64 NumPy (relative QR residual, orthogonality of Q);
    ``cordic_pallas`` must equal the ``cordic`` backend bit for bit on the
    first 64 matrices (the reference runs on the host CPU).
(b) the tiled routes on ``blockfp_pallas``: a 4096x32 least-squares solve
    (TSQR, batch 8) against ``np.linalg.lstsq`` within
    ``SOLVE_TOLERANCES``, and a 64x64 panel-route QR (batch 64).
(c) the served QRD-RLS fleet through `repro.launch.serve.serve`: preset
    ``equalizer-hub``, 2^17 slots, n = 4, 200 ticks at the server's batch,
    with checkpoints, then evict -> restore (weights bit-identical), and
    eight slots compared bit for bit with independently driven
    `RLSState` filters.

``--chips 4`` instead serves the same fleet sharded over a (4, 1) mesh of
the four chips and on one chip, compares the served weights bit for bit
and checks that each chip holds a quarter of the slots.

Every Pallas phase asserts it ran compiled: ``auto_interpret(None)`` is
False and the compiled program holds a ``tpu_custom_call``.  Timings are
smoke timings, not a benchmark.  The script exits nonzero, printing no
result, when JAX finds no TPU or any phase fails; otherwise its last line
is ``{"ok": true, "device": {...}}``.
"""
import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
FLAT_SHAPE = (4096, 8, 8)
SOLVE_SHAPE = (8, 4096, 32)
PANEL_SHAPE = (64, 64, 64)
FLEET_PRESET = "equalizer-hub"
FLEET_SLOTS = 1 << 17
FLEET_STEPS = 200


def fail(msg):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class Smoke:
    """Per-phase reporting: one line each, failures collected."""

    def __init__(self, jax):
        self.jax = jax
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.failed = []

    def report(self, name, ok, compile_s, steady_s, detail=""):
        d = self.device
        print(f"[{name}] platform={d['platform']} kind={d['kind']!r} "
              f"count={d['count']} compile_s={compile_s:.3f} "
              f"steady_s={steady_s:.4f} (smoke timing, not a benchmark) "
              f"{'PASS' if ok else 'FAIL'} {detail}", flush=True)
        if not ok:
            self.failed.append(name)

    def timed(self, fn, *args, reps=3):
        """(result, cold seconds incl. compile, mean warm seconds)."""
        block = self.jax.block_until_ready
        t0 = time.perf_counter()
        out = block(fn(*args))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            block(fn(*args))
        return out, cold, (time.perf_counter() - t0) / reps

    def assert_compiled(self, name, lowered):
        """The phase's kernels ran through Mosaic, not the interpreter."""
        from repro.kernels.ops import auto_interpret
        if auto_interpret(None):
            fail(f"{name}: Pallas would run in interpret mode here")
        if "tpu_custom_call" not in lowered.compile().as_text():
            fail(f"{name}: compiled program holds no tpu_custom_call")


def phase_flat(smoke, rng):
    """(a) 4096 8x8 QRDs with Q through the engine."""
    import jax
    from repro.qrd import QRDEngine

    A = rng.standard_normal(FLAT_SHAPE)
    n = A.shape[-1]
    for backend, schedule in (("blockfp_pallas", "col"),
                              ("blockfp_pallas", "sameh_kuck"),
                              ("cordic_pallas", "col")):
        name = f"a:{backend}/{schedule}"
        eng = QRDEngine(backend=backend, schedule=schedule)
        (Q, R), cold, warm = smoke.timed(eng, A)
        smoke.assert_compiled(name, eng.lower(A))
        Q, R = np.asarray(Q), np.asarray(R)
        if backend == "blockfp_pallas":
            resid = np.max(np.abs(Q @ R - A)) / np.max(np.abs(A))
            orth = np.max(np.abs(np.swapaxes(Q, -1, -2) @ Q - np.eye(n)))
            ok = bool(resid < 1e-4 and orth < 1e-4)
            detail = f"rel_resid={resid:.3e} orth={orth:.3e} (tol 1e-4)"
        else:
            # the bit-accurate host reference loop, on the host CPU
            with jax.default_device(jax.devices("cpu")[0]):
                Qr, Rr = QRDEngine(backend="cordic")(A[:64])
            same = (np.array_equal(Q[:64], np.asarray(Qr))
                    and np.array_equal(R[:64], np.asarray(Rr)))
            ok = bool(same)
            detail = f"bit-identical to 'cordic' on 64 matrices: {same}"
        smoke.report(name, ok, cold - warm, warm, detail)


def phase_tiled(smoke, rng):
    """(b) TSQR least squares at 4096x32 and a 64x64 panel-route QR."""
    import jax.numpy as jnp
    from repro.qrd import SOLVE_TOLERANCES, QRDEngine

    eng = QRDEngine(backend="blockfp_pallas")
    A = rng.standard_normal(SOLVE_SHAPE)
    b = rng.standard_normal(SOLVE_SHAPE[:2])
    x, cold, warm = smoke.timed(eng.solve, A, b)
    smoke.assert_compiled("b:solve-tsqr", eng.lower(
        jnp.concatenate([jnp.asarray(A), jnp.asarray(b)[..., None]], -1),
        compute_q=False))
    ref = np.stack([np.linalg.lstsq(a, y, rcond=None)[0]
                    for a, y in zip(A, b)])
    err = np.max(np.abs(np.asarray(x) - ref)) / np.max(np.abs(ref))
    tol = SOLVE_TOLERANCES["blockfp_pallas"]
    smoke.report(f"b:solve-tsqr {A.shape[1]}x{A.shape[2]}", bool(err < tol),
                 cold - warm, warm, f"rel_err={err:.3e} (tol {tol:g})")

    A = rng.standard_normal(PANEL_SHAPE)
    m = A.shape[-1]
    (Q, R), cold, warm = smoke.timed(eng, A)
    smoke.assert_compiled("b:panel", eng.lower(A))
    Q, R = np.asarray(Q), np.asarray(R)
    recon = np.max(np.abs(Q @ R - A))
    orth = np.max(np.abs(np.swapaxes(Q, -1, -2) @ Q - np.eye(m)))
    smoke.report(f"b:panel {m}x{m}", bool(recon < 2e-3 and orth < 1e-3),
                 cold - warm, warm,
                 f"recon={recon:.3e} (tol 2e-3) orth={orth:.3e} (tol 1e-3)")


def phase_fleet(smoke):
    """(c) the served fleet, checkpoint/restore, RLSState parity."""
    from repro.core.givens import GivensUnit
    from repro.launch.serve import serve
    from repro.qrd.rls import RLSState
    from repro.serve import fleet_preset

    with tempfile.TemporaryDirectory() as ckpt:
        t0 = time.perf_counter()
        out = serve(FLEET_PRESET, slots=FLEET_SLOTS, n=4, steps=FLEET_STEPS,
                    ckpt_dir=ckpt, ckpt_every=FLEET_STEPS // 2,
                    log=lambda s: print(f"  {s}", flush=True))
        total = time.perf_counter() - t0
    fleet, server = out["fleet"], out["server"]

    # the eight busiest members of the first cohort, replayed one by one
    first = out["ticks"][0][0]
    start = next(c.start for c in server.cohorts() if c.name == first)
    users = np.concatenate([u for c, u, _, _ in out["ticks"] if c == first])
    counts = np.bincount(users)
    members = np.argsort(-counts, kind="stable")[:8]
    spec = fleet_preset(FLEET_PRESET)
    unit = GivensUnit(spec["config"].givens)
    lam = spec["fleet"]["lam"]
    refs = {int(m): RLSState(fleet.n, lam=lam, mode="unit", unit=unit)
            for m in members}
    for c, u, X, d in out["ticks"]:
        if c != first:
            continue
        for ui, xi, di in zip(u, X, d):
            if int(ui) in refs:
                refs[int(ui)].update(xi, di)
    same = all(
        np.array_equal(fleet.export_state(start + m)["R"], r.R)
        and np.array_equal(fleet.export_state(start + m)["z"], r.z)
        for m, r in refs.items())
    ok = not out["failures"] and same
    smoke.report(
        f"c:fleet {FLEET_PRESET} {FLEET_SLOTS} slots", ok,
        total - out["elapsed"], out["elapsed"],
        f"applied={out['applied']} failures={out['failures']} "
        f"rls_parity(8 slots, {int(counts[members].sum())} updates)={same}")


def phase_fleet_sharded(smoke):
    """--chips 4: the fleet sharded over four chips vs on one chip."""
    from repro.launch.serve import device_mesh, serve

    runs = {}
    for label, mesh in (("4 chips", device_mesh(4)), ("1 chip", None)):
        t0 = time.perf_counter()
        out = serve(FLEET_PRESET, slots=FLEET_SLOTS, n=4, steps=FLEET_STEPS,
                    mesh=mesh, log=lambda s: print(f"  {s}", flush=True))
        out["total"] = time.perf_counter() - t0
        runs[label] = out
    shard, single = runs["4 chips"], runs["1 chip"]
    work = shard["fleet"].state.work
    rows = sorted((s.device.id, s.data.shape[0])
                  for s in work.addressable_shards)
    quarter = all(r == FLEET_SLOTS // 4 for _, r in rows) and len(rows) == 4
    names = [c.name for c in single["server"].cohorts()]
    w_shard = np.concatenate([shard["server"].query(n) for n in names])
    w_single = np.concatenate([single["server"].query(n) for n in names])
    same = (np.array_equal(w_shard, w_single)
            and np.array_equal(np.asarray(work),
                               np.asarray(single["fleet"].state.work)))
    ok = quarter and same and not shard["failures"] and not single["failures"]
    smoke.report(
        f"fleet sharded (4,1) vs 1 chip, {FLEET_SLOTS} slots", ok,
        shard["total"] - shard["elapsed"], shard["elapsed"],
        f"rows_per_device={rows} weights_bit_identical={same} "
        f"one_chip_steady_s={single['elapsed']:.4f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet sharded over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not next to this script ({SRC})")
    sys.path.insert(0, SRC)
    import jax

    import repro  # noqa: F401  (x64 on, as for every user of the package)
    from repro.launch.cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {platform!r}")
    if len(jax.devices()) < args.chips:
        fail(f"--chips {args.chips}: only {len(jax.devices())} TPU chips")
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    smoke = Smoke(jax)
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_fleet_sharded(smoke)
    else:
        phase_flat(smoke, rng)
        phase_tiled(smoke, rng)
        phase_fleet(smoke)
    if smoke.failed:
        fail(f"phases failed: {smoke.failed}")
    print(json.dumps({"ok": True, "device": smoke.device}))


if __name__ == "__main__":
    main()
