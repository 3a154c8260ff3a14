"""Tables 6-7 — throughput/latency/area vs prior FP CORDIC designs.

The initiation-interval model is exact (it is architectural, not
technological):
    ours          II = e                     (vectoring/rotation overlapped)
    FP CORDIC[32] II = 69 + e                (angle before rotations)
    FP CORDIC[21] II = 212 + 224 e           (word-serial)
    7x7 QRD [30]  II = 364
Throughput at each design's reported fmax reproduces the paper's MOp/s
column; we also measure our emulation's actual throughput on this CPU
(vectorized over a batch of rotations — the "spatial" analogue of the
pipeline) and the Pallas-kernel rotations/s.

Timing hygiene (schema_version 2): every engine row records **cold**
(first call: trace + compile + run) and **warm** (median of
``REPRO_BENCH_WARM_REPS`` ``block_until_ready`` reps) separately —
the old ``end_to_end_s`` conflated them and is kept as an alias of cold
for v1 consumers.  Rates (``qrd_per_s``/``solve_per_s``) are computed
from warm.  Each row also carries its resolved ``interpret_mode`` and
``tile_b`` plus the measured-vs-analytic ``roofline_fraction``
(`repro.launch.roofline.roofline_for_row`), and the run exercises the
`repro.kernels.autotune` tuner on two shapes before measuring.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import csv_row, timed

E = 8  # elements per row (4x4 QRD with Q, as in the paper)
BENCH_JSON = os.environ.get("REPRO_BENCH_QRD_JSON", "BENCH_qrd.json")
WARM_REPS = int(os.environ.get("REPRO_BENCH_WARM_REPS", "5"))
SCHEMA_VERSION = 2


def _cold_warm(run, warm_reps=None):
    """(cold first-call seconds, median warm seconds) for a thunk."""
    import jax
    warm_reps = WARM_REPS if warm_reps is None else warm_reps
    t0 = time.perf_counter()
    jax.block_until_ready(run())
    cold = time.perf_counter() - t0
    times = []
    for _ in range(warm_reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    return cold, float(np.median(times))


def _engine_tile_b(eng):
    """The tile_b the engine actually dispatched with (autotuned or
    default) — read off the jitted-callable LRU key's resolved config."""
    from repro.kernels.qrd_blocked import TILE_B
    cache = getattr(eng, "_fn_cache", None) or {}
    for key in cache:
        cfg = key[3][0]
        return cfg.tile_b if cfg.tile_b is not None else TILE_B
    return TILE_B

DESIGNS = {
    # name: (fmax MHz, latency cycles, II(e) lambda)
    "fp_cordic_[21]": (67.1, 224, lambda e: 212 + 224 * e),
    "fp_cordic_[32]": (173.3, 138, lambda e: 69 + e),
    "hub_fp_rotator (ours)": (255.8, 60, lambda e: e),
}
PAPER_MOPS = {"fp_cordic_[21]": 0.033, "fp_cordic_[32]": 2.25,
              "hub_fp_rotator (ours)": 31.97}


def measured_kernel_rate(batch=512, L=128, iters=24):
    import jax.numpy as jnp
    from repro.kernels import ops
    x = (np.random.default_rng(0).uniform(-1.5, 1.5, (2, batch, L))
         * 2 ** 24).astype(np.int32)
    xj, yj = jnp.asarray(x[0]), jnp.asarray(x[1])

    def run():
        return ops.givens_rotate_rows_fixed(xj, yj, iters=iters, hub=True)

    sec = timed(run)
    return batch / sec


def measured_qrd_rates(batch=64, m=4,
                       combos=(("cordic", "col"),
                               ("cordic_pallas", "col"),
                               ("cordic_pallas", "sameh_kuck"),
                               ("blockfp_pallas", "col"),
                               ("blockfp_pallas", "sameh_kuck"))):
    """Full m x m QRD throughput across backends *and* schedules.

    Two architectural axes (DESIGN.md §5, §8):

    - HBM passes: the 'cordic' loop makes 2·steps passes over the working
      set (one read + one write per rotation launch); every blocked kernel
      makes exactly 2 (stage in, write back).
    - Sequential depth: the step-serial blocked kernels run ``steps``
      dependent rotations; with ``schedule='sameh_kuck'`` the Pallas
      backends route onto the wavefront datapath and run ``stages``
      dependent scan iterations — min(m + n − 2, 2m − 3) instead of
      m·n/2-ish.

    Returns ``{f"{backend}/{schedule}": record}`` where each record holds
    the warm steady-state rate (``qrd_per_s``), cold vs warm wall times
    (``cold_s`` / ``warm_s``; cold includes trace + compile — the
    wavefront's biggest win: its trace is one stage body, not the
    unrolled schedule), the resolved ``interpret_mode`` / ``tile_b``,
    the measured-vs-analytic ``roofline_fraction``, and the depth/pass
    accounting.
    """
    import jax.numpy as jnp
    from repro.core import (GivensConfig, QRDEngine, givens_schedule,
                            sameh_kuck_schedule)
    from repro.kernels.ops import auto_interpret
    from repro.launch.roofline import roofline_for_row

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.choice([-1.0, 1.0], (batch, m, m))
                    * np.exp2(rng.uniform(-4, 4, (batch, m, m))))
    steps = len(givens_schedule(m, m))
    stages = len(sameh_kuck_schedule(m, m))
    cfg = GivensConfig(hub=True, n=26)
    interp = auto_interpret(None)
    out = {}
    for backend, sched in combos:
        eng = QRDEngine(backend=backend, givens_config=cfg, schedule=sched)
        cold, warm = _cold_warm(lambda: eng(A))
        wavefront = sched == "sameh_kuck" and backend != "cordic"
        pallas = backend.endswith("_pallas")
        row = {
            "backend": backend, "schedule": sched,
            "batch": batch, "m": m,
            "qrd_per_s": batch / warm,
            "cold_s": cold, "warm_s": warm,
            "end_to_end_s": cold,        # v1 alias (cold time)
            "interpret_mode": interp if pallas else None,
            "tile_b": _engine_tile_b(eng) if pallas else None,
            "iters": cfg.resolved_iters(),
            "steps": steps, "stages": stages,
            "seq_depth": stages if wavefront else steps,
            "hbm_passes_per_qrd": 2 * steps if backend == "cordic" else 2,
        }
        terms = roofline_for_row(row)
        if terms is not None:
            row["roofline_fraction"] = terms["roofline_fraction"]
            row["roofline_bound_qrd_per_s"] = terms["bound_qrd_per_s"]
            row["roofline_dominant"] = terms["dominant"]
        out[f"{backend}/{sched}"] = row
    return out


def measured_tiled_qrd_rates():
    """Production-shape QRD throughput on the tiled routes (DESIGN.md §14).

    Two acceptance shapes, keyed ``tiled:{m}x{n}`` (the
    `check_bench_regression.REQUIRED_ROWS` set — CI fails if either
    stops being measured):

    * ``tiled:64x64`` — the panel route on ``blockfp_pallas``: the
      whole 64-row tile stays kernel-resident, columns sweep in panels,
      full Q computed.  Four matrices per batch keep the warm time
      measurable without bloating the interpret-mode compile.
    * ``tiled:4096x32`` — the TSQR tree on ``blockfp_pallas``:
      32 leaf tiles reduced over 5 tree levels, Q-free (the
      least-squares workload; the Q composition is benchmarked by its
      own cost term in `repro.launch.perfmodel.tsqr_qrd_cost`).

    Rows carry ``tiling``/``tile_m``/``panel_n``/``compute_q`` so
    `repro.launch.roofline.roofline_for_row` scores them against the
    *tiled* cost models (trailing-panel re-reads and tree work included
    in the bound).
    """
    from repro import qrd as api
    from repro.kernels.ops import auto_interpret
    from repro.launch.roofline import roofline_for_row

    rng = np.random.default_rng(0)
    interp = auto_interpret(None)
    shapes = (
        # key suffix,  m,    n,  batch, tiling,  compute_q
        ("64x64",      64,   64, 4,     "panel", True),
        ("4096x32",    4096, 32, 1,     "tsqr",  False),
    )
    out = {}
    for label, m, n, batch, tiling, compute_q in shapes:
        A = rng.standard_normal((batch, m, n))
        eng = api.QRDEngine(api.QRDConfig(backend="blockfp_pallas",
                                          dtype="float64", tiling=tiling))
        cold, warm = _cold_warm(lambda: eng(A, compute_q=compute_q))
        resolved = eng._resolve_tuned(eng.config, m, n)
        from repro.qrd import tiled as _tiled
        tile_m, panel_n = _tiled.resolve_tiles(resolved, eng.capabilities)
        row = {
            "backend": "blockfp_pallas", "schedule": "col",
            "tiling": tiling, "tile_m": tile_m, "panel_n": panel_n,
            "batch": batch, "m": m, "n": n, "compute_q": compute_q,
            "qrd_per_s": batch / warm,
            "cold_s": cold, "warm_s": warm,
            "end_to_end_s": cold,        # v1 alias (cold time)
            "interpret_mode": interp,
            "iters": 24,
        }
        terms = roofline_for_row(row)
        if terms is not None:
            row["roofline_fraction"] = terms["roofline_fraction"]
            row["roofline_bound_qrd_per_s"] = terms["bound_qrd_per_s"]
            row["roofline_dominant"] = terms["dominant"]
        out[f"tiled:{label}"] = row
    return out


def run_tiled_autotune_demo(m=64, n=64, batch=4):
    """Tune the panel width for the 64x64 panel route; record the sweep.

    Narrow two-candidate search (each candidate pays a full
    interpret-mode trace+compile, ~20 s on CI) — enough to demonstrate
    the tiled tuner end-to-end and to persist a winner the
    ``tiled:64x64`` row's engine picks up on the next run (the row's
    config leaves ``panel_n=None``).
    """
    from repro.kernels import autotune

    entry = autotune.tune_tiled("blockfp_pallas", m, n, batch,
                                tiling="panel", dtype="float64",
                                warm_reps=2, panel_ns=(4, 8))
    return {"backend": "blockfp_pallas", "tiling": "panel",
            "m": m, "n": n, "batch": batch,
            "panel_n": entry.panel_n, "tile_m": entry.tile_m,
            "warm_s": entry.warm_s,
            "cache_key": autotune.cache_key("blockfp_pallas", "col", m, n,
                                            "float64", "panel"),
            "candidates": list(entry.candidates)}


def measured_solve_rates(batch=64, m=6, n=3,
                         combos=(("jnp", "col"),
                                 ("givens_float", "col"),
                                 ("blockfp_pallas", "sameh_kuck"))):
    """Problem-level ``engine.solve(A, b)`` throughput (DESIGN.md §9).

    Times the full least-squares path — triangularize the augmented
    ``[A | b]`` with ``compute_q=False`` on the registry-dispatched
    engine, then back-substitute — the workload the paper's rotator
    exists for (QRD-based least squares in communication systems).
    Returns ``{f"solve:{backend}/{schedule}": record}`` with the warm
    steady-state ``solve_per_s`` plus cold/warm wall times.
    """
    from repro import qrd as api
    from repro.core import GivensConfig
    from repro.kernels.ops import auto_interpret

    rng = np.random.default_rng(0)
    A = (rng.choice([-1.0, 1.0], (batch, m, n))
         * np.exp2(rng.uniform(-2, 2, (batch, m, n))))
    b = rng.normal(size=(batch, m)) * 2.0
    cfg = GivensConfig(hub=True, n=26)
    interp = auto_interpret(None)
    out = {}
    for backend, sched in combos:
        eng = api.QRDEngine(backend=backend, schedule=sched, givens=cfg)
        cold, warm = _cold_warm(lambda: eng.solve(A, b))
        out[f"solve:{backend}/{sched}"] = {
            "backend": backend, "schedule": sched, "batch": batch,
            "m": m, "n": n,
            "solve_per_s": batch / warm,
            "cold_s": cold, "warm_s": warm, "end_to_end_s": cold,
            "interpret_mode": (interp if backend.endswith("_pallas")
                               else None),
        }
    return out


def measured_complex_qrd_rates(batch=64, m=4,
                               combos=(("cordic", "col"),
                                       ("cordic_pallas", "sameh_kuck"))):
    """Complex QRD throughput on the three-rotation datapath (§10).

    Every annihilation spends three unit rotations (two phase + one real
    Givens) across twice the lanes (re/im), so the architectural cost is
    ~6x the real path per step — these rows track that the measured ratio
    stays in that ballpark and that the complex wavefront's cold
    end-to-end time keeps its one-stage-body trace advantage.
    Returns ``{f"complex:{backend}/{schedule}": record}``.
    """
    from repro import qrd as api
    from repro.core import GivensConfig, givens_schedule, sameh_kuck_schedule
    from repro.kernels.ops import auto_interpret

    rng = np.random.default_rng(0)
    A = (rng.choice([-1.0, 1.0], (batch, m, m))
         * np.exp2(rng.uniform(-4, 4, (batch, m, m)))
         + 1j * (rng.choice([-1.0, 1.0], (batch, m, m))
                 * np.exp2(rng.uniform(-4, 4, (batch, m, m)))))
    steps = len(givens_schedule(m, m))
    stages = len(sameh_kuck_schedule(m, m))
    cfg = GivensConfig(hub=True, n=26)
    interp = auto_interpret(None)
    out = {}
    for backend, sched in combos:
        eng = api.QRDEngine(backend=backend, schedule=sched, givens=cfg,
                            dtype="complex128")
        cold, warm = _cold_warm(lambda: eng(A))
        wavefront = sched == "sameh_kuck" and backend != "cordic"
        out[f"complex:{backend}/{sched}"] = {
            "backend": backend, "schedule": sched, "dtype": "complex128",
            "batch": batch, "m": m,
            "qrd_per_s": batch / warm,
            "cold_s": cold, "warm_s": warm, "end_to_end_s": cold,
            "interpret_mode": (interp if backend.endswith("_pallas")
                               else None),
            "steps": steps, "stages": stages,
            "seq_depth": stages if wavefront else steps,
        }
    return out


def measured_complex_solve_rates(batch=64, m=6, n=3,
                                 combos=(("cordic", "col"),
                                         ("givens_float", "col"))):
    """Complex ``engine.solve`` throughput (MIMO-detection workload, §10).

    The batched complex least-squares path — triangularize ``[A | b]``
    with the three-rotation decomposition, conjugate-aware
    back-substitution — i.e. the per-channel-use work of the MIMO
    zero-forcing detector (`examples/mimo_detection.py`).
    Returns ``{f"complex-solve:{backend}/{schedule}": record}``.
    """
    from repro import qrd as api
    from repro.core import GivensConfig
    from repro.kernels.ops import auto_interpret

    rng = np.random.default_rng(0)
    A = (rng.normal(size=(batch, m, n))
         + 1j * rng.normal(size=(batch, m, n)))
    b = rng.normal(size=(batch, m)) + 1j * rng.normal(size=(batch, m))
    cfg = GivensConfig(hub=True, n=26)
    interp = auto_interpret(None)
    out = {}
    for backend, sched in combos:
        eng = api.QRDEngine(backend=backend, schedule=sched, givens=cfg,
                            dtype="complex128")
        cold, warm = _cold_warm(lambda: eng.solve(A, b))
        out[f"complex-solve:{backend}/{sched}"] = {
            "backend": backend, "schedule": sched, "dtype": "complex128",
            "batch": batch, "m": m, "n": n,
            "solve_per_s": batch / warm,
            "cold_s": cold, "warm_s": warm, "end_to_end_s": cold,
            "interpret_mode": (interp if backend.endswith("_pallas")
                               else None),
        }
    return out


def measured_rls_fleet_rates(sizes=(4096, 131072), n=4, batch=256):
    """Fleet serving throughput: updates/s vs fleet size (DESIGN.md §12).

    Times the donated single-step `RLSFleet.update` in float mode (the
    serving fleet's CPU-fast lane) at each fleet size with a fixed
    snapshot batch.  The donated step consumes its input state, so the
    usual ``_cold_warm(thunk)`` re-run pattern would touch deleted
    buffers — instead the fleet's own state is threaded forward through
    every timed call (which is also the honest serving workload: each
    step really does start from the previous step's output).  The slot
    count should be a *capacity* axis, not a cost axis: the gather/
    scatter step is O(batch), so ``updates_per_s`` staying flat across
    ``sizes`` is the claim these rows track.
    Returns ``{f"fleet:{slots}x{n} (b{batch})": record}``.
    """
    import jax
    from repro.serve import RLSFleet

    rng = np.random.default_rng(0)
    out = {}
    for slots in sizes:
        fleet = RLSFleet(slots, n, mode="float", lam=0.995)
        ids = fleet.admit(batch)
        X = rng.normal(size=(batch, n))
        d = rng.normal(size=batch)
        t0 = time.perf_counter()
        fleet.update(ids, X, d)
        jax.block_until_ready(fleet.state.work)
        cold = time.perf_counter() - t0
        times = []
        for _ in range(WARM_REPS):
            t0 = time.perf_counter()
            fleet.update(ids, X, d)
            jax.block_until_ready(fleet.state.work)
            times.append(time.perf_counter() - t0)
        warm = float(np.median(times))
        out[f"fleet:{slots}x{n} (b{batch})"] = {
            "mode": "float", "slots": slots, "n": n, "batch": batch,
            "updates_per_s": batch / warm,
            "warm_s": warm, "cold_s": cold, "end_to_end_s": cold,
            "interpret_mode": None,
        }
    return out


#: (m, batch) shapes the autotune demonstration covers: a tall batch of
#: tiny matrices (tile candidates run up to the batch) vs a small batch
#: of big matrices (the batch itself caps the tile) — the shapes whose
#: winning tiles should differ.
AUTOTUNE_SHAPES = ((4, 64), (32, 8))


def run_autotune_demo(backend="blockfp_pallas", schedule="sameh_kuck",
                      shapes=AUTOTUNE_SHAPES):
    """Tune (tile_b, table_layout) on two shapes; compare vs fixed TILE_B.

    Populates the persisted autotune cache (so the engine rows above it
    in future runs dispatch on tuned tiles) and returns the comparison
    record for BENCH_qrd.json: per shape, the winner, its warm time, and
    the fixed-``TILE_B`` candidate's warm time from the same sweep.
    """
    from repro.core import GivensConfig
    from repro.kernels import autotune
    from repro.kernels.qrd_blocked import TILE_B

    cfg = GivensConfig(hub=True, n=26)
    out = {"backend": backend, "schedule": schedule, "fixed_tile_b": TILE_B,
           "cache_path": autotune.cache_path(), "shapes": {}}
    for m, batch in shapes:
        # dtype must match the engine rows' dispatch key (the legacy
        # shim's default problem dtype) or the lookup misses.
        entry = autotune.tune(backend, schedule, m, m, batch, givens=cfg,
                              dtype="float32", warm_reps=3)
        fixed = next((c for c in entry.candidates
                      if c["tile_b"] == TILE_B
                      and c["table_layout"] in ("split", None)), None)
        rec = {"batch": batch,
               "tile_b": entry.tile_b, "table_layout": entry.table_layout,
               "warm_s": entry.warm_s,
               "fixed_tile_warm_s": fixed["warm_s"] if fixed else None,
               "speedup_vs_fixed": (fixed["warm_s"] / entry.warm_s
                                    if fixed else None),
               "candidates": list(entry.candidates)}
        out["shapes"][f"m{m}_b{batch}"] = rec
    return out


def main(full=False):
    print("# table6: design,fmax_mhz,latency_cyc,II_e8,mops_model,mops_paper")
    rows = []
    for name, (fmax, lat, ii) in DESIGNS.items():
        mops = fmax / ii(E)
        rows.append((name, mops))
        print(f"{name},{fmax},{lat},{ii(E)},{mops:.3f},{PAPER_MOPS[name]}")
    ours = dict(rows)["hub_fp_rotator (ours)"]
    gen = dict(rows)["fp_cordic_[32]"]
    print(f"# speedup vs [32]: {ours/gen:.1f}x (paper: ~15x)")
    print("# table7: design,precision,luts_paper")
    for n, l in [("fp_cordic_[21]", 11718), ("fp_cordic_[32]", 22189),
                 ("hub_fp_rotator", 8463)]:
        print(f"{n},double,{l}")

    # Tune first: the 4x4 engine rows below then dispatch on the tuned
    # tile (the tuner writes the persisted cache the engine consults).
    tuned = run_autotune_demo()
    print("# autotune: shape,tile_b,table_layout,warm_s,speedup_vs_fixed")
    for shape, r in tuned["shapes"].items():
        sp = r["speedup_vs_fixed"]
        print(f"{shape},{r['tile_b']},{r['table_layout']},"
              f"{r['warm_s']:.4f},{sp:.2f}x" if sp else
              f"{shape},{r['tile_b']},{r['table_layout']},"
              f"{r['warm_s']:.4f},n/a")

    hdr = ("backend/schedule,qrd_per_s,warm_s,cold_s,seq_depth,steps,"
           "stages,hbm_passes_per_qrd,tile_b,roofline_fraction")
    print(f"# blocked QRD engines (4x4): {hdr}")
    qrd = measured_qrd_rates(m=4)
    for key, r in qrd.items():
        print(f"{key},{r['qrd_per_s']:.1f},{r['warm_s']:.4f},"
              f"{r['cold_s']:.3f},{r['seq_depth']},{r['steps']},"
              f"{r['stages']},{r['hbm_passes_per_qrd']},{r['tile_b']},"
              f"{r.get('roofline_fraction', float('nan')):.2e}")

    # The wavefront acceptance point (ISSUE 2): batched 8x8 QRD with Q —
    # the sequential blocked path's trace unrolls all 28 steps, the
    # wavefront scans 13 stages.
    print(f"# blocked QRD engines (8x8): {hdr}")
    qrd8 = measured_qrd_rates(m=8, combos=(("blockfp_pallas", "col"),
                                           ("blockfp_pallas", "sameh_kuck")))
    for key, r in qrd8.items():
        print(f"{key},{r['qrd_per_s']:.1f},{r['warm_s']:.4f},"
              f"{r['cold_s']:.3f},{r['seq_depth']},{r['steps']},"
              f"{r['stages']},{r['hbm_passes_per_qrd']},{r['tile_b']},"
              f"{r.get('roofline_fraction', float('nan')):.2e}")
    speedup_8x8 = (qrd8["blockfp_pallas/col"]["end_to_end_s"]
                   / qrd8["blockfp_pallas/sameh_kuck"]["end_to_end_s"])
    print(f"# wavefront 8x8 end-to-end speedup vs sequential blocked: "
          f"{speedup_8x8:.1f}x")

    # Tiled routes (DESIGN.md §14): tune the panel width first so the
    # tiled:64x64 row below dispatches on the persisted winner, then
    # measure the two required production shapes.
    tuned_tiled = run_tiled_autotune_demo()
    print("# tiled autotune (64x64 panel): panel_n,warm_s")
    print(f"panel_n={tuned_tiled['panel_n']},{tuned_tiled['warm_s']:.4f}")
    print("# tiled QRD routes: key,qrd_per_s,warm_s,cold_s,tiling,tile_m,"
          "panel_n,roofline_fraction")
    tiled_rows = measured_tiled_qrd_rates()
    for key, r in tiled_rows.items():
        print(f"{key},{r['qrd_per_s']:.2f},{r['warm_s']:.4f},"
              f"{r['cold_s']:.2f},{r['tiling']},{r['tile_m']},"
              f"{r['panel_n']},"
              f"{r.get('roofline_fraction', float('nan')):.2e}")

    # Solve-path rows (DESIGN.md §9): the least-squares workload on the
    # registry-dispatched engine — triangularize [A | b], back-substitute.
    print("# solve paths (6x3 + rhs): backend/schedule,solve_per_s,"
          "end_to_end_s")
    solve = measured_solve_rates()
    for key, r in solve.items():
        print(f"{key},{r['solve_per_s']:.1f},{r['end_to_end_s']:.3f}")

    # Complex datapath rows (DESIGN.md §10): three-rotation QRD and the
    # MIMO-detection solve workload on the complex-capable backends.
    print("# complex QRD (4x4): backend/schedule,qrd_per_s,end_to_end_s,"
          "seq_depth")
    cqrd = measured_complex_qrd_rates(m=4)
    for key, r in cqrd.items():
        print(f"{key},{r['qrd_per_s']:.1f},{r['end_to_end_s']:.3f},"
              f"{r['seq_depth']}")
    print("# complex solve (6x3 + rhs): backend/schedule,solve_per_s,"
          "end_to_end_s")
    csolve = measured_complex_solve_rates()
    for key, r in csolve.items():
        print(f"{key},{r['solve_per_s']:.1f},{r['end_to_end_s']:.3f}")

    # Serving-fleet rows (DESIGN.md §12): donated-step updates/s at two
    # fleet sizes — flat across sizes means slots are capacity, not cost.
    print("# RLS fleet serving (float mode): slots,batch,updates_per_s,"
          "warm_s,cold_s")
    fleet_rows = measured_rls_fleet_rates()
    for key, r in fleet_rows.items():
        print(f"{key},{r['slots']},{r['batch']},{r['updates_per_s']:.1f},"
              f"{r['warm_s']:.4f},{r['cold_s']:.3f}")

    rate = measured_kernel_rate()
    tuned["tiled"] = tuned_tiled
    write_bench_json(qrd, qrd8, solve, speedup_8x8, rate,
                     complex_rows={**cqrd, **csolve}, autotune=tuned,
                     fleet_rows=fleet_rows, tiled_rows=tiled_rows)
    csv_row("table6_7_throughput", 1e6 / rate,
            f"model_speedup_vs_[32]={ours/gen:.1f}x;"
            f"pallas_interp_rot_per_s={rate:.0f};"
            f"qrd_loop_per_s={qrd['cordic/col']['qrd_per_s']:.1f};"
            f"qrd_blocked_per_s={qrd['cordic_pallas/col']['qrd_per_s']:.1f};"
            f"qrd_blockfp_per_s="
            f"{qrd['blockfp_pallas/col']['qrd_per_s']:.1f};"
            f"solve_jnp_per_s={solve['solve:jnp/col']['solve_per_s']:.1f};"
            f"complex_qrd_per_s={cqrd['complex:cordic/col']['qrd_per_s']:.1f};"
            f"wavefront_8x8_speedup={speedup_8x8:.1f}x;"
            f"tiled_64x64_per_s={tiled_rows['tiled:64x64']['qrd_per_s']:.1f};"
            f"tiled_4096x32_per_s="
            f"{tiled_rows['tiled:4096x32']['qrd_per_s']:.2f};"
            f"fleet_updates_per_s="
            f"{fleet_rows['fleet:131072x4 (b256)']['updates_per_s']:.0f}")


def write_bench_json(qrd4, qrd8, solve, speedup_8x8, rot_per_s,
                     complex_rows=None, autotune=None, fleet_rows=None,
                     tiled_rows=None, path=BENCH_JSON):
    """Emit the machine-readable perf trajectory (BENCH_qrd.json).

    Schema version 2: one record per (backend, schedule, m) row with
    warm/cold times split (``warm_s`` drives the rates and the CI gate;
    ``cold_s`` = trace + compile + first run, aliased as the v1
    ``end_to_end_s``), per-row ``interpret_mode`` / ``tile_b`` (the old
    top-level interpret flag is gone — rows can differ once a compiled
    backend exists), ``roofline_fraction`` for modeled rows, the
    ``autotune`` comparison section, and the ``tiled:{m}x{n}``
    production-shape rows (required by the regression gate).  These are the numbers future PRs
    diff against: `benchmarks.check_bench_regression` fails CI when any
    row's warm time regresses more than 2x vs the committed baseline,
    or a compiled row falls below the roofline floor.
    """
    doc = {
        "bench": "table6_7_throughput",
        "schema_version": SCHEMA_VERSION,
        "rotations_per_s": rot_per_s,
        "results": {**{f"{k} (4x4)": v for k, v in qrd4.items()},
                    **{f"{k} (8x8)": v for k, v in qrd8.items()},
                    **{f"{k} (6x3)": v for k, v in solve.items()},
                    **{f"{k} ({v['m']}x{v.get('n', v['m'])})": v
                       for k, v in (complex_rows or {}).items()},
                    **(fleet_rows or {}),
                    **(tiled_rows or {})},
        "wavefront_8x8_end_to_end_speedup": speedup_8x8,
    }
    if autotune is not None:
        doc["autotune"] = autotune
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
