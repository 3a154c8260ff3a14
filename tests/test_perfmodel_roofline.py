"""Analytic QRD roofline model + benchmark regression gate (DESIGN.md §11).

Pins the properties downstream tooling depends on:

* `qrd_cost` — monotone in shape and iteration depth, converter dataflow
  charged only on the packed path, HBM-pass contracts per backend;
* `roofline` / `roofline_fraction` — the bound is the slower of the two
  terms and fractions scale linearly with the measured rate;
* `roofline_for_row` — models exactly the real-datapath QRD rows of
  BENCH_qrd.json, picks the word representation from ``interpret_mode``,
  and declines solve/complex rows;
* `check_bench_regression.compare` — warm gate on schema-v2 documents,
  v1 cold fallback with a warning, missing-row failures, and the
  compiled-only roofline floor.
"""
import pytest

from benchmarks.check_bench_regression import compare
from repro.launch import perfmodel as pm
from repro.launch.roofline import analyze, roofline_for_row

SPEC = pm.DeviceSpec("test", peak_ops=1e11, hbm_bw=1e11)


# --------------------------------------------------------------------------
# qrd_cost
# --------------------------------------------------------------------------
def test_cost_monotone_in_shape_and_iters():
    c4 = pm.qrd_cost(4, 4)
    c8 = pm.qrd_cost(8, 8)
    assert c8.ops > c4.ops and c8.hbm_bytes > c4.hbm_bytes
    assert pm.qrd_cost(4, 4, iters=32).ops > pm.qrd_cost(4, 4, iters=16).ops
    assert pm.qrd_cost(4, 4, compute_q=True).ops > \
        pm.qrd_cost(4, 4, compute_q=False).ops


def test_packed_path_charges_converters_and_word_factor():
    blockfp = pm.qrd_cost(4, 4, backend="blockfp_pallas")
    packed = pm.qrd_cost(4, 4, backend="cordic_pallas")          # int64
    lanes = pm.qrd_cost(4, 4, backend="cordic_pallas", word="lanes")
    # Converter dataflow + 64-bit emulation make packed strictly costlier,
    # and the dual-int32 lane split costlier still (3.5x vs 2x factor).
    assert packed.ops > blockfp.ops
    assert lanes.ops == pytest.approx(packed.ops * 3.5 / 2.0)
    # int64 words move twice the bytes of int32 significands.
    assert packed.hbm_bytes > blockfp.hbm_bytes


def test_hbm_pass_contracts():
    # Kernel-resident: HBM_PASSES_PER_QRD passes; host loop: 2 per step.
    from repro.kernels.qrd_blocked import HBM_PASSES_PER_QRD
    m = n = 4
    e = n + m
    resident = pm.qrd_cost(m, n, backend="cordic_pallas")
    host = pm.qrd_cost(m, n, backend="cordic")
    encode = 2.0 * m * e * 8
    assert resident.hbm_bytes == HBM_PASSES_PER_QRD * m * e * 8 + encode
    rotations = sum(m - 1 - c for c in range(m - 1))
    assert host.hbm_bytes == 2.0 * rotations * m * e * 8 + encode
    assert host.hbm_bytes > resident.hbm_bytes


def test_active_elements_matches_bruteforce():
    from repro.core.qrd import givens_schedule
    m, n = 6, 4
    e = n + m
    want = sum(2 * (e - col) for _, _, col in givens_schedule(m, n))
    assert pm._active_elements(m, n, e) == want


# --------------------------------------------------------------------------
# roofline / fractions / device specs
# --------------------------------------------------------------------------
def test_roofline_bound_is_slower_term():
    pt = pm.roofline(pm.QRDCost(ops=1e6, hbm_bytes=1e3), SPEC)
    assert pt.dominant == "compute"
    assert pt.bound_s == pt.t_compute
    assert pt.bound_qrd_per_s == pytest.approx(1e11 / 1e6)
    pt = pm.roofline(pm.QRDCost(ops=1e3, hbm_bytes=1e6), SPEC)
    assert pt.dominant == "memory"
    assert pt.bound_s == pt.t_memory


def test_fraction_linear_in_rate():
    cost = pm.qrd_cost(4, 4)
    bound = pm.roofline(cost, SPEC).bound_qrd_per_s
    assert pm.roofline_fraction(bound, cost, SPEC) == pytest.approx(1.0)
    assert pm.roofline_fraction(bound / 10, cost, SPEC) == \
        pytest.approx(0.1)


def test_device_spec_prefix_match_and_fallback():
    assert pm.device_spec("TPU v5 lite").name == "tpu v5 lite"
    assert pm.device_spec("cpu").name == "cpu"
    with pytest.raises(KeyError, match="warp drive"):
        pm.device_spec("warp drive")


# --------------------------------------------------------------------------
# roofline_for_row
# --------------------------------------------------------------------------
def _row(**kw):
    base = {"backend": "blockfp_pallas", "schedule": "sameh_kuck",
            "m": 4, "n": 4, "qrd_per_s": 1e5, "iters": 24,
            "hbm_passes_per_qrd": 2, "interpret_mode": True}
    base.update(kw)
    return base


def test_row_modeled():
    terms = roofline_for_row(_row(), SPEC)
    assert terms is not None
    assert 0 < terms["roofline_fraction"] < 1
    assert terms["device"] == "test"
    assert terms["dominant"] in ("compute", "memory")


def test_row_word_follows_interpret_mode():
    # Packed rows: interpret (or host loop, interpret_mode None) costs
    # int64 emulation; only an explicitly compiled row costs the lane
    # split — a *higher* bound denominator means a lower fraction.
    fi = roofline_for_row(_row(backend="cordic_pallas"),
                          SPEC)["roofline_fraction"]
    fn = roofline_for_row(_row(backend="cordic", interpret_mode=None,
                               hbm_passes_per_qrd=None),
                          SPEC)["roofline_fraction"]
    fc = roofline_for_row(_row(backend="cordic_pallas",
                               interpret_mode=False),
                          SPEC)["roofline_fraction"]
    assert fi != fc and fn > 0
    lanes_cost = pm.qrd_cost(4, 4, backend="cordic_pallas", word="lanes",
                             hbm_passes=2)
    assert fc == pytest.approx(pm.roofline_fraction(1e5, lanes_cost, SPEC))


def test_row_declines_unmodeled():
    assert roofline_for_row(_row(backend="jnp"), SPEC) is None
    assert roofline_for_row(_row(backend="solve:jnp"), SPEC) is None
    assert roofline_for_row(_row(dtype="complex128"), SPEC) is None
    assert roofline_for_row(_row(qrd_per_s=None), SPEC) is None


def test_analyze_covers_modeled_rows_only():
    doc = {"results": {"a": _row(), "b": _row(backend="solve:jnp"),
                       "c": _row(backend="cordic", interpret_mode=None,
                                 hbm_passes_per_qrd=None)}}
    rows = analyze(doc, SPEC)
    assert [r["key"] for r in rows] == ["a", "c"]


# --------------------------------------------------------------------------
# check_bench_regression.compare
# --------------------------------------------------------------------------
def _doc(rows, version=2):
    return {"schema_version": version, "results": rows}


def test_checker_warm_gate():
    base = _doc({"x": {"warm_s": 0.01, "cold_s": 1.0}})
    ok = _doc({"x": {"warm_s": 0.015, "cold_s": 5.0}})   # cold ignored
    bad = _doc({"x": {"warm_s": 0.03, "cold_s": 1.0}})
    fails, _ = compare(base, ok, factor=2.0)
    assert not fails
    fails, _ = compare(base, bad, factor=2.0)
    assert len(fails) == 1 and "warm" in fails[0]


def test_checker_missing_row_fails_new_row_passes():
    base = _doc({"x": {"warm_s": 0.01}})
    fresh = _doc({"y": {"warm_s": 0.01}})
    fails, lines = compare(base, fresh, factor=2.0)
    assert any("missing" in f for f in fails)
    assert any(line.startswith("new  y") for line in lines)


def test_checker_v1_fallback_warns_and_gates_cold():
    base = _doc({"x": {"end_to_end_s": 1.0}}, version=1)
    fresh = _doc({"x": {"end_to_end_s": 3.0}}, version=1)
    fails, lines = compare(base, fresh, factor=2.0)
    assert any("schema v1" in line for line in lines)
    assert len(fails) == 1 and "cold" in fails[0]


def test_checker_roofline_gate_compiled_rows_only():
    base = _doc({"x": {"warm_s": 0.01}})
    interp = _doc({"x": {"warm_s": 0.01, "interpret_mode": True,
                         "roofline_fraction": 1e-6}})
    fails, _ = compare(base, interp, factor=2.0, min_roofline=0.02)
    assert not fails                       # interpret rows exempt
    compiled = _doc({"x": {"warm_s": 0.01, "interpret_mode": False,
                           "roofline_fraction": 1e-6}})
    fails, _ = compare(base, compiled, factor=2.0, min_roofline=0.02)
    assert len(fails) == 1 and "roofline" in fails[0]
    fast = _doc({"x": {"warm_s": 0.01, "interpret_mode": False,
                       "roofline_fraction": 0.5}})
    fails, _ = compare(base, fast, factor=2.0, min_roofline=0.02)
    assert not fails
