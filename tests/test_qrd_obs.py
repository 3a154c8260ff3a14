"""What `QRDEngine` shows an operator: counters, and the spans and program
names a profiler trace holds (`repro.obs`)."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import qrd as api

RNG = np.random.default_rng(13)


def test_stats_count_calls_matrices_builds_and_evictions():
    eng = api.QRDEngine(backend="jnp", max_cache=2)
    assert eng.stats() == {"calls": 0, "matrices": 0,
                           "padded_matrices": 0, "builds": 0,
                           "evictions": 0}
    eng(RNG.normal(size=(3, 4, 4)))
    eng(RNG.normal(size=(3, 4, 4)))              # same shape: LRU hit
    assert eng.stats() == {"calls": 2, "matrices": 6,
                           "padded_matrices": 0, "builds": 1,
                           "evictions": 0}
    eng(RNG.normal(size=(2, 5, 4, 2)))           # new shape, batch 2 x 5
    eng(RNG.normal(size=(4, 3)))                 # one matrix: evicts 4x4
    assert eng.stats() == {"calls": 4, "matrices": 17,
                           "padded_matrices": 0, "builds": 3,
                           "evictions": 1}
    eng.solve(RNG.normal(size=(2, 6, 3)), RNG.normal(size=(2, 6)))
    assert eng.stats() == {"calls": 5, "matrices": 19,
                           "padded_matrices": 0, "builds": 4,
                           "evictions": 2}
    assert len(eng._fn_cache) == 2


def test_lower_builds_without_counting_a_call():
    eng = api.QRDEngine(backend="jnp")
    eng.lower(RNG.normal(size=(2, 4, 4)))
    eng(RNG.normal(size=(2, 4, 4)))              # the program lower() built
    assert eng.stats() == {"calls": 1, "matrices": 2,
                           "padded_matrices": 0, "builds": 1,
                           "evictions": 0}


def _host_events(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for line in host.lines for ev in line.events
            if ev.name.startswith("repro.")]


def test_trace_holds_nested_call_spans_with_args(tmp_path):
    eng = api.QRDEngine(backend="jnp")
    A = RNG.normal(size=(3, 5, 4))

    def two_calls():
        for _ in range(2):
            jax.block_until_ready(eng(A))
    events = sorted(_host_events(tmp_path, two_calls), key=lambda e: e[1])
    calls = [e for e in events if e[0] == "repro.qrd.call"]
    assert len(calls) == 2
    for i, (_, s, e, stats) in enumerate(calls):
        assert stats == {"call": i + 1, "m": 5, "n": 4, "batch": 3,
                         "backend": "jnp"}
        inner = [ev[0] for ev in events
                 if ev[0] != "repro.qrd.call" and s <= ev[1] and ev[2] <= e]
        # the first call builds (traces and compiles), the second launches
        assert inner == ["repro.qrd.prepare",
                         "repro.qrd.build" if i == 0 else "repro.qrd.launch"]


@pytest.mark.parametrize("backend,route,name", [
    ("jnp", None, "jit_qrd_jnp"),
    ("blockfp_pallas", None, "jit_qrd_blockfp_pallas"),
    ("blockfp_pallas", "panel", "jit_qrd_blockfp_pallas_panel"),
])
def test_program_carries_a_stable_name(backend, route, name):
    kw = {} if route is None else {"tiling": route, "panel_n": 4}
    eng = api.QRDEngine(backend=backend, dtype="float64", **kw)
    text = eng.lower(RNG.normal(size=(2, 6, 4))).as_text()
    assert f"module @{name} " in text


def test_blockfp_codec_ops_carry_their_scopes():
    eng = api.QRDEngine(backend="blockfp_pallas", dtype="float64")
    text = eng.lower(RNG.normal(size=(2, 4, 4))).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    # the exponent and the rounding belong to encode, the rescale to decode
    assert {"encode/reduce_max", "encode/jit(rint)",
            "decode/exp2"} <= names
    assert any(n.startswith("givens_qr_blockfp/") for n in names)
