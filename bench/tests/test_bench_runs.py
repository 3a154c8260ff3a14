"""Whole runs of each kind of cell on the CPU, at tiny sizes.

Each run skips the harness's look for a chip and drives the rest:
set-up, window, answers, reference.  A sound run comes out correct; the
configuration's lower-precision control, and the program broken under
the timed path in each way the cell can break, come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness
from bench.tests import tiny

SEED = 2**31 + 12345


@pytest.fixture
def bench(tmp_path):
    """A tiny benchmark tree; JAX's cache settings restored afterwards."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    root = tiny.bench_root(tmp_path)
    yield lambda wl, **kw: harness.run(
        tmp_path / "BENCHMARK.json", wl, SEED, 0.5, False,
        require_chip=False, bench=root, log=lambda s: None, **kw)
    for k, v in saved.items():
        jax.config.update(k, v)


CELLS = ["mimo.slot", "mimo.prb"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(bench, workload):
    r = bench(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert "setup_s" in r["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_lower_precision_control_is_not_correct(bench, workload):
    r = bench(workload, control=True)
    assert not r["correct"], r["checks"]


def _engine_fault(monkeypatch, fault):
    from repro.qrd.engine import QRDEngine
    orig = QRDEngine.__call__

    def call(self, A, compute_q=True):
        B = A.shape[0]
        if fault == "unchanged":
            eye = jnp.broadcast_to(jnp.eye(A.shape[-2]), A.shape)
            return eye, A
        if fault == "half_batch":
            Q, R = orig(self, A[:B // 2], compute_q)
            pad = B - B // 2
            return (jnp.concatenate([Q, Q[:pad]]),
                    jnp.concatenate([R, R[:pad]]))
        Q, R = orig(self, A, compute_q)
        return Q, R.at[0, 0, 0].add(0.05 * jnp.max(jnp.abs(A[0])))

    monkeypatch.setattr(QRDEngine, "__call__", call)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_qrd_faults_are_not_correct(bench, monkeypatch, fault, workload):
    _engine_fault(monkeypatch, fault)
    r = bench(workload)
    assert not r["correct"], r["checks"]


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mimo.slot",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(tiny.ROOT, env)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert "{" not in p.stdout
    json.loads((tmp_path / "BENCHMARK.json").read_text())
