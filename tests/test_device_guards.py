"""What may not reach a compiler, and where compiled runs keep their cache.

The int64 kernels are interpret-only and the TPU compiler aborts on
complex128 arithmetic: these paths must raise ``NotImplementedError``
before anything is compiled.  ``interpret=False`` on this CPU host stands
in for a compiled backend; the TPU backend is faked for the complex128
guard.
"""
import jax
import numpy as np
import pytest

from repro.core import qrd as q
from repro.core.givens import GivensConfig, GivensUnit
from repro.kernels import ops
from repro.qrd import QRDEngine
from repro.qrd.rls import RLSState
from repro.serve import RLSFleet

CFG = GivensConfig()


@pytest.mark.parametrize("overrides", [
    dict(dtype="complex128"),
    dict(tiling="panel"),
    dict(tiling="tsqr"),
], ids=["complex", "panel", "tsqr"])
def test_engine_refuses_interpret_only_cordic_pallas(overrides):
    with pytest.raises(NotImplementedError, match="no compiled"):
        QRDEngine(backend="cordic_pallas", interpret=False, **overrides)


def test_auto_tiling_refuses_at_dispatch():
    eng = QRDEngine(backend="cordic_pallas", interpret=False)
    with pytest.raises(NotImplementedError, match="tiling"):
        eng(np.ones((2, 64, 8)))


def test_blockfp_tiled_routes_are_not_refused():
    eng = QRDEngine(backend="blockfp_pallas", interpret=False, tiling="panel")
    assert eng.capabilities.interpret_only == ()


@pytest.mark.parametrize("call", ["int64", "complex", "panel"])
def test_int64_kernels_never_compile(call):
    P = GivensUnit(CFG).encode(q._augment(np.ones((2, 4, 4)), True))
    steps = q.givens_schedule(4, 4)
    with pytest.raises(NotImplementedError):
        if call == "int64":
            ops.qr_packed(P, cfg=CFG, steps=steps, lanes=False,
                          interpret=False)
        elif call == "complex":
            ops.qr_packed_complex(np.stack([P, P], -1), cfg=CFG, steps=steps,
                                  interpret=False)
        else:
            ops.qr_packed_panel(P, cfg=CFG, n_cols=4, interpret=False)


@pytest.mark.parametrize("build", ["engine", "rls", "fleet"])
def test_complex128_refused_on_tpu(monkeypatch, build):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="complex128"):
        if build == "engine":
            QRDEngine(backend="jnp", dtype="complex128")
        elif build == "rls":
            RLSState(4, dtype="complex128")
        else:
            RLSFleet(8, 4, mode="float", dtype="complex128")


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch import cache
    seen = []
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda key, value: seen.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = str(cache.CHECKOUT / ".jax_cache")
    assert cache.enable_compile_cache() == fixed
    assert seen == [("jax_compilation_cache_dir", str(tmp_path)),
                    ("jax_compilation_cache_dir", fixed)]
    with open(cache.CHECKOUT / ".gitignore") as f:
        assert ".jax_cache/" in f.read().split()


def test_serve_mesh_needs_enough_devices():
    from repro.launch.serve import device_mesh
    assert device_mesh(1).shape == {"data": 1, "model": 1}
    with pytest.raises(SystemExit, match="device"):
        device_mesh(len(jax.devices()) + 1)
