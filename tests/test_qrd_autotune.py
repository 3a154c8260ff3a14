"""Autotuner contracts: candidate model, persistence, engine linkage.

`repro.kernels.autotune` (DESIGN.md §11) searches (tile_b,
table_layout) per problem shape and persists winners in a JSON cache
keyed by device kind; `QRDEngine` consults it at dispatch time when the
config leaves ``tile_b=None``.  These tests pin:

* the VMEM-budget candidate model (power-of-two tiles, batch cap,
  never-empty);
* `tune` with an injected deterministic timer — writes the winner,
  `lookup` round-trips it, candidates recorded;
* the engine picks the tuned tile up transparently (inspected through
  its dispatch cache) and numerics are unchanged;
* an explicit ``tile_b`` in the config always beats the cache.

All timing is faked, so the suite is fast and deterministic.
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.qrd_blocked import TILE_B
from repro.qrd import QRDConfig, QRDEngine


@pytest.fixture
def cache(tmp_path, monkeypatch):
    """Point the autotune cache at a fresh per-test file."""
    path = str(tmp_path / "qrd_autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.clear_memo()
    yield path
    autotune.clear_memo()


# --------------------------------------------------------------------------
# Candidate model
# --------------------------------------------------------------------------
def test_candidates_are_powers_of_two_capped_by_batch():
    cands = autotune.candidate_tile_bs(24, 4, 8, 8,
                                       vmem_budget=1 << 30)
    assert cands == (1, 2, 4, 8, 16)          # <= batch, powers of two
    assert autotune.candidate_tile_bs(256, 4, 8, 8, vmem_budget=1 << 30,
                                      batch_minor=False)[-1] == 64
    # 256 4x4 matrices with Q run batch-minor: whole lane rows
    assert autotune.candidate_tile_bs(256, 4, 8, 8,
                                      vmem_budget=1 << 30) == (128, 256)


def test_candidates_respect_vmem_budget():
    # 6 buffers * tile_b * 4*8 elements * 8 B = 1536 B per tile unit:
    # a 8 KiB budget admits tile_b in {1, 2, 4} but not 8.
    cands = autotune.candidate_tile_bs(64, 4, 8, 8, vmem_budget=8192)
    assert cands == (1, 2, 4)


def test_candidates_never_empty():
    # Budget too small even for tile_b=1: the smallest tile survives.
    assert autotune.candidate_tile_bs(64, 32, 64, 8,
                                      vmem_budget=16) == (1,)


def test_candidate_layouts():
    assert autotune.candidate_layouts("sameh_kuck") == ("split", "stacked")
    assert autotune.candidate_layouts("col") == (None,)


# --------------------------------------------------------------------------
# tune() + lookup() with an injected timer
# --------------------------------------------------------------------------
def test_tune_persists_winner_and_lookup_roundtrips(cache):
    calls = []

    def timer(fn, A, warm_reps):
        out = fn(A)                     # real dispatch, fake clock
        assert out[-1].shape == (6, 4, 4)
        calls.append(warm_reps)
        # Favor tile_b=2 with the stacked layout deterministically.
        return len(calls) * 1e-3 if calls else 1e-3

    # Monotone clock makes the *first* candidate the winner: tile 1/split.
    entry = autotune.tune("blockfp_pallas", "sameh_kuck", 4, 4, 6,
                          dtype="float64", warm_reps=2, timer=timer,
                          vmem_budget=1 << 30)
    assert entry.tile_b == 1 and entry.table_layout == "split"
    # 3 tiles (1, 2, 4) x 2 layouts timed.
    assert len(calls) == 6 and set(calls) == {2}
    assert len(entry.candidates) == 6

    hit = autotune.lookup("blockfp_pallas", "sameh_kuck", 4, 4, "float64")
    assert hit is not None
    assert (hit.tile_b, hit.table_layout) == (1, "split")

    # The file is keyed by device kind and carries the schema version.
    doc = json.load(open(cache))
    assert doc["schema_version"] == 1
    key = autotune.cache_key("blockfp_pallas", "sameh_kuck", 4, 4,
                             "float64")
    assert key in doc[autotune.device_kind()]


def test_lookup_misses_cleanly(cache):
    assert autotune.lookup("blockfp_pallas", "col", 9, 9, "float64") is None


def test_tune_rejects_untunable_backend(cache):
    with pytest.raises(ValueError, match="not tunable"):
        autotune.tune("jnp", "col", 4, 4, 6)


# --------------------------------------------------------------------------
# Engine linkage
# --------------------------------------------------------------------------
def _dispatch_config(eng):
    """The resolved QRDConfig of the engine's sole cached dispatch."""
    (key,) = eng._fn_cache.keys()
    return key[3][0]


def _tuned_entry(cache, tile_b, layout):
    """Fake-time a tune() so (tile_b, layout) wins and lands on disk."""
    def timer(fn, A, warm_reps):
        fn(A)
        cfg = timer.configs.pop(0)
        return 1e-3 if cfg == (tile_b, layout) else 2e-3

    tiles = autotune.candidate_tile_bs(6, 4, 8, 4, vmem_budget=1 << 30)
    timer.configs = [(tb, lay) for tb in tiles
                     for lay in ("split", "stacked")]
    return autotune.tune("blockfp_pallas", "sameh_kuck", 4, 4, 6,
                         dtype="float64", warm_reps=1, timer=timer,
                         vmem_budget=1 << 30)


def test_engine_consults_cache(cache):
    entry = _tuned_entry(cache, 2, "stacked")
    assert (entry.tile_b, entry.table_layout) == (2, "stacked")

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((6, 4, 4)))

    tuned = QRDEngine(QRDConfig(backend="blockfp_pallas",
                                schedule="sameh_kuck", dtype="float64"))
    Qt, Rt = tuned(A)
    cfg = _dispatch_config(tuned)
    assert cfg.tile_b == 2 and cfg.table_layout == "stacked"

    # Numerics are invariant under the tuned tile.
    fixed = QRDEngine(QRDConfig(backend="blockfp_pallas",
                                schedule="sameh_kuck", dtype="float64",
                                tile_b=TILE_B))
    Qf, Rf = fixed(A)
    assert bool(jnp.all(Qt == Qf)) and bool(jnp.all(Rt == Rf))


def test_explicit_tile_b_beats_cache(cache):
    _tuned_entry(cache, 4, "split")
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((6, 4, 4)))
    eng = QRDEngine(QRDConfig(backend="blockfp_pallas",
                              schedule="sameh_kuck", dtype="float64",
                              tile_b=2, table_layout="stacked"))
    eng(A)
    cfg = _dispatch_config(eng)
    assert cfg.tile_b == 2 and cfg.table_layout == "stacked"


def test_untuned_backend_ignores_cache(cache):
    _tuned_entry(cache, 2, "stacked")
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.standard_normal((6, 4, 4)))
    eng = QRDEngine(QRDConfig(backend="jnp", dtype="float64"))
    eng(A)
    cfg = _dispatch_config(eng)
    assert cfg.tile_b is None


def test_config_validation():
    # validate() runs at engine construction, not dataclass __init__.
    with pytest.raises(ValueError, match="table_layout"):
        QRDEngine(QRDConfig(backend="blockfp_pallas",
                            table_layout="diagonal"))
    with pytest.raises(ValueError, match="tile_b"):
        QRDEngine(QRDConfig(backend="blockfp_pallas", tile_b=0))
