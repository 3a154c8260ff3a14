"""Per-shape autotuning for the blocked QRD Pallas kernels (DESIGN.md §11).

The blocked kernels used to run with one hardcoded batch tile
(``qrd_blocked.TILE_B = 8``) and one stage-table layout, whatever the
problem shape or device.  This module searches the small discrete space
that actually matters for these kernels —

* ``tile_b``  — how many matrices ride in one kernel instance's VMEM
  block (powers of two up to the batch, capped by the kernels' VMEM
  budget model, `qrd_blocked.tile_vmem_bytes`; whole lane rows of 128
  matrices where the shape runs batch-minor);
* ``table_layout`` — ``'split'`` (three (S, Pmax) stage-table operands)
  vs ``'stacked'`` (one concatenated (3S, Pmax) operand) for the
  wavefront kernels

— by timing real engine dispatches, and persists the winners in a JSON
cache keyed by **device kind** so results survive processes but never
leak across hardware.  `repro.qrd.QRDEngine` consults `lookup` at
dispatch time whenever the config leaves ``tile_b=None``; `tune` is the
explicit (and benchmark-suite) entry point that fills the cache.

Cache file: ``$REPRO_AUTOTUNE_CACHE`` or
``~/.cache/repro/qrd_autotune.json``.  Schema::

    {"schema_version": 1,
     "<device kind>": {
        "<backend>/<schedule>/m4/n4/float64": {
            "tile_b": 16, "table_layout": "split",
            "warm_s": 1.2e-3,
            "candidates": [{"tile_b": 8, ...,  "warm_s": ...}, ...]}}}

Lookups are mtime-memoized: the file is re-read only when it changed on
disk, so the per-dispatch cost is one ``os.stat``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from . import qrd_blocked as qb

__all__ = ["TuneEntry", "TUNABLE_BACKENDS", "cache_path", "device_kind",
           "cache_key", "lookup", "tune", "tune_tiled",
           "candidate_tile_bs", "candidate_layouts", "candidate_panel_ns",
           "candidate_tile_ms", "clear_memo"]

TUNABLE_BACKENDS = ("cordic_pallas", "blockfp_pallas")

_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TuneEntry:
    """One persisted winner: the parameters `lookup` hands the engine.

    ``panel_n`` / ``tile_m`` are the tiled-route knobs (`tune_tiled`);
    flat entries leave them None and serialize without them, so cache
    files written before the tiled routes existed still load.
    """

    tile_b: int
    table_layout: str | None
    warm_s: float
    candidates: tuple = ()
    panel_n: int | None = None
    tile_m: int | None = None

    def to_json(self):
        d = {"tile_b": self.tile_b, "table_layout": self.table_layout,
             "warm_s": self.warm_s, "candidates": list(self.candidates)}
        if self.panel_n is not None:
            d["panel_n"] = self.panel_n
        if self.tile_m is not None:
            d["tile_m"] = self.tile_m
        return d

    @classmethod
    def from_json(cls, d):
        pn, tm = d.get("panel_n"), d.get("tile_m")
        return cls(tile_b=int(d["tile_b"]),
                   table_layout=d.get("table_layout"),
                   warm_s=float(d.get("warm_s", 0.0)),
                   candidates=tuple(d.get("candidates", ())),
                   panel_n=None if pn is None else int(pn),
                   tile_m=None if tm is None else int(tm))


# --------------------------------------------------------------------------
# Cache file plumbing
# --------------------------------------------------------------------------
def cache_path() -> str:
    """Resolve the cache file path (env override, else ~/.cache)."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "qrd_autotune.json")


def device_kind() -> str:
    """The accelerator identity the cache is keyed by (e.g. 'cpu',
    'TPU v5 lite') — tuned tiles must never leak across hardware."""
    import jax
    return jax.devices()[0].device_kind


def cache_key(backend: str, schedule: str, m: int, n: int,
              dtype: str, tiling: str | None = None) -> str:
    """Cache key; tiled-route entries get a ``/tiled-<route>`` suffix so
    they never collide with (or shadow) a flat entry at the same shape."""
    key = f"{backend}/{schedule}/m{m}/n{n}/{dtype}"
    return key if tiling is None else f"{key}/tiled-{tiling}"


# path -> (mtime_ns, parsed doc); lookup() re-reads only on mtime change
_MEMO: dict = {}


def clear_memo():
    """Drop the mtime memo (tests that swap cache files under one path)."""
    _MEMO.clear()


def _load(path: str):
    try:
        stat = os.stat(path)
    except OSError:
        _MEMO.pop(path, None)
        return None
    hit = _MEMO.get(path)
    if hit is not None and hit[0] == stat.st_mtime_ns:
        return hit[1]
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    _MEMO[path] = (stat.st_mtime_ns, doc)
    return doc


def _store(path: str, device: str, key: str, entry: TuneEntry):
    doc = _load(path) or {"schema_version": _SCHEMA_VERSION}
    doc.setdefault(device, {})[key] = entry.to_json()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    _MEMO.pop(path, None)   # force a re-read (mtime granularity)


def lookup(backend: str, schedule: str, m: int, n: int, dtype: str,
           path: str | None = None,
           tiling: str | None = None) -> TuneEntry | None:
    """Cache-only lookup (never tunes): the engine's dispatch-time hook.

    Returns the persisted `TuneEntry` for this (device kind, backend,
    schedule, m, n, dtype[, tiled route]) or None on a miss.  Cost on
    the hot path is one ``os.stat`` (the parsed file is memoized by
    mtime).
    """
    doc = _load(path or cache_path())
    if not doc:
        return None
    per_dev = doc.get(device_kind())
    if not per_dev:
        return None
    raw = per_dev.get(cache_key(backend, schedule, m, n, dtype, tiling))
    if raw is None:
        return None
    try:
        return TuneEntry.from_json(raw)
    except (KeyError, TypeError, ValueError):
        return None


# --------------------------------------------------------------------------
# Candidate generation
# --------------------------------------------------------------------------
def candidate_tile_bs(batch: int, m: int, e: int, itemsize: int,
                      vmem_budget: int | None = None,
                      batch_minor: bool | None = None) -> tuple:
    """Batch tiles that fit the VMEM budget model.

    The model (`qrd_blocked.tile_vmem_bytes`) charges
    ``qrd_blocked.VMEM_BUFFERS`` resident copies of the (tile_b, m, e)
    working block at ``itemsize`` bytes per element against the budget
    (``$REPRO_TILE_VMEM_BUDGET`` or ``qrd_blocked.DEFAULT_VMEM_BUDGET``).
    Rows-first, candidates are powers of two capped at
    ``min(batch, 64)``; batch-minor (``batch_minor=None`` asks the
    kernels' rule, `qrd_blocked.batch_minor`), whole lane rows — 128
    times a power of two, up to the batch rounded up to a lane row.
    The smallest candidate always survives so the search space is never
    empty.
    """
    if vmem_budget is None:
        vmem_budget = int(os.environ.get("REPRO_TILE_VMEM_BUDGET",
                                         qb.DEFAULT_VMEM_BUDGET))
    if batch_minor is None:
        batch_minor = qb.batch_minor(m, e, batch, itemsize)
    unit = qb.LANES if batch_minor else 1
    cap = (-(-int(batch) // unit) * unit if batch_minor
           else max(1, min(int(batch), 64)))
    cands = []
    tb = unit
    while tb <= cap:
        cands.append(tb)
        tb *= 2
    fit = [tb for tb in cands
           if qb.tile_vmem_bytes(m, e, itemsize, tb) <= vmem_budget]
    return tuple(fit) if fit else (cands[0],)


def candidate_layouts(schedule: str) -> tuple:
    """Stage-table layouts worth timing: only the wavefront path has
    stage tables at all."""
    return ("split", "stacked") if schedule == "sameh_kuck" else (None,)


def candidate_panel_ns(n: int) -> tuple:
    """Panel widths worth timing for the tiled routes.

    Powers of two in the lane-friendly range, capped at the column
    count — a panel wider than n degenerates to the flat schedule with
    padding.  Never empty: a narrow problem tunes at its own width.
    """
    cands = tuple(w for w in (4, 8, 16) if w <= n)
    return cands if cands else (max(1, n),)


def candidate_tile_ms(m: int, n: int, max_m: int = 128) -> tuple:
    """Leaf heights worth timing for the tsqr route.

    Powers of two up to the backend's row capacity ``max_m``, strictly
    below m (a single leaf is just the panel route) and at least ``2n``
    (shorter leaves do less annihilation per launch than the tree nodes
    they feed).  Never empty: the row capacity itself always survives.
    """
    cands = tuple(t for t in (32, 64, 128)
                  if t <= max_m and t < m and t >= 2 * n)
    return cands if cands else (min(max_m, max(2, m - 1)),)


# --------------------------------------------------------------------------
# The tuner
# --------------------------------------------------------------------------
def _default_timer(fn, A, warm_reps: int):
    """Cold call (trace+compile, discarded), then median of warm reps."""
    import jax
    jax.block_until_ready(fn(A))
    times = []
    for _ in range(warm_reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(A))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def tune(backend: str, schedule: str, m: int, n: int, batch: int, *,
         dtype: str = "float64", givens=None, compute_q: bool = True,
         path: str | None = None, warm_reps: int = 3, timer=None,
         vmem_budget: int | None = None, seed: int = 0) -> TuneEntry:
    """Search (tile_b, table_layout) for one problem shape and persist.

    Builds one `repro.qrd.QRDEngine` per candidate (explicit ``tile_b``
    / ``table_layout`` in its config, so nothing consults the cache
    being filled), times each with a cold call discarded and the median
    of ``warm_reps`` warm ``block_until_ready`` reps, writes the winner
    into the cache file, and returns its `TuneEntry` (with the full
    candidate table attached for the benchmark report).

    Parameters
    ----------
    backend, schedule, m, n, dtype : the cache key coordinates.
    batch : int
        Batch size to tune at — tile candidates never exceed it.
    givens : GivensConfig, optional
        Unit parameters for the engine configs.
    timer : callable, optional
        ``timer(fn, A, warm_reps) -> seconds`` override (tests inject a
        deterministic fake; the default runs real wall-clock timing).
    """
    from repro.qrd import QRDConfig, QRDEngine

    if backend not in TUNABLE_BACKENDS:
        raise ValueError(f"backend {backend!r} is not tunable; "
                         f"expected one of {TUNABLE_BACKENDS}")
    if timer is None:
        timer = _default_timer

    # Working-element size of the kernel-resident block: the packed
    # cordic word is 8 bytes (int64, or the dual-int32 lane pair); the
    # block-FP path holds int32 significands.  Interpreted, the cordic
    # words are int64 and run rows-first.
    from .ops import auto_interpret
    itemsize = 8 if backend == "cordic_pallas" else 4
    e = n + (m if compute_q else 0)
    rows_first = backend == "cordic_pallas" and auto_interpret(None)
    tiles = candidate_tile_bs(batch, m, e, itemsize, vmem_budget,
                              batch_minor=False if rows_first else None)
    layouts = candidate_layouts(schedule)

    kwargs = {} if givens is None else {"givens": givens}
    rng = np.random.default_rng(seed)
    A = np.asarray(rng.standard_normal((batch, m, n)), dtype=np.float64)

    rows = []
    for tb in tiles:
        for layout in layouts:
            cfg = QRDConfig(backend=backend, schedule=schedule, dtype=dtype,
                            tile_b=tb, table_layout=layout, **kwargs)
            eng = QRDEngine(cfg)
            warm = float(timer(lambda X: eng(X, compute_q=compute_q), A,
                               warm_reps))
            rows.append({"tile_b": tb, "table_layout": layout,
                         "warm_s": warm})

    best = min(rows, key=lambda r: r["warm_s"])
    entry = TuneEntry(tile_b=best["tile_b"],
                      table_layout=best["table_layout"],
                      warm_s=best["warm_s"], candidates=tuple(rows))
    _store(path or cache_path(), device_kind(),
           cache_key(backend, schedule, m, n, dtype), entry)
    return entry


def tune_tiled(backend: str, m: int, n: int, batch: int, *, tiling: str,
               dtype: str = "float64", givens=None, compute_q: bool = True,
               path: str | None = None, warm_reps: int = 3, timer=None,
               max_tile_m: int = 128, seed: int = 0,
               panel_ns: tuple | None = None,
               tile_ms: tuple | None = None) -> TuneEntry:
    """Search the tiled-route knobs for one problem shape and persist.

    ``tiling='panel'`` searches ``panel_n`` (`candidate_panel_ns`);
    ``tiling='tsqr'`` searches ``tile_m x panel_n``
    (`candidate_tile_ms`).  Each candidate is timed through a real
    `repro.qrd.QRDEngine` with the route and knobs pinned explicitly —
    nothing consults the cache being filled, and an explicit
    ``panel_n`` / ``tile_m`` in a user's `QRDConfig` always wins over
    the stored entry at dispatch (the engine only fills fields left
    None).  The winner is stored under the ``/tiled-<route>`` cache key
    and returned with the full candidate table for the benchmark
    report's autotune section.  ``panel_ns`` / ``tile_ms`` override the
    candidate generators — large shapes pay a full trace+compile per
    candidate, so cost-sensitive callers (the CI bench) narrow the
    sweep explicitly.
    """
    from repro.qrd import QRDConfig, QRDEngine

    if backend not in TUNABLE_BACKENDS:
        raise ValueError(f"backend {backend!r} is not tunable; "
                         f"expected one of {TUNABLE_BACKENDS}")
    if tiling not in ("panel", "tsqr"):
        raise ValueError(f"tiling {tiling!r} is not tunable; "
                         "expected 'panel' or 'tsqr'")
    if timer is None:
        timer = _default_timer

    if panel_ns is None:
        panel_ns = candidate_panel_ns(n)
    if tile_ms is None:
        tile_ms = (candidate_tile_ms(m, n, max_tile_m) if tiling == "tsqr"
                   else (None,))

    kwargs = {} if givens is None else {"givens": givens}
    rng = np.random.default_rng(seed)
    A = np.asarray(rng.standard_normal((batch, m, n)), dtype=np.float64)

    rows = []
    for tm in tile_ms:
        for pw in panel_ns:
            cfg = QRDConfig(backend=backend, dtype=dtype, tiling=tiling,
                            panel_n=pw, tile_m=tm, **kwargs)
            eng = QRDEngine(cfg)
            warm = float(timer(lambda X: eng(X, compute_q=compute_q), A,
                               warm_reps))
            rows.append({"tile_m": tm, "panel_n": pw, "warm_s": warm})

    best = min(rows, key=lambda r: r["warm_s"])
    entry = TuneEntry(tile_b=0, table_layout=None, warm_s=best["warm_s"],
                      candidates=tuple(rows), panel_n=best["panel_n"],
                      tile_m=best["tile_m"])
    _store(path or cache_path(), device_kind(),
           cache_key(backend, "col", m, n, dtype, tiling), entry)
    return entry
