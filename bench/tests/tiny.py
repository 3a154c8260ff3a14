"""A copy of the benchmark's files at sizes a CPU test run can hold.

`bench_root` writes ``BENCHMARK.json``, ``configs/`` and ``traffic/`` with
the committed contents and a few sizes patched down, and links the code
directories, so the harness runs every step of a cell as it is.
"""
from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_CONFIG = {
    "nr-ul-mimo-4x4": {},
}
TINY_TRAFFIC = {
    "slot": {"matrices_per_call": 16, "pool": 2, "warmup_calls": 1,
             "sample_calls": 2},
    "prb": {"matrices_per_call": 4, "pool": 2, "warmup_calls": 1,
            "sample_calls": 2},
}


def bench_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark directory under ``tmp`` with tiny sizes; returns it."""
    root = tmp / "bench"
    for sub in ("configs", "traffic"):
        (root / sub).mkdir(parents=True)
    for name, patch in TINY_CONFIG.items():
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        (root / "configs" / f"{name}.json").write_text(
            json.dumps(dict(cfg, **patch)))
    for name, patch in TINY_TRAFFIC.items():
        tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        (root / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(tr, **patch)))
    for sub in ("systems", "reference", "work", "metrics", "peaks.json"):
        (root / sub).symlink_to(BENCH / sub)
    (tmp / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    return root
