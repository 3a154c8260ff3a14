"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under the benchmark directory:

* ``configs/<config>.json``  — the deployment (its ``system`` names the
  driver, ``systems/<system>.py``, and its ``reference`` the plain
  reference, ``reference/<reference>.py``);
* ``traffic/<traffic>.json`` — the mix's parameters, read by the driver;
* ``metrics/<metric>.py``    — a per-layer reader with ``read(ctx)``; a
  metric ``<name>.<cells>``, one quantity split by the cells that report
  it, is read by ``metrics/<name>.py`` unless it has a file of its own;
* ``work/<kernel>.py``       — a kernel's lower-bound work count;
* ``peaks.json``             — the peak table, keyed by device kind.

A later cell, mix or metric is added by adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric names carry dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """A metric with ``workloads`` is read in those cells; one without, in
    every cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def cell(benchmark_json: pathlib.Path, workload: str,
         bench: pathlib.Path = BENCH) -> Cell:
    """The cell ``workload`` of a BENCHMARK.json, with its files read."""
    spec = read_json(benchmark_json)
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = ", ".join(w["name"] for w in spec["workloads"])
        raise KeyError(f"unknown workload {workload!r}; cells: {names}")
    config = read_json(bench / "configs" / f"{entry['config']}.json")
    traffic = read_json(bench / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(entry["chips"]), config, traffic, e2e,
                per_layer)


def system(name: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "systems" / f"{name}.py", f"bench_sys_{name}")


def reference(name: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "reference" / f"{name}.py",
                       f"bench_ref_{name}")


def work(name: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "work" / f"{name}.py", f"bench_work_{name}")


def reader_path(metric: str, bench: pathlib.Path = BENCH) -> pathlib.Path:
    """``metrics/<metric>.py``, or else that of the metric's name without
    its last ``.<suffix>``."""
    path = bench / "metrics" / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = bench / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r}: "
                                f"{bench / 'metrics'}/{metric}.py")
    return path


def reader(metric: str, bench: pathlib.Path = BENCH):
    """The ``read(ctx)`` of per-layer metric ``metric``."""
    path = reader_path(metric, bench)
    return load_module(path, "bench_metric_" + path.stem.replace(".", "_")).read
