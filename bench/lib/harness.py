"""One run of one cell: set-up, measured window, correctness, result line.

The order follows what each number needs: set-up (timed as ``setup_s``)
builds the program and its inputs and warms every shape; the window runs
the traffic for ``seconds`` (under the profiler with ``trace``); the
device's peak memory is read; the window's answers are copied to the
host and the program's device state is dropped; then the plain reference
checks them on the host.  Metrics come from the window (``trace`` off) or
from the per-layer readers over the reduced trace (``trace`` on).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import json
import os
import pathlib
import shutil
import tempfile
import time
from collections import defaultdict

from . import peaks as peak_table
from . import spec as specs
from . import trace as traces


# A traced run profiles the last TRACE_S seconds of its window: the
# profiler's collection and the reduction grow with the events traced,
# and the whole run has to end within its time limit.
TRACE_S = 20.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Spans:
    """The benchmark's host spans around calls into the program.

    Off (``trace`` False) they cost one context manager.  On, each span is
    also a `jax.profiler.TraceAnnotation` named ``bench.<name>``, so the
    trace holds it on the device's clock, and its seconds are summed.
    With ``start``, the first span at or after ``start_at`` (a
    `time.perf_counter` reading) calls it to start the profiler; spans
    before it are neither traced nor counted.
    """

    def __init__(self, trace: bool, start=None, start_at: float = 0.0):
        self.trace = trace
        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        self._start, self._start_at = start, start_at

    @property
    def started(self) -> bool:
        return self._start is None

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.trace:
            yield
            return
        if self._start is not None:
            if time.perf_counter() < self._start_at:
                yield
                return
            self._start()
            self._start = None
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.seconds[name] += time.perf_counter() - t0
        self.count[name] += 1


class ProgramCounter:
    """Counts programs while it is open: lowered to XLA, loaded from the
    persistent cache, and compiled (the backend's compile-or-load calls
    that did not load)."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "backend",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loaded"}

    def __init__(self):
        self._n = dict.fromkeys(self.EVENTS.values(), 0)

    @property
    def n(self) -> dict:
        return {"lowered": self._n["lowered"], "loaded": self._n["loaded"],
                "compiled": self._n["backend"] - self._n["loaded"]}

    def _event(self, event, duration, **_):
        if event in self.EVENTS:
            self._n[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees."""

    cell: specs.Cell
    window: dict             # the driver's window result
    spans: Spans
    trace: traces.Reduced
    peaks: dict

    def traced_calls(self) -> int:
        """Calls into the program inside the traced window: the trace's
        spans named by the driver's ``call_span``."""
        return self.trace.spans.get(self.window["call_span"], (0,))[0]

    def work(self):
        """The cell kernel's lower-bound (ops, bytes) per engine call."""
        w = self.window["work"]
        mod = specs.work(w["kernel"])
        per = w["matrices_per_call"]
        args = (w["m"], w["n"], w["compute_q"])
        return (per * mod.ops(*args, w["iters"]),
                per * mod.bytes_moved(*args))


def per_layer(ctx: Context, bench=specs.BENCH) -> dict:
    """The cell's per-layer metrics, each read by the reader named after
    it; a reader that finds nothing to read returns None and its metric
    is left out."""
    metrics = {}
    for m in ctx.cell.per_layer:
        value = specs.reader(m["name"], bench)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no accelerator: JAX runs on {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def control_config(config: dict) -> dict:
    """The configuration with its stated lower-precision control switched
    on: the program's own path one precision step down."""
    cfg = dict(config, control=True)
    cfg["engine"] = dict(cfg["engine"])
    for key, value in cfg["precision"]["lower_precision_control"].items():
        if key in ("fmt", "n", "hub", "iters") and "givens" in cfg["engine"]:
            cfg["engine"]["givens"] = dict(cfg["engine"]["givens"],
                                           **{key: value})
        else:
            cfg["engine"][key] = value
    return cfg


def run(benchmark_json, workload: str, seed: int, seconds: float,
        trace: bool, *, require_chip: bool = True, bench=specs.BENCH,
        control: bool = False, overrides=None, window=None, log=print):
    """Run the cell once; returns the result dict (the last output line).

    The benchmark's own runs take the defaults.  ``control`` runs the
    configuration's lower-precision control in the program's place,
    ``overrides`` patches the traffic mix and ``window`` (a dict)
    receives the driver's window numbers: these serve the tools that set
    the limits and the knee, and the tests, never a measured run.
    """
    cell = specs.cell(pathlib.Path(benchmark_json), workload, bench)
    if control:
        cell.config = control_config(cell.config)
    if overrides:
        cell.traffic = dict(cell.traffic, **overrides)
    import jax

    import repro  # noqa: F401  (x64 on, as for every user of the package)
    devs = devices(cell.chips, require_chip)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however fast it compiled: only a cell's first
    # run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devs[0]
    table = peak_table.peaks(dev.device_kind) if require_chip else {}

    driver = specs.system(cell.config["system"]).Driver(cell, seed, seconds)
    t0 = time.perf_counter()
    with ProgramCounter() as built:
        driver.setup()
    # what set-up made lives for the run: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    log("set-up: " + json.dumps(dict(getattr(driver, "setup_phases", {}),
                                     programs=built.n)))

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        gcs = [g["collections"] for g in gc.get_stats()]
        with ProgramCounter() as counter:
            spans = Spans(trace)
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                spans = Spans(True, lambda: jax.profiler.start_trace(
                    tmp, profiler_options=opts),
                    time.perf_counter() + max(0.0, seconds - TRACE_S))
            win = driver.window(seconds, spans)
            if window is not None:
                window.update(win)
            if trace:
                if not spans.started:
                    raise RuntimeError("the window ended before its trace "
                                       "began")
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                t_stop = time.perf_counter() - t_stop
        log(f"programs inside the window: {json.dumps(counter.n)}")
        log("window: " + json.dumps(dict(win.get("diag", {}), collections=[
            g["collections"] - c for g, c in zip(gc.get_stats(), gcs)])))
        gc.unfreeze()
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs)
        got = driver.answers()
        checks = driver.check(got)
        reduced = None
        if trace:
            (xplane,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                  recursive=True)
            t_red = time.perf_counter()
            reduced = traces.reduce(xplane, span_prefix="bench.")
            log("trace: " + json.dumps({
                "traced_s": reduced.window_s, "stop_trace_s": t_stop,
                "reduce_s": time.perf_counter() - t_red}))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    correct = all(value <= limit for _, value, limit in checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        ctx = Context(cell, win, spans, reduced, table)
        metrics = per_layer(ctx, bench)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result
