"""Reduce a profiler trace (``.xplane.pb``) of a window to its numbers.

What a TPU trace holds, and what is taken from it:

* Each chip is a plane ``/device:TPU:<i>``.  Its line ``XLA Ops`` has one
  event per HLO instruction executed, named by the instruction's text
  (``%name = type op(...), custom_call_target="..."``); its line ``XLA
  Modules`` one event per program executed (``jit_step(<hash>)``).
* Pallas kernels are the ops whose text holds
  ``custom_call_target="tpu_custom_call"``.  Every other op (fusions,
  copies, the float64 emulation's ``X64Split*``/``X64Combine`` calls) is
  "other" device time.
* The host plane ``/host:CPU`` holds the benchmark's own spans
  (`jax.profiler.TraceAnnotation`, named ``bench.<name>``), on the same
  clock as the device events.

Busy time is the union of a chip's op intervals inside the traced window
(from the first benchmark span's start to the last one's end), averaged
over the chips; idle gaps are the rest of the window, each put down to
the benchmark span that overlaps it most.  The profiler aligns the two
clocks only to within a fraction of a millisecond (one trace showed a
program's first op ~0.3 ms before the span that dispatched it), so that
attribution is approximate for spans of a millisecond or less.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(text: str) -> str:
    """``custom-call.8 X64Combine f64[131072,11,12]`` from an op's text."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    target = _TARGET.search(rest)
    if target:
        return f"{name} {target.group(1)} {shape}"
    return f"{name} {shape}"


def module_name(text: str) -> str:
    """``jit_step`` from ``jit_step(2394160940167438997)``."""
    return text.split("(", 1)[0]


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    """A window's trace, reduced.  Seconds throughout."""

    window_s: float
    busy_s: float                 # averaged over the chips with ops
    kernel_s: float               # Pallas kernels, summed over chips
    other_s: float                # every other device op
    ops: dict                     # label -> [count, seconds]
    kernels: dict                 # kernel label -> [count, seconds]
    modules: dict                 # program name -> [count, seconds]
    spans: dict                   # span name (no prefix) -> [count, s]
    gaps: list                    # (seconds, span name) per idle gap

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, and idle time by the
        benchmark span that was open during it."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        idle = defaultdict(float)
        for seconds, span in self.gaps:
            idle[span] += seconds
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(source, *, span_prefix: str = "bench.") -> Reduced:
    """Reduce an ``.xplane.pb`` path (or a `ProfileData`)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(source) if isinstance(source, str) else source

    spans = []                                  # (start, end, name)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len(span_prefix):]))
    if not spans:
        raise ValueError("the trace holds no benchmark span")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)

    ops = defaultdict(lambda: [0, 0.0])
    kernels = defaultdict(lambda: [0, 0.0])
    modules = defaultdict(lambda: [0, 0.0])
    busy_by_chip = []
    kernel_ns = other_ns = 0.0
    for plane in pd.planes:
        if not _DEVICE.match(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    if ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo:
                        m = modules[module_name(ev.name)]
                        m[0] += 1
                        m[1] += ev.duration_ns * 1e-9
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                intervals.append((s, e))
                label = op_label(ev.name)
                ops[label][0] += 1
                ops[label][1] += ev.duration_ns * 1e-9
                if KERNEL_TARGET in ev.name:
                    kernel_ns += ev.duration_ns
                    k = kernels[label.split(" ", 1)[0].rsplit(".", 1)[0]]
                    k[0] += 1
                    k[1] += ev.duration_ns * 1e-9
                else:
                    other_ns += ev.duration_ns
        if intervals:
            busy_by_chip.append(clip(union(intervals), lo, hi))
    if not busy_by_chip:
        raise ValueError("no operation ran on a device in the window")

    busy_ns = sum(e - s for b in busy_by_chip for s, e in b) / len(busy_by_chip)
    # idle gaps of the first chip, each put down to the span over it
    gaps, t = [], lo
    for s, e in busy_by_chip[0] + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    span_list = sorted(spans)
    starts = [s for s, _, _ in span_list]
    longest = max(e - s for s, e, _ in span_list)
    labelled = []
    for gs, ge in gaps:
        best, where = 0.0, "no span"
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(span_list) and span_list[i][0] < ge:
            s, e, name = span_list[i]
            over = min(e, ge) - max(s, gs)
            if over > best:
                best, where = over, name
            i += 1
        labelled.append(((ge - gs) * 1e-9, where))

    by_span = defaultdict(lambda: [0, 0.0])
    for s, e, name in spans:
        by_span[name][0] += 1
        by_span[name][1] += (e - s) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   kernel_s=kernel_ns * 1e-9,
                   other_s=other_ns * 1e-9, ops=dict(ops),
                   kernels=dict(kernels), modules=dict(modules),
                   spans=dict(by_span), gaps=labelled)
