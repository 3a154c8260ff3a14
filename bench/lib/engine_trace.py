"""Reduce what the program writes into a trace: its host spans and its
named device work.

`bench/lib/trace.py` reduces a window by the benchmark's own spans and
the ops' HLO text.  The program (`repro.obs`) adds, on the same clock:

* host spans named ``repro.<...>`` on the thread that calls it:
  ``repro.qrd.call`` (stats ``call``, ``m``, ``n``, ``batch``,
  ``backend``) holding ``repro.qrd.prepare`` and ``repro.qrd.launch``, or
  ``repro.qrd.build`` on a compile;
* device ops whose ``op_name`` carries the `jax.named_scope` they were
  traced in (``encode``, ``decode``; a Pallas kernel's own name);
* programs named ``jit_qrd_<backend>``.

Reduced over the window `trace.reduce` takes (first to last benchmark
span), on the first chip:

* ``spans``: each ``repro.`` span as (name, start, end, parent index,
  stats), the parent being the innermost ``repro.`` span around it;
* ``scopes``: device time by the outermost named scope of each op's
  ``op_name``; ops in no scope go under ``unscoped``;
* ``pairs``: each ``repro.qrd.call`` with the execution of an engine
  program (``jit_qrd...``) that ended before the next call began
  (`pair_calls`), and ``lags_s``: how long after its launch span ended
  each program seemed to start, which shows the clocks' offset where it
  is negative;
* ``idle_before_s`` / ``idle_after_s``: the device's idle time in each
  paired call's segment cut at its program's end: before it, from the
  segment's start (the call span's start; the window's start for the
  first call) up to the program's end, which holds the wait for the
  program to start and the gaps between its ops; after it, up to the
  next call's start (the window's end for the last).  Together they are
  the window's idle time, but for a first or last call whose program
  the trace's edge cut off.  The host and device clocks agree only to a
  fraction of a millisecond, so the two can trade time;
* ``idle_by_host``: each idle gap split over time, each slice put down to
  the innermost ``repro.`` span open on the thread that holds them, else
  to the shortest host event open on another host line (the runtime's
  threads), else to ``none``: time in which the trace shows the host
  doing nothing it names.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from collections import defaultdict

from . import trace as traces

PREFIX = "repro."
CALL = "repro.qrd.call"
PROGRAM = "jit_qrd"
UNSCOPED = "unscoped"
_TRANSFORM = re.compile(r"^\w+\(.*\)$")        # jit(f), vmap(f), ...


@dataclasses.dataclass
class Engine:
    """The program's spans and named device work in a window; seconds."""

    window_s: float
    spans: list          # (name, start_ns, end_ns, parent index, stats)
    scopes: dict         # scope -> [ops, seconds]
    pairs: list | None   # (call span index, (module start, end) ns)
    idle_before_s: float
    idle_after_s: float
    lags_s: list         # per pair: program start - launch span's end
    idle_by_host: list   # [(label, seconds)], largest first

    @property
    def calls(self) -> int:
        return sum(1 for s in self.spans if s[0] == CALL)

    def span_s(self, name: str) -> float:
        return sum(e - s for n, s, e, _, _ in self.spans if n == name) * 1e-9

    def scope_s(self, name: str) -> float:
        return self.scopes.get(name, (0, 0.0))[1]

    def breakdown(self, top: int = 10) -> dict:
        return {"idle_by_host": [[k, v] for k, v in self.idle_by_host[:top]]}


def op_scope(op_name: str) -> str:
    """``encode`` from ``jit(qrd_blockfp_pallas)/jit(g)/encode/mul``: the
    outermost path element that is neither a transform nor the primitive."""
    parts = [p for p in op_name.split("/")[:-1] if not _TRANSFORM.match(p)]
    return parts[0] if parts else UNSCOPED


def _nest(events):
    """Parent index of each (start, end, ...) of one thread's properly
    nested events, sorted by start (outer first on ties)."""
    parents, stack = [], []
    for i, (s, e, *_) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        parents.append(stack[-1] if stack else None)
        stack.append(i)
    return parents


def innermost(events):
    """Disjoint (start, end, label) slices, each labelled by the innermost
    of one thread's nested (start, end, label) events open over it."""
    out, stack, t = [], [], None
    for s, e, label in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            if top[1] > t:
                out.append((t, top[1], top[2]))
            t = max(t, top[1])
        if stack and s > t:
            out.append((t, s, stack[-1][2]))
        stack.append((s, e, label))
        t = s
    while stack:
        top = stack.pop()
        if top[1] > t:
            out.append((t, top[1], top[2]))
        t = max(t, top[1])
    return out


def _subtract(pieces, cover):
    """Parts of disjoint sorted ``pieces`` (start, end) outside the
    disjoint sorted ``cover`` (start, end, label); and the covered parts
    as (start, end, label)."""
    left, hit = [], []
    j = 0
    for s, e in pieces:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(cover) and cover[k][0] < e:
            cs, ce, label = cover[k]
            if cs > t:
                left.append((t, cs))
            a, b = max(cs, t), min(ce, e)
            if b > a:
                hit.append((a, b, label))
            t = max(t, ce)
            k += 1
        if e > t:
            left.append((t, e))
    return left, hit


def _shortest_open(pieces, events):
    """Disjoint sorted (start, end) ``pieces`` cut into (start, end, label)
    slices, each labelled by the shortest of the (start, end, label)
    ``events`` open over it (the innermost, on nested threads), or
    ``none``."""
    events = sorted(events)
    out, heap, i = [], [], 0
    for s, e in pieces:
        t = s
        while t < e:
            while i < len(events) and events[i][0] <= t:
                es, ee, name = events[i]
                heapq.heappush(heap, (ee - es, ee, name))
                i += 1
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
            label = heap[0][2] if heap else "none"
            nxt = min([e, events[i][0] if i < len(events) else e]
                      + [x[1] for x in heap if x[1] > t])
            out.append((t, nxt, label))
            t = nxt
    return out


def idle_split(gaps, spans, host_events):
    """[(label, seconds)] of the idle ``gaps`` (disjoint sorted (start,
    end)) split over time: under the innermost of ``spans`` (one thread's
    nested (start, end, label)), else under the shortest of the
    ``host_events`` (start, end, label) open, else ``none``."""
    left, hit = _subtract(gaps, innermost(spans))
    total = defaultdict(float)
    for s, e, label in hit + _shortest_open(left, host_events):
        total[label] += (e - s) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])


def _idle_in(busy, starts, lo, hi):
    """Idle nanoseconds of [lo, hi) given the sorted disjoint ``busy``
    intervals and their starts."""
    if hi <= lo:
        return 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    covered = 0.0
    while i < len(busy) and busy[i][0] < hi:
        covered += max(0.0, min(busy[i][1], hi) - max(busy[i][0], lo))
        i += 1
    return (hi - lo) - covered


def pair_calls(calls, programs, hi):
    """[(k, program)]: the k-th call (its start) with the program (start,
    end) that ended in the call's segment, from its start to the next
    call's (to ``hi`` for the last).  That holds while the two clocks
    agree to within the host's work on either side of a program (~0.4
    ms here).  None when a segment holds two programs, or a call other
    than the first or last (whose programs the trace's edges may cut)
    holds none."""
    out, j = [], 0
    for k, start in enumerate(calls):
        end = calls[k + 1] if k + 1 < len(calls) else hi
        while j < len(programs) and programs[j][1] < start:
            j += 1
        mine = []
        while j < len(programs) and programs[j][1] < end:
            mine.append(programs[j])
            j += 1
        if len(mine) > 1 or (not mine and 0 < k < len(calls) - 1):
            return None
        out += [(k, p) for p in mine]
    return out or None


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field, value) of the protobuf message in ``buf[lo:hi]``: an int
    for a varint, (start, end) for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(serialized: bytes) -> dict:
    """{op text: op_name} of the device planes of a serialized XSpace.

    The profiler gives each op its ``op_name`` (the `jax.named_scope`
    path) as the stat ``tf_op`` of the op's event metadata, which
    `jax.profiler.ProfileData` does not expose; this reads just those
    (XPlane fields 2, 4 and 5; XEventMetadata 2 and 5; XStat 1 and 5),
    skipping the events themselves.
    """
    buf = memoryview(serialized)
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, tf_op = None, [], set()
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                key = value = None
                for g, w in _fields(buf, *v):
                    if g == 1:
                        key = w
                    elif g == 2:
                        value = dict(_fields(buf, *w)).get(2)
                if value is not None and _text(buf, value) == "tf_op":
                    tf_op.add(key)
        if not (name and traces._DEVICE.match(name)) or not tf_op:
            continue
        for entry in metas:
            value = dict(_fields(buf, *entry)).get(2)
            if value is None:
                continue
            text = op_name = None
            for g, w in _fields(buf, *value):
                if g == 2:
                    text = _text(buf, w)
                elif g == 5:
                    stat = dict(_fields(buf, *w))
                    if stat.get(1) in tf_op and 5 in stat:
                        op_name = _text(buf, stat[5]).rstrip(":")
            if text is not None and op_name is not None:
                out[text] = op_name
    return out


def reduce(serialized: bytes, *, span_prefix: str = "bench.",
           pd=None) -> Engine:
    """Reduce a serialized XSpace (an ``.xplane.pb`` file's bytes; ``pd``
    its `ProfileData`, if already read) over the window of its
    ``span_prefix`` spans, as `trace.reduce` does."""
    if pd is None:
        from jax.profiler import ProfileData
        pd = ProfileData.from_serialized_xspace(serialized)
    window, ours, host, device = [], [], [], None
    for plane in pd.planes:
        if device is None and traces._DEVICE.match(plane.name):
            device = plane
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, ev)
                   for ev in line.events]
            mine = [e for e in evs if e[2].startswith(PREFIX)]
            window += [e[:2] for e in evs if e[2].startswith(span_prefix)]
            if mine and not ours:
                ours = mine
            else:
                host += [e[:3] for e in evs
                         if not e[2].startswith((PREFIX, span_prefix))]
    if not window:
        raise ValueError("the trace holds no benchmark span")
    if device is None:
        raise ValueError("no device in the trace")
    lo = min(s for s, _ in window)
    hi = max(e for _, e in window)

    ours = sorted((e for e in ours if lo <= e[0] < hi),
                  key=lambda x: (x[0], -x[1]))
    spans = [(name, s, e, parent, dict(ev.stats))
             for (s, e, name, ev), parent in zip(ours, _nest(ours))]
    scopes, busy, programs = _device(device, op_names(serialized), lo, hi)
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)

    calls = [i for i, sp in enumerate(spans) if sp[0] == CALL]
    ran = pair_calls([spans[i][1] for i in calls], programs, hi)
    pairs, before, after, lags = None, 0.0, 0.0, []
    if ran:
        pairs = [(calls[k], prog) for k, prog in ran]
        starts = [s for s, _ in busy]
        cuts = [lo] + [spans[i][1] for i in calls[1:]] + [hi]
        for k, (_, pe) in ran:
            mid = min(max(pe, cuts[k]), cuts[k + 1])
            before += _idle_in(busy, starts, cuts[k], mid)
            after += _idle_in(busy, starts, mid, cuts[k + 1])
        launched = {sp[3]: sp[2] for sp in spans
                    if sp[0] in ("repro.qrd.launch", "repro.qrd.build")}
        lags = [(ps - launched[i]) * 1e-9 for i, (ps, _) in pairs
                if i in launched]

    others = [(max(s, lo), min(e, hi), name) for s, e, name in host
              if e > lo and s < hi]
    by_host = idle_split(gaps, [(s, e, n) for n, s, e, _, _ in spans],
                         others)
    return Engine(window_s=(hi - lo) * 1e-9, spans=spans,
                  scopes=dict(scopes), pairs=pairs,
                  idle_before_s=before * 1e-9, idle_after_s=after * 1e-9,
                  lags_s=lags, idle_by_host=by_host)


def _device(plane, names, lo, hi):
    """Device seconds by named scope, the busy union and the engine's
    program executions (start, end) of one chip's plane in [lo, hi)."""
    scopes = defaultdict(lambda: [0, 0.0])
    intervals, programs = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            programs += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events
                         if ev.name.startswith(PROGRAM)
                         and ev.start_ns < hi
                         and ev.start_ns + ev.duration_ns > lo]
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            intervals.append((s, e))
            sc = scopes[op_scope(names.get(ev.name, ""))]
            sc[0] += 1
            sc[1] += ev.duration_ns * 1e-9
    return scopes, traces.clip(traces.union(intervals), lo, hi), sorted(programs)


def of(ctx):
    """The `Engine` reduction a reader's context carries, or None (a
    harness that does not reduce the program's spans, or a program that
    writes none)."""
    return getattr(ctx.trace, "engine", None)
