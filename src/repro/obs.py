"""Host spans on the profiler's clock.

`span(name, **args)` is `jax.profiler.TraceAnnotation`: while a profiler
runs (`jax.profiler.trace`), each ``with span(...)`` block is one event
on the trace's host plane, on the same clock as the device's ops, with
``args`` (and anything later given to ``.set_metadata(**args)``) as the
event's stats.  With no profiler running it costs the annotation object
alone (about a microsecond): the name and args are formatted only when
a profiler is recording.

Every span name starts with ``repro.``.  The engine's spans
(`repro.qrd.QRDEngine`):

``repro.qrd.call``     one decomposition or solve, with ``call`` (the
                       engine's call index), ``m``, ``n``, ``batch``
                       (matrices in the call) and ``backend``;
``repro.qrd.prepare``  nested in ``call``: operand checks, tuned-config
                       lookup, the cache key, the callable LRU and mesh
                       placement — everything before the program runs;
``repro.qrd.launch``   nested in ``call``: the jitted program's dispatch
                       into the runtime, when the LRU held it;
``repro.qrd.build``    nested in ``call`` in place of ``launch``, on an
                       LRU miss only: building the jitted program and its
                       first launch, which traces and compiles (or loads)
                       it.

Device work is named inside the programs: `jax.named_scope` ``encode``
and ``decode`` around the block-FP codec, Pallas kernels
``givens_qr_<datapath>`` / ``givens_replay_<datapath>``, and the engine's
programs ``jit_qrd_<backend>`` (``jit_qrd_<backend>_<route>`` on a tiled
route).
"""
from jax.profiler import TraceAnnotation as span

__all__ = ["span"]
