"""The second reduction of a trace, by what the program writes into it:
its ``repro.`` host spans, the named scopes of its device ops, its
programs by name, and the idle time split by the host's events."""
import pathlib

import pytest
from jax.profiler import ProfileData

from bench.lib import engine_trace as et
from bench.lib import harness, spec
from bench.lib import trace as traces

DATA = pathlib.Path(__file__).parent / "data"
READERS = ("engine_prepare_ms_per_call", "engine_launch_ms_per_call",
           "encode_ms_per_call", "decode_ms_per_call",
           "idle_before_program_ms", "idle_after_program_ms")


def op(name, scope):
    """An op's HLO text and its ``tf_op`` (None: the op has no scope)."""
    text = f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop"
    return text, None if scope is None else f"jit(qrd_x)/{scope}/mul:"


ENC, DEC, KER, SPLIT = (op("fusion.1", "jit(g)/encode"),
                        op("fusion.2", "decode"),
                        op("k.3", "givens_qr_blockfp/pallas_call"),
                        op("custom-call.4", None))


def xspace(device_events, python_events, runtime_events=(), modules=()):
    """Serialized XSpace: events as (name, start_ns, duration_ns[, stats
    dict]); device events are (op, start, duration) with `op` as above,
    their ``tf_op`` on the event metadata as a TPU trace has it."""
    stat_ids = {}

    def stat(name, value):
        sid = stat_ids.setdefault(name, len(stat_ids) + 1)
        key = "int64_value" if isinstance(value, int) else "str_value"
        val = value if isinstance(value, int) else '"' + value + '"'
        return f"stats {{ metadata_id: {sid} {key}: {val} }}"

    def plane(pid, name, lines):
        meta, out = {}, [f"planes {{ id: {pid} name: \"{name}\""]
        for i, (line, events) in enumerate(lines):
            out.append(f"  lines {{ id: {i + 1} name: \"{line}\" "
                       "timestamp_ns: 0")
            for ev, start, dur, *st in events:
                text, tf_op = ev if isinstance(ev, tuple) else (ev, None)
                mid = meta.setdefault(text, (len(meta) + 1, tf_op))[0]
                stats = " ".join(stat(k, v) for k, v in (st or [{}])[0]
                                 .items())
                out.append(f"    events {{ metadata_id: {mid} "
                           f"offset_ps: {start * 1000} "
                           f"duration_ps: {dur * 1000} {stats} }}")
            out.append("  }")
        for text, (mid, tf_op) in meta.items():
            esc = text.replace('"', '\\"')
            st = "" if tf_op is None else stat("tf_op", tf_op)
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: \"{esc}\" {st} }} }}")
        for name, sid in stat_ids.items():
            out.append(f"  stat_metadata {{ key: {sid} value {{ id: {sid} "
                       f"name: \"{name}\" }} }}")
        stat_ids.clear()
        out.append("}")
        return "\n".join(out)
    return ProfileData.text_proto_to_serialized_xspace("\n".join([
        plane(1, "/device:TPU:0", [("XLA Ops", device_events),
                                   ("XLA Modules", list(modules))]),
        plane(2, "/host:CPU", [("python3", python_events),
                               ("runtime/7", list(runtime_events))]),
    ]))


def two_calls():
    """Two calls over [1000, 1100): each call span holds prepare and
    launch; the runtime enqueues, then the program runs; the first call's
    program [1030, 1040) (encode 4, kernel 4, unscoped 2), the second's
    [1080, 1090) (decode 10)."""
    call = {"call": 1, "m": 8, "n": 8, "batch": 3, "backend": "x"}
    py = [("bench.engine_call", 1000, 20), ("repro.qrd.call", 1001, 18,
                                            call),
          ("repro.qrd.prepare", 1002, 8), ("repro.qrd.launch", 1010, 8),
          ("bench.wait_device", 1020, 30),
          ("bench.engine_call", 1050, 20),
          ("repro.qrd.call", 1051, 18, dict(call, call=2)),
          ("repro.qrd.prepare", 1052, 8), ("repro.qrd.launch", 1060, 8),
          ("bench.wait_device", 1070, 30), ("not.ours", 1000, 99)]
    rt = [("Enqueue", 1015, 10), ("ReadSyncFlag", 1042, 4),
          ("Enqueue", 1070, 5)]
    dev = [(ENC, 1030, 4), (KER, 1034, 4), (SPLIT, 1038, 2),
           (DEC, 1080, 10), (ENC, 900, 5)]
    mods = [("jit_qrd_x(1)", 1030, 10), ("jit_qrd_x(1)", 1080, 10),
            ("jit_other(2)", 1095, 1)]
    return xspace(dev, py, rt, mods)


def test_op_names_read_from_the_event_metadata():
    names = et.op_names(two_calls())
    assert names == {ENC[0]: "jit(qrd_x)/jit(g)/encode/mul",
                     DEC[0]: "jit(qrd_x)/decode/mul",
                     KER[0]: "jit(qrd_x)/givens_qr_blockfp/pallas_call/mul"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(qrd_x)/jit(givens_block_apply)/encode/reduce_max", "encode"),
    ("jit(qrd_x)/decode/jit(_where)/select_n", "decode"),
    ("jit(qrd_x)/jit(g)/givens_qr_blockfp/pallas_call", "givens_qr_blockfp"),
    ("jit(qrd_x)/jit(g)/jit(_pad)/pad", "unscoped"),
    ("A", "unscoped"), ("", "unscoped")])
def test_op_scope(op_name, scope):
    assert et.op_scope(op_name) == scope


def test_nested_spans_with_their_parents_and_stats():
    r = et.reduce(two_calls())
    got = [(n, s, e, p) for n, s, e, p, _ in r.spans]
    assert got == [("repro.qrd.call", 1001, 1019, None),
                   ("repro.qrd.prepare", 1002, 1010, 0),
                   ("repro.qrd.launch", 1010, 1018, 0),
                   ("repro.qrd.call", 1051, 1069, None),
                   ("repro.qrd.prepare", 1052, 1060, 3),
                   ("repro.qrd.launch", 1060, 1068, 3)]
    assert r.spans[3][4] == {"call": 2, "m": 8, "n": 8, "batch": 3,
                             "backend": "x"}
    assert r.calls == 2
    assert r.span_s("repro.qrd.prepare") == pytest.approx(16e-9)


def test_device_time_by_scope():
    r = et.reduce(two_calls())
    # the op before the window is left out, as `trace.reduce` does
    assert r.scopes == {"encode": [1, pytest.approx(4e-9)],
                        "givens_qr_blockfp": [1, pytest.approx(4e-9)],
                        "unscoped": [1, pytest.approx(2e-9)],
                        "decode": [1, pytest.approx(10e-9)]}


def test_calls_pair_with_programs_and_idle_splits_at_their_end():
    r = et.reduce(two_calls())
    assert r.pairs == [(0, (1030, 1040)), (3, (1080, 1090))]
    # busy [1030, 1040) and [1080, 1090) in [1000, 1100): 80 ns idle;
    # call 1 [1000, 1051): 30 before its program's end, 11 after;
    # call 2 [1051, 1100): 29 before, 10 after
    assert r.idle_before_s == pytest.approx(59e-9)
    assert r.idle_after_s == pytest.approx(21e-9)
    bench = traces.reduce(ProfileData.from_serialized_xspace(two_calls()))
    assert r.idle_before_s + r.idle_after_s == pytest.approx(
        bench.window_s - bench.busy_s)
    # each program started 12 ns after its launch span ended
    assert r.lags_s == pytest.approx([12e-9, 12e-9])


def test_calls_pair_with_the_program_that_ended_in_their_segment():
    calls = [0, 10, 20]
    assert et.pair_calls(calls, [(2, 5), (12, 15), (22, 25)], 30) == [
        (0, (2, 5)), (1, (12, 15)), (2, (22, 25))]
    # clocks off by a little: the program seems to start before its call
    assert et.pair_calls(calls, [(-1, 5), (9, 15), (19, 25)], 30) == [
        (0, (-1, 5)), (1, (9, 15)), (2, (19, 25))]
    # the trace's edges may cut the first or last call's program; a
    # program that ended before the first call is not the window's
    assert et.pair_calls(calls, [(-9, -5), (12, 15), (22, 25)], 30) == [
        (1, (12, 15)), (2, (22, 25))]
    assert et.pair_calls(calls, [(2, 5), (12, 15)], 30) == [
        (0, (2, 5)), (1, (12, 15))]
    # a middle call with no program, or a call with two: no pairing
    assert et.pair_calls(calls, [(2, 5), (22, 25)], 30) is None
    assert et.pair_calls(calls, [(2, 5), (6, 8), (12, 15)], 30) is None
    assert et.pair_calls([], [], 30) is None


def test_idle_by_host_splits_gaps_over_time():
    r = et.reduce(two_calls())
    # gap [1000, 1030): none 1, call 1 + 1, prepare 8, launch 8, then
    # the runtime's Enqueue [1019, 1025) under no span, none 5 (not.ours
    # is on the spans' own thread: not a host event); gap [1040, 1080):
    # none 2, ReadSyncFlag 4, none 5, call 2, prepare 8, launch 8, none
    # 1, Enqueue 5, none 5; gap [1090, 1100): none 10
    split = dict(r.idle_by_host)
    assert split == pytest.approx({
        "repro.qrd.call": 4e-9, "repro.qrd.prepare": 16e-9,
        "repro.qrd.launch": 16e-9, "Enqueue": 11e-9, "ReadSyncFlag": 4e-9,
        "none": 29e-9})
    assert sum(split.values()) == pytest.approx(80e-9)
    assert r.breakdown()["idle_by_host"][0] == ["none",
                                                pytest.approx(29e-9)]


def test_innermost_and_shortest_open():
    nested = [(0, 10, "a"), (2, 6, "b"), (3, 4, "c"), (8, 9, "d")]
    assert et.innermost(nested) == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                                    (4, 6, "b"), (6, 8, "a"), (8, 9, "d"),
                                    (9, 10, "a")]
    events = [(0, 10, "long"), (2, 5, "short"), (4, 12, "other")]
    assert et._shortest_open([(1, 11), (13, 14)], events) == [
        (1, 2, "long"), (2, 4, "short"), (4, 5, "short"), (5, 10, "other"),
        (10, 11, "other"), (13, 14, "none")]


def test_a_program_without_spans_reads_nothing():
    """The parent's program writes no spans or scopes: the readers find
    nothing to read and raise nothing."""
    py = [("bench.engine_call", 1000, 20), ("bench.wait_device", 1020, 30)]
    data = xspace([(op("fusion.1", "mul")[0], 1030, 4)], py,
                  [("Enqueue", 1015, 10)], [("jit__lambda(1)", 1030, 10)])
    r = et.reduce(data)
    assert r.spans == [] and r.pairs is None
    assert r.scopes == {"unscoped": [1, pytest.approx(4e-9)]}
    ctx = context(data)
    assert all(read(name, ctx) is None for name in READERS)


def context(data):
    pd = ProfileData.from_serialized_xspace(data)
    reduced = traces.reduce(pd)
    reduced.engine = et.reduce(data, pd=pd)
    return harness.Context(None, {}, None, reduced, {})


def read(name, ctx):
    return spec.reader(f"{name}.mimo")(ctx)


def test_recorded_mimo_trace_with_spans():
    """Three engine calls of mimo.prb (273 8x8 QRDs each) on a TPU v5e,
    traced as the harness traces, cut to those calls' events: the ops'
    text with their ``tf_op``, the host's events with the call spans'
    stats."""
    text = (DATA / "mimo_prb_3calls_spans.pbtxt").read_text()
    data = ProfileData.text_proto_to_serialized_xspace(text)
    ctx = context(data)
    r, eng = ctx.trace, ctx.trace.engine
    # the benchmark's own reading of it, with the program's new names
    assert r.spans["engine_call"][0] == r.spans["wait_device"][0] == 3
    assert r.kernels == {"givens_qr_blockfp": [3, pytest.approx(777.412e-6)]}
    assert r.modules == {"jit_qrd_blockfp_pallas": [
        3, pytest.approx(855.968e-6)]}
    assert r.other_s == pytest.approx(77.564e-6)
    assert eng.calls == len(eng.pairs) == 3
    assert [sp[4]["call"] for sp in eng.spans if sp[4]] == [11282, 11283,
                                                           11284]
    got = {name: read(name, ctx) for name in READERS}
    assert got == pytest.approx({
        "engine_prepare_ms_per_call": 0.27438667,
        "engine_launch_ms_per_call": 0.29033,
        "encode_ms_per_call": 0.009374,
        "decode_ms_per_call": 0.00918967,
        "idle_before_program_ms": 0.79144533,
        "idle_after_program_ms": 0.48980567}, rel=1e-6)
    # the scopes and the unscoped float64 emulation make up the codec
    codec = 1e3 * r.other_s / 3
    unscoped = 1e3 * eng.scope_s("unscoped") / 3
    assert got["encode_ms_per_call"] + got["decode_ms_per_call"] + \
        unscoped == pytest.approx(codec)
    # before + after is the idle time per call
    assert got["idle_before_program_ms"] + got["idle_after_program_ms"] == \
        pytest.approx(1e3 * (r.window_s - r.busy_s) / 3)
    # the engine's spans lie inside the benchmark's call span
    assert got["engine_prepare_ms_per_call"] + got[
        "engine_launch_ms_per_call"] <= 1e3 * r.spans["engine_call"][1] / 3
    top = [label for label, _ in eng.idle_by_host[:5]]
    assert top == ["none", "repro.qrd.launch", "repro.qrd.prepare",
                   "ReadSyncFlag", "tpu::System::Execute=>Done"]
    assert sum(s for _, s in eng.idle_by_host) == pytest.approx(
        r.window_s - r.busy_s)


def test_trace_engine_tool_checks_on_the_recorded_trace():
    from bench.tools import trace_engine
    text = (DATA / "mimo_prb_3calls_spans.pbtxt").read_text()
    ctx = context(ProfileData.text_proto_to_serialized_xspace(text))
    metrics = trace_engine.read_all(ctx)
    assert set(metrics) == set(READERS)
    metrics["codec_ms_per_call.mimo"] = {"value": 1e3 * ctx.trace.other_s
                                         / 3}
    c = trace_engine.consistency(metrics, ctx.trace, ctx.trace.engine)
    assert c["bench_calls"] == c["engine_calls"] == c["paired_calls"] == 3
    assert c["idle_before_plus_after_ms"] == pytest.approx(
        c["idle_ms_per_call"])
    assert c["codec_share_in_scopes"] == pytest.approx(0.7181, abs=1e-4)
    assert c["idle_none_share"] == pytest.approx(0.3136, abs=1e-4)
