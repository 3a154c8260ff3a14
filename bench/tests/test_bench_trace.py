"""The trace reduction: busy/idle union, kernels against other device
time, and the benchmark's host spans on the same clock."""
import pathlib

import pytest
from jax.profiler import ProfileData

from bench.lib import trace

DATA = pathlib.Path(__file__).parent / "data"
KERNEL = ('%k.1 = s32[8,16]{1,0} custom-call(s32[8,16]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
SPLIT = ('%custom-call.3 = f32[8]{0} custom-call(f64[8]{0} %x), '
         'custom_call_target="X64SplitHigh"')


def xspace(device_events, host_events, modules=()):
    """An XSpace text proto: events as (name, start_ns, duration_ns)."""
    def plane(pid, name, lines):
        meta, out = {}, [f"planes {{ id: {pid} name: \"{name}\""]
        for i, (line, events) in enumerate(lines):
            out.append(f"  lines {{ id: {i + 1} name: \"{line}\" "
                       "timestamp_ns: 0")
            for ev, start, dur in events:
                mid = meta.setdefault(ev, len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} "
                           f"offset_ps: {start * 1000} "
                           f"duration_ps: {dur * 1000} }}")
            out.append("  }")
        for ev, mid in meta.items():
            esc = ev.replace('"', '\\"')
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: \"{esc}\" }} }}")
        out.append("}")
        return "\n".join(out)
    return ProfileData.from_text_proto("\n".join([
        plane(1, "/device:TPU:0", [("XLA Ops", device_events),
                                   ("XLA Modules", list(modules))]),
        plane(2, "/host:CPU", [("python3", host_events)]),
        plane(3, "/host:metadata", []),
    ]))


def test_union_and_clip():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert trace.clip([(0, 4), (5, 10)], 2, 7) == [(2, 4), (5, 7)]


def test_op_label():
    assert trace.op_label(KERNEL) == "k.1 tpu_custom_call s32[8,16]"
    assert trace.op_label(FUSION) == "fusion.2 f32[8]"
    assert trace.module_name("jit_step(2394160940167438997)") == "jit_step"


def test_reduce_by_hand():
    # device: [0,8] and [5,15] overlap, [20,29]; one op before the window
    pd = xspace(
        device_events=[(KERNEL, 1000, 8), (FUSION, 1005, 10),
                       (SPLIT, 1020, 9), (FUSION, 900, 50)],
        host_events=[("bench.a", 1000, 18), ("bench.b", 1018, 22),
                     ("not.ours", 1000, 99)],
        modules=[("jit_f(123)", 1000, 15), ("jit_f(123)", 1020, 10)])
    r = trace.reduce(pd)
    assert r.window_s == pytest.approx(40e-9)
    assert r.busy_s == pytest.approx(24e-9)          # 15 + 9
    assert r.idle_share() == pytest.approx(16 / 40)
    assert r.kernel_s == pytest.approx(8e-9)
    assert r.other_s == pytest.approx(19e-9)
    assert r.kernels == {"k": [1, pytest.approx(8e-9)]}
    assert r.modules == {"jit_f": [2, pytest.approx(25e-9)]}
    assert r.spans == {"a": [1, pytest.approx(18e-9)],
                       "b": [1, pytest.approx(22e-9)]}
    # gaps [15,20] (3 ns under a, 2 under b) and [29,40] (under b)
    assert sorted(r.gaps) == [(pytest.approx(5e-9), "a"),
                              (pytest.approx(11e-9), "b")]
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["fusion.2 f32[8]", pytest.approx(10e-9)]
    assert dict(bd["idle_gaps"]) == {"b": pytest.approx(11e-9),
                                     "a": pytest.approx(5e-9)}


def test_reduce_needs_spans_and_device_ops():
    with pytest.raises(ValueError, match="no benchmark span"):
        trace.reduce(xspace([(KERNEL, 0, 10)], []))
    with pytest.raises(ValueError, match="no operation"):
        trace.reduce(xspace([], [("bench.a", 0, 10)]))


def recorded(name):
    return ProfileData.from_text_proto((DATA / name).read_text())


def test_recorded_mimo_trace():
    """Three engine calls of mimo.prb (273 8x8 QRDs each), recorded by the
    harness on a TPU v5e and trimmed to the ops of those calls."""
    pd = recorded("mimo_prb_3calls.pbtxt")
    r = trace.reduce(pd)
    calls = r.spans["engine_call"][0]
    assert calls == r.spans["wait_device"][0] == 3
    # every call runs the block-FP kernel once, in one program
    assert r.kernels["givens_block_apply"][0] == calls
    assert r.modules["jit__lambda"][0] == calls
    # the same sums taken straight from the events
    dev = pd.find_plane_with_name("/device:TPU:0")
    ops = [e for ln in dev.lines if ln.name == "XLA Ops" for e in ln.events]
    kern = sum(e.duration_ns for e in ops if trace.KERNEL_TARGET in e.name)
    other = sum(e.duration_ns for e in ops if trace.KERNEL_TARGET not in e.name)
    assert r.kernel_s == pytest.approx(kern * 1e-9)
    assert r.other_s == pytest.approx(other * 1e-9)
    assert r.kernel_s + r.other_s >= r.busy_s > r.kernel_s
    assert r.busy_s < r.window_s
    assert sum(s for s, _ in r.gaps) == pytest.approx(r.window_s - r.busy_s)
    # the device idles while the host waits for it, between calls
    idle = dict(r.breakdown()["idle_gaps"])
    assert max(idle, key=idle.get) == "wait_device"
    assert len(r.breakdown()["device_ops"]) == 10
