"""BENCHMARK.json and the files the harness finds by name."""
import json
import re
import types

import pytest

from bench.lib import harness, peaks, spec, trace
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for c in BENCHMARK["configs"]:
        assert NAME.match(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert (tiny.ROOT / c["file"]).is_file()
    configs = {c["name"] for c in BENCHMARK["configs"]}
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for e in BENCHMARK["configs"] + BENCHMARK["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert w["config"] in configs
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = spec.cell(tiny.ROOT / "BENCHMARK.json", w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["per_layer"]:
        assert spec.reader_path(m["name"]).is_file()
        assert m["moves"] in e2e


def test_every_config_states_precision_and_limits():
    for c in BENCHMARK["configs"]:
        cfg = json.loads((tiny.ROOT / c["file"]).read_text())
        assert cfg["precision"]["lower_precision_control"]
        assert cfg["limits"] and all(v > 0 for v in cfg["limits"].values())
        assert (spec.BENCH / "reference" / f"{cfg['reference']}.py").is_file()
        assert (spec.BENCH / "systems" / f"{cfg['system']}.py").is_file()


def reduced(**kw):
    base = dict(window_s=2.0, busy_s=0.5, kernel_s=0.25,
                other_s=0.25, ops={}, kernels={}, modules={}, spans={},
                gaps=[])
    return trace.Reduced(**dict(base, **kw))


def _metrics_copy(tmp_path):
    """A tiny tree whose ``metrics/`` is a copy that a test may add to."""
    root = tiny.bench_root(tmp_path)
    (root / "metrics").unlink()
    (root / "metrics").mkdir()
    for f in (spec.BENCH / "metrics").glob("*.py"):
        (root / "metrics" / f.name).write_text(f.read_text())
    return root


def _add_per_layer(tmp_path, *entries):
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bj["per_layer"] += [dict(unit="ms", better="lower",
                             source="device_trace", layer="device", **e)
                        for e in entries]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))


def test_a_metric_reader_is_added_by_files_alone(tmp_path):
    root = _metrics_copy(tmp_path)
    (root / "metrics" / "busy_ms.prb.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx.trace.busy_s\n")
    (root / "metrics" / "nothing.py").write_text(
        "def read(ctx):\n    return None\n")
    _add_per_layer(
        tmp_path,
        dict(name="busy_ms.prb", moves="qrd_per_s", workloads=["mimo.prb"]),
        dict(name="nothing.all", moves="setup_s"))

    cell = spec.cell(tmp_path / "BENCHMARK.json", "mimo.prb", root)
    names = [m["name"] for m in cell.per_layer]
    assert "busy_ms.prb" in names and "nothing.all" in names
    # a metric without "workloads" follows the end-to-end metric it moves
    slot = [m["name"] for m in spec.cell(
        tmp_path / "BENCHMARK.json", "mimo.slot", root).per_layer]
    assert "nothing.all" in slot and "busy_ms.prb" not in slot

    spans = harness.Spans(True)
    spans.seconds["engine_call"] = 0.6
    spans.count["engine_call"] = 200
    window = {"calls": 300, "call_span": "engine_call", "work": {
        "kernel": "blockfp_qr", "m": 8, "n": 8, "compute_q": True,
        "iters": 23, "matrices_per_call": 273}}
    ctx = harness.Context(cell, window, spans,
                          reduced(spans={"engine_call": [100, 0.6]}),
                          peaks.peaks("TPU v5 lite"))
    got = harness.per_layer(ctx, root)
    assert got["busy_ms.prb"] == {"value": 500.0, "unit": "ms"}
    assert "nothing.all" not in got                # found nothing: left out
    assert got["codec_ms_per_call.mimo"]["value"] == pytest.approx(2.5)
    assert got["dispatch_ms_per_call.mimo"]["value"] == pytest.approx(3.0)
    assert got["idle_share.mimo"]["value"] == pytest.approx(75.0)
    assert 0 < got["blockfp_qr_roofline"]["value"] < 100


@pytest.mark.parametrize("suffix", ["mimo", "prb", "slot"])
def test_a_split_metric_shares_its_reader(tmp_path, suffix):
    """``<name>.<cells>`` is read by ``metrics/<name>.py`` unless it has a
    file of its own; a name with neither file is an error."""
    root = _metrics_copy(tmp_path)
    assert spec.reader_path(f"idle_share.{suffix}", root) == \
        root / "metrics" / "idle_share.py"
    (root / "metrics" / f"idle_share.{suffix}.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    assert spec.reader_path(f"idle_share.{suffix}", root) == \
        root / "metrics" / f"idle_share.{suffix}.py"
    with pytest.raises(FileNotFoundError):
        spec.reader_path(f"no_such_reader.{suffix}", root)


def test_spans_start_the_trace_late(monkeypatch):
    """A traced window profiles only its end: spans before ``start_at``
    are neither traced nor counted, the first after it starts the
    profiler once."""
    clock = iter([0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0])
    monkeypatch.setattr(harness, "time", types.SimpleNamespace(
        perf_counter=lambda: next(clock)))
    started = []
    spans = harness.Spans(True, lambda: started.append(1), start_at=1.5)
    with spans("engine_call"):                      # at 0.0: before
        pass
    assert not spans.started and not spans.count
    with spans("engine_call"):                      # at 1.0: before
        pass
    with spans("engine_call"):                      # at 2.0: starts
        pass
    with spans("wait_device"):
        pass
    assert spans.started and started == [1]
    assert dict(spans.count) == {"engine_call": 1, "wait_device": 1}
    assert spans.seconds["engine_call"] == 1.0


def test_unknown_workload():
    with pytest.raises(KeyError):
        spec.cell(tiny.ROOT / "BENCHMARK.json", "mimo.nothing")
