#!/usr/bin/env python3
"""A traced run of a cell, read by the program's own spans and scopes too.

    python3 bench/tools/trace_engine.py --workload mimo.prb --seed <n> \
        --seconds 50 [--keep DIR]

Runs the cell as ``bench/run.py --trace 1`` does and reduces the same
trace a second time by what the program writes into it
(`bench/lib/engine_trace.py`).  The last line of output is the traced
run's result line with, added: the engine readers' metrics beside the
cell's own, ``idle_by_host`` in the breakdown, and ``consistency``: the
idle time per call two ways, the engine spans against
``dispatch_ms_per_call``, the scopes' share of ``codec_ms_per_call``,
the share of idle time no host event covers, and how long after its
launch span each program seemed to start (negative: the clocks are
off).  ``--keep DIR`` copies the trace there.
"""
import argparse
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
READERS = ("engine_prepare_ms_per_call", "engine_launch_ms_per_call",
           "encode_ms_per_call", "decode_ms_per_call",
           "idle_before_program_ms", "idle_after_program_ms")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from jax.profiler import ProfileData

    from bench.lib import engine_trace, harness, spec
    from bench.lib import trace as traces

    reduce_bench = traces.reduce
    seen = {}

    def reduce_both(xplane, **kw):
        if args.keep:
            pathlib.Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, pathlib.Path(args.keep) /
                        f"{args.workload}.{args.seed}.xplane.pb")
        data = pathlib.Path(xplane).read_bytes()
        pd = ProfileData.from_serialized_xspace(data)
        seen["reduced"] = reduced = reduce_bench(pd, **kw)
        reduced.engine = engine_trace.reduce(data, pd=pd, **kw)
        return reduced

    # the harness reduces the trace and deletes it within one run
    traces.reduce = reduce_both
    win = {}
    result = harness.run(ROOT / "BENCHMARK.json", args.workload, args.seed,
                         args.seconds, True, window=win,
                         log=lambda s: print(s, flush=True))
    reduced = seen["reduced"]
    cell = spec.cell(ROOT / "BENCHMARK.json", args.workload)
    ctx = harness.Context(cell, win, None, reduced, {})
    metrics = result["metrics"]
    metrics.update(read_all(ctx))
    result["breakdown"].update(reduced.engine.breakdown())
    result["consistency"] = consistency(metrics, reduced, reduced.engine)
    print(json.dumps(result), flush=True)


def read_all(ctx) -> dict:
    """The engine readers' metrics that find something to read."""
    from bench.lib import spec
    out = {}
    for name in READERS:
        value = spec.reader(name)(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": "ms"}
    return out


def consistency(metrics, reduced, eng) -> dict:
    def ms(prefix):
        return next((v["value"] for k, v in metrics.items()
                     if k.split(".")[0] == prefix), None)
    calls = reduced.spans.get("engine_call", (0,))[0]
    idle = reduced.window_s - reduced.busy_s
    out = {"bench_calls": calls, "engine_calls": eng.calls,
           "paired_calls": len(eng.pairs or ()),
           "idle_ms_per_call": 1e3 * idle / calls if calls else None,
           "idle_none_share": dict(eng.idle_by_host).get("none", 0.0) / idle}
    if eng.lags_s:
        lags = sorted(eng.lags_s)
        out["launch_to_program_ms"] = {"min": 1e3 * lags[0],
                                       "median": 1e3 * lags[len(lags) // 2]}
    before, after = ms("idle_before_program_ms"), ms("idle_after_program_ms")
    if before is not None and after is not None:
        out["idle_before_plus_after_ms"] = before + after
    prep, launch = ms("engine_prepare_ms_per_call"), \
        ms("engine_launch_ms_per_call")
    if prep is not None and launch is not None:
        out["prepare_plus_launch_ms"] = prep + launch
        out["dispatch_ms"] = ms("dispatch_ms_per_call")
    enc, dec, codec = ms("encode_ms_per_call"), ms("decode_ms_per_call"), \
        ms("codec_ms_per_call")
    if enc is not None and dec is not None and codec:
        out["codec_share_in_scopes"] = (enc + dec) / codec
        out["unscoped_ms_per_call"] = 1e3 * eng.scope_s(
            "unscoped") / eng.calls
    return out


if __name__ == "__main__":
    main()
