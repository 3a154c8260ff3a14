#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``.  Set-up builds the cell's program and
inputs from the seed and warms every shape; the window then runs the
cell's traffic for ``--seconds``; the window's answers are checked
against the plain reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared
with its limit, which also end standard error.

Exits nonzero and prints no result when JAX finds no accelerator, or
fewer chips than the cell asks for, or when anything else fails.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness
    try:
        result = harness.run(ROOT / "BENCHMARK.json", args.workload,
                             args.seed, args.seconds, bool(args.trace),
                             log=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
