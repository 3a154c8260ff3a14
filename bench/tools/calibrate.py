#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process.

    python3 bench/tools/calibrate.py --workload mimo.slot --seeds 12 \
        --control-seeds 3 --seconds 5 [--out FILE]

Runs the cell as the benchmark does on ``--seeds`` seeds, then its
configuration's lower-precision control on ``--control-seeds`` other
seeds, and prints every number compared.  A limit sits above the largest
sound reading and below the smallest control reading.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--base-seed", type=int, default=2**31 + 40_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness

    rows = []
    runs = [(False, args.base_seed + i) for i in range(args.seeds)]
    runs += [(True, args.base_seed + 1000 + i)
             for i in range(args.control_seeds)]
    for control, seed in runs:
        r = harness.run(ROOT / "BENCHMARK.json", args.workload, seed,
                        args.seconds, False, control=control,
                        log=lambda s: None)
        row = {"control": control, "seed": seed, "correct": r["correct"],
               "checks": {k: v["value"] for k, v in r["checks"].items()},
               "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["checks"]:
        sound = [r["checks"][name] for r in rows if not r["control"]]
        ctrl = [r["checks"][name] for r in rows if r["control"]]
        summary[name] = {"sound_max": max(sound), "control_min":
                         min(ctrl) if ctrl else None,
                         "sound": sound, "control": ctrl}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "rows": rows, "summary": summary},
            indent=1))


if __name__ == "__main__":
    main()
