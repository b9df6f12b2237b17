#!/usr/bin/env python3
"""Repeat a cell's runs as the bounds are set from them: ``--sets`` sets of
``--runs`` runs, the same seeds in every set, each run a new process of
``run.py`` (this parent never touches JAX, so the chip is the child's). For
each end-to-end metric prints each set's median and spread, the distance
between the first and the third quartile (``statistics.quantiles(n=4)``) as
a share of the median, leaving out the first run of the first set for
``setup_s`` (it compiles), and beside them the bound of ``BENCHMARK.json``.

    python3 perfbench/tools/spread.py --workload <cell> --seconds 40 \\
        [--runs 6] [--sets 2] [--first-seed 3000000000] [--out runs.jsonl]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--first-seed", type=int, default=3000000000)
    p.add_argument("--out")
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    rows = []
    for k in range(args.sets):
        for i in range(args.runs):
            seed = args.first_seed + 104729 * i
            done = subprocess.run(
                [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", "0"],
                cwd=ROOT, capture_output=True, text=True)
            if done.returncode:
                sys.stderr.write(done.stderr[-4000:])
                raise SystemExit(f"run failed: set {k} seed {seed}")
            line = json.loads(done.stdout.strip().splitlines()[-1])
            row = {"set": k, "run": i, "seed": seed,
                   "correct": line["correct"],
                   "attempted": line["attempted"], "failed": line["failed"],
                   "memory_peak_bytes": line["device"]["memory_peak_bytes"],
                   "compared": {n: v["value"]
                                for n, v in line["compared"].items()
                                if isinstance(v, dict)},
                   **{n: m["value"] for n, m in line["metrics"].items()}}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    for name in bounds:
        if name not in rows[0]:
            continue
        for k in range(args.sets):
            values = [r[name] for r in rows if r["set"] == k
                      and not (name == "setup_s" and k == 0 and r["run"] == 0)]
            if len(values) >= 2:
                print(f"{args.workload} {name} set {k}: median "
                      f"{statistics.median(values):.6g} spread "
                      f"{spread(values):.5f} (bound {bounds[name]}) "
                      f"min {min(values):.6g} max {max(values):.6g}",
                      flush=True)
    wrong = [r for r in rows if not r["correct"]]
    print(f"{args.workload}: {len(rows)} runs, {len(wrong)} not correct",
          flush=True)


if __name__ == "__main__":
    main()
