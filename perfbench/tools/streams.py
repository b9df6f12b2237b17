#!/usr/bin/env python3
"""Every stream of one run compared alone, in one process on the chip: a
served cell's limit has to hold every sound stream and not only the three
that a run draws (PERF.md section 7.8 (a): two of 140 streams of one plan
read over a limit that seventeen runs' samples had all passed). The cell
runs once with its own traffic, and every request that finished (ramp,
window and tail alike: more than one whole cycle of the plan) is then held
to the plain reference by itself. One JSON line a stream, in the form of
``tools/readings.py``'s ``program`` lines, with the verdict of
``check.verdict`` at the cell's committed limits; ``limits/readings/
<cell>.jsonl`` keeps them beside the runs' readings.

    python3 perfbench/tools/streams.py --workload <cell> --seed 100 \\
        [--seconds 40] [--most 64]
"""
import argparse
import contextlib
import io
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run  # noqa: E402
from perfbench.harness import check, spec, stats  # noqa: E402
from perfbench.runners import generate  # noqa: E402


def main(argv=None, cell=None, allow_cpu=False):
    """``cell`` and ``allow_cpu`` are the tests' alone: a cell at a tiny
    size, the CPU in the chip's place."""
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=100)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--most", type=int, default=64)
    args = p.parse_args(argv)
    cell = cell or spec.Cell(args.workload)
    limits, cfg = cell.limits(), cell.config
    probe = {}
    with contextlib.redirect_stdout(io.StringIO()):
        line = run.main(["--workload", cell.name, "--seed", str(args.seed),
                         "--seconds", str(args.seconds), "--trace", "0"],
                        probe=probe, cell=cell, allow_cpu=allow_cpu)
    done = [r for r in probe["ctx"]["log"]
            if r.get("done") and not stats.failed(r)][:args.most]
    reference = cell.reference()
    weights = reference.init_weights(cfg, args.seed)
    for r in done:
        tokens, chosen, mask = generate.pack([r], cfg["n_positions"])
        gaps = np.asarray(reference.gaps_below_best(cfg, weights, tokens,
                                                    chosen))
        numbers = {"served_logit_gap_max": float(np.max(gaps[mask]))}
        ok, table = check.verdict(numbers, limits)
        print(json.dumps(dict(
            kind="program", seed=args.seed, numbers=numbers, correct=ok,
            over=[n for n, value, limit in table if not value <= limit],
            stream=r["id"], prompt=len(r["prompt"]), served=len(r["tokens"]),
            gap_mean=float(np.mean(gaps[mask])),
            gap_p99=float(np.quantile(gaps[mask], 0.99)),
            run_correct=line["correct"])), flush=True)


if __name__ == "__main__":
    main()
