#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process on the
chip: the program's numbers over many seeds (the lower reading), the
control's over a few (the upper one), and the faults that a training cell
can have. One JSON line each, with the verdict of ``check.verdict`` at the
cell's committed limits: ``correct`` has to be true on every ``program`` line
and false on every other. ``perfbench/README.md`` says how the limits follow
from the readings, and ``limits/readings/<cell>.jsonl`` keeps the lines.

    python3 perfbench/tools/readings.py --workload <cell> --seeds 12 \\
        --control-seeds 3 [--seconds 1] [--first-seed 100]
"""
import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run  # noqa: E402
from perfbench.harness import check, plant, spec  # noqa: E402


def quiet_run(cell, seed, seconds, **planted):
    with contextlib.redirect_stdout(io.StringIO()):
        return run.main(["--workload", cell.name, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        **planted)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=100)
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args()
    cell = spec.Cell(args.workload)
    limits = cell.limits()

    def say(kind, seed, numbers, **more):
        """One reading, through the verdict that a run's numbers get."""
        ok, table = check.verdict({k: v for k, v in numbers.items()
                                   if k in limits}, limits)
        print(json.dumps(dict(
            kind=kind, seed=seed, numbers=numbers, correct=ok,
            over=[name for name, value, limit in table
                  if not value <= limit], **more)), flush=True)
    for i in range(args.seeds):
        seed, control = args.first_seed + i * 7919, {}
        want = cell.kind == "generate" and i < args.control_seeds
        line = quiet_run(cell, seed, args.seconds,
                         control=control if want else None)
        numbers = {k: v["value"] for k, v in line["compared"].items()
                   if k != "problems"}
        numbers.update(line.get("recorded", {}))
        say(kind="program", seed=seed, numbers=numbers,
            run_correct=line["correct"], attempted=line["attempted"],
            failed=line["failed"], metrics=line["metrics"])
        if want:
            say(kind="control", seed=seed, numbers=control)
    if cell.kind != "train":
        return
    for i in range(args.control_seeds):
        seed = args.first_seed + i * 7919
        say(kind="control", seed=seed,
            numbers=plant.train_reference_in_place(
                cell, seed, cell.chips, precision="fp8"))
        say(kind="fault:unchanged_state(losses)", seed=seed,
            numbers=plant.train_reference_in_place(cell, seed, cell.chips,
                                                   frozen=True))
        if cell.chips > 1:
            say(kind="fault:exchange_left_out", seed=seed,
                numbers=plant.train_reference_in_place(
                    cell, seed, cell.chips,
                    shard_rows=int(cell.traffic["batch_per_chip"])))
        if cell.chips > 1:  # in the reference: no second program to compile
            say(kind="fault:half_batch", seed=seed,
                numbers=plant.train_reference_in_place(
                    cell, seed, cell.chips, shard_rows=cell.chips * int(
                        cell.traffic["batch_per_chip"]) // 2))
            continue
        line = quiet_run(cell, seed, args.seconds, fault=plant.half_batch)
        say(kind="fault:half_batch", seed=seed,
            numbers={**{k: v["value"] for k, v in line["compared"].items()},
                     **line.get("recorded", {})})


if __name__ == "__main__":
    main()
