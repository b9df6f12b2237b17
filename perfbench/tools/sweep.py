#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the cell's mix at
each of a few rates, one process, one line a rate. The knee is the highest
rate at which nothing is shed or expires and the queue is no deeper at the
window's end than at its start; the cell's ``rate_per_s`` is four fifths of
it (``perfbench/README.md`` keeps the table).

    python3 perfbench/tools/sweep.py --workload gpt2_small.chat_open \\
        --rates 2,3,4,5,6,8 --seconds 20
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
from perfbench.harness import spec  # noqa: E402


def looked_at(probe):
    """What the knee's rule reads of one rate's run."""
    ctx = probe["ctx"]
    return {"counters": ctx.get("counters"),
            "queue_pending": ctx.get("pending"),
            "out_tokens_per_s": probe["values"].get("out_tokens_per_s"),
            "slots_busy_mean": spec.metric_reader("slots_busy_mean.chat")(ctx),
            "gen_late_p95_ms": spec.metric_reader("gen_late_p95_ms")(ctx)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=77)
    args = p.parse_args()
    for rate in (float(r) for r in args.rates.split(",")):
        cell = spec.Cell(args.workload)
        cell.traffic["rate_per_s"] = rate
        probe = {}
        with contextlib.redirect_stdout(io.StringIO()):
            line = run.main(["--workload", cell.name, "--seed",
                             str(args.seed), "--seconds", str(args.seconds),
                             "--trace", "0"], cell=cell, probe=probe)
        print(json.dumps({
            "rate_per_s": rate, "attempted": line["attempted"],
            "failed": line["failed"], "correct": line["correct"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            **looked_at(probe)}), flush=True)


if __name__ == "__main__":
    main()
