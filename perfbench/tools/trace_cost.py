#!/usr/bin/env python3
"""What tracing costs when it is on: one ``--trace 1`` run of a cell in this
process, through ``run.main(probe=...)``, printing what the traced result
line leaves out — the run's end-to-end values, read while the program's
span hooks listened and the profiler took its stretch — beside the
per-layer metrics. Set them beside ``--trace 0`` runs of the same cell on
the same machine (``run.py`` itself, one process each).

    python3 perfbench/tools/trace_cost.py --workload <cell> --seed <n> --seconds 40
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run  # noqa: E402


def main():
    probe = {}
    line = run.main(sys.argv[1:] + ["--trace", "1"], probe=probe)
    ctx = probe["ctx"]
    capture = ctx.get("capture")
    stretch = {}
    if capture is not None and len(capture.sync) >= 2:
        # the traced stretch on perf_counter, and the loop's spans in it:
        # host milliseconds a step times steps, over the stretch, is the
        # share of it in which a serial loop leaves the device idle
        t0, t1 = capture.sync[0], capture.sync[-1]
        stretch = {"seconds": t1 - t0,
                   "steps": ctx["spans"].count("serve.step", t0, t1)
                   or ctx["spans"].count("train_step", t0, t1),
                   "host_s": {name: ctx["spans"].total((name,), t0, t1)
                              for name in ("serve.step",
                                           "profile.serving.fetch",
                                           "profile.serving.dispatch",
                                           "serve.post", "serve.admit",
                                           "train.feed_wait")}}
    print(json.dumps({
        "traced_end_to_end": probe["values"], "correct": line["correct"],
        "spans": len(ctx["spans"].spans), "stretch": stretch,
        "per_layer": {k: v["value"] for k, v in line["metrics"].items()},
        "breakdown": line.get("breakdown")}), flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
