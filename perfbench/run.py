#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``, in a process of its own:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; its last key holds every number that ``correct`` compared
beside its limit, and the same numbers are the last lines of standard
error. No TPU, fewer chips than the cell asks for, or a device that
``peaks.json`` lacks: exit code 2 and no result."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import check, device, spec, tracing  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def result_line(cell, args, out):
    """The run's result as the contract's object."""
    ctx, values = out["ctx"], dict(out["values"])
    described = dict(out["device"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        capture = ctx.get("capture")
        if capture is not None:
            ctx["trace"] = tracing.reduce(
                tracing.read_planes(capture.path()), capture.sync,
                ctx["spans"].spans)
        ctx["values"] = values
        metrics = {}
        for m in cell.per_layer():
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if ctx.get("trace"):
            described["busy_s"] = ctx["trace"]["busy_s"]
            described["window_s"] = ctx["trace"]["window_s"]
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    line["metrics"], line["device"] = metrics, described
    if ctx.get("trace"):
        line["breakdown"] = ctx["trace"]["breakdown"]
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in out["table"]}
    if out["problems"]:
        line["compared"]["problems"] = out["problems"][:20]
    if out.get("recorded"):  # numbers read beside the comparison, unheld
        line = dict(recorded=out["recorded"], **line)
    return line


def main(argv=None, cell=None, allow_cpu=False, fault=None, control=None,
         probe=None):
    """All but ``argv`` are the tests' and the tools' alone: a cell at a tiny
    size or another rate, the CPU in the chip's place, a fault planted under
    the timed path, a dict that is given the control's reading on the run's
    own sample, and one that is given the run's ``ctx`` and ``values`` for a
    tool to look at."""
    args = parse(argv)
    args.fault, args.control = fault, control
    cell = cell or spec.Cell(args.workload)
    device.place_compile_cache()
    devices, peaks = device.require_chips(cell.chips, allow_cpu=allow_cpu)
    out = cell.runner().run(cell, args, T_START, devices, peaks)
    line = result_line(cell, args, out)
    if probe is not None:
        probe.update(ctx=out["ctx"], values=out["values"])
    sys.stdout.flush()
    check.report(out["table"], out["problems"])
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
    sys.exit(0)
