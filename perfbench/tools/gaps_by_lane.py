#!/usr/bin/env python3
"""A traced run's idle gaps named by the serve loop's own lane, and the
loop's iteration by its records: one ``--trace 1`` run of a serving cell in
this process, through ``run.main(probe=...)`` as ``trace_cost.py`` makes it.

``breakdown.idle_gaps`` names a gap by the shortest span over its middle on
any thread, so a write on the result publisher's thread or a stretch of a
request's life can name a gap that the loop was never in. Here a gap is
named by the shortest record of the lane that emits ``serve.step`` (records
of other lanes and of no lane, ``lane`` ``None``, are left out), and both
tables are printed side by side. Beside them: milliseconds a ``serve.step``
of every name under it by ``parent`` and of its self time (``PERF.md``
section 5's host table), the chunked cells' three prefill numbers read
through ``harness/records.py``, and the traced run's end-to-end values.

    python3 perfbench/tools/gaps_by_lane.py --workload <cell> --seed <n> --seconds 40
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import run  # noqa: E402
from perfbench.harness import records, tracing  # noqa: E402


def gaps_by_lane(ctx, recs):
    """``[[name, idle seconds], ...]``, longest first, of the first
    device's idle gaps in the traced stretch, each named by the loop's
    lane; ``None`` without a device trace or a ``serve.step``."""
    trace, found = ctx.get("trace"), records.traced_steps(ctx)
    if not trace or found is None:
        return None
    lane = records.lane_of(found[1])
    offset = trace["t0"] - ctx["capture"].sync[0]
    spans = [(r.name, r.start + offset, r.seconds) for r in recs
             if r.lane == lane]
    busy = trace["devices"][sorted(trace["devices"])[0]]["busy"]
    named = tracing.name_gaps(
        tracing.gaps(busy, trace["t0"], trace["t1"]), spans)
    return sorted(([k, v] for k, v in named.items()), key=lambda kv: -kv[1])


def iteration_table(ctx):
    """Milliseconds a ``serve.step`` over the traced stretch: the step, its
    self time, and every name under it (a name at depth two or more, such
    as ``serve.claim`` inside ``serve.admit``, is marked with its depth and
    is part of its parent's figure)."""
    found = records.traced_steps(ctx)
    if found is None:
        return None
    _, steps, kids = found
    table = {}

    def walk(rec, depth):
        for child in kids.get(rec.id, ()):
            key = child.name if depth == 1 else f"{child.name} (depth {depth})"
            table[key] = table.get(key, 0.0) + child.seconds
            walk(child, depth + 1)
    for step in steps:
        walk(step, 1)
    n = len(steps)
    out = {"steps": n,
           "serve.step": 1e3 * sum(s.seconds for s in steps) / n,
           "self": 1e3 * sum(records.self_seconds(s, kids) for s in steps) / n}
    out.update({k: 1e3 * v / n for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])})
    out["self, by the child before it"] = self_by_place(steps, kids)
    return out


def self_by_place(steps, kids):
    """Where a ``serve.step``'s self time lies: milliseconds a step between
    the end of each direct child and the start of the next (``start`` for
    the stretch before the first), by the name of the child before it."""
    table = {}
    for step in steps:
        at, before = step.start, "start"
        for child in sorted(kids.get(step.id, ()), key=lambda r: r.start):
            if child.start > at:
                table[before] = table.get(before, 0.0) + child.start - at
            if child.start + child.seconds > at:
                at, before = child.start + child.seconds, child.name
        table[before] = table.get(before, 0.0) + max(
            0.0, step.start + step.seconds - at)
    return {k: 1e3 * v / len(steps) for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])}


def report(line, ctx, values):
    """What the tool prints, from a traced run's result line, its context
    and its end-to-end values."""
    recs = records.of(ctx)
    out = {"traced_end_to_end": values, "correct": line["correct"],
           "per_layer": {k: v["value"] for k, v in line["metrics"].items()},
           "idle_gaps": (line.get("breakdown") or {}).get("idle_gaps")}
    if recs is not None:
        window = ctx["t0"], ctx["t1"]
        out.update(
            records=len(recs), loop_lane=records.lane_of(recs),
            idle_gaps_by_lane=gaps_by_lane(ctx, recs),
            iteration_ms_per_step=iteration_table(ctx),
            prefill={
                "wait_p95_ms": records.p95_ms(ctx, "serve.prefill_wait"),
                "waits": len(records.starting_in(
                    recs, "serve.prefill_wait", *window)),
                "pending_mean": records.snapshot_mean(
                    ctx, "prefills_pending"),
                "chunk_fill_pct": records.fill_pct(
                    ctx, "serve.prefill_chunk", "rows", "width")})
    return out


def main():
    probe = {}
    line = run.main(sys.argv[1:] + ["--trace", "1"], probe=probe)
    print(json.dumps(report(line, probe["ctx"], probe["values"])),
          flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
