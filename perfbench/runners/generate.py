"""Runner of the cells of kind ``generate``: a served decoder under a
traffic mix. One thread (this one) is the load: it sends what is due,
sweeps the results of everything in flight and stamps each new token with
its arrival, for a closed loop of callers or an open loop of arrivals
alike. The server is the program's own thread. The window opens after a
ramp of fixed length, on a system in steady state; requests due in the
window are followed to their terminal after it closes."""
import os
import shutil
import threading
import time

import numpy as np

from ..harness import check, device, spec, stats, tracing, traffic

SWEEP_S = 0.01      # pause between two sweeps of the results
SAMPLE_S = 0.25     # between two samples of the health snapshot
FOLLOW_S = 60.0     # how long a request due in the window is waited for


def run(cell, args, t_start, devices, peaks):
    adapter, reference = cell.adapter(), cell.reference()
    cfg, mix = cell.config, cell.traffic
    queue_dir = os.path.join(spec.ROOT, ".perfbench_queue", cell.name)
    shutil.rmtree(queue_dir, ignore_errors=True)
    os.makedirs(queue_dir)
    served = adapter.Served(cfg, reference.init_weights(cfg, args.seed),
                            "dir://" + queue_dir)
    if getattr(args, "fault", None):  # the tests' alone
        args.fault("built", served)
    spans = tracing.HostSpans()
    load = Load(served, mix, cfg["vocab_size"], args, spans)
    try:
        served.server.start()
        load.warm_up()
        out = load.drive(t_start)
    finally:
        described = device.describe(devices)
        served.release()
        shutil.rmtree(queue_dir, ignore_errors=True)
    log, t0, t1 = out["log"], out["t0"], out["t1"]
    due = stats.due_in(log, t0, t1)
    problems = [f"request {r['id']}: {r.get('error') or 'no terminal'}"
                if not r.get("done") else
                f"request {r['id']}: {len(r['tokens'])} tokens for "
                f"{r['max_new']} asked" for r in due if stats.failed(r)]
    numbers = compare(cfg, reference, args.seed, due, mix,
                      getattr(args, "control", None)) if due else {}
    if not due:
        problems.append("no request was due in the window")
    ok, table = check.verdict(numbers, cell.limits(), problems)

    worst_ms = (args.seconds + FOLLOW_S) * 1e3
    values = {
        "out_tokens_per_s": stats.tokens_in(log, t0, t1) / (t1 - t0),
        "ttft_p95_ms": stats.percentile(
            stats.ttft_ms(log, t0, t1, worst_ms), 95),
        "tpot_p95_ms": stats.percentile(stats.token_gaps_ms(log, t0, t1), 95),
        "setup_s": out["setup_s"]}
    ctx = {"cell": cell, "peaks": peaks, "chips": len(devices), "t0": t0,
           "t1": t1, "log": log, "spans": spans, "device": described,
           "slot_samples": out["slot_samples"], "worst_ms": worst_ms,
           "counters": out["counters"], "pending": out["pending"],
           "trace": None, "capture": out["capture"]}
    return {"correct": ok, "table": table, "problems": problems,
            "attempted": len(due), "failed": len(problems), "values": values,
            "ctx": ctx, "device": described}


def compare(cfg, reference, seed, due, mix, control=None):
    """The widest gap by which a served token's logit lies below the plain
    reference's best, over a sample of the requests that finished: the
    longest and others drawn from the seed. ``control`` (a dict, the tools'
    alone) is given the control's reading on the same sample."""
    done = [r for r in due if not stats.failed(r)]
    if not done:
        return {"served_logit_gap_max": float("inf")}
    sample = pick(done, int(mix["compare_requests"]), seed)
    tokens, chosen, mask = pack(sample, cfg["n_positions"])
    weights = reference.init_weights(cfg, seed)
    gaps = np.concatenate([np.asarray(reference.gaps_below_best(
        cfg, weights, tokens[i:i + 2], chosen[i:i + 2]))
        for i in range(0, len(tokens), 2)])
    if control is not None:
        from ..harness import plant
        control["served_logit_gap_max"] = max(
            plant.serve_control(cfg, reference, weights, tokens[i:i + 2],
                                mask[i:i + 2])
            for i in range(0, len(tokens), 2))
        control["served_tokens_compared"] = int(mask.sum())
    return {"served_logit_gap_max": float(np.max(gaps[mask]))}


def pick(done, count, seed):
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    order = traffic.rng(seed, 9).permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in order]


def pack(sample, limit):
    """Each request's prompt with its served tokens as one padded row, the
    token that was served after each position, and where those are."""
    width = min(limit, 128 * -(-max(len(r["prompt"]) + len(r["tokens"])
                                    for r in sample) // 128))
    tokens = np.zeros((len(sample), width), np.int32)
    chosen = np.zeros((len(sample), width), np.int32)
    mask = np.zeros((len(sample), width), bool)
    for i, r in enumerate(sample):
        row = list(r["prompt"]) + list(r["tokens"])
        tokens[i, :len(row)] = row
        first = len(r["prompt"]) - 1
        chosen[i, first:first + len(r["tokens"])] = r["tokens"]
        mask[i, first:first + len(r["tokens"])] = True
    return tokens, chosen, mask


class Load:
    """The one thread of load: callers or arrivals, and the sweep."""

    PLAN_AHEAD = 256  # requests drawn at a time

    def __init__(self, served, mix, vocab, args, spans):
        self.served, self.mix, self.vocab = served, mix, vocab
        self.args, self.spans = args, spans
        self.flight, self.log = {}, []
        self.plan, self.drawn = [], 0

    def _peek(self):
        """The next request of the plan, which grows as it is used up."""
        if not self.plan:
            self.plan = traffic.requests(self.mix, self.vocab, self.args.seed,
                                         self.PLAN_AHEAD, self.drawn)
            self.drawn += self.PLAN_AHEAD
        return self.plan[0]

    def _send(self, request, due, prefix="r"):
        uri = f"{prefix}{request['id']}"
        entry = dict(request, uri=uri, due=due, sent=time.perf_counter(),
                     tokens=[], token_times=[], done=False, error=None)
        self.served.send(uri, request["prompt"], request["max_new"])
        self.flight[uri] = entry
        return entry

    def _sweep(self):
        """Look at every request in flight once; returns those that ended."""
        ended = []
        for uri, entry in list(self.flight.items()):
            seen = self.served.poll(uri)
            if seen is None:
                continue
            tokens, done, error = seen
            now = time.perf_counter()
            fresh = tokens[len(entry["tokens"]):]
            entry["tokens"] += fresh
            entry["token_times"] += [now] * len(fresh)
            if done:
                entry["done"], entry["error"] = error is None, error
                entry["ended"] = now
                del self.flight[uri]
                self.served.forget(uri)
                ended.append(entry)
        return ended

    def warm_up(self):
        """One request through every prefill bucket that the mix's grid
        reaches and through the decode program, before anything is timed."""
        lengths = traffic.quantile_grid(self.mix["prompt_len"]["quantiles"],
                                        int(self.mix["grid"]))
        by_bucket = {}
        for n in lengths:
            by_bucket[self.served.bucket(int(n))] = int(n)
        for i, n in enumerate(sorted(by_bucket.values())):
            self._send({"id": i, "prompt": [1] * n, "max_new": 2}, 0.0,
                       prefix="warm")
        deadline = time.perf_counter() + 900
        while self.flight:
            self.served.server.check_health()
            if time.perf_counter() > deadline:
                raise RuntimeError("warm-up requests were never answered")
            for entry in self._sweep():
                if not entry["done"]:
                    raise RuntimeError(f"warm-up failed: {entry['error']}")
            time.sleep(SWEEP_S)

    def drive(self, t_start):
        mix, args = self.mix, self.args
        closed = mix["loop"] == "closed"
        undo = self.served.watch(self.spans) if args.trace else None
        capture, samples, next_sample = None, [], 0.0
        tracer, stopping = None, False
        start = time.perf_counter()
        t0 = start + float(mix["ramp_seconds"])
        t1 = t0 + args.seconds
        setup_s = t0 - t_start
        trace_at = t0 + min(2.0, args.seconds / 4)
        idle = [start] * int(mix.get("callers", 0))  # closed: free since
        pending = {}
        while True:
            now = time.perf_counter()
            self.served.server.check_health()
            if closed:
                while idle and now < t1:
                    self._peek()
                    self.log.append(self._send(self.plan.pop(0), idle.pop()))
            else:
                while start + self._peek()["due"] <= now:
                    r = self.plan.pop(0)
                    self.log.append(self._send(r, start + r["due"]))
            for entry in self._sweep():
                if closed:
                    idle.append(entry["ended"])
            now = time.perf_counter()
            if now >= next_sample:
                snap = self.served.snapshot()
                samples.append((now, snap["slots_occupied"]))
                for edge, at in (("start", t0), ("end", t1)):
                    if edge not in pending and now >= at:
                        pending[edge] = snap["queue_pending"]
                next_sample = now + SAMPLE_S
            # the profiler starts and stops on a thread of its own: writing
            # the trace takes seconds, in which the load has to go on
            if args.trace and capture is None and now >= trace_at:
                capture = tracing.Capture(
                    os.path.join(spec.ROOT, ".perfbench_trace"))
                tracer = threading.Thread(target=capture.start)
                tracer.start()
            elif (capture is not None and not stopping
                  and len(capture.sync) == 1
                  and now >= trace_at + float(mix["trace_seconds"])):
                stopping = True
                tracer = threading.Thread(target=capture.stop)
                tracer.start()
            if now >= t1:
                waiting = [e for e in self.flight.values()
                           if t0 <= e["due"] < t1]
                if not waiting or now >= t1 + FOLLOW_S:
                    break
            time.sleep(SWEEP_S)
        if capture is not None:
            tracer.join()
            if len(capture.sync) == 1:
                capture.stop()
        if undo:
            undo()
        return {"log": self.log, "t0": t0, "t1": t1, "setup_s": setup_s,
                "slot_samples": samples, "capture": capture,
                "counters": dict(self.served.server.counters),
                "pending": pending}
