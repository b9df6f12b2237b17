"""Rates and tails from a log of requests, as the client saw them. A
request is a dict with ``due`` (when it should have been sent), ``sent``,
``token_times`` (arrival of each output token at the client), ``done``
(terminal seen, without error) and ``max_new``; all times on one clock."""
import math


def percentile(values, p):
    """The ``p``-th percentile by nearest rank (no interpolation: a tail is
    a value that some request really saw)."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(p / 100.0 * len(ordered)) - 1))]


def due_in(log, t0, t1):
    return [r for r in log if t0 <= r["due"] < t1]


def failed(r):
    return (not r.get("done")) or len(r["token_times"]) != r["max_new"]


def ttft_ms(log, t0, t1, worst_ms):
    """Time to first token of every request due in the window, from when it
    was due; a failed request counts as ``worst_ms``."""
    return [worst_ms if failed(r) or not r["token_times"]
            else (r["token_times"][0] - r["due"]) * 1e3
            for r in due_in(log, t0, t1)]


def token_gaps_ms(log, t0, t1):
    """Every gap between consecutive tokens of the requests due in the
    window."""
    return [(b - a) * 1e3 for r in due_in(log, t0, t1)
            for a, b in zip(r["token_times"], r["token_times"][1:])]


def tokens_in(log, t0, t1):
    """Output tokens that reached a client inside the window, whatever
    request they belong to."""
    return sum(1 for r in log for t in r["token_times"] if t0 <= t < t1)


def late_ms(log, t0, t1):
    return [(r["sent"] - r["due"]) * 1e3 for r in due_in(log, t0, t1)
            if r.get("sent") is not None]


def mean(values):
    return sum(values) / len(values) if values else None
