"""The one general generator of traffic. A traffic file gives each
distribution as quantiles; the generator takes evenly spaced quantiles of it
for a cycle of ``grid`` requests, puts them in an order fixed by the file's
``order_seed``, and lets ``--seed`` choose only where in the cycle a run
starts, and the token ids. Every seed therefore offers the same requests at
the same gaps, the same neighbours beside each, from another starting
point: a tail does not move because one run drew three long prompts more, or
a burst that another run did not have."""
import numpy as np


def rng(seed, stream):
    """Independent streams of one ``--seed`` (any whole number up to 2**32)."""
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


def quantile_grid(points, n):
    """``n`` evenly spaced quantiles, ``(i + 0.5) / n``, of the distribution
    whose inverse CDF runs straight between ``points`` = ``[[q, value], ...]``,
    rounded to whole numbers."""
    qs, vs = zip(*points)
    if list(qs) != sorted(qs) or qs[0] != 0 or qs[-1] != 1:
        raise ValueError(f"quantiles must rise from 0 to 1: {points}")
    at = (np.arange(n) + 0.5) / n
    return np.rint(np.interp(at, qs, vs)).astype(np.int64)


def exponential_grid(rate, n):
    """``n`` evenly spaced quantiles of the gap between Poisson arrivals at
    ``rate`` a second, scaled so that they add up to exactly ``n / rate``."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (n / rate) / gaps.sum()


def cycle(values, mix, stream):
    """``values`` in the order that the traffic file fixes for them."""
    return np.asarray(values)[rng(mix.get("order_seed", 0),
                                  stream).permutation(len(values))]


def from_cycle(values, first, count):
    """``count`` values of the endless repetition of ``values``, from
    position ``first`` on."""
    return np.asarray(values)[(first + np.arange(count)) % len(values)]


def start_of(mix, seed):
    """Where in the cycle a run with ``seed`` starts."""
    return int(rng(seed, 5).integers(int(mix["grid"])))


def requests(mix, vocab, seed, count, first=0):
    """Requests ``first`` to ``first + count`` of a run with ``seed``: prompt
    token ids, output length and, for an open loop, the time each is due
    (seconds from the generator's start; a cycle of ``grid`` requests lasts
    exactly ``grid / rate_per_s``)."""
    n = int(mix["grid"])
    at = start_of(mix, seed) + first
    prompt_len = from_cycle(cycle(quantile_grid(
        mix["prompt_len"]["quantiles"], n), mix, 1), at, count)
    output_len = from_cycle(cycle(quantile_grid(
        mix["output_len"]["quantiles"], n), mix, 2), at, count)
    ids = rng(seed, 3 + 16 * (first + 1))
    out = [{"id": first + i,
            "prompt": ids.integers(0, vocab, int(p)).tolist(),
            "max_new": int(o)}
           for i, (p, o) in enumerate(zip(prompt_len, output_len))]
    if mix["loop"] == "open":
        gaps = cycle(exponential_grid(float(mix["rate_per_s"]), n), mix, 4)
        before = from_cycle(gaps, start_of(mix, seed), first).sum()
        due = before + np.cumsum(from_cycle(gaps, at, count))
        for r, t in zip(out, due):
            r["due"] = float(t)
    return out


def classification_rows(mix, vocab, seed, rows):
    """``rows`` padded token rows ``[rows, seq_len]`` (0 pads) with real
    lengths from the mix's cycle, and a label each. The labels are spread
    evenly at the mix's ``positive_share``, so that every batch, and every
    chip's shard of it, holds the same count of each class whatever the
    seed; the second token's parity carries the label, so the tokens decide
    it."""
    seq = int(mix["seq_len"])
    lengths = from_cycle(cycle(quantile_grid(
        mix["lengths"]["quantiles"], int(mix["grid"])), mix, 1),
        start_of(mix, seed), rows)
    tokens = rng(seed, 3).integers(1, vocab - 1, (rows, seq))
    share = float(mix["positive_share"])
    at = np.arange(rows)
    labels = (np.floor((at + 1) * share) - np.floor(at * share)).astype(
        np.int64)
    tokens[:, 1] += (tokens[:, 1] % 2) != labels
    tokens[np.arange(seq)[None, :] >= lengths[:, None]] = 0
    return tokens.astype(np.int32), labels
