"""The comparison that decides ``correct``: numbers of the timed path
beside the plain reference's, each held to a limit of its own."""
import sys

import numpy as np


def leaf_norms(tree):
    """``{path: norms}`` of a tree in the benchmark's layout. A leaf under
    ``blocks`` is stacked over the layers and gives one norm a layer."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", p)) for p in path]
        leaf = jnp.asarray(leaf, jnp.float32)
        if keys[0] == "blocks":
            norms = jnp.sqrt(jnp.sum(jnp.square(leaf.reshape(len(leaf), -1)),
                                     axis=1))
        else:
            norms = jnp.sqrt(jnp.sum(jnp.square(leaf)))[None]
        out["/".join(keys)] = np.asarray(norms, np.float64)
    return out


def flat(norms):
    names = sorted(norms)
    return (np.concatenate([norms[n] for n in names]),
            [f"{n}[{i}]" for n in names for i in range(len(norms[n]))])


def norm_gap(program, reference, keep=None):
    """The gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: ``(the worst leaf's gap, the median leaf's gap, the
    worst leaf's name)``. ``keep`` (a mask over the flat leaves) leaves some
    out of both."""
    got, names = flat(program)
    want, _ = flat(reference)
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / scale
    kept = gap if keep is None else gap[keep]
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    worst = int(np.argmax(gap))
    return float(gap[worst]), float(np.median(kept)), names[worst]


def diff_norms(program, reference):
    """``{path: norms}`` of ``program - reference``; ``program`` may be a
    tree of host arrays."""
    import jax
    import jax.numpy as jnp
    return leaf_norms(jax.tree_util.tree_map(
        lambda p, r: jnp.asarray(p) - r, program, reference))


def diff_share_median(diff, reference):
    """The median leaf's norm of the difference as a share of the
    reference's norm of that leaf (or of the median leaf, if larger)."""
    got, _ = flat(diff)
    want, _ = flat(reference)
    return float(np.median(got / np.maximum(want, np.median(want))))


def moved_leaves(reference_grad, share=1e-3):
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's. The others (a key projection's bias under softmax) have a
    gradient of nought to rounding and move under Adam by round-off alone,
    so the change of the parameters is not compared on them."""
    want, _ = flat(reference_grad)
    return want >= share * np.median(want)


def verdict(numbers, limits, problems=()):
    """``(correct, table)``: every number at or under its limit, a limit for
    every number, and no ``problems`` (answers that never came or said the
    wrong thing). ``table`` rows are ``[name, number, limit]``."""
    table = [[name, float(value), limits.get(name)]
             for name, value in numbers.items()]
    ok = not problems and all(
        limit is not None and np.isfinite(value) and value <= limit
        for _, value, limit in table)
    return bool(ok), table


def report(table, problems=(), stream=None):
    """The numbers compared, each beside its limit, as the run's last lines
    on standard error."""
    stream = stream or sys.stderr
    for problem in problems:
        print(f"perfbench correct: {problem}", file=stream)
    for name, value, limit in table:
        mark = "ok" if limit is not None and value <= limit else "OVER"
        print(f"perfbench correct: {name} = {value:.6g} (limit {limit}) "
              f"{mark}", file=stream)
    stream.flush()
