"""The control and the faults of "how ``correct`` is decided", kept where
the tests (at a tiny size) and ``tools/readings.py`` (on the chip, at the
cell's own size) both find them. A fault hook is ``fault(stage, object)``;
``run.main`` hands it the program's object once it is built."""
import numpy as np

from . import check, traffic


def _followed(cell, seed, chips):
    cfg, mix = cell.config, cell.traffic
    batch = int(mix["batch_per_chip"]) * chips
    steps = int(mix["follow_steps"])
    tokens, labels = traffic.classification_rows(
        mix, cfg["vocab_size"], seed, (steps + 1) * batch
        + batch * int(mix["steps_per_epoch"]))
    return [(tokens[i * batch:(i + 1) * batch],
             labels[i * batch:(i + 1) * batch]) for i in range(steps)]


def train_reference_in_place(cell, seed, chips=1, precision="highest",
                             shard_rows=None, frozen=False):
    """A training cell's numbers with the plain reference in the program's
    place, on the batches that a run with ``seed`` follows: at a lower
    ``precision`` (the control), with each step taken on the first
    ``shard_rows`` rows alone, one chip's shard (the exchange between chips
    left out), or ``frozen``, the parameters left as they were after every
    step (what a state returned unchanged does to the losses; its gradient
    as the optimizer's state shows it is nought, which reads 1)."""
    import jax
    from ..runners import train
    ref, cfg = cell.reference(), cell.config
    rows = int(cell.traffic["reference_rows"])
    batches = _followed(cell, seed, chips)
    want = ref.follow(cfg, ref.init_weights(cfg, seed), batches, rows=rows)
    seen = batches if shard_rows is None else [
        (t[:shard_rows], y[:shard_rows]) for t, y in batches]
    still = dict(cfg, optimizer=dict(cfg["optimizer"], learning_rate=0.0))
    got = ref.follow(still if frozen else cfg, ref.init_weights(cfg, seed),
                     seen, precision, rows=min(rows, shard_rows or rows))
    start = ref.init_weights(cfg, seed)
    program = {"losses": got["losses"], "grad_tree": got["first_grad"],
               "grad": check.leaf_norms(got["first_grad"]),
               "change": check.leaf_norms(jax.tree_util.tree_map(
                   lambda a, b: a - b, got["params"], start))}
    return train.compare(program, want, start)


def unchanged_state(stage, clf):
    """A train step that returns its state as it got it."""
    if stage != "built":
        return
    import jax
    import jax.numpy as jnp
    est = clf.model.get_estimator()
    est._build_train_step = lambda: jax.jit(
        lambda params, opt, state, rng, x, y:
        (params, opt, state, jnp.float32(0.6931)))


def half_batch(stage, clf):
    """Half of every batch left out, the mean taken over the rest."""
    if stage != "built":
        return
    plain = clf.fit

    def fit(tokens, labels, batch_size=32, epochs=1, **kw):
        keep = np.arange(len(tokens)) % batch_size < batch_size // 2
        return plain(tokens[keep], labels[keep], batch_size=batch_size // 2,
                     epochs=epochs, **kw)
    clf.fit = fit


def altered_token(stage, served):
    """One slot's token altered where it is produced, every step."""
    if stage != "built":
        return
    server = served.server
    plain = server._fetch_tokens

    def fetch(nxt):
        out = np.array(plain(nxt))
        if out.ndim == 1:
            out[0] = (out[0] + 1) % served.lm.vocab_size
        return out
    server._fetch_tokens = fetch


def serve_control(cfg, reference, weights, tokens, mask):
    """The served cell's number with the reference at float8 in the
    program's place: at each served position of the same prompts and
    tokens, how far the token that float8 puts first lies below the
    reference's best."""
    first = np.asarray(reference.first_choice(cfg, weights, tokens, "fp8"))
    gaps = np.asarray(reference.gaps_below_best(cfg, weights, tokens, first))
    return float(np.max(gaps[mask]))
