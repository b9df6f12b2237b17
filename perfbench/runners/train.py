"""Runner of the cells of kind ``train``: one classifier, built once from
the seed, driven through its first steps (which the reference follows) and
then handed to the window, which repeats short epochs through the model's
own ``fit`` until ``--seconds`` have passed."""
import gc
import os
import time

from ..harness import check, device, spec, tracing, traffic


def run(cell, args, t_start, devices, peaks):
    import jax
    adapter, reference = cell.adapter(), cell.reference()
    cfg, mix = cell.config, cell.traffic
    chips = len(devices)
    batch = int(mix["batch_per_chip"]) * chips
    seq, follow = int(mix["seq_len"]), int(mix["follow_steps"])
    epoch_rows = batch * int(mix["steps_per_epoch"])
    tokens, labels = traffic.classification_rows(
        mix, cfg["vocab_size"], args.seed, (follow + 1) * batch + epoch_rows)
    head, body = (follow + 1) * batch, slice((follow + 1) * batch, None)

    # -- set-up: one object, driven through its first steps -----------------
    clf = adapter.build(cfg, devices)
    adapter.load(clf, reference.init_weights(cfg, args.seed), tokens[:batch])
    fault = getattr(args, "fault", None)  # the tests' alone
    if fault:
        fault("built", clf)
    losses, program = [], {}
    for step in range(follow + 1):
        rows = slice(step * batch, (step + 1) * batch)
        losses += adapter.fit(clf, tokens[rows], labels[rows], batch, 1)
        if step == 0:
            # to the host: the reference's gradient exists only once the
            # program's state is gone, and the two are compared leaf by leaf
            program["grad_tree"] = jax.device_get(adapter.first_gradient(
                clf, cfg["optimizer"]["beta1"]))
            program["grad"] = check.leaf_norms(program["grad_tree"])
        if step == follow - 1:
            start = reference.init_weights(cfg, args.seed)
            program["change"] = check.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, adapter.parameters(clf), start))
            del start
    program["losses"] = losses[:follow]
    if fault:
        fault("read", program)
    # the epoch-sized feed once, so that nothing is built inside the window
    adapter.fit(clf, tokens[body], labels[body], batch, 1)

    # -- the window ----------------------------------------------------------
    spans = tracing.HostSpans()
    capture, hooks = None, _watch(spans) if args.trace else (lambda: None)
    per_epoch = int(mix["steps_per_epoch"])
    steps, epochs = 0, 0
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        if args.trace and epochs == 1:
            capture = tracing.Capture(os.path.join(spec.ROOT, ".perfbench_trace"))
            capture.start()
        adapter.fit(clf, tokens[body], labels[body], batch, 1)
        if capture is not None and len(capture.sync) == 1:
            capture.stop()
        steps, epochs = steps + per_epoch, epochs + 1
    t1 = time.perf_counter()
    hooks()
    described = device.describe(devices)

    # -- the reference, once the program's state is freed -------------------
    adapter.release(clf)
    del clf
    gc.collect()
    want = reference.follow(
        cfg, reference.init_weights(cfg, args.seed),
        [(tokens[i * batch:(i + 1) * batch], labels[i * batch:(i + 1) * batch])
         for i in range(follow)], rows=int(mix["reference_rows"]))
    numbers = compare(program, want, reference.init_weights(cfg, args.seed))
    limits = cell.limits()
    recorded = {k: v for k, v in numbers.items() if k not in limits}
    ok, table = check.verdict({k: v for k, v in numbers.items()
                               if k in limits}, limits)

    ctx = {"cell": cell, "peaks": peaks, "chips": chips, "t0": t0, "t1": t1,
           "steps": steps, "tokens": steps * batch * seq, "seq": seq,
           "batch": batch, "spans": spans, "device": described,
           "trace": None, "capture": capture}
    values = {"train_tokens_per_s": ctx["tokens"] / (t1 - t0),
              "setup_s": setup_s}
    return {"correct": ok, "table": table, "problems": [],
            "recorded": recorded, "attempted": steps, "failed": 0, "values": values, "ctx": ctx,
            "device": described}


def compare(program, want, start):
    """A training cell's numbers: each followed step's loss, the first
    gradient's norm and the parameters' change after the followed steps by
    the worst leaf and by the median leaf, and the median leaf's share of
    the first gradient that differs from the reference's (a norm hardly
    moves under rounding that has no bias, whatever the precision; the
    difference does). ``limits/<cell>.json`` says which of them ``correct``
    holds to a limit, and why not the others."""
    import jax
    grad = check.leaf_norms(want["first_grad"])
    change = check.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, want["params"], start))
    numbers = {f"loss_gap_step{i + 1}": abs(got - ref) / abs(ref)
               for i, (got, ref) in enumerate(zip(program["losses"],
                                                  want["losses"]))}
    for name, got, ref, keep in (
            ("grad", program["grad"], grad, None),
            ("change", program["change"], change, check.moved_leaves(grad))):
        worst, median, _ = check.norm_gap(got, ref, keep)
        numbers[f"{name}_norm_gap"] = worst
        numbers[f"{name}_norm_gap_median"] = median
    numbers["grad_diff_median"] = check.diff_share_median(
        check.diff_norms(program["grad_tree"], want["first_grad"]), grad)
    return numbers


def _watch(spans):
    """Spans for the traced run: the program's own (``train_step`` and
    whatever else it offers its hooks), and the benchmark's around the
    feed's ``next`` (the time a step waits for data). Returns the undo."""
    from analytics_zoo_tpu.common import utils as program_utils
    from analytics_zoo_tpu.feature import device_feed
    program_utils.span_hooks.append(spans.add)
    plain = device_feed.DeviceFeed.__next__
    device_feed.DeviceFeed.__next__ = spans.timed("feed.next", plain)

    def undo():
        device_feed.DeviceFeed.__next__ = plain
        program_utils.span_hooks.remove(spans.add)
    return undo
