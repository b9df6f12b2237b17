"""Decode steps that went to the device ahead of the fold of the step before
them (the program's ``serve.step_ahead`` spans), as a share of the loop's
iterations that stepped (``serve.step``), over the traced stretch: near 100
where the loop keeps one step in flight. A program that emits no such span
(one whose loop is serial) reads ``None``."""
from perfbench.harness import readers


def read(ctx):
    window = readers.traced_window(ctx)
    if window is None:
        return None
    steps = ctx["spans"].count("serve.step", *window)
    if not steps or not any(n == "serve.step_ahead"
                            for n, _, _ in list(ctx["spans"].spans)):
        return None
    return 100.0 * ctx["spans"].count("serve.step_ahead", *window) / steps
