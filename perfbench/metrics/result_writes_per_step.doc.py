"""Result records written (the program's ``serve.put_result`` spans) for each
``serve.step``, over the traced stretch: a loop that writes one partial a
resident stream a token reads the resident streams; under a publisher that
lands each stream's newest record it reads what the backend takes in an
iteration. A program that emits no such span reads ``None``."""
from perfbench.harness import readers


def read(ctx):
    window = readers.traced_window(ctx)
    if window is None:
        return None
    steps = ctx["spans"].count("serve.step", *window)
    if not steps or not any(n == "serve.put_result"
                            for n, _, _ in list(ctx["spans"].spans)):
        return None
    return ctx["spans"].count("serve.put_result", *window) / steps
