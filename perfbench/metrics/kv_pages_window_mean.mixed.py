"""Window-layer pages held for each stream that holds any, the mean over the
health snapshots inside the window (``kv_pages_in_use.window`` over the
resident streams and the one prompt being fed): 65 pages of 64 is what a
window of 4096 can lie on; a stream shorter than the window holds fewer."""
from perfbench.harness import readers_smallthinker


def read(ctx):
    return readers_smallthinker.window_pages_per_stream(ctx)
