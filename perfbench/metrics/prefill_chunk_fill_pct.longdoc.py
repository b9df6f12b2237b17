"""How full the prefill chunks were: the prompt's own positions (``rows``)
over the padded width of the chunk program (``width``), summed over the
program's ``serve.prefill_chunk`` records in the traced stretch, in %.
``None`` from a program whose spans carry no args."""
from perfbench.harness import records


def read(ctx):
    return records.fill_pct(ctx, "serve.prefill_chunk", "rows", "width")
