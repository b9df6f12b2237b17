"""Share of device busy time under the program's scope ``linear_attn`` (the
lightning layers' chunked scan in prefill, the state's update and read in
decode), in the traced stretch."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.share_pct(ctx, ("linear_attn",))
