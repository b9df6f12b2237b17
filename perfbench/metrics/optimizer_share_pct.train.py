"""Share of device busy time under the program's scope ``optimizer``
(clipping, the optimizer's update, ``apply_updates``) in the train step.
An update that XLA fuses into the operation that produces the gradient
carries that operation's scope, not this one."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.share_pct(ctx, ("optimizer",))
