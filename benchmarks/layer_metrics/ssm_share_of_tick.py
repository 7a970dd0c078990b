"""Share of the decode tick's device time under the state-space layers'
scopes; prints the shares of `beside` and the remainder too."""

from benchmarks.harness import tickscopes


def read(ctx, module, pattern, beside):
    tickscopes.say_remainder(ctx, module, [pattern, *beside])
    return tickscopes.scope_share(ctx, module, pattern)
