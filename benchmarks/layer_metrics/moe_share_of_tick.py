"""Share of the decode tick's device time under the expert layers'
scopes (`harness/tickscopes.py`)."""

from benchmarks.harness import tickscopes


def read(ctx, module, pattern):
    return tickscopes.scope_share(ctx, module, pattern)
