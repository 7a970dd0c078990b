"""Share of the decode tick's device time under the sliding-window
layers' scopes."""

from benchmarks.harness import tickscopes


def read(ctx, module, pattern):
    return tickscopes.scope_share(ctx, module, pattern)
