"""Share of the decode tick's device time under the latent-attention
layers' scopes where a block has one such mixer (`block_<i>/mla/`);
prints the shares of `beside` (routed experts, shared expert, dense FFN)
and the remainder too."""

from benchmarks.harness import tickscopes


def read(ctx, module, pattern, beside):
    tickscopes.say_remainder(ctx, module, [pattern, *beside])
    return tickscopes.scope_share(ctx, module, pattern)
