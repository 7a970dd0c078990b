"""A decode tick over recurrent state AND latent rows against its
roofline: `latent_tick_roofline`'s reduction (the tick programs' mean
device time under the least time at the MEANS of the traced ticks'
records - `lanes_decoding`, `context_sum` on `sched.tick_dispatch`,
`moe_experts_hit`, `moe_pairs` on `sched.tick_sync`), with the counts of
an architecture module whose `tick_least_seconds` asks for both caches:
the decoding lanes' state read and written in every KDA layer, the
latent rows by `context_sum` read once in every latent layer. A module
without `kda_step_least_seconds` has no such pool, and a program whose
tick records lack one of the four gives nothing to read."""

import os

from benchmarks.harness import cells

_latent = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "latent_tick_roofline.py"),
    "benchmarks_metric_latent_tick_roofline")


def read(ctx, module):
    if not hasattr(ctx.get("arch_module"), "kda_step_least_seconds"):
        return None
    return _latent.read(ctx, module)
