"""Pairs a held expert gets per decode tick and layer, over the measured
window: the engine's `moe_pairs` over `moe_layers_ticks`, over the
experts the configuration holds here."""


def read(ctx):
    n = ctx.get("window_moe_layers_ticks")
    if not n:
        return None
    held = ctx["cell"].config["arch"]["experts_held"][1]
    return ctx["window_moe_pairs"] / n / held
