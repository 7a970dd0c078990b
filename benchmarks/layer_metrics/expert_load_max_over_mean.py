"""The busiest held expert's load over the mean one's, from the same
counters as `expert_pairs_per_expert` (a ratio of the window's means)."""


def read(ctx):
    pairs = ctx.get("window_moe_pairs")
    if not pairs:
        return None
    held = ctx["cell"].config["arch"]["experts_held"][1]
    return ctx["window_moe_load_max"] / (pairs / held)
