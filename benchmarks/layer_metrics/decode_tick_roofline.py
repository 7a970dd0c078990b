"""The decode tick's share of its roofline: the least time the chip
could take for what each tick was ASKED to do, over the device time
the tick programs took.

What a tick is asked to do is the tick record the scheduler stores
where it dispatches (`sched.tick_dispatch`): `lanes_decoding` rows
and `context_sum` cached positions. Required bytes: every matmul
weight once (a lane more costs no weight read), the keys and values
of the cached positions over all layers, and one new position
written per decoding lane. Required flops: two per matmul parameter
per row, and the row's attention over its context. Lanes that do not
decode, and cache positions beyond a lane's context, are work the
program may do but was not asked for: they lower the share.

Means on both sides: the ticks dispatched inside the trace's time
range and the tick programs the trace holds differ by one or two at
the edges."""

from benchmarks.harness import flops, loopspans, trace


def kv_bytes_per_position(arch, kv_bytes=2):
    """K and V of one position, all layers."""
    return (2 * arch["num_layers"] * arch["num_kv_heads"]
            * arch["head_dim"] * kv_bytes)


def tick_bytes(arch, lanes_decoding, context_sum, weight_bytes=2,
               kv_bytes=2):
    """Bytes one tick must move: the weights once, the cached
    positions read, one position a decoding lane written."""
    per_pos = kv_bytes_per_position(arch, kv_bytes)
    return (flops.matmul_params(arch) * weight_bytes
            + (context_sum + lanes_decoding) * per_pos)


def tick_flops(arch, lanes_decoding, context_sum):
    """Flops one tick must do: 2 per matmul parameter per row, and
    Q K^T and P V over each row's context (2 * 2 * D per head and
    cached position)."""
    attn = (4 * arch["num_layers"] * arch["num_heads"]
            * arch["head_dim"] * context_sum)
    return 2 * flops.matmul_params(arch) * lanes_decoding + attn


def least_seconds(arch, tick, peaks):
    return flops.roofline_seconds(
        tick_flops(arch, tick["lanes_decoding"], tick["context_sum"]),
        tick_bytes(arch, tick["lanes_decoding"], tick["context_sum"]),
        peaks)[0]


def read(ctx, module):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    found = loopspans.traced(ctx)
    if found is None:
        return None
    ticks = [x["attrs"] for x in found["records"]
             if x["name"] == "sched.tick_dispatch"]
    times = trace.module_times(ctx["trace"], module)
    if not ticks or not times:
        return None
    arch = ctx["cell"].config["arch"]
    least = sum(least_seconds(arch, t, ctx["peaks"]) for t in ticks)
    return (least / len(ticks)) / (sum(times) / len(times)) * 100.0
