"""The flash kernels' share of their roofline, forward and backward
together: the least time the chip could take for the calls traced
(the larger of required flops / peak flops and required bytes / peak
bandwidth, per call; `harness/flops.py`, recomputation not counted)
over the device time of the kernel's events."""

from benchmarks.harness import flops, trace


def read(ctx, op, calls_per_layer):
    if ctx.get("trace") is None or not ctx.get("traced_steps"):
        return None
    seconds, n = trace.op_seconds(ctx["trace"], op)
    arch, job = ctx["cell"].config["arch"], ctx["cell"].traffic
    want = ctx["traced_steps"] * arch["num_layers"] * calls_per_layer
    if not n:
        return None
    if n != want:
        raise SystemExit(
            f"flash_roofline: {n} kernel events match {op!r} in the "
            f"trace, {want} calls were made")
    shape = (job["per_chip_batch"], job["seq_len"], arch["num_heads"],
             arch["num_kv_heads"], arch["head_dim"])
    least = sum(flops.roofline_seconds(*fn(*shape), ctx["peaks"])[0]
                for fn in (flops.flash_fwd, flops.flash_bwd))
    least *= ctx["traced_steps"] * arch["num_layers"]
    return least / seconds * 100.0
