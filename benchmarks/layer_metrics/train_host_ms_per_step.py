"""Host time per train step in the traced window: the step's call
(`train.step`, which under a full dispatch queue includes waiting for
the device to take the next program) and the batch's placement
(`train.shard_batch`)."""

from benchmarks.harness import loopspans


def read(ctx):
    found = loopspans.traced(ctx) if ctx.get("traced_steps") else None
    if found is None:
        return None
    rec = found["records"]
    steps = sum(x["name"] == "train.step" for x in rec)
    if not steps:
        return None
    return (loopspans.total_ms(rec, "train.step")
            + loopspans.total_ms(rec, "train.shard_batch")) / steps
