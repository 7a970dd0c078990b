"""Stock readers for per-layer metrics. A reader takes the run's
context and its metric file's `args` and returns a number, or None
where it finds nothing to read (the harness then leaves the metric out
of the line). A metric whose reading none of these covers brings its
own `layer_metrics/<name>.py` with a `read(ctx, **args)`.

The context: `trace` (the dictionary of `harness/trace.py`),
`trace_window_s`, `peaks` (this device kind's row of `peaks.json`),
`cell`, `values` (the run's end-to-end numbers) and what the cell's
driver adds (`kinds/<kind>.py`, the `ctx` it returns).
"""

from benchmarks.harness import flops as _flops
from benchmarks.harness import trace as _trace


def _no_device(ctx):
    """No trace, or a trace with no device plane (a CPU's)."""
    return ctx.get("trace") is None or not ctx["trace"]["devices"]


def busy_ms_per_unit(ctx, units):
    """Device busy time in the traced window per `ctx[units]` (steps,
    ticks): milliseconds."""
    n = ctx.get(units)
    if not n or _no_device(ctx):
        return None
    return _trace.busy_seconds(ctx["trace"]) / n * 1e3


def idle_share(ctx):
    """1 - (union of device-op intervals) / traced window, in %."""
    if _no_device(ctx) or not ctx.get("trace_window_s"):
        return None
    busy = _trace.busy_seconds(ctx["trace"])
    return (1.0 - busy / ctx["trace_window_s"]) * 100.0


def module_mean_ms(ctx, module):
    """Mean device time of one run of the programs whose module name
    matches `module` (a regular expression): milliseconds."""
    if _no_device(ctx):
        return None
    times = _trace.module_times(ctx["trace"], module)
    return sum(times) / len(times) * 1e3 if times else None


def exposed_collective_ms_per_unit(ctx, units):
    """Collective time that no compute covers, per `ctx[units]`."""
    n = ctx.get(units)
    if not n or _no_device(ctx):
        return None
    ov = _trace.collective_overlap(ctx["trace"])
    return None if ov is None else ov["exposed_s"] / n * 1e3


def train_mfu(ctx):
    """tokens/s/chip x required flops per token / the chip's peak."""
    rate = ctx.get("tokens_per_s_per_chip")
    if rate is None:
        return None
    cell = ctx["cell"]
    per_token = _flops.train_flops_per_token(
        cell.config["arch"], cell.traffic["seq_len"])
    return rate * per_token / ctx["peaks"]["bf16_flops_per_s"] * 100.0


def context_value(ctx, key, scale=1.0):
    """A number the driver put into the context, times `scale`
    (100 for a share in %, 2**-30 for bytes in GiB)."""
    v = ctx.get(key)
    return None if v is None else v * scale


def module_ms_per_1k(ctx, module, units):
    """Summed device time of the programs matching `module`, per 1000
    of `ctx[units]` (say, prompt tokens prefilled in the traced
    window): milliseconds."""
    n = ctx.get(units)
    if not n or _no_device(ctx):
        return None
    times = _trace.module_times(ctx["trace"], module)
    return sum(times) * 1e3 / n * 1000.0 if times else None

