"""What one call of each instrument of the serving dispatch thread costs
that thread in CPU time, on whatever host this runs on: tight loops
timed with `time.thread_time_ns`, the least of a few repeats. No device
is used (JAX is held to the CPU). Given another checkout it also times
that tree's `loop_span`, for a parent -> change pair:

    python3 benchmarks/tools/instrument_cost.py [other_tree]

PERF.md §6 (PR 36) multiplies these by the calls a decode tick to say
what the instrumentation costs a scheduler step. A benchmark run never
runs this.
"""

import importlib.util
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TICK = {"lanes_decoding": 32, "lanes_prefilling": 0, "lanes_free": 0,
        "queue_depth": 1, "context_sum": 1000, "context_max": 100,
        "context_window_sum": 0, "lanes_sampling": 0, "lanes_nucleus": 0}


def per_call_us(fn, n=20000, repeats=5):
    """This thread's CPU time a call of `fn`, in microseconds."""
    best = None
    for _ in range(repeats):
        c0 = time.thread_time_ns()
        for _ in range(n):
            fn()
        c = (time.thread_time_ns() - c0) / n
        best = c if best is None else min(best, c)
    return best / 1e3


def loop_span_costs(spans, label):
    def plain():
        with spans.loop_span("sched.housekeeping"):
            pass

    def with_attrs():
        with spans.loop_span("sched.admit") as sp:
            sp.set(slot=1, prompt_tokens=3, prefix_cached=0,
                   queue_wait_ms=1.5)

    def tick_record():
        with spans.loop_span("sched.tick_dispatch", **TICK):
            pass

    return {f"loop_span ({label})": per_call_us(plain),
            f"loop_span + set of 4 attrs ({label})": per_call_us(with_attrs),
            f"loop_span(**tick record) ({label})": per_call_us(tick_record)}


def request_tree(spans):
    """The nine spans a served request leaves, begun and ended."""
    tid = spans.mint_trace_id()
    root = spans.begin_span("serving.request", trace_id=tid)
    for name in ("serving.queued", "serving.admission"):
        spans.end_span(spans.begin_span(name, trace_id=tid,
                                        parent_id=root))
    prefill = spans.begin_span("serving.prefill", trace_id=tid,
                               parent_id=root)
    for _ in range(3):
        spans.end_span(spans.begin_span(
            "serving.prefill_chunk", trace_id=tid, parent_id=prefill))
    spans.end_span(prefill)
    spans.end_span(spans.begin_span("serving.decode", trace_id=tid,
                                    parent_id=root))
    spans.end_span(root)
    return tid


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from horovod_tpu.obs import spans
    from horovod_tpu.serving.metrics import EngineMetrics
    from horovod_tpu.utils.stall import StallMonitor

    out = loop_span_costs(spans, "this tree")
    if argv:
        spec = importlib.util.spec_from_file_location(
            "other_tree_spans",
            os.path.join(argv[0], "horovod_tpu", "obs", "spans.py"))
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        out.update(loop_span_costs(other, "other tree"))
    out["time.thread_time_ns"] = per_call_us(time.thread_time_ns, n=100000)
    out["time.time_ns"] = per_call_us(time.time_ns, n=100000)

    previous = spans.install(spans.SpanRecorder(None, sample=1.0))
    try:
        out["begin_span + end_span"] = per_call_us(
            lambda: spans.end_span(spans.begin_span(
                "serving.prefill_chunk", trace_id="ab", parent_id="",
                tokens=3, off=0)), n=5000)
        tree = per_call_us(lambda: request_tree(spans), n=2000)
        observed = per_call_us(
            lambda: spans.observe_request(request_tree(spans)), n=2000)
        out["a request's tree: 9 spans begun and ended"] = tree
        out["spans.observe_request (phase_anatomy) a request"] = (
            observed - tree)
    finally:
        spans.install(previous)

    metrics = EngineMetrics("instrument-cost")
    try:
        now = time.time()
        out["EngineMetrics.count"] = per_call_us(
            lambda: metrics.count("tokens_out"))
        out["EngineMetrics.count(name, 128)"] = per_call_us(
            lambda: metrics.count("tokens_out", 128))
        out["observe_tick"] = per_call_us(
            lambda: metrics.observe_tick(TICK))
        out["observe_gauges"] = per_call_us(
            lambda: metrics.observe_gauges(3, 30, 32))
        out["observe_kv"] = per_call_us(lambda: metrics.observe_kv(
            {"blocks_free": 1, "blocks_used": 2, "blocks_cached": 3}))
        out["EngineMetrics.observe_request"] = per_call_us(
            lambda: metrics.observe_request(
                t_submit=now, t_prefill=now + .1, t_first=now + .2,
                t_done=now + 1, n_tokens=100, trace_id="ab"), n=5000)
    finally:
        metrics.close()

    stall = StallMonitor(warning_time_s=60.0)
    try:
        def bracket():
            stall.begin("serving_tick_0.1")
            stall.end("serving_tick_0.1")
        out["stall.begin + stall.end"] = per_call_us(bracket)
    finally:
        stall.stop()

    for what, us in out.items():
        print(f"COST {what}: {us:.3f} us", flush=True)
    return out


if __name__ == "__main__":
    main()
