"""HVD012 fixture: span names drifting from SPAN_CATALOG.

Run against this file alone the rule falls back to the INSTALLED
`horovod_tpu.obs.spans.SPAN_CATALOG` for the declared-name set (the
dead-promise direction needs the spans module in the analyzed set
and stays off here).
"""

from horovod_tpu.obs import spans


def undeclared():
    spans.begin_span("fixture.unknown_span", trace_id="t")      # EXPECT


def undeclared_local_import():
    from horovod_tpu.obs import spans as _spans
    _spans.record_span("fixture.other_unknown", trace_id="t",   # EXPECT
                       t0=0.0, duration=1.0)


def undeclared_direct_fn():
    from horovod_tpu.obs.spans import begin_span
    begin_span("fixture.third_unknown", trace_id="t")           # EXPECT


def undeclared_loop_span():
    with spans.loop_span("fixture.unknown_loop", tick=1):       # EXPECT
        pass


def undeclared_loop_span_direct_fn():
    from horovod_tpu.obs.spans import loop_span
    with loop_span("fixture.other_unknown_loop"):               # EXPECT
        pass


def suppressed_prototype():
    # hvd: disable=HVD012(prototype span behind a flag; catalogued before the flag flips on - SUPPRESSED)
    spans.begin_span("fixture.experimental", trace_id="t")


def declared_ok():
    # Clean negative: a name the real catalog declares.
    spans.begin_span("serving.prefill", trace_id="t")


def declared_loop_ok():
    # Clean negative: a loop span the real catalog declares.
    with spans.loop_span("sched.step", tick=0):
        pass


def dynamic_ok(name):
    # Non-literal name: out of scope for the literal scan.
    spans.begin_span(name, trace_id="t")


def other_handle_ok(rec):
    # Clean negative: a begin_span METHOD reached through some other
    # object's handle, not a spans-module alias.
    rec.begin_span("anything.goes")
