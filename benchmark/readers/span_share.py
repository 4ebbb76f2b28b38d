"""Share of the traced stretch that one of the program's host spans was open:
seconds of the span over the stretch's length, a thread's headroom where the
span is its wait. The stretch runs from the first to the last event kept and
needs a device plane: a CPU rehearsal's trace has none (no /device:TPU), its
window_s is 0, and nothing is read there.

A span that the program opens only while it waits (`stream_wait`: only when
the subscriber's queue is found empty) is absent from the stretch of a thread
that never waited. Its metric file names under `zero_if_seen` a span of the
same layer that the program opens whenever it runs: with that one in the
trace and the wait absent, the share is 0; with neither (a program without
these spans), nothing is read."""


def read(run, meta):
    trace = run["trace"]
    if not trace or not trace.get("window_s"):
        return None
    spans = trace.get("spans", {})
    if meta["span"] in spans:
        return spans[meta["span"]][1] / trace["window_s"]
    return 0.0 if meta.get("zero_if_seen") in spans else None
