"""What one utils.tracing.span costs the thread that opens it, profiler off
and on, and what a queue hand-off's timing costs (the publish stamp, the
pick-up with its record; ISSUE 38): a CPU microbenchmark of host code
(interpreter time, no device number). `python scripts/span_cost.py [n
[idle_threads]]` prints one JSON line; idle threads stand in for a TPU host's
runtime threads, which every reading of the process's CPU clock has to sum
over."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ns_per_span(n: int, **meta) -> float:
    from gome_tpu.utils.tracing import span

    t0 = time.perf_counter_ns()
    for _ in range(n):
        with span("span_cost", **meta):
            pass
    return (time.perf_counter_ns() - t0) / n


def ns_per_record(n: int) -> float:
    from gome_tpu.utils.tracing import record

    t0 = time.perf_counter_ns()
    for k in range(n):
        record("record_cost", k)
    return (time.perf_counter_ns() - t0) / n


def ns_per_handoff(n: int) -> dict:
    """One message through a memory queue: the publish with and without its
    stamp, and the pick-up (the stamp taken, the dwell recorded) as
    poll_span does it once per poll that brought messages."""
    from gome_tpu.bus import MemoryQueue
    from gome_tpu.bus.base import _PublishStamps
    from gome_tpu.utils.tracing import _wall_ns, record

    queue = MemoryQueue("handoff_cost")
    t0 = time.perf_counter_ns()
    for _ in range(n):
        queue.publish(b"x")
    publish = (time.perf_counter_ns() - t0) / n
    stamps = _PublishStamps()
    t0 = time.perf_counter_ns()
    for k in range(n):
        stamps.put(k, 1)
    put = (time.perf_counter_ns() - t0) / n
    stamps = _PublishStamps()
    t0 = time.perf_counter_ns()
    for k in range(n):
        stamps.put(k, 1)
        published = stamps.take(k, k)
        if published is not None:
            record("handoff_cost", _wall_ns() - published)
    pick_up = (time.perf_counter_ns() - t0) / n - put
    return dict(publish_with_stamp_ns=publish, stamp_ns=put,
                pick_up_ns=pick_up)


def main(argv) -> int:
    import jax

    n = int(argv[1]) if len(argv) > 1 else 200_000
    idle = int(argv[2]) if len(argv) > 2 else 0
    parked = threading.Event()
    for _ in range(idle):
        threading.Thread(target=parked.wait, daemon=True).start()
    meta = dict(rows=8, t=32, grid="full")
    out = dict(
        what="CPU microbenchmark of host code: ns per span(), no device number",
        n=n, idle_threads=idle, off_ns=ns_per_span(n), off_meta3_ns=ns_per_span(n, **meta),
        record_ns=ns_per_record(n), **ns_per_handoff(n),
    )
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            out.update(on_ns=ns_per_span(n // 4),
                       on_meta3_ns=ns_per_span(n // 4, **meta))
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
