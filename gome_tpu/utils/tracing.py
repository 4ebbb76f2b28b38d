"""Host spans on the profiler's clock — the one frame-scoped span primitive.

  span(name, **meta) — context manager around a named stretch of host work.
                       It opens a jax.profiler.TraceAnnotation, so whenever a
                       profile is taken ("tracing on") the span lies on the
                       profiler's host lines, on the same clock as the
                       device's XLA Ops; `meta` is for a reader of that trace
                       and is formatted only while a profile runs. Profile or
                       not, it adds the span's wall and thread-CPU time to a
                       per-name total (totals()), keeps every span of
                       SLOW_SPAN_NS or longer in a bounded ring (slow()) and
                       logs it once at WARNING with wall against thread and
                       process CPU time, and hands a closed span to an armed
                       TRACER under the taxonomy's stage name (STAGE_OF_SPAN),
                       so per-order journeys keep their batch-scoped stages.
  poll_span(name)    — one span over consecutive empty polls of an idle loop
                       and its sleep between them; the poll that brings
                       messages back is the pick-up of a queue hand-off
                       (below).
  record(name, ns)   — a stretch that no one thread held open, into the same
                       table: a hand-off between two threads, a boot's replay.
  annotate(name)     — a bare TraceAnnotation: the parent that only groups
                       leaves on the trace (pipeline_feed).
  trace(dir), maybe_trace(dir) — jax.profiler.trace around a block.

Granularity rule: a span is opened per request, per frame, per grid or per
queue batch — NEVER per order and never per event (tests/test_spans.py holds
gateway.py, matchfeed.py and frames.py to it). A span costs a few
microseconds with the profiler off (PERF.md has the measurement); an order
costs less than that everywhere on the served path.

Every serving thread is always inside exactly one named leaf span, so an idle
gap of the device, and a stall, has a name per thread:

  gRPC handler, DoOrderBatch   gateway_admit
  consumer                     consumer_poll, frame_unpack, [pipeline_feed:]
                               frame_admit, frame_pack, grid_dispatch (one per
                               grid), frame_fetch, frame_decode, publish_events
  match feed                   feed_poll, feed_decode, feed_fanout (one each
                               per match message)
  gRPC handler, SubscribeMatches   stream_wait (while its queue is empty),
                               stream_send (one per queue item, while gRPC
                               takes a match message's events from it)

A frame changes threads three times, and each hand-off is timed where the
frame is picked up (HANDOFF_OF_POLL, service.matchfeed): the queue keeps the
instant of its publish (bus.base.Queue), the poll that first returns the
message records pick-up minus publish under the hand-off's name and notes it
on the span that closes with the message (dwell_us=):

  order_queue_dwell        gateway's publish  -> consumer's read
  match_queue_dwell        consumer's publish -> feed's read
  subscriber_queue_dwell   feed's put         -> handler's get

Once per poll that brought messages, the oldest message's; nothing for a
message this process did not publish or reads a second time. The spans of
one frame note frame=<order-queue offset>, the feed's and the handler's
match=<match-queue offset>, publish_events both: a reader of the profile
follows one frame across the four threads. On a file-backed queue a read that
returns messages is a log_read span and a cursor's write a cursor_commit span
(bus.filelog, bus.native), inside whichever leaf asked for them.

Wall minus thread CPU is the time a thread held a span open without running:
the interpreter lock, a blocking call, or the scheduler. A slow-span line
adds the process's CPU time over the same stretch (a thread computed under
the lock, or nothing ran at all; counted from a reading of the process's
clock at most PROCESS_CPU_SAMPLE_NS before the span began) and, read only
then, the cgroup's throttle counters and the process's context switches as
absolute values; log_baseline() prints the same at EngineService.start().
"""

from __future__ import annotations

import contextlib
import resource
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from .logging import get_logger
from .metrics import REGISTRY
from .trace import TRACER

log = get_logger("tracing")

#: A span this long goes into the slow ring and is logged: above every poll
#: timeout on the served path (2 ms polls, the stream's 100 ms get).
SLOW_SPAN_NS = 250_000_000
SLOW_RING = 256
#: A poll_span over empty polls closes at the first poll that ends this late;
#: the longest an idle reader sleeps before it looks again (poll_span.idle).
MERGE_POLLS_NS = 100_000_000
#: Spans that are long by arithmetic, not by a stall: the handler hands a
#: 4,096-order frame's ~2,200 events to gRPC in 300 ms. They never enter the
#: slow ring and never log (a slow line reads the cgroup's files, on the
#: thread that sets the pace); a stall inside one still shows as its longest
#: and in wall minus thread CPU.
LONG_BY_SIZE = frozenset({"stream_send"})
#: Poll span -> the hand-off its pick-up ends, and the identifier that the
#: queue's offsets are on a frame's spans (module docstring).
HANDOFF_OF_POLL = {
    "consumer_poll": ("order_queue_dwell", "frame"),
    "feed_poll": ("match_queue_dwell", "match"),
}

#: Span name -> the order-lifecycle taxonomy's stage (utils.trace.STAGES) an
#: armed TRACER records it under. A span without an entry is not forwarded
#: (grid_dispatch is: engine.frames splits it into compile_hit/compile_miss
#: once the dispatched combo is known).
STAGE_OF_SPAN = {
    "frame_pack": "pad_pack",
    "frame_fetch": "device_execute",
    "frame_decode": "decode",
    "publish_events": "publish",
}

# The clocks, as module globals so a test can script them.
_wall_ns = time.monotonic_ns  # CLOCK_MONOTONIC: the benchmark client's clock
_thread_cpu_ns = time.thread_time_ns
_process_cpu_ns = time.process_time_ns

#: The process's CPU clock sums over every thread of the process, so one
#: reading costs microseconds per hundred threads (a TPU host's runtime has
#: hundreds): it is read at a span's entry only when the last reading is
#: older than this, and a slow span's process CPU then counts from a reading
#: at most this long before its start.
PROCESS_CPU_SAMPLE_NS = 100_000_000
_process_sample = [0, 0]  # [wall ns, process-CPU ns]; a race costs a reading

_lock = threading.Lock()
#: name -> [count, wall ns, thread-CPU ns, longest wall ns]
_totals: dict[str, list] = {}  # guarded by _lock
_slow: deque = deque(maxlen=SLOW_RING)  # guarded by _lock


class span:
    """One named stretch of host work (module docstring). After the block,
    `t0_ns`, `wall_ns` and `cpu_ns` hold what was measured."""

    __slots__ = ("name", "_ann", "t0_ns", "wall_ns", "cpu_ns", "_c0", "_p0",
                 "_stage", "_tr0")

    def __init__(self, name: str, **meta):
        self.name = name
        self._ann = TraceAnnotation(name, **meta)

    def note(self, **meta) -> None:
        """Metadata known only inside the block (accepted counts), for the
        trace's reader; formatted only while a profile runs."""
        self._ann.set_metadata(**meta)

    def __enter__(self):
        self._stage = self._tr0 = None
        if TRACER.recorder is not None:
            self._stage = STAGE_OF_SPAN.get(self.name)
            if self._stage is not None:
                # The tracer's own clock (tests script it), so the stage
                # lies among the journey's other spans.
                self._tr0 = TRACER.clock()
        self._ann.__enter__()
        self._c0 = _thread_cpu_ns()
        t0 = self.t0_ns = _wall_ns()
        sample = _process_sample
        if t0 - sample[0] > PROCESS_CPU_SAMPLE_NS:
            sample[:] = t0, _process_cpu_ns()
        self._p0 = sample[1]
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = self.wall_ns = _wall_ns() - self.t0_ns
        cpu = self.cpu_ns = _thread_cpu_ns() - self._c0
        self._ann.__exit__(exc_type, exc, tb)
        record(self.name, wall, cpu)
        if wall >= SLOW_SPAN_NS and self.name not in LONG_BY_SIZE:
            _note_slow(self.name, self.t0_ns, wall, cpu,
                       _process_cpu_ns() - self._p0)
        if self._stage is not None:
            TRACER.observe_span(self._stage, self._tr0, TRACER.clock())
        return False


def record(name: str, wall_ns: int, cpu_ns: int = 0) -> None:
    """Add one closed stretch to the table and /metrics: a span's exit, or a
    stretch that no one thread held open (a queue hand-off from its publish
    to its pick-up; a boot's replay, which starts where one thread's restore
    ends and ends at another's commit), timed by its owner; such a stretch
    lies on no profile and is never a slow span."""
    with _lock:
        row = _totals.get(name)
        if row is None:
            row = _totals[name] = [0, 0, 0, 0]
            _export(name)
        row[0] += 1
        row[1] += wall_ns
        row[2] += cpu_ns
        if wall_ns > row[3]:
            row[3] = wall_ns


class poll_span:
    """One span over an idle stretch of a loop that polls: a poll that came
    back empty, the loop's sleep until its queue has something for it
    (`poller.idle(queue, start)`: the queue's wait_idle, at most
    MERGE_POLLS_NS long), the next poll, and so on; a span per poll costs a
    thread that has just woken ten times what it costs a running one
    (PERF.md, PR 25). `poller(fn, *args)`, or `poller.batch(queue, *args)` /
    `poller.ahead(queue, *args)` for a queue's poll_batch / read_from, makes
    one poll inside the span and closes it when the poll brought something
    back or the span is MERGE_POLLS_NS old, so it stays far under
    SLOW_SPAN_NS unless a single poll or sleep overran: a slow poll span
    still means a stall. The span that closes notes `polls=` and, where the
    loop slept in it, what ended its last sleep (`woken_by=`). A queue's poll
    that brings messages back is the pick-up of the hand-off HANDOFF_OF_POLL
    names for the span (module docstring). Owned by the one thread that
    polls."""

    __slots__ = ("name", "_span", "_polls", "_woken_by", "_handoff")

    def __init__(self, name: str):
        self.name = name
        self._span = None
        self._polls = 0
        self._woken_by = None
        self._handoff = HANDOFF_OF_POLL.get(name)

    def __call__(self, poll, *args):
        got = self._poll(poll, args)
        if got:
            self.close()
        return got

    def batch(self, queue, *args):
        """`queue.poll_batch(*args)` as one poll; the span that closes with
        messages notes what ended the queue's wait (bus.base.Queue)."""
        got = self._poll(queue.poll_batch, args)
        if got:
            self._picked_up(queue, got, ended_by=queue.poll_ended_by)
        return got

    def ahead(self, queue, *args):
        """`queue.read_from(*args)` as one poll: a reader that runs ahead of
        its commits and does not wait."""
        got = self._poll(queue.read_from, args)
        if got:
            self._picked_up(queue, got)
        return got

    def idle(self, queue, start: int) -> str:
        """The loop's sleep after a poll that brought nothing, inside the
        span: `queue.wait_idle(start, ...)` for what is left of the span's
        MERGE_POLLS_NS (a span that old already is closed, and the sleep
        opens the next); returns what ended it. Not a poll: the loop polls
        next, and that poll closes the span with its messages or by its
        age."""
        self._open()
        left = MERGE_POLLS_NS - (_wall_ns() - self._span.t0_ns)
        if left <= 0:
            self.close()
            self._open()
            left = MERGE_POLLS_NS
        try:
            self._woken_by = queue.wait_idle(start, left / 1e9)
        except BaseException:
            self.close()
            raise
        return self._woken_by

    def _open(self) -> None:
        if self._span is None:
            self._span = span(self.name).__enter__()
            self._polls = 0
            self._woken_by = None

    def _poll(self, poll, args):
        self._open()
        try:
            got = poll(*args)
        except BaseException:
            self.close()
            raise
        self._polls += 1
        if not got and _wall_ns() - self._span.t0_ns >= MERGE_POLLS_NS:
            self.close()  # an idle stretch: closed by its age
        return got

    def _picked_up(self, queue, msgs, **meta) -> None:
        """Close with a queue's messages. Once per poll, never per message:
        the oldest message's dwell, where the queue has the instant of its
        publish (this process published it and no read returned it before)."""
        if self._handoff is not None:
            name, key = self._handoff
            meta[key] = msgs[0].offset
            published = queue.publish_ns(msgs[0].offset, msgs[-1].offset)
            if published is not None:
                dwell = _wall_ns() - published
                record(name, dwell)
                meta["dwell_us"] = dwell // 1000
        self.close(**meta)

    def close(self, **meta) -> None:
        """End the open span, if any (work follows, or the loop ends)."""
        open_, self._span = self._span, None
        if open_ is not None:
            if self._woken_by is not None:
                meta["woken_by"] = self._woken_by
            open_.note(polls=self._polls, **meta)
            open_.__exit__(None, None, None)


def _export(name: str) -> None:
    """The three /metrics families' children for a name new to the table."""
    for family, help_, col, scale in (
        ("gome_span_seconds_total", "wall seconds inside the span", 1, 1e-9),
        ("gome_span_cpu_seconds_total",
         "thread-CPU seconds inside the span", 2, 1e-9),
        ("gome_span_count", "spans closed", 0, 1.0),
    ):
        REGISTRY.callback_gauge(
            family, help_,
            lambda name=name, col=col, scale=scale: _totals[name][col] * scale,
            labels={"span": name},
        )


def host_pressure() -> dict:
    """Absolute counters that say whether the host held the process back:
    the cgroup's CPU throttle counts (v2 cpu.stat, or v1's under the cpu
    controller; None where neither is readable) and the process's context
    switches. Read at a slow span and at the baseline, never per span."""
    stat = {}
    try:
        with open("/proc/self/cgroup") as f:
            entries = [ln.strip().split(":", 2) for ln in f if ln.strip()]
    except OSError:
        entries = []
    paths = []
    for _id, controllers, path in entries:
        if controllers == "":
            paths.append("/sys/fs/cgroup" + path)
        elif "cpu" in controllers.split(","):
            paths.append("/sys/fs/cgroup/" + controllers + path)
            paths.append("/sys/fs/cgroup/cpu" + path)
    for base in paths:
        try:
            with open(base.rstrip("/") + "/cpu.stat") as f:
                stat = dict(ln.split()[:2] for ln in f if ln.strip())
        except (OSError, ValueError):
            continue
        if "nr_throttled" in stat:
            break
    usec = stat.get("throttled_usec")
    if usec is None and "throttled_time" in stat:  # v1 counts nanoseconds
        usec = int(stat["throttled_time"]) // 1000
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return dict(
        nr_throttled=int(stat["nr_throttled"]) if "nr_throttled" in stat
        else None,
        throttled_usec=None if usec is None else int(usec),
        voluntary_ctx=usage.ru_nvcsw, involuntary_ctx=usage.ru_nivcsw,
    )


def _note_slow(name, t0_ns, wall_ns, cpu_ns, process_cpu_ns) -> None:
    thread = threading.current_thread().name
    with _lock:
        _slow.append((thread, name, t0_ns, wall_ns, cpu_ns, process_cpu_ns))
    p = host_pressure()
    log.warning(
        "slow span: thread=%s span=%s t0_monotonic_s=%.6f wall_ms=%.3f "
        "thread_cpu_ms=%.3f process_cpu_ms=%.3f nr_throttled=%s "
        "throttled_usec=%s voluntary_ctx=%s involuntary_ctx=%s",
        thread, name, t0_ns / 1e9, wall_ns / 1e6, cpu_ns / 1e6,
        process_cpu_ns / 1e6, p["nr_throttled"], p["throttled_usec"],
        p["voluntary_ctx"], p["involuntary_ctx"],
    )


def log_baseline() -> None:
    """The slow-span line's absolute counters at a known instant
    (EngineService.start()), so a later line's values read as deltas."""
    p = host_pressure()
    log.warning(
        "span baseline: t_monotonic_s=%.6f process_cpu_ms=%.3f "
        "nr_throttled=%s throttled_usec=%s voluntary_ctx=%s "
        "involuntary_ctx=%s slow_span_ms=%d",
        _wall_ns() / 1e9, _process_cpu_ns() / 1e6, p["nr_throttled"],
        p["throttled_usec"], p["voluntary_ctx"], p["involuntary_ctx"],
        SLOW_SPAN_NS // 1_000_000,
    )


def log_totals() -> None:
    """The table, one line (EngineService.stop()): at WARNING when a slow
    span was logged, so the stalls come with what every span cost over the
    process's life; at INFO otherwise."""
    rows = totals()
    level = log.warning if slow() else log.info
    level(
        "span totals (name count wall_s cpu_s longest_ms): %s",
        "; ".join(
            f"{name} {r['count']} {r['wall_s']:.3f} {r['cpu_s']:.3f} "
            f"{r['longest_s'] * 1e3:.1f}"
            for name, r in sorted(rows.items())
        ),
    )


def totals() -> dict:
    """{name: {count, wall_s, cpu_s, longest_s}} — a copy of the table."""
    with _lock:
        rows = {name: list(r) for name, r in _totals.items()}
    return {
        name: dict(count=r[0], wall_s=r[1] / 1e9, cpu_s=r[2] / 1e9,
                   longest_s=r[3] / 1e9)
        for name, r in rows.items()
    }


def slow() -> list[dict]:
    """The slow ring, oldest first."""
    with _lock:
        rows = list(_slow)
    return [
        dict(thread=t, span=n, t0_s=t0 / 1e9, wall_s=w / 1e9, cpu_s=c / 1e9,
             process_cpu_s=p / 1e9)
        for t, n, t0, w, c, p in rows
    ]


def reset() -> None:
    """Empty the table and the ring (tests; the /metrics children stay and
    read 0 until their span closes again)."""
    with _lock:
        for row in _totals.values():
            row[:] = [0, 0, 0, 0]
        _slow.clear()
        _process_sample[:] = 0, 0


def annotate(name: str, **meta):
    return TraceAnnotation(name, **meta)


@contextlib.contextmanager
def trace(log_dir: str):
    import jax

    with jax.profiler.trace(log_dir):
        yield


@contextlib.contextmanager
def maybe_trace(log_dir: str | None):
    if not log_dir:
        yield
        return
    with trace(log_dir):
        yield
