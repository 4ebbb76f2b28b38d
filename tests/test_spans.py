"""utils.tracing.span (ISSUE 25): totals and the slow ring on a scripted
clock, the forward to an armed TRACER, the granularity rule held at source
level, and the leaf spans of the served path as a profile of a small
EngineService shows them.
"""

from __future__ import annotations

import ast
import itertools
import logging
import os
import sys
import threading
import time

import pytest

from gome_tpu.api import order_pb2 as pb
from gome_tpu.utils import tracing
from gome_tpu.utils.metrics import Registry
from gome_tpu.utils.trace import STAGES, TRACER, FlightRecorder
from gome_tpu.utils.tracing import span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # benchmark.tracered, as tests/benchmark does
    sys.path.insert(0, ROOT)

MS = 1_000_000


class Clock:
    """Scripted clocks: every reading is the current value; `advance` moves
    wall, thread-CPU and process-CPU time by what the test says."""

    def __init__(self, monkeypatch):
        self.wall = 5_000_000_000
        self.thread = self.process = 0
        monkeypatch.setattr(tracing, "_wall_ns", lambda: self.wall)
        monkeypatch.setattr(tracing, "_thread_cpu_ns", lambda: self.thread)
        monkeypatch.setattr(tracing, "_process_cpu_ns", lambda: self.process)

    def advance(self, wall_ms, thread_ms=0.0, process_ms=0.0):
        self.wall += int(wall_ms * MS)
        self.thread += int(thread_ms * MS)
        self.process += int(process_ms * MS)


@pytest.fixture
def clock(monkeypatch):
    tracing.reset()
    yield Clock(monkeypatch)
    tracing.reset()


def test_totals_and_the_longest_span_on_a_scripted_clock(clock):
    for wall, cpu in ((3.0, 1.0), (7.5, 7.0), (2.0, 0.0)):
        with span("unit_a", rows=8) as s:
            clock.advance(wall, cpu)
        assert (s.wall_ns, s.cpu_ns) == (int(wall * MS), int(cpu * MS))
    with span("unit_b"):
        clock.advance(1.0, 1.0)
    rows = tracing.totals()
    assert rows["unit_a"] == dict(
        count=3, wall_s=pytest.approx(0.0125), cpu_s=pytest.approx(0.008),
        longest_s=pytest.approx(0.0075),
    )
    assert rows["unit_b"]["count"] == 1
    rows["unit_a"]["count"] = 99  # a copy: the table is not the caller's
    assert tracing.totals()["unit_a"]["count"] == 3
    assert tracing.slow() == []


def test_a_span_that_raises_is_counted_and_does_not_swallow(clock):
    with pytest.raises(KeyError):
        with span("unit_raises"):
            clock.advance(4.0)
            raise KeyError("x")
    assert tracing.totals()["unit_raises"]["count"] == 1


def test_a_250ms_span_is_kept_and_logged_once_with_every_field(clock, caplog):
    caplog.set_level(logging.WARNING, logger="gome_tpu.tracing")
    with span("unit_fast"):
        clock.advance(249.999, 10.0, 20.0)
    assert tracing.slow() == [] and not caplog.records
    t0 = clock.wall
    with span("unit_stall"):
        clock.advance(250.0, 12.5, 180.25)
    ring = tracing.slow()
    assert ring == [dict(
        thread=threading.current_thread().name, span="unit_stall",
        t0_s=t0 / 1e9, wall_s=0.25, cpu_s=0.0125, process_cpu_s=0.18025,
    )]
    (record,) = caplog.records
    assert record.levelno == logging.WARNING
    assert record.name == "gome_tpu.tracing"
    line = record.getMessage()
    for field in (f"thread={threading.current_thread().name}",
                  "span=unit_stall", f"t0_monotonic_s={t0 / 1e9:.6f}",
                  "wall_ms=250.000", "thread_cpu_ms=12.500",
                  "process_cpu_ms=180.250", "nr_throttled=",
                  "throttled_usec=", "voluntary_ctx=", "involuntary_ctx="):
        assert field in line, (field, line)


def test_the_process_clock_is_read_once_in_100ms_not_once_a_span(
        clock, monkeypatch):
    """It sums over every thread of the process (tens of microseconds on a
    TPU host): a span's entry reuses a reading under 100 ms old."""
    reads = []
    monkeypatch.setattr(
        tracing, "_process_cpu_ns",
        lambda: reads.append(clock.wall) or clock.process)
    for _ in range(200):  # 200 spans of 1 ms: 200 ms of wall
        with span("unit_poll"):
            clock.advance(1.0, 0.1, 0.5)
    assert len(reads) == 2
    clock.advance(100.0, 0.0, 40.0)
    with span("unit_stall"):
        clock.advance(300.0, 1.0, 2.0)
    # the entry's own reading (the last was over 100 ms old) and the slow
    # span's: its process CPU counts from its start
    assert len(reads) == 4
    assert tracing.slow()[-1]["process_cpu_s"] == pytest.approx(0.002)


def test_a_poll_span_covers_consecutive_empty_polls(clock):
    """An idle loop's polls share one span: it closes when a poll brings
    something back, when work follows, or once it is 100 ms old, so a slow
    poll span still means that one poll overran."""
    poller = tracing.poll_span("unit_idle")

    def poll(result, wall_ms=2.0):
        clock.advance(wall_ms)
        return result

    for _ in range(7):
        assert poller(poll, []) == []
    assert "unit_idle" not in tracing.totals()  # still open
    assert poller(poll, ["m"]) == ["m"]
    row = tracing.totals()["unit_idle"]
    assert (row["count"], row["wall_s"]) == (1, pytest.approx(0.016))
    for _ in range(50):  # an idle stretch: closed by its age
        poller(poll, [])
    assert tracing.totals()["unit_idle"]["count"] == 2
    assert tracing.totals()["unit_idle"]["longest_s"] == pytest.approx(0.1)
    for _ in range(30):  # polls stretched by a busy interpreter lock: 6 ms
        poller(poll, [], 6.0)
    assert tracing.totals()["unit_idle"]["count"] == 3
    assert tracing.totals()["unit_idle"]["longest_s"] == pytest.approx(0.102)
    poller.close()  # work follows
    poller.close()  # nothing open: nothing happens
    assert tracing.totals()["unit_idle"]["count"] == 4
    assert tracing.slow() == []
    with pytest.raises(OSError):  # a poll that raises closes its span
        poller(lambda: (_ for _ in ()).throw(OSError("bus down")))
    assert tracing.totals()["unit_idle"]["count"] == 5
    poller(poll, [], 3000.0)  # one poll that stood still is a slow span
    assert [r["span"] for r in tracing.slow()] == ["unit_idle"]


def test_a_queue_poll_that_brings_messages_notes_what_ended_its_wait(
    clock, monkeypatch
):
    """`poll_span.batch` polls a queue's poll_batch; the span that closes
    with messages carries `ended_by` (bus.base.Queue.poll_batch: a frame is
    a whole batch and ends the wait) beside `polls`."""
    from gome_tpu.bus import MemoryQueue

    notes = []
    monkeypatch.setattr(
        tracing.span, "note", lambda self, **meta: notes.append(meta))
    queue = MemoryQueue("unit")
    poller = tracing.poll_span("unit_queue_idle")
    assert poller.batch(queue, 8, 0) == []
    assert notes == []  # an empty poll: still open
    queue.publish(b"GCO2 stands for a whole ORDER frame")
    assert len(poller.batch(queue, 8, 5.0)) == 1
    assert notes == [{"polls": 2, "ended_by": "batch"}]
    queue.commit(1)
    queue.publish(b'{"one": "order"}')
    assert len(poller.batch(queue, 1, 5.0)) == 1
    assert notes[1:] == [{"polls": 1, "ended_by": "full"}]
    assert tracing.totals()["unit_queue_idle"]["count"] == 2


def test_a_poll_spans_sleep_lies_inside_it_and_is_cut_to_its_age(
    clock, monkeypatch
):
    """`poll_span.idle` is the loop's sleep between two polls (ISSUE 42):
    inside the span, for what is left of the span's 100 ms, so that the span
    still closes by its age; the span that closes notes what ended its last
    sleep beside `polls`, which counts polls and not sleeps."""
    notes = []
    monkeypatch.setattr(
        tracing.span, "note", lambda self, **meta: notes.append(meta))

    class Sleeper:
        name, asked = "unit", []

        def wait_idle(self, start, bound_s):
            self.asked.append((start, bound_s))
            clock.advance(bound_s * 1e3)
            return "timer"

        def poll_batch(self, *args):
            clock.advance(2.0)
            return []

    queue, poller = Sleeper(), tracing.poll_span("unit_sleep")
    assert poller.batch(queue, 8, 0.002) == []  # opens the span: 2 ms
    assert poller.idle(queue, 7) == "timer"  # the 98 ms that are left
    assert queue.asked == [(7, pytest.approx(0.098))]
    assert "unit_sleep" not in tracing.totals()  # still open
    assert poller.batch(queue, 8, 0.002) == []  # 102 ms old: closed by age
    assert notes == [{"polls": 2, "woken_by": "timer"}]
    assert poller.idle(queue, 7) == "timer"  # opens the next: the whole 100
    assert poller.idle(queue, 7) == "timer"  # that one is old: and the next
    assert [b for _s, b in queue.asked[1:]] == [pytest.approx(0.1)] * 2
    assert notes[1:] == [{"polls": 0, "woken_by": "timer"}]
    poller.close()
    rows = tracing.totals()["unit_sleep"]
    assert (rows["count"], rows["wall_s"]) == (3, pytest.approx(0.302))
    assert rows["longest_s"] == pytest.approx(0.102)
    assert tracing.slow() == []
    queue.wait_idle = lambda *a: (_ for _ in ()).throw(OSError("bus down"))
    with pytest.raises(OSError):  # a sleep that raises closes its span
        poller.idle(queue, 7)
    assert tracing.totals()["unit_sleep"]["count"] == 4


def test_the_table_holds_under_many_threads():
    """More threads than cores, a short switch interval: every span of
    every thread is counted once and the families' children are made once."""
    tracing.reset()
    before = tracing.totals().get("unit_stress", {}).get("count", 0)
    n_threads, n_spans = 16, 500

    def work():
        for _ in range(n_spans):
            with span("unit_stress"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    row = tracing.totals()["unit_stress"]
    assert row["count"] - before == n_threads * n_spans
    assert row["wall_s"] >= row["longest_s"] > 0


def test_the_ring_is_bounded(clock):
    for _ in range(tracing.SLOW_RING + 10):
        with span("unit_stall"):
            clock.advance(300.0)
    assert len(tracing.slow()) == tracing.SLOW_RING
    assert tracing.totals()["unit_stall"]["count"] == tracing.SLOW_RING + 10


def test_the_baseline_line_carries_the_slow_lines_counters(caplog):
    caplog.set_level(logging.WARNING, logger="gome_tpu.tracing")
    tracing.log_baseline()
    (record,) = caplog.records
    line = record.getMessage()
    for field in ("span baseline:", "t_monotonic_s=", "process_cpu_ms=",
                  "nr_throttled=", "throttled_usec=", "voluntary_ctx=",
                  "involuntary_ctx=", "slow_span_ms=250"):
        assert field in line, (field, line)
    p = tracing.host_pressure()
    assert p["voluntary_ctx"] >= 0 and p["involuntary_ctx"] >= 0


def test_the_totals_are_on_metrics_as_one_labelled_family_each(clock):
    from gome_tpu.utils.metrics import REGISTRY

    with span("unit_exported"):
        clock.advance(2.0, 1.0)
    with span("unit_exported"):
        clock.advance(4.0, 1.0)
    text = REGISTRY.render()
    assert 'gome_span_seconds_total{span="unit_exported"} 0.006' in text
    assert 'gome_span_cpu_seconds_total{span="unit_exported"} 0.002' in text
    assert 'gome_span_count{span="unit_exported"} 2.0' in text
    assert text.count("# TYPE gome_span_seconds_total gauge") == 1


@pytest.fixture
def armed():
    ticks = itertools.count(1)
    recorder = FlightRecorder(keep_n=8)
    registry = Registry()
    TRACER.install(recorder, registry=registry,
                   clock=lambda: next(ticks) * 1e-3)
    try:
        yield recorder, registry
    finally:
        TRACER.disable()
        TRACER.clock = time.perf_counter


def test_an_armed_tracer_gets_the_frame_stages_under_their_taxonomy_names(
        armed):
    """The same journeys as TRACER.stage() fed before: one histogram
    observation per span and one journey span per batch id, on the tracer's
    own clock; a span with no stage (grid_dispatch, the polls) is not
    forwarded."""
    recorder, _registry = armed
    with TRACER.batch(["t1", "t2"]):
        for name in ("frame_pack", "grid_dispatch", "frame_fetch",
                     "frame_decode", "publish_events", "consumer_poll"):
            with span(name):
                pass
    for tid in ("t1", "t2"):
        TRACER.complete(tid)
    j1, j2 = recorder.journeys()
    stages = [s[0] for s in sorted(j1["spans"], key=lambda s: s[1])]
    assert stages == ["pad_pack", "device_execute", "decode", "publish"]
    assert [s[:3] for s in j1["spans"]] == [s[:3] for s in j2["spans"]]
    assert all(s[2] > s[1] for s in j1["spans"])  # the scripted 1 ms clock
    summary = TRACER.stage_summary()
    assert {k: v["count"] for k, v in summary.items()} == {
        "pad_pack": 1, "device_execute": 1, "decode": 1, "publish": 1}
    assert set(tracing.STAGE_OF_SPAN.values()) <= set(STAGES)


def test_a_disarmed_tracer_gets_nothing_and_the_span_is_timed_all_the_same():
    tracing.reset()
    assert not TRACER.enabled
    before = TRACER.stage_summary()  # an earlier test's histograms stay
    with span("frame_pack"):
        pass
    assert tracing.totals()["frame_pack"]["count"] == 1
    assert TRACER.stage_summary() == before


def test_doorderbatch_takes_the_columnar_path_with_spans_live(monkeypatch):
    """The gateway's gate is on TRACER alone: spans are live in every
    process, and a disarmed TRACER leaves DoOrderBatch on _apply_columnar."""
    from tests.test_colgateway import _make_gateway, _req

    tracing.reset()
    assert not TRACER.enabled
    gw, pool, bus = _make_gateway(True)
    calls = []
    inner = gw._apply_columnar
    monkeypatch.setattr(
        gw, "_apply_columnar",
        lambda *a, **k: calls.append(len(a[0])) or inner(*a, **k))
    monkeypatch.setattr(
        gw, "_apply_entries",
        lambda *a, **k: pytest.fail("the scalar admit loop was taken"))
    reqs = [_req("u", f"o{i}", "s", pb.SALE, 1.0, 1.0) for i in range(5)]
    resp = gw.DoOrderBatch(pb.OrderBatchRequest(orders=reqs), None)
    assert (resp.code, resp.accepted) == (0, 5)
    assert calls == [5]
    assert tracing.totals()["gateway_admit"]["count"] == 1
    assert bus.order_queue.end_offset() == 1  # one frame, not five messages


# --- the granularity rule, at source level -------------------------------

#: file -> the loops a span( call may sit in: per grid, per queue message
#: (one ORDER or EVENT frame, or one run of JSON messages), per attempt.
ALLOWED_LOOPS = {
    "gome_tpu/service/gateway.py": set(),
    "gome_tpu/service/matchfeed.py": {
        "while i < len(msgs)",  # one EVENT frame or one run of JSON messages
        # the subscriber's wait, when empty, and its send: one per queue item
        "while not self._stop.is_set()",
    },
    "gome_tpu/engine/frames.py": {
        "for (g_i, (ops, meta, lane_ids, cap_g)) in enumerate(grids)",
        "for (ops, meta, lane_ids, cap_g) in grids",
    },
}


def _loops_around_span_calls(path):
    tree = ast.parse(open(os.path.join(ROOT, path)).read())
    found = []

    def visit(node, loops):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            loops = ()
        if isinstance(node, ast.For):
            head = (f"for {ast.unparse(node.target)} in "
                    f"{ast.unparse(node.iter)}")
            loops = loops + (head,)
        elif isinstance(node, ast.While):
            loops = loops + (f"while {ast.unparse(node.test)}",)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            loops = loops + ("comprehension",)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "span"):
            found.append((node.args[0].value, node.lineno, loops))
        for child in ast.iter_child_nodes(node):
            visit(child, loops)

    visit(tree, ())
    return found


@pytest.mark.parametrize("path", sorted(ALLOWED_LOOPS))
def test_no_span_is_opened_per_order_or_per_event(path):
    calls = _loops_around_span_calls(path)
    assert calls, f"{path} opens no span"
    for name, lineno, loops in calls:
        for loop in loops:
            assert loop in ALLOWED_LOOPS[path], (
                f"{path}:{lineno} span({name!r}) sits inside `{loop}`: a "
                "span is per request, frame, grid or queue batch, never per "
                "order or event")


def test_the_guard_sees_a_span_in_a_per_event_loop(tmp_path, monkeypatch):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def fan(results):\n"
        "    for mr in results:\n"
        "        with span('per_event'):\n"
        "            pass\n")
    monkeypatch.setitem(globals(), "ROOT", str(tmp_path))
    assert _loops_around_span_calls("bad.py") == [
        ("per_event", 3, ("for mr in results",))]


# --- the leaves, as a profile of a small service shows them --------------

LEAVES = {
    "gateway": {"gateway_admit"},
    "consumer": {"consumer_poll", "frame_unpack", "frame_admit", "frame_pack",
                 "grid_dispatch", "frame_fetch", "frame_decode",
                 "publish_events"},
    "feed": {"feed_poll", "feed_decode", "feed_fanout"},
    "stream": {"stream_wait", "stream_send"},
}
PARENTS = {"pipeline_feed"}


@pytest.fixture(scope="module")
def served_profile(tmp_path_factory):
    """A small EngineService (frames, pipeline depth 2) under
    jax.profiler.trace, pushed through DoOrderBatch with one subscriber."""
    import grpc
    import jax

    from gome_tpu.api.service import OrderStub
    from gome_tpu.config import BusConfig, Config, EngineConfig, GrpcConfig
    from gome_tpu.service import EngineService

    tracing.reset()
    svc = EngineService(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0),
        engine=EngineConfig(cap=32, n_slots=8, max_t=8, pipeline_depth=2),
        bus=BusConfig(backend="memory", match_wire="frame"),
    ))
    svc.feed.log_events = False
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    svc.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{svc._server.bound_port}")
    try:
        stub = OrderStub(channel)
        events = stub.SubscribeMatches(pb.SubscribeRequest(), timeout=120)
        deadline = time.monotonic() + 30
        while not svc.feed._subs and time.monotonic() < deadline:
            time.sleep(0.01)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        got = []
        try:
            for k in range(3):  # three frames: the pipeline fills and drains
                reqs = [
                    pb.OrderRequest(
                        uuid="u", oid=f"o{k}-{i}", symbol=f"s{i // 2 % 4}",
                        transaction=pb.SALE if i % 2 else pb.BUY,
                        price=1.0, volume=1.0)
                    for i in range(16)
                ]
                resp = stub.DoOrderBatch(pb.OrderBatchRequest(orders=reqs))
                assert (resp.code, resp.accepted) == (0, 16)
                for _ in range(8):  # every SALE meets a resting BUY
                    got.append(next(events))
            time.sleep(0.3)  # idle polls and an empty subscriber queue
        finally:
            jax.profiler.stop_trace()
        events.cancel()
        assert len(got) == 24
    finally:
        channel.close()
        svc.stop()
    from benchmark import tracered

    return tracered.find_xplane(trace_dir)


def test_a_profile_of_the_served_path_holds_every_leaf(served_profile):
    from benchmark import tracered

    names = set().union(*LEAVES.values()) | PARENTS
    raw = tracered.extract(served_profile, names)
    seen = {name for name, _start, _dur in raw["host"]}
    assert seen == names, names - seen
    reduced = tracered.reduce(raw)
    assert reduced["window_s"] == 0.0  # a CPU trace has no device plane
    assert reduced["spans"]["gateway_admit"][0] == 3
    assert reduced["spans"]["frame_pack"][0] == 3
    assert reduced["spans"]["feed_fanout"][0] == 3
    assert reduced["spans"]["grid_dispatch"][0] >= 3


def test_the_leaves_of_a_thread_do_not_overlap(served_profile):
    from jax.profiler import ProfileData

    every = set().union(*LEAVES.values())
    by_line = {}
    for plane in ProfileData.from_file(served_profile).planes:
        for i, line in enumerate(plane.lines):  # a line is a thread
            rows = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name in every)
            if rows:
                by_line[f"{plane.name}|{line.name}|{i}"] = rows
    kinds = {}
    for line, rows in by_line.items():
        for (_s0, e0, n0), (s1, _e1, n1) in zip(rows, rows[1:]):
            assert s1 >= e0, f"{n0} and {n1} overlap on {line}"
        names = {n for _s, _e, n in rows}
        kind = next(k for k, leaves in LEAVES.items() if names & leaves)
        assert names <= LEAVES[kind], (line, names)  # a thread, one role
        kinds.setdefault(kind, []).append(rows)
    assert set(kinds) == set(LEAVES)
    # The consumer's and the feed's loops are inside a leaf nearly all the
    # time between their first and last span (what is left is the loop's own
    # bookkeeping between two spans).
    for kind in ("consumer", "feed"):
        (rows,) = kinds[kind]
        covered = sum(e - s for s, e, _n in rows)
        assert covered / (rows[-1][1] - rows[0][0]) > 0.8, kind


# --- one frame across the four threads (ISSUE 38) -------------------------

FRAME_LEAVES = {"gateway_admit", "frame_unpack", "frame_admit", "frame_pack",
                "grid_dispatch", "frame_fetch", "frame_decode",
                "publish_events"}
MATCH_LEAVES = {"feed_decode", "feed_fanout", "stream_send"}


def _noted(served_profile):
    """[(span name, its metadata)] of every named event of the profile."""
    from jax.profiler import ProfileData

    every = set().union(*LEAVES.values()) | PARENTS
    return [
        (e.name, dict(e.stats))
        for plane in ProfileData.from_file(served_profile).planes
        for line in plane.lines for e in line.events if e.name in every
    ]


def test_every_leaf_of_one_frame_carries_the_same_frame(served_profile):
    """frame= is the order-queue offset on the gateway's and the consumer's
    spans, match= the match-queue offset on the feed's and the handler's,
    and publish_events has both: one frame can be followed across threads."""
    noted = _noted(served_profile)
    frames = {}
    for name, meta in noted:
        if name in FRAME_LEAVES | PARENTS:
            assert "frame" in meta, (name, meta)
            frames.setdefault(meta["frame"], set()).add(name)
    assert frames == {k: FRAME_LEAVES | PARENTS for k in range(3)}
    match_of = {meta["frame"]: meta["match"] for name, meta in noted
                if name == "publish_events"}
    assert match_of == {0: 0, 1: 1, 2: 2}  # a frame of fills, each of them
    matches = {}
    for name, meta in noted:
        if name in MATCH_LEAVES:
            matches.setdefault(meta["match"], set()).add(name)
    assert matches == {k: MATCH_LEAVES for k in range(3)}
    # the polls that picked a frame up say which, and how long it had lain
    for poll, key in (("consumer_poll", "frame"), ("feed_poll", "match")):
        picked = [meta for name, meta in noted
                  if name == poll and key in meta]
        # (the poll that brought the first may have begun before the trace)
        assert {1, 2} <= {meta[key] for meta in picked} <= {0, 1, 2}, poll
        assert all(meta["dwell_us"] >= 0 for meta in picked)
    sends = [meta for name, meta in noted if name == "stream_send"]
    assert all(meta["events"] == 8 and "first_us" in meta for meta in sends)


def test_the_handler_is_inside_one_of_its_two_leaves_all_the_time(
        served_profile):
    """stream_wait while its queue is empty, stream_send while gRPC takes a
    chunk: between its first and last span nothing else."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(served_profile).planes:
        for line in plane.lines:
            rows = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name in LEAVES["stream"])
            if rows:
                lines.append(rows)
    (rows,) = lines  # one subscriber, one thread
    assert {name for _s, _e, name in rows} == LEAVES["stream"]
    covered = sum(e - s for s, e, _n in rows)
    assert covered / (rows[-1][1] - rows[0][0]) > 0.95


@pytest.mark.parametrize("backend", ["memory", "file"])
def test_a_served_request_puts_the_new_names_on_metrics(backend, tmp_path):
    """gome_span_seconds_total / gome_span_count carry the stream's span and
    the three hand-offs after one request; on the file bus the log's reads
    and the cursors' writes too."""
    from gome_tpu.config import BusConfig, Config, EngineConfig, GrpcConfig
    from gome_tpu.service import EngineService
    from gome_tpu.utils.metrics import REGISTRY

    tracing.reset()
    svc = EngineService(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0),
        engine=EngineConfig(cap=32, n_slots=8, max_t=8, pipeline_depth=2),
        bus=BusConfig(backend=backend, dir=str(tmp_path / "bus"),
                      match_wire="frame"),
    ))
    svc.feed.log_events = False
    svc.start()
    try:
        events = svc.feed.subscribe()
        got = []
        reader = threading.Thread(
            target=lambda: got.extend(itertools.islice(events, 9)),
            daemon=True)
        reader.start()
        deadline = time.monotonic() + 30
        while not svc.feed._subs and time.monotonic() < deadline:
            time.sleep(0.01)
        for k in range(2):  # the second frame's first event closes the first
            reqs = [
                pb.OrderRequest(
                    uuid="u", oid=f"o{k}-{i}", symbol=f"s{i // 2 % 4}",
                    transaction=pb.SALE if i % 2 else pb.BUY,
                    price=1.0, volume=1.0)
                for i in range(16)
            ]
            resp = svc.gateway.DoOrderBatch(
                pb.OrderBatchRequest(orders=reqs), None)
            assert (resp.code, resp.accepted) == (0, 16)
        reader.join(timeout=60)
        assert len(got) == 9
    finally:
        svc.stop()
    names = ["stream_send", "order_queue_dwell", "match_queue_dwell",
             "subscriber_queue_dwell"]
    if backend == "file":
        names += ["log_read", "cursor_commit"]
    text = REGISTRY.render()
    rows = tracing.totals()
    for name in names:
        assert rows[name]["count"] >= 1, name
        for family in ("gome_span_seconds_total", "gome_span_count"):
            assert f'{family}{{span="{name}"}}' in text, (family, name)
    if backend == "memory":
        assert not {"log_read", "cursor_commit"} & {
            name for name, row in rows.items() if row["count"]}
