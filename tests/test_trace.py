"""Order-lifecycle tracing (ISSUE 2): span propagation gateway → bus →
consumer, per-stage histograms on /metrics, the flight recorder behind
/trace, labeled metric families, the Prometheus exposition golden, and
the no-op-recorder hot-path guard.
"""

from __future__ import annotations

import itertools
import json
import sys
import time
import urllib.request

import pytest

from gome_tpu.api import order_pb2 as pb
from gome_tpu.bus import decode_orders_batch
from gome_tpu.bus.codec import decode_order, encode_order
from gome_tpu.bus.colwire import decode_order_frame, encode_orders
from gome_tpu.types import Action, Order, Side
from gome_tpu.utils.metrics import Histogram, Registry
from gome_tpu.utils.trace import (
    STAGES,
    TRACER,
    FlightRecorder,
    Tracer,
    decode_context,
    encode_context,
)


@pytest.fixture
def global_tracer():
    """Arm the process-global tracer with a scripted clock + scripted ids
    and a private registry; restore the disabled zero-overhead state (and
    the real clock) afterwards, whatever the test did."""
    ticks = itertools.count(1)
    ids = itertools.count(1)
    registry = Registry()
    recorder = FlightRecorder(keep_n=16, slow_threshold_s=5.0)
    TRACER.install(
        recorder,
        registry=registry,
        clock=lambda: next(ticks) * 1e-3,  # 1ms per reading, monotone
        new_id=lambda: f"trace-{next(ids)}",
    )
    try:
        yield TRACER, recorder, registry
    finally:
        TRACER.disable()
        TRACER.clock = time.perf_counter
        TRACER._new_id = None


def order(oid="o1", trace=None, side=Side.SALE, action=Action.ADD):
    return Order(
        uuid="u1", oid=oid, symbol="eth2usdt", side=side,
        price=100, volume=5, action=action, trace=trace,
    )


# --- trace-context + wire propagation ------------------------------------


def test_context_codec_roundtrip():
    ctx = encode_context("abc-123", 1.25)
    assert decode_context(ctx) == ("abc-123", 1.25)
    # A bare id (header written by a non-tracing producer) still decodes.
    assert decode_context("abc-123") == ("abc-123", 0.0)


def test_trace_context_roundtrips_json_codec():
    o = order(trace="tid-1@0.500000000")
    d = decode_order(encode_order(o))
    assert d == o  # trace is compare=False, but the rest is identical
    assert d.trace == "tid-1@0.500000000"
    # ...and through the batch decoder (native parsers decline unknown
    # keys and must fall back to the exact json path).
    d2 = decode_orders_batch([encode_order(o)])[0]
    assert d2.trace == "tid-1@0.500000000"


def test_untraced_json_wire_is_reference_shaped():
    body = encode_order(order())
    assert b"Trace" not in body  # reference parity: no extension field


def test_trace_context_roundtrips_order_frame():
    traced = order(oid="a", trace="tid-9@2.000000000")
    plain = order(oid="b")
    frame = encode_orders([traced, plain])
    assert frame[:4] == b"GCO3"
    cols = decode_order_frame(frame)
    assert cols["trace"].tolist() == [b"tid-9@2.000000000", b""]
    # Untraced batches stay byte-identical GCO2 (zero wire overhead).
    frame2 = encode_orders([plain])
    assert frame2[:4] == b"GCO2"
    assert "trace" not in decode_order_frame(frame2)


def test_amqp_headers_survive_broker_hop():
    from gome_tpu.bus.amqp import AmqpQueue
    from gome_tpu.bus.fakebroker import FakeBroker

    broker = FakeBroker().start()
    try:
        q = AmqpQueue("doOrder", port=broker.port)
        try:
            assert q.supports_headers
            q.publish(b"payload-0")  # no headers
            q.publish(b"payload-1", headers={"x-trace": "tid-7@1.5"})
            msgs = q.read_from(0, 10)
            assert [m.body for m in msgs] == [b"payload-0", b"payload-1"]
            assert msgs[0].headers is None
            assert msgs[1].headers == {"x-trace": "tid-7@1.5"}
        finally:
            q.close()
    finally:
        broker.stop()


# --- labeled metrics + exposition golden (satellite) ----------------------


def test_labeled_counter_family_renders_once():
    r = Registry()
    a = r.counter("reqs_total", "requests", labels={"stage": "in"})
    b = r.counter("reqs_total", "requests", labels={"stage": "out"})
    a.inc(2)
    b.inc()
    # Re-registering the same labels returns the SAME series.
    assert r.counter("reqs_total", labels={"stage": "in"}) is a
    assert r.render() == (
        "# HELP reqs_total requests\n"
        "# TYPE reqs_total counter\n"
        'reqs_total{stage="in"} 2\n'
        'reqs_total{stage="out"} 1\n'
    )


def test_flat_vs_labeled_name_conflict_raises():
    r = Registry()
    r.counter("x_total")
    with pytest.raises(ValueError, match="WITHOUT labels"):
        r.counter("x_total", labels={"k": "v"})


def test_labeled_histogram_merges_le_labels():
    r = Registry()
    h = r.histogram("lat", "l", buckets=(0.1, 1.0), labels={"stage": "s"})
    h.observe(0.05)
    lines = h.render_samples()
    assert lines[0] == 'lat_bucket{stage="s",le="0.1"} 1'
    assert 'lat_sum{stage="s"}' in lines[-2]


def test_histogram_render_golden():
    """Golden exposition for a flat histogram: empty, then one in-range
    observation, then an overflow observation — cumulative buckets, +Inf
    == count, and the exact line layout Prometheus parses."""
    h = Histogram("d_seconds", "drill", buckets=(0.001, 0.01))
    assert h.render() == (
        "# HELP d_seconds drill\n"
        "# TYPE d_seconds histogram\n"
        'd_seconds_bucket{le="0.001"} 0\n'
        'd_seconds_bucket{le="0.01"} 0\n'
        'd_seconds_bucket{le="+Inf"} 0\n'
        "d_seconds_sum 0.0\n"
        "d_seconds_count 0"
    )
    h.observe(0.005)
    h.observe(5.0)  # overflow bucket
    assert h.render() == (
        "# HELP d_seconds drill\n"
        "# TYPE d_seconds histogram\n"
        'd_seconds_bucket{le="0.001"} 0\n'
        'd_seconds_bucket{le="0.01"} 1\n'
        'd_seconds_bucket{le="+Inf"} 2\n'
        "d_seconds_sum 5.005\n"
        "d_seconds_count 2"
    )


def test_histogram_quantile_edges():
    h = Histogram("q", buckets=(0.001, 0.01))
    assert h.quantile(0.5) == 0.0  # empty
    h.observe(0.005)  # single observation: inside (0.001, 0.01]
    assert 0.001 < h.quantile(0.5) <= 0.01
    assert h.value()["count"] == 1
    h2 = Histogram("q2", buckets=(0.001, 0.01))
    for _ in range(10):
        h2.observe(99.0)  # all overflow
    # Quantiles in the overflow bucket interpolate within the documented
    # cap (2x the last finite bucket) — never 0, never unbounded.
    assert 0.01 < h2.quantile(0.99) <= 0.02
    assert h2.quantile(1.0) == pytest.approx(0.02)


# --- flight recorder ------------------------------------------------------


def test_flight_recorder_rings_and_chrome_trace():
    rec = FlightRecorder(keep_n=2, slow_threshold_s=0.5)
    for i in range(4):
        tid = f"t{i}"
        rec.record(tid, "ingress", 0.0, 0.1)
        # journey t3 is slow (2s end to end)
        rec.record(tid, "publish", 0.1, 2.0 if i == 3 else 0.2)
        rec.complete(tid)
    js = rec.journeys()
    ids = [j["trace_id"] for j in js]
    assert ids[:2] == ["t2", "t3"]  # last-N ring
    assert "t3" in ids  # slow journey pinned
    dump = rec.chrome_trace()
    json.loads(json.dumps(dump))  # valid JSON
    evs = [e for e in dump["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in evs} == {"ingress", "publish"}
    assert all(
        set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
        for e in evs
    )


def test_flight_recorder_bounds_open_journeys():
    rec = FlightRecorder(keep_n=4, max_open=8)
    for i in range(50):  # lost publishes must not leak
        rec.record(f"t{i}", "ingress", 0.0, 1.0)
    assert len(rec._open) == 8
    assert rec.dropped_open == 42


# --- the deterministic end-to-end drill (acceptance) ----------------------


def _drive_drill(bus):
    """One crossing pair through gateway → bus → consumer on the scripted
    clock; returns the consumer after both orders processed."""
    import jax.numpy as jnp

    from gome_tpu.engine.book import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.gateway import OrderGateway

    engine = MatchEngine(
        config=BookConfig(cap=16, max_fills=8, dtype=jnp.int64),
        n_slots=4,
        max_t=8,
    )
    consumer = OrderConsumer(engine, bus, batch_n=16, batch_wait_s=0)
    gateway = OrderGateway(
        bus, accuracy=8, mark=engine.mark, unmark=engine.unmark
    )
    r1 = gateway.DoOrder(
        pb.OrderRequest(uuid="u1", oid="a1", symbol="eth2usdt",
                        transaction=pb.SALE, price=1.0, volume=5.0),
        None,
    )
    r2 = gateway.DoOrder(
        pb.OrderRequest(uuid="u2", oid="b1", symbol="eth2usdt",
                        transaction=pb.BUY, price=1.0, volume=3.0),
        None,
    )
    assert r1.code == 0 and r2.code == 0
    processed = 0
    deadline = time.monotonic() + 60
    while processed < 2 and time.monotonic() < deadline:
        processed += consumer.run_once()
    assert processed == 2
    return consumer


def _assert_contiguous_journey(journey, expect_stages):
    """The acceptance shape: one shared trace id, spans present for every
    expected stage, ordered and contiguous (each span starts at or after
    the previous one's start and the chain is monotone in time)."""
    spans = sorted(journey["spans"], key=lambda s: (s[1], s[2]))
    names = [s[0] for s in spans]
    for stage in expect_stages:
        assert stage in names, f"missing span {stage}: {names}"
    # Pipeline order respected for the expected subset...
    positions = [names.index(stage) for stage in expect_stages]
    assert positions == sorted(positions), names
    # ...and the chain is contiguous: monotone start times, and every
    # span starts no earlier than the journey start / ends by the end.
    starts = [s[1] for s in spans]
    assert starts == sorted(starts)
    assert all(
        journey["start"] <= s[1] <= s[2] <= journey["end"] for s in spans
    )
    # Scripted 1ms clock: every reading is distinct, so zero-length or
    # overlapping-identical spans cannot hide a broken chain.
    assert journey["end"] > journey["start"]


def test_single_order_journey_survives_amqp_hop(global_tracer):
    """ISSUE 2 acceptance: a single order's journey yields a contiguous
    span chain ingress→publish with ONE shared trace id surviving the
    AMQP hop (fake broker, real 0-9-1 framing), /trace returns valid
    Chrome trace-event JSON containing it, and the per-stage histograms
    scrape with nonzero counts."""
    tracer, recorder, registry = global_tracer
    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.bus.amqp import AmqpQueue
    from gome_tpu.bus.fakebroker import FakeBroker
    from gome_tpu.service.ops import OpsServer

    broker = FakeBroker().start()
    oq = AmqpQueue("doOrder", port=broker.port)
    bus = QueueBus(order_queue=oq, match_queue=MemoryQueue("matchOrder"))
    try:
        _drive_drill(bus)
        journeys = recorder.journeys()
        assert len(journeys) == 2  # both orders completed their journeys
        j = journeys[0]
        assert j["trace_id"] == "trace-1"
        _assert_contiguous_journey(
            j,
            ["ingress", "enqueue", "bus_transit", "pad_pack",
             "device_execute", "decode", "publish"],
        )
        # One shared trace id end to end: every span of this journey was
        # recorded under it (journeys are keyed by id, so presence of the
        # full chain IS the shared-id property), and the two journeys
        # never bled into each other.
        assert journeys[1]["trace_id"] == "trace-2"

        # Per-stage histograms on /metrics with nonzero counts.
        exposition = registry.render()
        for stage in ("ingress", "enqueue", "bus_transit", "pad_pack",
                      "device_execute", "decode", "publish"):
            val = tracer._hist[stage].value()
            assert val["count"] > 0, f"no {stage} observations"
        assert 'gome_stage_seconds_count{stage="ingress"} 2' in exposition

        # /trace over real HTTP returns valid Chrome trace-event JSON
        # containing the trace id.
        ops = OpsServer(registry=registry).start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ops.port}/trace"
            ) as resp:
                assert resp.status == 200
                dump = json.load(resp)
            assert isinstance(dump["traceEvents"], list)
            ids = {
                e["args"]["trace_id"]
                for e in dump["traceEvents"]
                if e.get("ph") == "X"
            }
            assert "trace-1" in ids and "trace-2" in ids
            phases = {e["ph"] for e in dump["traceEvents"]}
            assert phases <= {"X", "M"}
            # /metrics over the same endpoint shows the stage family.
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ops.port}/metrics"
            ) as resp:
                assert "gome_stage_seconds" in resp.read().decode()
        finally:
            ops.stop()
    finally:
        oq.close()
        broker.stop()


def test_journey_through_batcher_frame_path(global_tracer):
    """The frame topology: gateway → FrameBatcher (GCO3 ORDER frame) →
    consumer. The journey gains a batch_wait span and the context
    survives the columnar hop."""
    tracer, recorder, registry = global_tracer
    from gome_tpu.bus import MemoryQueue, QueueBus

    import jax.numpy as jnp

    from gome_tpu.engine.book import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.batcher import FrameBatcher
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.gateway import OrderGateway

    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    engine = MatchEngine(
        config=BookConfig(cap=16, max_fills=8, dtype=jnp.int64),
        n_slots=4, max_t=8,
    )
    consumer = OrderConsumer(
        engine, bus, batch_n=16, batch_wait_s=0, match_wire="frame"
    )
    batcher = FrameBatcher(bus.order_queue, max_n=4096, max_wait_s=60)
    try:
        gateway = OrderGateway(
            bus, accuracy=8, mark=engine.mark, unmark=engine.unmark,
            batcher=batcher,
        )
        for uuid, oid, side in (("u1", "a1", pb.SALE), ("u2", "b1", pb.BUY)):
            r = gateway.DoOrder(
                pb.OrderRequest(uuid=uuid, oid=oid, symbol="eth2usdt",
                                transaction=side, price=1.0, volume=2.0),
                None,
            )
            assert r.code == 0
        assert batcher.flush() == 2  # one GCO3 frame for both orders
        body = bus.order_queue.read_from(0, 1)[0].body
        assert body[:4] == b"GCO3"
        processed = 0
        deadline = time.monotonic() + 60
        while processed < 2 and time.monotonic() < deadline:
            processed += consumer.run_once()
        assert processed == 2
        journeys = recorder.journeys()
        assert [j["trace_id"] for j in journeys] == ["trace-1", "trace-2"]
        _assert_contiguous_journey(
            journeys[0],
            ["ingress", "enqueue", "batch_wait", "bus_transit",
             "pad_pack", "device_execute", "decode", "publish"],
        )
        assert tracer._hist["batch_wait"].value()["count"] == 2
    finally:
        batcher.close()


# --- hot-path overhead guard (acceptance) ---------------------------------


def test_disabled_tracer_spans_allocate_nothing():
    """With the recorder disabled, the span hooks on the frame hot path
    are the SAME shared no-op object and allocate nothing — asserted via
    sys.getallocatedblocks over a tight loop (CPython exact)."""
    t = Tracer()  # never installed
    assert not t.enabled
    assert t.new_trace() is None
    s = t.span("device_execute")
    assert s is t.span("pad_pack") is t.batch(["x"][:0]) is t.bind(None)
    # The frame-scoped stage()/annotation() hooks went with PR 25: those
    # stages are utils.tracing.span's now (tests/test_spans.py).
    assert not hasattr(t, "stage") and not hasattr(t, "annotation")

    def drill(n):
        i = 0
        while i < n:  # small ints are interned: the loop itself is clean
            with t.span("device_execute"):
                pass
            with t.batch(None):
                pass
            t.observe("decode", 0.0)
            t.observe_span("publish", 0.0, 0.0)
            t.complete(None)
            i += 1

    drill(64)  # warm any lazy caches
    before = sys.getallocatedblocks()
    drill(200)
    after = sys.getallocatedblocks()
    assert after - before <= 2, f"hot-path hooks allocated {after - before}"


def test_disabled_tracer_emits_no_trace_on_wire():
    """Tracing off ⇒ orders carry no context and frames stay GCO2 — the
    wire is byte-identical to the pre-tracing build."""
    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.service.gateway import OrderGateway

    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    gateway = OrderGateway(bus, accuracy=8)
    r = gateway.DoOrder(
        pb.OrderRequest(uuid="u", oid="o", symbol="s",
                        transaction=pb.SALE, price=1.0, volume=1.0),
        None,
    )
    assert r.code == 0
    msg = bus.order_queue.read_from(0, 1)[0]
    assert b"Trace" not in msg.body
    assert msg.headers is None


# --- logging join (satellite) --------------------------------------------


def test_json_log_formatter_injects_trace_id():
    import logging

    from gome_tpu.utils.logging import JsonLineFormatter

    fmt = JsonLineFormatter()
    rec = logging.LogRecord(
        "gome_tpu.gateway", logging.INFO, __file__, 1,
        "accepted %s", ("a1",), None,
    )
    with TRACER.bind("tid-42"):
        line = json.loads(fmt.format(rec))
    assert line["msg"] == "accepted a1"
    assert line["trace_id"] == "tid-42"
    assert line["level"] == "INFO"
    # Outside a bound context: no trace_id key at all.
    line2 = json.loads(fmt.format(rec))
    assert "trace_id" not in line2


def test_stage_taxonomy_is_documented():
    """ARCHITECTURE.md's span table and the code must not drift."""
    import pathlib

    doc = (
        pathlib.Path(__file__).resolve().parents[1] / "ARCHITECTURE.md"
    ).read_text()
    for stage in STAGES:
        assert f"`{stage}`" in doc or stage in doc, stage
