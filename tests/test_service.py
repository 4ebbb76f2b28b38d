"""End-to-end service tests: gRPC gateway → bus → consumer → engine →
matchOrder feed, against the oracle as referee (SURVEY §3.1-3.4 call paths).
"""

import grpc
import pytest

from gome_tpu.api import order_pb2 as pb
from gome_tpu.api.service import OrderStub
from gome_tpu.bus import decode_match_result
from gome_tpu.config import Config, EngineConfig, GrpcConfig
from gome_tpu.oracle import OracleEngine
from gome_tpu.service import EngineService
from gome_tpu.types import MatchResult, Order, Side


def make_service(**engine_kw):
    cfg = Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0),  # ephemeral port
        engine=EngineConfig(cap=32, n_slots=8, max_t=8, **engine_kw),
    )
    return EngineService(cfg)


class TestEndToEnd:
    def setup_method(self):
        self.svc = make_service()
        from concurrent import futures

        from gome_tpu.api.service import add_order_servicer

        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        add_order_servicer(self.server, self.svc.gateway)
        self.port = self.server.add_insecure_port("127.0.0.1:0")
        assert self.port != 0
        self.server.start()
        self.channel = grpc.insecure_channel(f"127.0.0.1:{self.port}")
        self.stub = OrderStub(self.channel)

    def teardown_method(self):
        self.channel.close()
        self.server.stop(grace=None)

    def do(self, uuid, oid, side, price, volume, kind=0):
        return self.stub.DoOrder(
            pb.OrderRequest(
                uuid=uuid, oid=oid, symbol="eth2usdt",
                transaction=side, price=price, volume=volume, kind=kind,
            )
        )

    def test_submit_match_cancel_flow(self):
        # SALE 1.00 x 5 rests; BUY 1.00 x 3 fills 3; cancel ask remainder.
        r1 = self.do("u1", "a1", pb.SALE, 1.00, 5.0)
        assert r1.code == 0 and "accepted" in r1.message
        r2 = self.do("u2", "b1", pb.BUY, 1.00, 3.0)
        assert r2.code == 0
        assert self.svc.pump() == 2

        msgs = self.svc.bus.match_queue.read_from(0, 10)
        events = [decode_match_result(m.body) for m in msgs]
        assert len(events) == 1
        ev = events[0]
        assert ev.match_volume == 3 * 10**8
        assert ev.node.oid == "b1" and ev.match_node.oid == "a1"
        assert ev.match_node.price == 10**8  # fill at maker level
        assert ev.match_node.volume == 2 * 10**8  # partial: remaining

        r3 = self.stub.DeleteOrder(
            pb.OrderRequest(
                uuid="u1", oid="a1", symbol="eth2usdt",
                transaction=pb.SALE, price=1.00, volume=5.0,
            )
        )
        assert r3.code == 0
        self.svc.pump()
        events = [
            decode_match_result(m.body)
            for m in self.svc.bus.match_queue.read_from(0, 10)
        ]
        assert len(events) == 2
        assert events[1].is_cancel
        assert events[1].node.volume == 2 * 10**8  # remaining at cancel

    def test_gateway_rejects_bad_input(self):
        r = self.do("u", "x", pb.BUY, 1.0, 0.0)
        assert r.code == 3  # volume must be positive
        r = self.do("u", "x2", pb.BUY, 0.0, 1.0)
        assert r.code == 3  # limit price must be positive
        r = self.do("u", "x3", pb.BUY, 1.000000001, 1.0)  # > accuracy=8 dp? no: 9dp
        assert r.code == 3
        self.svc.pump()
        assert self.svc.bus.match_queue.end_offset() == 0

    def test_market_order_extension(self):
        self.do("m1", "s1", pb.SALE, 1.00, 5.0)
        self.do("m2", "t1", pb.BUY, 0.0, 2.0, kind=pb.MARKET)
        self.svc.pump()
        events = [
            decode_match_result(m.body)
            for m in self.svc.bus.match_queue.read_from(0, 10)
        ]
        assert len(events) == 1
        assert events[0].match_volume == 2 * 10**8
        assert events[0].match_node.price == 10**8

    def test_cancel_before_consume_race(self):
        """SURVEY §2.3.3: DEL consumed before the queued ADD kills it via the
        pre-pool."""
        self.do("u1", "r1", pb.SALE, 1.00, 5.0)  # marked + queued
        self.stub.DeleteOrder(
            pb.OrderRequest(
                uuid="u1", oid="r1", symbol="eth2usdt",
                transaction=pb.SALE, price=1.00, volume=5.0,
            )
        )
        # Reorder delivery: consumer sees DEL first (simulates the race the
        # reference handles via the pre-pool). With FIFO bus both arrive in
        # one batch; the admission loop clears the mark on DEL only if DEL
        # precedes — here ADD precedes so it IS admitted, then DEL cancels.
        self.svc.pump()
        books = self.svc.engine.batch.lane_books()
        assert int(books.count.sum()) == 0  # nothing left resting

    def test_subscribe_stream_delivers(self):
        sub = self.stub.SubscribeMatches(pb.SubscribeRequest())
        self.do("u1", "a1", pb.SALE, 1.00, 1.0)
        self.do("u2", "b1", pb.BUY, 1.00, 1.0)
        self.svc.pump()
        ev = next(iter(sub))
        assert ev.match_volume == pytest.approx(1e8)
        assert ev.node.oid == "b1"
        sub.cancel()


def test_service_parity_vs_oracle():
    """Full mixed stream through the service loop equals the oracle's event
    stream (the §4 golden-replay strategy at the service layer)."""
    from gome_tpu.utils.streams import mixed_stream

    svc = make_service()
    oracle = OracleEngine()
    orders = mixed_stream(n=300, seed=11, cancel_prob=0.25)
    expected: list[MatchResult] = []
    for o in orders:
        expected.extend(oracle.process(o))

    got: list[MatchResult] = []
    for o in orders:
        svc.engine.mark(o)
    from gome_tpu.bus import encode_order

    for o in orders:
        svc.bus.order_queue.publish(encode_order(o))
    svc.pump()
    got = [
        decode_match_result(m.body)
        for m in svc.bus.match_queue.read_from(
            0, svc.bus.match_queue.end_offset()
        )
    ]
    assert got == expected


class TestFrameBatcher:
    """The gateway->frame batching bridge (service.batcher): per-request
    gRPC traffic leaves as columnar ORDER frames (SURVEY L4's missing
    production story: who aggregates, at what latency cost)."""

    def _orders(self, n, start=0):
        from gome_tpu.types import Action, Order, OrderType, Side

        return [
            Order(
                uuid="u", oid=f"o{start + i}", symbol="s", side=Side.BUY,
                price=100, volume=1, action=Action.ADD,
                order_type=OrderType.LIMIT,
            )
            for i in range(n)
        ]

    def test_size_bound_flush_preserves_order(self):
        from gome_tpu.bus import MemoryQueue
        from gome_tpu.bus.colwire import decode_order_frame
        from gome_tpu.service.batcher import FrameBatcher

        q = MemoryQueue("doOrder")
        b = FrameBatcher(q, max_n=16, max_wait_s=60)
        for o in self._orders(40):
            b.submit(o)
        try:
            # Two full frames flushed by size; 8 remain buffered.
            msgs = q.read_from(0, 10)
            assert len(msgs) == 2
            oids = []
            for m in msgs:
                cols = decode_order_frame(m.body)
                assert cols["n"] == 16
                oids.extend(x.decode() for x in cols["oids"])
            assert oids == [f"o{i}" for i in range(32)]
            assert b.flush() == 8
            cols = decode_order_frame(q.read_from(2, 10)[0].body)
            assert [x.decode() for x in cols["oids"]] == [
                f"o{i}" for i in range(32, 40)
            ]
        finally:
            b.close()

    def test_deadline_flush(self):
        import time

        from gome_tpu.bus import MemoryQueue
        from gome_tpu.service.batcher import FrameBatcher

        q = MemoryQueue("doOrder")
        b = FrameBatcher(q, max_n=1 << 20, max_wait_s=0.05)
        try:
            for o in self._orders(5):
                b.submit(o)
            deadline = time.monotonic() + 5
            while q.end_offset() == 0:
                assert time.monotonic() < deadline, "deadline never flushed"
                time.sleep(0.01)
            from gome_tpu.bus.colwire import decode_order_frame

            assert decode_order_frame(q.read_from(0, 1)[0].body)["n"] == 5
        finally:
            b.close()

    def test_close_flushes_remainder(self):
        from gome_tpu.bus import MemoryQueue
        from gome_tpu.service.batcher import FrameBatcher

        q = MemoryQueue("doOrder")
        b = FrameBatcher(q, max_n=100, max_wait_s=60)
        for o in self._orders(7):
            b.submit(o)
        b.close()
        assert q.end_offset() == 1


class TestGatewayBatcherEndToEnd:
    """Real channel -> OrderGateway(batcher=...) -> ORDER frames -> frame
    consumer: the gRPC-inclusive ingest path, oracle-checked."""

    def test_grpc_to_frames_to_events(self):
        from concurrent import futures

        from gome_tpu.api.service import add_order_servicer
        from gome_tpu.bus import MemoryQueue, QueueBus
        from gome_tpu.bus.colwire import decode_event_frame, is_frame
        from gome_tpu.engine import BookConfig
        from gome_tpu.engine.orchestrator import MatchEngine
        from gome_tpu.service.batcher import FrameBatcher
        from gome_tpu.service.consumer import OrderConsumer
        from gome_tpu.service.gateway import OrderGateway

        engine = MatchEngine(
            config=BookConfig(cap=32, max_fills=8), n_slots=8, max_t=8
        )
        bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
        batcher = FrameBatcher(bus.order_queue, max_n=8, max_wait_s=60)
        gw = OrderGateway(bus, accuracy=8, mark=engine.mark, batcher=batcher)
        server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        add_order_servicer(server, gw)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()
        oracle = OracleEngine()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
                stub = OrderStub(ch)
                reqs = [
                    ("u1", "a1", pb.SALE, 1.00, 5.0),
                    ("u2", "b1", pb.BUY, 1.00, 3.0),
                    ("u1", "a2", pb.SALE, 1.01, 2.0),
                    ("u2", "b2", pb.BUY, 1.01, 4.0),
                ]
                for uuid, oid, side, price, vol in reqs:
                    r = stub.DoOrder(
                        pb.OrderRequest(
                            uuid=uuid, oid=oid, symbol="s",
                            transaction=side, price=price, volume=vol,
                        )
                    )
                    assert r.code == 0
                # Cancel b2's remainder over gRPC too.
                stub.DeleteOrder(
                    pb.OrderRequest(
                        uuid="u2", oid="b2", symbol="s",
                        transaction=pb.BUY, price=1.01, volume=0,
                    )
                )
            batcher.close()
            # Everything left as ONE frame (5 ops < max_n after close).
            msgs = bus.order_queue.read_from(0, 10)
            assert len(msgs) == 1 and is_frame(msgs[0].body)
            consumer = OrderConsumer(
                engine, bus, batch_n=8, batch_wait_s=0, match_wire="frame"
            )
            consumer.drain()
            got = []
            for m in bus.match_queue.read_from(0, 100):
                got.extend(decode_event_frame(m.body).to_results())
            from gome_tpu.types import Action, Order, OrderType, Side
            from gome_tpu.fixed import scale

            expected = []
            for uuid, oid, side, price, vol in reqs:
                expected.extend(
                    oracle.process(
                        Order(
                            uuid=uuid, oid=oid, symbol="s",
                            side=Side(side), price=scale(price, 8),
                            volume=scale(vol, 8), action=Action.ADD,
                            order_type=OrderType.LIMIT,
                        )
                    )
                )
            expected.extend(
                oracle.process(
                    Order(
                        uuid="u2", oid="b2", symbol="s", side=Side.BUY,
                        price=scale(1.01, 8), volume=0, action=Action.DEL,
                        order_type=OrderType.LIMIT,
                    )
                )
            )
            assert got == expected
        finally:
            server.stop(grace=None)


def test_engine_service_mesh_devices_config():
    """EngineConfig.mesh_devices shards the service's engine over a 1-D
    device mesh at construction — the config-level deployment knob for a
    mesh-sharded consumer (VERDICT r4 #4)."""
    import jax

    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.config import Config, EngineConfig, GrpcConfig

    svc = EngineService(
        Config(
            grpc=GrpcConfig(port=0),
            engine=EngineConfig(
                cap=16, n_slots=8, max_t=8, mesh_devices=4
            ),
        )
    )
    assert svc.engine.batch.mesh is not None
    assert svc.engine.batch.mesh.size == 4
    r = svc.gateway.DoOrder(
        pb.OrderRequest(
            uuid="u", oid="a", symbol="eth2usdt",
            transaction=pb.SALE, price=2.0, volume=1.0,
        ),
        None,
    )
    assert r.code == 0
    r = svc.gateway.DoOrder(
        pb.OrderRequest(
            uuid="u", oid="b", symbol="eth2usdt",
            transaction=pb.BUY, price=2.0, volume=1.0,
        ),
        None,
    )
    assert r.code == 0
    svc.pump()
    msgs = svc.bus.match_queue.read_from(0, 100)
    assert len(msgs) == 1  # the cross matched while sharded
    specs = {
        str(getattr(l.sharding, "spec", None))
        for l in jax.tree.leaves(svc.engine.books)
    }
    assert "PartitionSpec('sym',)" in specs


class TestBatchIngestRpc:
    """DoOrderBatch / DoOrderStream (the amortized front door, VERDICT r4
    #3): same admission semantics as the unary RPCs, same event stream,
    per-order rejects reported, same-batch ADD->DEL ordering preserved."""

    def _setup(self, max_n=64):
        from gome_tpu.bus import MemoryQueue, QueueBus
        from gome_tpu.engine import BookConfig
        from gome_tpu.engine.orchestrator import MatchEngine
        from gome_tpu.service.batcher import FrameBatcher
        from gome_tpu.service.consumer import OrderConsumer
        from gome_tpu.service.gateway import OrderGateway

        engine = MatchEngine(
            config=BookConfig(cap=32, max_fills=8), n_slots=8, max_t=16
        )
        bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
        batcher = FrameBatcher(bus.order_queue, max_n=max_n, max_wait_s=60)
        gw = OrderGateway(
            bus, accuracy=8, mark=engine.mark, unmark=engine.unmark,
            batcher=batcher,
        )
        consumer = OrderConsumer(
            engine, bus, batch_n=64, batch_wait_s=0, match_wire="frame"
        )
        return engine, bus, batcher, gw, consumer

    def _req(self, uuid, oid, side, price, vol):
        return pb.OrderRequest(
            uuid=uuid, oid=oid, symbol="s", transaction=side,
            price=price, volume=vol,
        )

    def test_batch_rpc_matches_unary_semantics(self):
        from concurrent import futures

        from gome_tpu.bus.colwire import decode_event_frame

        engine, bus, batcher, gw, consumer = self._setup()
        server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        from gome_tpu.api.service import add_order_servicer

        add_order_servicer(server, gw)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
                stub = OrderStub(ch)
                orders = [
                    self._req("u1", "a1", pb.SALE, 1.00, 5.0),
                    self._req("u2", "b1", pb.BUY, 1.00, 3.0),
                    self._req("u1", "a2", pb.SALE, 1.01, 2.0),
                    self._req("u2", "bad", pb.BUY, 1.00, -1.0),  # reject
                    self._req("u2", "b2", pb.BUY, 1.01, 4.0),
                    self._req("u2", "b2", pb.BUY, 1.01, 0.0),  # cancel b2
                ]
                resp = stub.DoOrderBatch(
                    pb.OrderBatchRequest(
                        orders=orders,
                        cancel=[False] * 5 + [True],
                    )
                )
                assert resp.code == 0
                assert resp.accepted == 5
                assert list(resp.reject_index) == [3]
                assert resp.rejects[0].code == 3
                batcher.flush()
                consumer.drain()
        finally:
            server.stop(0)
        # Oracle comparison: the same flow (minus the reject) unary-style.
        oracle = OracleEngine()
        expected = []
        from gome_tpu.fixed import scale
        from gome_tpu.types import Action, Order, Side

        for uuid, oid, side, price, vol, action in [
            ("u1", "a1", Side.SALE, 1.00, 5.0, Action.ADD),
            ("u2", "b1", Side.BUY, 1.00, 3.0, Action.ADD),
            ("u1", "a2", Side.SALE, 1.01, 2.0, Action.ADD),
            ("u2", "b2", Side.BUY, 1.01, 4.0, Action.ADD),
            ("u2", "b2", Side.BUY, 1.01, 0.0, Action.DEL),
        ]:
            expected.extend(
                oracle.process(
                    Order(
                        uuid=uuid, oid=oid, symbol="s", side=side,
                        price=scale(price, 8), volume=scale(vol, 8),
                        action=action,
                    )
                )
            )
        got = []
        for m in bus.match_queue.read_from(0, 100):
            got.extend(decode_event_frame(m.body).to_results())
        assert got == expected

    def test_stream_rpc_and_mask_validation(self):
        from concurrent import futures

        from gome_tpu.api.service import add_order_servicer

        engine, bus, batcher, gw, consumer = self._setup()
        server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        add_order_servicer(server, gw)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()
        try:
            with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
                stub = OrderStub(ch)
                resp = stub.DoOrderStream(
                    iter(
                        [
                            self._req("u1", "s1", pb.SALE, 1.0, 2.0),
                            self._req("u2", "s2", pb.BUY, 1.0, 2.0),
                        ]
                    )
                )
                assert resp.code == 0 and resp.accepted == 2
                # Mismatched cancel mask is a whole-batch code-3 reject.
                bad = stub.DoOrderBatch(
                    pb.OrderBatchRequest(
                        orders=[self._req("u1", "x", pb.BUY, 1.0, 1.0)],
                        cancel=[False, True],
                    )
                )
                assert bad.code == 3 and bad.accepted == 0
                batcher.flush()
                consumer.drain()
        finally:
            server.stop(0)
        assert len(bus.match_queue.read_from(0, 10)) == 1  # s2 crossed s1

    def test_batch_aborts_cleanly_when_batcher_closed(self):
        engine, bus, batcher, gw, consumer = self._setup()
        batcher.close()
        resp = gw.DoOrderBatch(
            pb.OrderBatchRequest(
                orders=[
                    self._req("u1", "a", pb.SALE, 1.0, 1.0),
                    self._req("u2", "b", pb.BUY, 1.0, 1.0),
                ]
            ),
            None,
        )
        assert resp.code == 3 and resp.accepted == 0
        assert "aborted at entry 0" in resp.message
        # The aborted entry's mark was undone.
        assert len(engine.pre_pool) == 0


def test_build_service_attaches_the_persister_the_file_enables(tmp_path):
    """gome_tpu.service.app.build_service(config): the deployment of a
    loaded Config, which `main` starts: EngineService with its Persister
    where persist.enabled, without one otherwise."""
    from gome_tpu.config import PersistConfig
    from gome_tpu.service.app import build_service

    engine = EngineConfig(cap=32, n_slots=8, max_t=8)
    durable = build_service(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0), engine=engine,
        persist=PersistConfig(enabled=True, dir=str(tmp_path / "snaps"),
                              every_n_batches=1)))
    assert isinstance(durable, EngineService)
    assert durable.persist is not None
    assert durable.persist.consumer is durable.consumer
    assert durable.consumer.on_batch is not None
    plain = build_service(Config(
        grpc=GrpcConfig(host="127.0.0.1", port=0), engine=engine,
        persist=PersistConfig(dir=str(tmp_path / "never"))))
    assert plain.persist is None and plain.consumer.on_batch is None
