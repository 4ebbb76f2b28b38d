"""The client: the wire encoding against protobuf's own parser, events decoded
back to the reference's rows, the closed loop's bound on requests outstanding,
the open loop's schedule."""

import time

import numpy as np
import pytest

from benchmark import client, compare, stream, wire
from test_bench_stream import R, flow_of


@pytest.fixture(scope="module")
def made():
    return stream.generate(flow_of("hotpair8"), 5, 12, R)


def test_encoded_requests_parse_as_the_protos_they_stand_for(made):
    from gome_tpu.api import order_pb2 as pb

    cols = made["cols"]
    requests = wire.build_requests(cols, R, 8)
    assert len(requests) == 12
    for k, raw in enumerate(requests[:3]):
        msg = pb.OrderBatchRequest.FromString(raw)
        lo = k * R
        assert list(msg.cancel) == cols["cancel"][lo:lo + R].tolist()
        for i, o in enumerate(msg.orders):
            j = lo + i
            assert (o.uuid, o.oid, o.symbol) == (
                f"u{cols['uid'][j]:03d}", f"o{cols['oid'][j]:09d}",
                f"s{cols['sym'][j]:05d}")
            assert (o.transaction, o.kind) == (cols["side"][j], cols["kind"][j])
            assert o.price == cols["price"][j] / 1e8
            assert o.volume == cols["volume"][j] / 1e8
        assert pb.OrderBatchRequest.FromString(msg.SerializeToString()) == msg


def as_wire(events):
    """The reference's events as the match feed would send them."""
    from gome_tpu.api import order_pb2 as pb

    def snap(uid, oid, sym, side, price, volume):
        return pb.OrderSnapshot(uuid=f"u{uid:03d}", oid=f"o{oid:09d}",
                                symbol=f"s{sym:05d}", transaction=side,
                                price=float(price), volume=float(volume))

    return [pb.MatchEvent(
        node=snap(e[2], e[3], e[1], e[4], e[5], e[6]),
        match_node=snap(e[7], e[8], e[1], e[9], e[10], e[11]),
        match_volume=float(e[12])).SerializeToString()
        for e in events.tolist()]


def test_decoded_events_equal_the_references_rows(made):
    raws = as_wire(made["events"])
    got = wire.decode_events(raws)
    want = compare.expected_rows(made["events"], 12 * R)
    assert compare.compare_events(want, got) == {
        "events.mismatched": 0, "events.missing": 0, "events.extra": 0,
        "_first_difference": None}


def test_an_altered_price_and_a_dropped_event_are_seen(made):
    from gome_tpu.api import order_pb2 as pb

    raws = as_wire(made["events"])
    want = compare.expected_rows(made["events"], 12 * R)
    e = pb.MatchEvent.FromString(raws[40])
    e.match_node.price += 1.0
    altered = raws[:40] + [e.SerializeToString()] + raws[41:]
    out = compare.compare_events(want, wire.decode_events(altered))
    assert out["events.mismatched"] == 1 and out["_first_difference"] == 40
    dropped = raws[:40] + raws[41:]
    out = compare.compare_events(want, wire.decode_events(dropped))
    assert out["events.missing"] == 1 and out["events.mismatched"] > 0


class FakeSender:
    """Acknowledges at once, as a gateway that is never the bottleneck."""

    def __init__(self):
        self.sent = []
        self.on_ack = None

    def send(self, k, release_ns=None):
        self.sent.append(k)
        self.pending = getattr(self, "pending", []) + [k]


class FakeSub:
    def __init__(self):
        self.target = 0
        self.on_target = None
        self.stamps = []


def ack_all(loop, sender):
    while getattr(sender, "pending", []):
        loop._acked(sender.pending.pop(0), 1)


@pytest.mark.parametrize("outstanding", [1, 4, 8])
def test_the_loop_never_has_more_than_its_bound_outstanding(outstanding):
    cum = np.cumsum(np.full(30, 10))
    sender, sub = FakeSender(), FakeSub()
    loop = client.Loop(sender, sub, cum)
    loop.open(outstanding)
    assert sender.sent == list(range(outstanding))
    n = 0
    while loop.done < 30:
        ack_all(loop, sender)
        n += 1
        sub.stamps.append(n)
        if n >= sub.target:
            sub.on_target(n, n)
        assert 0 <= loop.sent - loop.done <= outstanding
        assert loop.sent == min(loop.done + outstanding, 30)
    assert loop.done_ns[:3] == [10, 20, 30]
    assert loop.close() == 30


def test_a_request_completes_only_when_all_its_events_have_arrived():
    sender, sub = FakeSender(), FakeSub()
    loop = client.Loop(sender, sub, [3, 3, 7])  # request 1 makes no event
    loop.open(1)
    sub.stamps = [1, 2, 3]
    sub.on_target(3, 100)
    assert loop.done == 0  # not acknowledged yet
    ack_all(loop, sender)  # request 1 completes on its acknowledgement
    assert loop.done == 2 and sender.sent == [0, 1, 2] and sub.target == 7
    sub.stamps = list(range(7))
    sub.on_target(7, 200)
    assert loop.done == 3 and loop.done_ns[2] == 200


def test_requests_without_events_at_the_streams_start_do_not_stall_the_loop():
    sender, sub = FakeSender(), FakeSub()
    loop = client.Loop(sender, sub, [0, 0, 0, 5])  # a listing: no events
    loop.open(2)
    for _ in range(4):
        ack_all(loop, sender)
    assert sender.sent == [0, 1, 2, 3] and loop.done == 3


def owing_loop():
    """One request acknowledged, its five events owed, none arrived."""
    sender, sub = FakeSender(), FakeSub()
    loop = client.Loop(sender, sub, [5])
    loop.open(1)
    ack_all(loop, sender)
    return loop, sub


def test_a_silent_subscription_is_no_stall_while_the_serving_process_moves(
        monkeypatch):
    # the first run in a checkout compiles for minutes before its first event
    monkeypatch.setattr(client, "STALL_POLL_S", 0.01)
    loop, sub = owing_loop()
    calls = []

    def progress():
        calls.append(len(calls))
        if len(calls) == 20:  # far past stall_s: the events come at last
            sub.stamps = [1] * 5
            sub.on_target(5, 99)
        return calls[-1]  # a counter that moves at every look

    assert loop.wait_done(1, 30, None, 0.05, progress)
    assert loop.done == 1 and len(calls) == 20
    assert loop.longest_silence_s > 0.05


def test_a_silent_subscription_is_a_stall_once_the_serving_process_stands_still(
        monkeypatch):
    monkeypatch.setattr(client, "STALL_POLL_S", 0.01)
    loop, _sub = owing_loop()
    t = time.monotonic()
    assert not loop.wait_done(1, 30, None, 0.1, lambda: (7, 7))
    assert time.monotonic() - t < 5 and loop.done == 0
    loop, _sub = owing_loop()  # and with nobody to ask, as before
    assert not loop.wait_done(1, 30, None, 0.1)


def test_the_schedule_is_kept_whatever_became_of_earlier_requests():
    class Slow(FakeSender):
        def __init__(self):
            super().__init__()
            self.at = []

        def send(self, k, release_ns=None):
            self.at.append(time.monotonic_ns())
            super().send(k)

    sender, sub = Slow(), FakeSub()
    loop = client.Loop(sender, sub, [1] * 10)
    loop.close()
    t0 = time.monotonic_ns() + 5_000_000
    due = client.paced(loop, 2, 6, t0, 4_000_000)
    assert sender.sent == [2, 3, 4, 5, 6, 7]
    assert due == [t0 + i * 4_000_000 for i in range(6)]
    late = [a - d for a, d in zip(sender.at, due)]
    assert min(late) >= 0  # never early; how late is the machine's business
