"""The durable cut (ISSUE 36): the books are cut behind a frame's dispatch
whatever is in flight, the cut is carried to disk by the Persister's writer
thread, and a boot on what a dead process left restores and replays to the
uninterrupted run's events and books, each seq once."""

import json
import os
import threading
import time

import numpy as np
import pytest

from gome_tpu.bus import decode_match_result
from gome_tpu.bus.colwire import decode_event_frame
from gome_tpu.config import (
    BusConfig, Config, EngineConfig, PersistConfig,
)
from gome_tpu.engine import frames as engine_frames
from gome_tpu.oracle import OracleEngine
from gome_tpu.persist import Persister, SnapshotStore
from gome_tpu.service.app import EngineService
from gome_tpu.service.matchfeed import SeqTracker
from gome_tpu.types import Order, Side
from gome_tpu.utils import tracing
from gome_tpu.utils.faults import FAULTS, FaultPlan, FaultSpec
from gome_tpu.utils.streams import multi_symbol_stream

from test_frames import orders_to_frame

CHUNK = 40
ENGINE = dict(cap=32, max_fills=8, n_slots=16, max_t=8)
BOOK_LEAVES = ("price", "lots", "seq", "oid", "uid", "count", "next_seq")
LANE_VECTORS = ("price_base", "base_set", "env_lo", "env_hi")


def stream(n_frames, seed=5, n_symbols=6):
    return multi_symbol_stream(n=n_frames * CHUNK, n_symbols=n_symbols,
                               seed=seed, cancel_prob=0.2)


def make_svc(tmp_path, every_n=3, depth=2, keep=2, wire="frame", **engine):
    cfg = Config(
        engine=EngineConfig(**{**ENGINE, "pipeline_depth": depth, **engine}),
        bus=BusConfig(backend="file", dir=str(tmp_path / "bus"),
                      match_wire=wire),
        persist=PersistConfig(enabled=True, dir=str(tmp_path / "snap"),
                              every_n_batches=every_n, keep=keep),
    )
    svc = EngineService(cfg, persist=Persister(cfg.persist))
    # one frame a step, so the pipeline's depth is what stays in flight
    svc.consumer.batch_n = 1
    svc.feed.log_events = False
    return svc


def feed(svc, orders, first_frame=0, n_frames=None):
    """Gateway role, frame by frame: mark, then publish."""
    last = len(orders) // CHUNK if n_frames is None else first_frame + n_frames
    for k in range(first_frame, last):
        part = orders[k * CHUNK:(k + 1) * CHUNK]
        for o in part:
            svc.engine.mark(o)
        svc.bus.order_queue.publish(orders_to_frame(part))


def drain_steadily(svc, steps=None):
    """The consumer's loop, a frame a step, with the writer given the time a
    chip's frames give it (a toy frame takes less than a toy write, and a
    tick that finds the writer busy is skipped: its own test)."""
    q = svc.bus.order_queue
    while q.committed() < q.end_offset() and steps != 0:
        svc.consumer.run_once()
        assert svc.persist.wait(30)
        steps = None if steps is None else steps - 1


def reference_run(orders, **engine):
    """The uninterrupted run: memory bus, no pipeline, no persister."""
    ref = EngineService(Config(
        engine=EngineConfig(**{**ENGINE, **engine}),
        bus=BusConfig(backend="memory", match_wire="frame")))
    feed(ref, orders)
    ref.consumer.drain()
    return ref


def events_of(svc):
    """Every event on the match queue as (seq, MatchResult fields)."""
    out = []
    mq = svc.bus.match_queue
    for m in mq.read_from(0, mq.end_offset()):
        batch = decode_event_frame(m.body)
        for i, mr in enumerate(batch.to_results()):
            out.append((batch.seq0 + i, mr))
    return out


def assert_same_run(svc, ref, orders):
    got, want = events_of(svc), events_of(ref)
    assert [mr for _s, mr in got] == [mr for _s, mr in want]
    # each seq once, none missing: what a subscriber's guard would count
    seen = SeqTracker(first_seq=0)
    assert all(seen.observe(s) for s, _mr in got)
    assert (seen.dupes, seen.gaps) == (0, 0)
    # and the oracle's own events, from its own book
    oracle = OracleEngine()
    assert [mr for _s, mr in got] == [
        r for o in orders for r in oracle.process(o)]
    a, b = svc.engine.batch.lane_books(), ref.engine.batch.lane_books()
    for leaf in ("price", "lots", "seq", "count", "next_seq"):
        np.testing.assert_array_equal(np.asarray(getattr(a, leaf)),
                                      np.asarray(getattr(b, leaf)))
    assert sorted(svc.engine.pre_pool) == sorted(ref.engine.pre_pool)
    svc.engine.batch.verify_books()


# --- the cut ---------------------------------------------------------------


def test_a_cut_is_taken_every_n_committed_frames_with_the_pipeline_never_empty(
        tmp_path):
    """every_n_batches counts committed frames. Ten frames stand in the
    queue, so from the second on the pipeline is never empty when a frame
    commits; the parent waited for an empty pipeline and would have cut once,
    at the end."""
    orders = stream(10)
    svc = make_svc(tmp_path, every_n=3, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders)
    cuts = []
    inner = svc.persist._cut

    def spy(end_offset):
        cuts.append((end_offset, len(svc.consumer._pipe),
                     svc.bus.order_queue.committed()))
        return inner(end_offset)

    svc.persist._cut = spy
    drain_steadily(svc)
    # behind the 3rd, 6th and 9th frame's dispatch, two or three in flight
    assert [c[0] for c in cuts] == [3, 6, 9]
    assert all(in_flight >= 2 for _end, in_flight, _c in cuts)
    assert all(committed < end for end, _n, committed in cuts)
    assert svc.persist.snapshots_taken == 3
    assert svc.persist.snapshots_skipped == svc.persist.cuts_discarded == 0
    manifest, _arrays = SnapshotStore(str(tmp_path / "snap")).load_latest()
    assert manifest["version"] == 2 and manifest["order_committed"] == 9


@pytest.mark.parametrize("mesh_devices", [0, 4])
def test_a_cut_with_frames_in_flight_equals_the_stopped_consumers_state(
        tmp_path, mesh_devices):
    """Array for array: what the writer put on disk for the cut behind frame
    6, taken with frames 7 and 8 on the device, is what export_state gives
    on an engine stopped after exactly six frames; under a mesh of four CPU
    devices too, in the one-chip order, so it restores into no mesh."""
    orders = stream(8)
    svc = make_svc(tmp_path, every_n=6, depth=2, mesh_devices=mesh_devices)
    svc.persist.restore_latest()
    feed(svc, orders)
    svc.consumer.drain()
    assert svc.persist.wait(30) and svc.persist.snapshots_taken == 1
    store = SnapshotStore(str(tmp_path / "snap"))
    manifest, arrays = store.load_latest()
    assert manifest["order_committed"] == 6

    stopped = reference_run(orders[:6 * CHUNK])
    state = stopped.engine.batch.export_state()
    for leaf in BOOK_LEAVES:
        np.testing.assert_array_equal(arrays[leaf], state["books"][leaf])
    for name in LANE_VECTORS:
        np.testing.assert_array_equal(arrays[name].astype(np.int64),
                                      np.asarray(state[name]))
    for name in ("symbols", "oids", "uids"):
        # the id files reach as far as the cut's frame, not the process's
        assert manifest[name] == state[name]
    assert manifest["match_end"] == stopped.bus.match_queue.end_offset()
    assert manifest["match_seq"] == stopped.consumer.match_seq
    assert sorted(map(tuple, manifest["pre_pool"])) == sorted(
        set(stopped.engine.pre_pool) | {
            (o.symbol, o.uuid, o.oid) for o in orders[6 * CHUNK:]
            if o.action.name == "ADD"})
    # ... and it restores into an engine without a mesh
    plain = make_svc(tmp_path, every_n=6, depth=0)
    assert plain.persist.restore_latest()
    plain.consumer.drain()
    assert_same_run(plain, reference_run(orders), orders)


def test_a_rewound_frame_discards_the_cut_that_hangs_on_it(tmp_path):
    """Frame 3 (the cadence's) is dispatched and cut; frame 2, still in
    flight under it, trips its fill-record budget and is re-run on the exact
    path with frame 3 resubmitted on top. The cut's books are no frame's:
    it is dropped, and the next frame to be dispatched is cut instead."""
    makers = [Order(uuid="m", oid=f"m{i}", symbol="s", side=Side.SALE,
                    price=100 + i % 3, volume=1) for i in range(30)]
    quiet = lambda tag: [
        Order(uuid="q", oid=f"{tag}{i}", symbol=f"q{i % 4}",
              side=Side(i % 2), price=50 + i % 5, volume=2)
        for i in range(10)]
    sweep = [Order(uuid="t", oid="sweep", symbol="s", side=Side.BUY,
                   price=200, volume=30)]  # 30 fills > max_fills 4
    units = [makers, sweep + quiet("a"), quiet("b"), quiet("c"), quiet("d"),
             quiet("e"), quiet("f")]
    orders = [o for unit in units for o in unit]
    svc = make_svc(tmp_path, every_n=3, depth=2, max_fills=4)
    svc.persist.restore_latest()
    for unit in units:
        for o in unit:
            svc.engine.mark(o)
        svc.bus.order_queue.publish(orders_to_frame(unit))
    svc.consumer.drain()
    assert svc.persist.wait(30)
    assert svc.engine.stats.frame_fallbacks >= 1
    assert svc.persist.cuts_discarded == 1
    assert svc.persist.snapshots_taken == 1
    manifest, _ = SnapshotStore(str(tmp_path / "snap")).load_latest()
    # frame 6: the first dispatched after frame 3 committed and its cut went
    assert manifest["order_committed"] == 6
    # what was written restores and replays to the uninterrupted run
    svc2 = make_svc(tmp_path, every_n=3, depth=2, max_fills=4)
    assert svc2.persist.restore_latest()
    svc2.consumer.drain()
    ref = EngineService(Config(
        engine=EngineConfig(**{**ENGINE, "max_fills": 4}),
        bus=BusConfig(backend="memory", match_wire="frame")))
    for unit in units:
        for o in unit:
            ref.engine.mark(o)
        ref.bus.order_queue.publish(orders_to_frame(unit))
    ref.consumer.drain()
    assert_same_run(svc2, ref, orders)


def test_a_tick_that_finds_the_writer_busy_is_skipped_and_counted(tmp_path):
    """At most one cut is on its way. The writer is held inside its write
    while six more frames commit: the tick at frame 4 takes no cut and is
    counted once, none is queued behind the held write, and the first frame
    dispatched after the writer is free is cut."""
    orders = stream(10)
    svc = make_svc(tmp_path, every_n=2, depth=1)
    svc.persist.restore_latest()
    hold, entered = threading.Event(), threading.Event()
    inner = svc.persist.store.save

    def held_save(*args, **kwargs):
        entered.set()
        assert hold.wait(30)
        return inner(*args, **kwargs)

    svc.persist.store.save = held_save
    feed(svc, orders, n_frames=8)
    svc.consumer.drain()
    assert entered.wait(30)
    assert svc.persist.snapshots_taken == 0  # the cut behind frame 2 waits
    assert svc.persist.snapshots_skipped == 1  # 4, 6 and 8 are one tick
    assert svc.persist._pending is None and svc.persist._job is None
    hold.set()
    assert svc.persist.wait(30) and svc.persist.snapshots_taken == 1
    feed(svc, orders, first_frame=8)
    svc.consumer.drain()
    assert svc.persist.wait(30)
    assert svc.persist.snapshots_taken == 2
    manifest, _ = SnapshotStore(str(tmp_path / "snap")).load_latest()
    assert manifest["order_committed"] == 9  # the first after the writer was free
    from gome_tpu.utils.metrics import Registry

    reg = Registry()
    svc.persist.export_metrics(registry=reg)
    assert "gome_snapshots_skipped_total 1" in reg.render()


def test_snapshot_is_cut_then_wait_and_takes_the_cadences_path(tmp_path):
    """Persister.snapshot(), which shutdown, the tests and scripts/chaos.py
    call: the same cut, the same writer thread, the same files."""
    orders = stream(4)
    svc = make_svc(tmp_path, every_n=10**9, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders)
    svc.consumer.drain()
    path = svc.persist.snapshot()
    assert os.path.basename(path) == "snap-0"
    assert svc.persist.snapshots_taken == 1
    assert svc.persist._writer.name == "snapshot-writer"
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["version"] == 2 and manifest["order_committed"] == 4
    assert "oids" not in manifest and set(manifest["ids"]) == {
        "symbols", "oids", "uids"}
    assert tracing.totals()["snapshot_write"]["count"] >= 1


# --- a manifest as large as the venue, not as its history --------------------


def snapshot_bytes(tmp_path, n_frames):
    """A venue of fixed size (cap 256: no lane outgrows it here) fed as it
    consumes, so the marks in flight are a frame's or two's at every cut."""
    orders = stream(n_frames, seed=9)
    svc = make_svc(tmp_path, every_n=4, depth=2, cap=256)
    svc.persist.restore_latest()
    for k in range(n_frames):
        feed(svc, orders, first_frame=k, n_frames=1)
        svc.consumer.run_once()
        assert svc.persist.wait(30)
    drain_steadily(svc)
    assert svc.persist.snapshots_taken == n_frames // 4
    path = svc.persist._last_path
    on_disk = sum(os.path.getsize(os.path.join(path, name))
                  for name in os.listdir(path))
    return svc.persist.last_snapshot_bytes, on_disk, len(svc.engine.batch.oids)


def test_the_bytes_a_cut_writes_do_not_grow_with_the_orders_admitted(tmp_path):
    """After ten times as many orders the last cut writes the same: the
    books, a manifest without the interners, and the ids that are new since
    the cut before it. The parent's manifest held every order id the process
    had admitted."""
    wrote_1, dir_1, ids_1 = snapshot_bytes(tmp_path / "one", 8)
    wrote_10, dir_10, ids_10 = snapshot_bytes(tmp_path / "ten", 80)
    assert ids_10 > 8 * ids_1  # the history did grow
    assert abs(wrote_10 - wrote_1) < 4096 and abs(dir_10 - dir_1) < 4096
    # the id files hold the history once, appended to, never rewritten
    ids_file = tmp_path / "ten" / "snap" / "ids.oids"
    assert os.path.getsize(ids_file) > 8 * os.path.getsize(
        tmp_path / "one" / "snap" / "ids.oids")


def test_a_version_1_snapshot_restores_and_the_next_one_is_version_2(tmp_path):
    """What the parent wrote (manifest with the interners' tables and the
    per-lane vectors as JSON lists, books.npz with the books alone) still
    boots; the first cut after it writes the id files whole."""
    orders = stream(6)
    old = reference_run(orders[:3 * CHUNK])
    state = old.engine.batch.export_state()
    store = SnapshotStore(str(tmp_path / "snap"))
    store.save({"version": 1, "order_committed": 3,
                "match_end": old.bus.match_queue.end_offset(),
                "match_seq": old.consumer.match_seq,
                "pre_pool": sorted(old.engine.pre_pool),
                **{k: v for k, v in state.items() if k != "books"}},
               state["books"])
    svc = make_svc(tmp_path, every_n=2, depth=2)
    feed(svc, orders)
    for m in old.bus.match_queue.read_from(0, 1 << 20):
        svc.bus.match_queue.publish(m.body)
    svc.bus.order_queue.commit(3)
    assert svc.persist.restore_latest()
    assert svc.persist.last_restore == "restored"
    drain_steadily(svc)
    assert svc.persist.snapshots_taken == 1
    assert_same_run(svc, reference_run(orders), orders)
    manifest, _ = SnapshotStore(str(tmp_path / "snap")).load_latest()
    assert manifest["version"] == 2
    assert manifest["oids"] == svc.engine.batch.oids.to_list()[
        :manifest["ids"]["oids"][0]]
    assert manifest["ids"]["oids"][0] >= len(state["oids"])


# --- a death, and the boot after it ----------------------------------------


class Died(BaseException):
    """The process's death, where a test cannot afford a real one."""


def die(_code):
    raise Died()


@pytest.fixture
def mortal(monkeypatch):
    monkeypatch.setattr(FAULTS, "_exit", die)
    yield
    FAULTS.clear() if hasattr(FAULTS, "clear") else FAULTS.install(
        FaultPlan(faults=()))


@pytest.mark.parametrize("killed_after", range(6, 13))
def test_a_kill_at_every_frame_boundary_between_two_cuts_replays_to_the_reference(
        tmp_path, killed_after):
    """Cuts fall behind frames 6 and 12 (every 6, depth 2). The process is
    abandoned with `killed_after` frames fed to the pipeline, so with up to
    two in flight and their events unpublished; the boot restores the cut
    behind frame 6 (or replays the whole log), replays at most the frames
    since it and what was in flight, and the log's events and the books are
    the uninterrupted run's, each seq once."""
    orders = stream(14)
    svc = make_svc(tmp_path, every_n=6, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders)
    drain_steadily(svc, steps=killed_after)  # (a death inside the write
    # is the next test)
    committed = svc.bus.order_queue.committed()
    assert killed_after - 2 <= committed <= killed_after
    del svc

    svc2 = make_svc(tmp_path, every_n=6, depth=2)
    restored = svc2.persist.restore_latest()
    assert restored == (committed >= 6)
    cut = 6 * (committed // 6) if restored else 0
    assert svc2.bus.order_queue.committed() == cut
    # at most the cadence's frames, what was in flight, and what stood behind
    assert svc2.persist.wal_replay_frames == 14 - cut
    svc2.consumer.drain()
    assert svc2.persist.wait(30)
    assert_same_run(svc2, reference_run(orders), orders)
    assert svc2.persist.last_replay_seconds > 0
    assert tracing.totals()["recover_replay"]["count"] >= 1
    assert tracing.totals()["recover_restore"]["count"] >= 1


@pytest.mark.parametrize("point, at", [("snapshot.rename", 2),
                                       ("filelog.append", 9)])
def test_a_death_inside_a_write_restores_and_replays_to_the_reference(
        tmp_path, mortal, point, at):
    """snapshot.rename: the second snapshot's manifest is torn and renamed
    into place as the writer thread dies; the boot skips it for the one
    before. filelog.append: a torn record at the match log's end as the
    consumer dies; the boot cuts it off."""
    orders = stream(14)
    svc = make_svc(tmp_path, every_n=4, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders)
    FAULTS.install(FaultPlan(seed=3, faults=(
        FaultSpec(point, mode="torn", at=(at,)),)))
    died = False
    q = svc.bus.order_queue
    try:
        while q.committed() < q.end_offset() and not died:
            svc.consumer.run_once()
            assert svc.persist.wait(30)
            died = isinstance(svc.persist._write_error, Died)
    except Died:
        died = True
    finally:
        FAULTS.install(FaultPlan(faults=()))
    assert died
    del svc

    svc2 = make_svc(tmp_path, every_n=4, depth=2)
    assert svc2.persist.restore_latest()
    svc2.consumer.drain()
    assert svc2.persist.wait(30)
    assert_same_run(svc2, reference_run(orders), orders)


def test_snapshots_that_outlive_their_log_boot_on_the_books(tmp_path, caplog):
    """`commit past end: 4 > 0` stopped the parent's boot when the bus
    directory was lost and the snapshots were not. The boot now says so,
    takes the books and consumes the log that is there from its end."""
    import shutil

    orders = stream(8)
    svc = make_svc(tmp_path, every_n=4, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders, n_frames=4)
    svc.consumer.drain()
    assert svc.persist.wait(30) and svc.persist.snapshots_taken == 1
    before = svc.engine.batch.lane_books()
    del svc
    shutil.rmtree(tmp_path / "bus")

    svc2 = make_svc(tmp_path, every_n=4, depth=2)
    with caplog.at_level("WARNING", logger="gome_tpu.persist"):
        assert svc2.persist.restore_latest()
    assert any("lies past the end of the order log" in r.getMessage()
               for r in caplog.records)
    assert svc2.bus.order_queue.committed() == 0
    after = svc2.engine.batch.lane_books()
    for leaf in ("price", "lots", "seq", "count", "next_seq"):
        np.testing.assert_array_equal(np.asarray(getattr(after, leaf)),
                                      np.asarray(getattr(before, leaf)))
    # and it goes on: the new log's orders meet the restored books
    feed(svc2, orders, first_frame=4)
    svc2.consumer.drain()
    ref = reference_run(orders)
    got = [mr for _s, mr in events_of(svc2)]
    want = [mr for _s, mr in events_of(ref)]
    assert got == want[len(want) - len(got):] and got
    svc2.engine.batch.verify_books()


# --- the feed's cursor trails the hand-over --------------------------------


def test_the_match_cursor_trails_what_a_subscriber_has_been_handed(tmp_path):
    """A match message is committed when every live subscriber's handler has
    handed the whole of it to gRPC. A subscriber that has taken two events
    of the first message holds the cursor at 0; a boot on that directory
    delivers the first message again and loses nothing."""
    orders = stream(4)
    svc = make_svc(tmp_path, every_n=10**9, depth=2)
    svc.persist.restore_latest()
    feed(svc, orders)
    svc.consumer.drain()
    mq = svc.bus.match_queue
    sub = svc.feed.subscribe()
    taken = []
    waiting = threading.Thread(target=lambda: taken.append(next(sub)))
    waiting.start()  # registers, and waits for its first message
    deadline = time.monotonic() + 30
    while not svc.feed._subs and time.monotonic() < deadline:
        time.sleep(0.001)
    svc.feed.drain()
    waiting.join(30)
    (first,) = taken
    assert mq.end_offset() == 4
    assert mq.committed() == 0  # fanned out, queued, not handed over
    got = [first, next(sub)]
    svc.feed.run_once()
    assert mq.committed() == 0  # inside the first message
    n_first = len(decode_event_frame(mq.read_from(0, 1)[0].body).to_results())
    got += [next(sub) for _ in range(n_first - 2)]
    svc.feed.run_once()
    assert mq.committed() == 0  # all yielded, not yet asked for what follows
    got.append(next(sub))  # the second message's first event
    svc.feed.run_once()
    assert mq.committed() == 1
    # no subscriber: committed when fanned out, as before
    sub.close()
    svc.feed.run_once()
    assert mq.committed() == 4


def test_the_spans_and_counters_of_the_durable_path_exist_and_none_on_memory(
        tmp_path):
    """order_log_append / match_log_append are opened where the queue is
    kept and never on the memory bus; gome_log_bytes_total counts both."""
    import grpc

    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.api.service import OrderStub
    from gome_tpu.config import GrpcConfig
    from gome_tpu.utils.metrics import REGISTRY
    from gome_tpu.utils.trace import TRACER

    TRACER.disable()  # an armed tracer moves DoOrderBatch to the scalar loop

    def served(bus):
        tracing.reset()
        svc = EngineService(Config(
            grpc=GrpcConfig(host="127.0.0.1", port=0),
            engine=EngineConfig(**{**ENGINE, "pipeline_depth": 2}), bus=bus))
        svc.feed.log_events = False
        svc.start()
        channel = grpc.insecure_channel(
            f"127.0.0.1:{svc._server.bound_port}")
        try:
            reqs = [pb.OrderRequest(
                uuid="u", oid=f"o{i}", symbol="s",
                transaction=pb.SALE if i % 2 else pb.BUY, price=1.0,
                volume=1.0) for i in range(16)]
            resp = OrderStub(channel).DoOrderBatch(
                pb.OrderBatchRequest(orders=reqs))
            assert (resp.code, resp.accepted) == (0, 16)
            deadline = time.monotonic() + 30
            while (svc.bus.match_queue.end_offset() == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            channel.close()
            svc.stop()
        return tracing.totals()

    on_file = served(BusConfig(backend="file", dir=str(tmp_path / "bus"),
                               match_wire="frame"))
    assert on_file["order_log_append"]["count"] == 1  # a request, not 16
    assert on_file["match_log_append"]["count"] == 1  # a frame
    kept = REGISTRY.snapshot()["gome_log_bytes_total"]
    assert kept['{queue="doOrder"}'] > 0 and kept['{queue="matchOrder"}'] > 0
    on_memory = served(BusConfig(backend="memory", match_wire="frame"))
    assert on_memory["gateway_admit"]["count"] == 1
    assert "order_log_append" not in on_memory or not on_memory[
        "order_log_append"]["count"]
    assert not on_memory.get("match_log_append", {}).get("count")
