"""What a durable configuration adds to the harness, piece by piece: where the
serving process places what the deployment keeps on disk, the service it
builds (with its Persister where the file enables one), and the comparison of
a run that was killed and booted again."""

import os

import numpy as np

from benchmark import compare, reference, serve

SERVICE = {"grpc": {"host": "127.0.0.1", "port": 0},
           "engine": {"n_slots": 8, "cap": 32, "max_t": 8},
           "bus": {"backend": "memory", "match_wire": "frame"}}


def test_a_deployment_that_keeps_nothing_on_disk_is_handed_over_as_it_is():
    assert serve.kept_on_disk(SERVICE) == {}
    assert serve.place_under(SERVICE, "/run") == SERVICE


def test_what_a_durable_deployment_keeps_lies_under_the_runs_directory():
    service = dict(SERVICE, bus={"backend": "file", "dir": "log"},
                   persist={"every_n_batches": 4})
    assert serve.kept_on_disk(service) == {"bus": "bus_data",
                                           "persist": "snapshots"}
    placed = serve.place_under(service, "/run")
    assert placed["bus"]["dir"] == "/run/log"
    assert placed["persist"] == {"every_n_batches": 4,
                                 "dir": "/run/snapshots"}  # the default name
    assert service["bus"]["dir"] == "log"  # the argument is not written to
    # an absolute path stays where the file put it
    outside = dict(service, persist={"dir": "/elsewhere"})
    assert serve.place_under(outside, "/run")["persist"]["dir"] == "/elsewhere"
    assert serve.kept_on_disk(dict(SERVICE, persist={"enabled": False})) == {}


def test_the_directorys_disk_is_named_and_its_fsync_timed(tmp_path):
    facts = serve.disk_facts(str(tmp_path))
    assert facts["filesystem"] != "unknown" and facts["mount"].startswith("/")
    assert facts["fsync_64k_median_ms"] > 0
    assert os.listdir(tmp_path) == []  # the probe's file is gone


def feed_orders(svc, n):
    from gome_tpu.bus import encode_order
    from gome_tpu.utils.streams import mixed_stream

    for order in mixed_stream(n=n, seed=3, cancel_prob=0.25):
        svc.engine.mark(order)
        svc.bus.order_queue.publish(encode_order(order))


def test_a_service_built_from_a_file_that_enables_persist_takes_snapshots(
        tmp_path):
    from gome_tpu.config import Config, EngineConfig, PersistConfig

    engine = EngineConfig(cap=32, n_slots=8, max_t=8)
    snaps = str(tmp_path / "snaps")
    svc = serve.build_service(Config(engine=engine, persist=PersistConfig(
        enabled=True, dir=snaps, every_n_batches=1)))
    assert svc.persist is not None and svc.persist.consumer is svc.consumer
    feed_orders(svc, 60)
    svc.pump()
    assert svc.persist.snapshots_taken >= 1
    assert any(d.startswith("snap-") for d in os.listdir(snaps))
    # without it the service is built as EngineService(config) builds it
    plain = serve.build_service(Config(engine=engine, persist=PersistConfig(
        dir=str(tmp_path / "never"))))
    assert plain.persist is None and plain.consumer.on_batch is None
    feed_orders(plain, 60)
    plain.pump()
    assert not os.path.exists(tmp_path / "never")


def rows(n, first=0):
    out = np.zeros((n, 13), np.int64)
    out[:, 3] = np.arange(first, first + n)
    return out


def restarted(first, second, second_from, counts=None, served=None,
              broken=0, recovered=True, owed=10):
    counts = {1: [2, 3]} if counts is None else counts
    served = {"s00001": [2, 3]} if served is None else served
    return compare.restart_numbers(rows(owed), first, second, second_from,
                                   counts, served, broken, recovered)


def test_each_seq_once_across_both_processes_compares_equal():
    # the second process starts where the first stopped, or before it
    assert set(restarted(rows(6), rows(4, 6), 6).values()) == {0}
    assert set(restarted(rows(6), rows(7, 3), 3).values()) == {0}
    # nothing was owed after the kill and nothing came
    assert set(restarted(rows(10), rows(0), None).values()) == {0}


def test_a_restart_that_loses_repeats_or_alters_events_is_counted():
    lost_between = restarted(rows(4), rows(4, 6), 6)
    assert lost_between["restart.events_missing"] == 2
    lost_at_the_end = restarted(rows(6), rows(2, 6), 6)
    assert lost_at_the_end["restart.events_missing"] == 2
    assert restarted(rows(6), rows(6, 6), 6)["restart.events_extra"] == 2
    altered = rows(4, 6)
    altered[1, 5] += 1
    assert restarted(rows(6), altered, 6)["restart.events_mismatched"] == 1
    replayed_wrongly = rows(7, 3)
    replayed_wrongly[0, 5] += 1  # an event the first process had delivered
    assert restarted(rows(6), replayed_wrongly, 3)[
        "restart.events_mismatched"] == 1
    down = restarted(rows(6), rows(0), None, recovered=False)
    assert down["restart.not_recovered"] == 1
    assert down["restart.events_missing"] == 4


def test_the_second_processs_books_are_held_to_the_references():
    same = restarted(rows(10), rows(0), None)
    assert same["restart.books_mismatched"] == 0
    one_side = restarted(rows(10), rows(0), None, served={"s00001": [2, 4]})
    assert one_side["restart.books_mismatched"] == 1
    unknown = restarted(rows(10), rows(0), None,
                        served={"s00001": [2, 3], "s00007": [1, 0]})
    assert unknown["restart.books_mismatched"] == 1
    assert restarted(rows(10), rows(0), None, broken=1)[
        "restart.books_mismatched"] == 1


def test_resting_counts_replay_the_venues_own_book():
    cols = dict(sym=[4, 4, 4, 9], uid=[1, 2, 3, 1], oid=[10, 11, 12, 13],
                side=[0, 0, 1, 1], kind=[0, 0, 0, 0],
                cancel=[False, False, False, False],
                price=[100, 101, 101, 200], volume=[5, 5, 5, 1])
    assert compare.resting_counts(cols, 4) == {4: [1, 0], 9: [0, 1]}
    assert compare.resting_counts(cols, 2) == {4: [2, 0]}

    class NothingRests(reference.Book):
        def add(self, *args, **kwargs):
            return False

    assert compare.resting_counts(cols, 4, NothingRests) == {4: [0, 0],
                                                             9: [0, 0]}
