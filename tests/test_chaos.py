"""Crash consistency under fire (ISSUE 11): the deterministic fault-
injection registry (utils.faults), torn-write hardening of the file
queue, exactly-once matchfeed seq numbers across failures and restarts,
the /durability surface, and the committed chaos verdict
(CHAOS_r01.json, produced by scripts/chaos.py)."""

import json
import os
import random
import sys

import numpy as np
import pytest

from gome_tpu.bus import decode_match_result, encode_order, make_bus
from gome_tpu.bus.colwire import (
    EVENT_MAGIC,
    EVENT_MAGIC_SEQ,
    decode_event_frame,
    encode_event_frame,
)
from gome_tpu.bus.filelog import FileQueue
from gome_tpu.config import (
    BusConfig,
    Config,
    EngineConfig,
    FaultsConfig,
    PersistConfig,
)
from gome_tpu.engine import BookConfig, MatchEngine
from gome_tpu.persist import DictRedis, Persister, restore_from_redis
from gome_tpu.persist.redis_schema import export_to_redis
from gome_tpu.service import EngineService
from gome_tpu.service.matchfeed import SeqTracker
from gome_tpu.types import Action, Order, Side
from gome_tpu.utils.faults import (
    EXIT_CODE,
    FAULTS,
    FaultInjected,
    FaultPlan,
    FaultRegistry,
    FaultSpec,
)
from gome_tpu.utils.metrics import Registry
from gome_tpu.utils.streams import mixed_stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    """The FAULTS singleton must never leak an armed plan across tests."""
    yield
    FAULTS.disable()


# -- the committed chaos verdict --------------------------------------------


def test_chaos_verdict_pinned_green():
    """CHAOS_r01.json is the committed machine-checked verdict of the
    seeded kill/restart soak (scripts/chaos.py). This pin fails if the
    artifact regresses — regenerate it with the script, never hand-edit."""
    with open(os.path.join(REPO, "CHAOS_r01.json")) as f:
        v = json.load(f)
    assert v["schema"] == "gome-chaos-verdict-v1"
    assert v["pass"] is True
    assert all(v["checks"].values()), v["checks"]
    # >= 3 injected kill/restart cycles, every death the injected one
    assert v["config"]["kills"] >= 3
    assert len(v["cycles"]) == v["config"]["kills"]
    assert all(c["exit_code"] == EXIT_CODE for c in v["cycles"])
    # every cycle's plan names a real fault point (reproducibility)
    for c in v["cycles"]:
        assert c["plan"]["faults"], c
    # bit-exact book digest vs the uninterrupted oracle
    assert v["oracle"]["book_digest"] == v["final"]["book_digest"]
    assert v["oracle"]["book_digest"]
    # queue-level match stream: exactly-once after all recoveries
    audit = v["matchfeed"]["seq_audit"]
    assert audit["dupes"] == 0 and audit["gaps"] == 0
    assert v["matchfeed"]["stamped"] == v["matchfeed"]["events"] > 0
    # measured recovery percentiles over >= kills restart samples
    rec = v["recovery"]
    assert rec["p50_s"] is not None and rec["p99_s"] is not None
    assert len(rec["samples_s"]) >= v["config"]["kills"]
    assert rec["wal_replay_frames_total"] > 0


# -- fault registry ----------------------------------------------------------


def test_disabled_fire_is_zero_alloc():
    """The disabled hot path is one attribute check, zero allocations —
    the same sys.getallocatedblocks guard as the tracer/journal/timeline
    singletons."""
    r = FaultRegistry()  # never installed
    assert not r.enabled

    def drill(n):
        i = 0
        while i < n:
            if r.fire("consumer.frame") != 0:
                raise AssertionError("unreachable")
            i += 1

    drill(64)  # warm lazy caches
    before = sys.getallocatedblocks()
    drill(200)
    after = sys.getallocatedblocks()
    assert after - before <= 2, f"disabled fire() allocated {after - before}"


def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec("p", mode="explode")
    with pytest.raises(ValueError):
        FaultSpec("p", mode="call")  # call needs a handler name
    with pytest.raises(ValueError):
        FaultSpec("")


def test_fault_plan_json_roundtrip():
    plan = FaultPlan(seed=42, faults=(
        FaultSpec("consumer.commit", mode="exit", at=(1, 5)),
        FaultSpec("filelog.offset", mode="torn", every=3, times=2),
        FaultSpec("bus.step", mode="call", prob=0.5, handler="broker.kill"),
    ))
    assert FaultPlan.from_json(plan.to_json()) == plan


def test_trigger_semantics_at_every_times():
    r = FaultRegistry()
    r.install(FaultPlan(seed=1, faults=(
        FaultSpec("a", mode="raise", at=(3,)),
        FaultSpec("b", mode="raise", every=2, times=2),
    )))
    assert r.fire("a") == 0 and r.fire("a") == 0
    with pytest.raises(FaultInjected):
        r.fire("a")  # hit 3
    assert r.fire("a") == 0  # and never again

    for hit in (1, 2, 3, 4, 5, 6):
        if hit in (2, 4):  # every=2, capped at times=2
            with pytest.raises(FaultInjected):
                r.fire("b")
        else:
            assert r.fire("b") == 0
    report = r.report()
    assert report["hits"] == {"a": 4, "b": 6}
    assert [f["hit"] for f in report["fired"] if f["point"] == "b"] == [2, 4]


def test_exit_mode_uses_injected_exit():
    r = FaultRegistry()
    died = []
    r._exit = lambda code: died.append(code)
    r.install(FaultPlan(faults=(FaultSpec("x", mode="exit", at=(1,)),)))
    r.fire("x")
    assert died == [EXIT_CODE]
    r.hard_exit()
    assert died == [EXIT_CODE, EXIT_CODE]


def test_torn_cuts_deterministic_across_installs():
    plan = FaultPlan(seed=7, faults=(
        FaultSpec("filelog.append", mode="torn", every=1),
    ))

    def cuts():
        r = FaultRegistry()
        r.install(plan)
        return [r.fire("filelog.append") for _ in range(8)]

    first, second = cuts(), cuts()
    assert first == second  # seeded per-spec RNG, process-stable
    assert all(c > 0 for c in first)
    other = FaultRegistry()
    other.install(FaultPlan(seed=8, faults=plan.faults))
    assert [other.fire("filelog.append") for _ in range(8)] != first


def test_call_mode_resp_restart_handler():
    """A counted fault point can trigger a REAL environmental fault: the
    RESP store restarts on schedule and the supervised client recovers —
    the kill_connections/restart hooks are wired through FAULTS.handler."""
    from gome_tpu.persist.resp import SupervisedRespClient
    from gome_tpu.persist.respserver import FakeRedisServer

    with FakeRedisServer() as srv:
        client = SupervisedRespClient("127.0.0.1", srv.port, name="t:chaos")
        assert client.ping()
        restarts = []
        FAULTS.handler("resp.restart", lambda: restarts.append(srv.restart()))
        FAULTS.install(FaultPlan(faults=(
            FaultSpec("store.op", mode="call", at=(2,),
                      handler="resp.restart"),
        )))
        assert FAULTS.fire("store.op") == 0
        assert FAULTS.fire("store.op") == 0  # handler runs, returns clean
        assert len(restarts) == 1
        assert client.ping()  # supervised session survived the restart
        client.close()


def test_call_mode_broker_kill_handler():
    """Same schedule mechanism against the AMQP broker: kill_connections
    severs live connections at the counted point; the supervised queue
    reconnects and the publish lands."""
    from gome_tpu.bus.fakebroker import FakeBroker

    broker = FakeBroker().start()
    try:
        bus = make_bus(BusConfig(backend="amqp", port=broker.port))
        bus.order_queue.publish(b"before")
        FAULTS.handler("broker.kill", broker.kill_connections)
        FAULTS.install(FaultPlan(faults=(
            FaultSpec("bus.step", mode="call", at=(1,),
                      handler="broker.kill"),
        )))
        FAULTS.fire("bus.step")
        assert FAULTS.report()["fired"]
        bus.order_queue.publish(b"after")  # supervised reconnect
        msgs = bus.order_queue.read_from(0, 10)
        assert [m.body for m in msgs] == [b"before", b"after"]
        bus.order_queue.close()
        bus.match_queue.close()
    finally:
        broker.stop()


# -- torn-write hardening (FileQueue) ----------------------------------------


def test_filequeue_recovers_from_random_torn_tail_and_sidecar(tmp_path):
    """Property test: random truncation of the log tail AND the offset
    sidecar must always recover to a consistent prefix — committed <=
    end <= published, and every readable record byte-identical."""
    rng = random.Random(11)
    for trial in range(25):
        base = str(tmp_path / f"q{trial}" / "doOrder")
        q = FileQueue("doOrder", base)
        bodies = [
            bytes([trial % 251, i]) * (1 + rng.randrange(40))
            for i in range(12)
        ]
        for b in bodies:
            q.publish(b)
        q.commit(rng.randrange(len(bodies) + 1))
        q.close()

        log_path = base + ".log"
        with open(log_path, "rb+") as f:
            f.truncate(rng.randrange(os.path.getsize(log_path) + 1))
        off_path = base + ".offset"
        with open(off_path, "rb") as f:
            side = f.read()
        with open(off_path, "wb") as f:
            f.write(side[: rng.randrange(len(side) + 1)])

        q2 = FileQueue("doOrder", base)
        end, committed = q2.end_offset(), q2.committed()
        assert 0 <= committed <= end <= len(bodies)
        assert [m.body for m in q2.read_from(0, end)] == bodies[:end]
        # the queue keeps working after recovery
        q2.publish(b"post-recovery")
        assert q2.read_from(end, 1)[0].body == b"post-recovery"
        q2.close()


def test_sidecar_garbage_and_overrun_clamped(tmp_path):
    base = str(tmp_path / "doOrder")
    q = FileQueue("doOrder", base)
    q.publish(b"one")
    q.publish(b"two")
    q.commit(2)
    q.close()
    # garbage sidecar -> full replay from 0
    with open(base + ".offset", "w") as f:
        f.write("not-a-number")
    q2 = FileQueue("doOrder", base)
    assert q2.committed() == 0
    q2.close()
    # sidecar ahead of a truncated log -> clamped to end
    with open(base + ".offset", "w") as f:
        f.write("999")
    q3 = FileQueue("doOrder", base)
    assert q3.committed() == q3.end_offset() == 2
    q3.close()


# -- seq wire format ---------------------------------------------------------


def _crossing_batch():
    eng = MatchEngine(
        config=BookConfig(cap=8, max_fills=4), n_slots=4, max_t=4
    )
    orders = [
        Order(uuid="u1", oid="a", symbol="s0", side=Side.BUY,
              price=100, volume=5),
        Order(uuid="u2", oid="b", symbol="s0", side=Side.SALE,
              price=100, volume=3),
        Order(uuid="u1", oid="a", symbol="s0", side=Side.BUY,
              price=100, volume=0, action=Action.DEL),
    ]
    for o in orders:
        eng.mark(o)
    return eng.process_columnar(orders)


def test_gce2_roundtrip_and_gce1_compat():
    batch = _crossing_batch()
    assert len(batch) >= 2  # a fill and a cancel

    stamped = encode_event_frame(batch, seq0=7)
    assert stamped[:4] == EVENT_MAGIC_SEQ
    out = decode_event_frame(stamped)
    assert out.seq0 == 7
    assert [r.seq for r in out.to_results()] == list(
        range(7, 7 + len(batch))
    )
    lines = out.to_json_lines()
    assert all(b'"Seq":' in ln for ln in lines)
    # decoded columns identical to the unstamped wire's
    plain = encode_event_frame(batch)
    assert plain[:4] == EVENT_MAGIC
    unstamped = decode_event_frame(plain)
    assert unstamped.seq0 is None
    assert all(r.seq is None for r in unstamped.to_results())
    assert all(b'"Seq"' not in ln for ln in unstamped.to_json_lines())
    # seq is metadata, not identity: results compare equal without it
    assert unstamped.to_results() == out.to_results()


def test_json_wire_carries_trailing_seq():
    batch = _crossing_batch()
    lines = batch.to_json_lines(seq0=3)
    for i, ln in enumerate(lines):
        doc = json.loads(ln)
        assert doc["Seq"] == 3 + i
        mr = decode_match_result(ln)
        assert mr.seq == 3 + i
    # unstamped lines stay byte-identical to the pre-seq wire
    assert all(b'"Seq"' not in ln for ln in batch.to_json_lines())


# -- SeqTracker / feed suppression -------------------------------------------


def test_seq_tracker_semantics():
    t = SeqTracker()  # mid-stream attach: baseline at first observe
    assert t.observe(5) and t.gaps == 0
    assert t.observe(6)
    assert not t.observe(6)  # dupe, suppressed
    assert not t.observe(2)  # late replay, suppressed
    assert t.observe(9)
    assert t.state() == {
        "last_seq": 9, "observed": 5, "dupes": 2, "gaps": 2
    }
    t0 = SeqTracker(first_seq=0)  # anchored full-stream audit
    assert t0.observe(1)
    assert t0.gaps == 1  # seq 0 missing counts


def test_feed_suppresses_replayed_seqs():
    """A queue-level duplicate (at-least-once replay window) carries the
    same seqs; the feed suppresses it before fan-out so subscribers see
    each event exactly once."""
    svc = EngineService(Config(
        bus=BusConfig(match_wire="frame"),
        engine=EngineConfig(cap=16, n_slots=4, max_t=4),
    ))
    batch = _crossing_batch()
    frame = encode_event_frame(batch, seq0=0)
    svc.bus.match_queue.publish(frame)
    svc.bus.match_queue.publish(frame)  # replayed duplicate
    svc.feed.drain()
    assert svc.feed.events_seen == len(batch)
    assert svc.feed.suppressed == len(batch)
    state = svc.feed.seq_state()
    assert state["dupes"] == len(batch) and state["gaps"] == 0


def test_failed_step_replays_with_identical_seqs(tmp_path):
    """raise-mode fault in the at-least-once window (after publish,
    before commit): the replay must regenerate the SAME seqs so the
    queue-level duplicate is suppressible downstream."""
    cfg = Config(
        bus=BusConfig(backend="file", dir=str(tmp_path / "bus"),
                      match_wire="frame"),
        engine=EngineConfig(cap=32, n_slots=8, max_t=8),
    )
    svc = EngineService(cfg)
    orders = mixed_stream(n=40, seed=13, cancel_prob=0.25)
    for o in orders:
        svc.engine.mark(o)
        svc.bus.order_queue.publish(encode_order(o))

    FAULTS.install(FaultPlan(faults=(
        FaultSpec("consumer.commit", mode="raise", at=(1,)),
    )))
    assert svc.consumer.step_with_policy() == 0  # injected failure
    assert svc.consumer.match_seq == 0  # rolled back to last commit
    FAULTS.disable()
    svc.consumer.drain()

    mq = svc.bus.match_queue
    seqs = []
    for m in mq.read_from(0, mq.end_offset()):
        b = decode_event_frame(m.body)
        seqs.extend(range(b.seq0, b.seq0 + len(b)))
    # the first batch's seqs appear twice (publish + replay), then the
    # stream continues gap-free
    assert seqs[0] == 0
    dupes = len(seqs) - len(set(seqs))
    assert dupes > 0
    assert sorted(set(seqs)) == list(range(len(set(seqs))))
    svc.feed.drain()
    assert svc.feed.suppressed == dupes
    assert svc.feed.seq_state()["gaps"] == 0
    assert svc.feed.events_seen == len(set(seqs))


# -- seq recovery across restarts --------------------------------------------


def _make_svc(tmp_path, every_n=1, **eng):
    cfg = Config(
        bus=BusConfig(backend="file", dir=str(tmp_path / "bus")),
        engine=EngineConfig(cap=32, n_slots=8, max_t=8, **eng),
        persist=PersistConfig(
            dir=str(tmp_path / "snaps"), every_n_batches=every_n
        ),
    )
    return EngineService(cfg, persist=Persister(cfg.persist))


def _feed(svc, orders):
    for o in orders:
        svc.engine.mark(o)
        svc.bus.order_queue.publish(encode_order(o))


def _stream(svc):
    mq = svc.bus.match_queue
    return [
        decode_match_result(m.body) for m in mq.read_from(0, mq.end_offset())
    ]


def test_recovery_rebases_and_regenerates_seqs(tmp_path):
    """Crash after a snapshot with an unsnapshotted tail: the restored
    consumer rebases match_seq from the manifest and WAL replay
    regenerates the truncated match tail with the SAME seqs — the full
    stream is gap-free, dupe-free, and equal to an uninterrupted run."""
    orders = mixed_stream(n=160, seed=9, cancel_prob=0.25)
    ref = EngineService(Config(engine=EngineConfig(cap=32, n_slots=8, max_t=8)))
    _feed(ref, orders)
    ref.pump()
    expected = [(mr, mr.seq) for mr in _stream(ref)]
    assert expected and all(s is not None for _, s in expected)

    svc = _make_svc(tmp_path, every_n=10**9)
    svc.persist.restore_latest()
    _feed(svc, orders[:80])
    svc.consumer.drain()
    svc.persist.snapshot()
    seq_at_cut = svc.consumer.match_seq
    _feed(svc, orders[80:])
    svc.consumer.drain()  # unsnapshotted tail the "crash" throws away

    svc2 = _make_svc(tmp_path, every_n=10**9)
    assert svc2.persist.restore_latest()
    assert svc2.consumer.match_seq == seq_at_cut  # rebased from manifest
    svc2.consumer.drain()
    got = [(mr, mr.seq) for mr in _stream(svc2)]
    assert got == expected
    assert [s for _, s in got] == list(range(len(got)))


def test_redis_import_composes_with_crash_recovery(tmp_path):
    """Satellite: reference-schema import + chaos recovery. Import the
    same Redis book into two services, crash one mid-tail, and require
    the recovered run to match the uninterrupted one exactly."""
    rng = np.random.default_rng(23)

    def stream(n, oid0):
        out = []
        for i in range(n):
            out.append(Order(
                uuid=f"u{int(rng.integers(0, 3))}",
                oid=str(oid0 + i),
                symbol=f"sym{int(rng.integers(0, 4))}",
                side=Side(int(rng.integers(0, 2))),
                price=100_000_000 + int(rng.integers(-500, 500)),
                volume=int(rng.integers(1, 20)),
            ))
        return out

    seeded = MatchEngine(
        config=BookConfig(cap=32, max_fills=8), n_slots=8, max_t=8
    )
    for o in stream(80, 0):
        seeded.mark(o)
        seeded.process([o])
    store = DictRedis()
    export_to_redis(seeded, client=store)

    def boot(name):
        svc = _make_svc(tmp_path / name, every_n=10**9)
        restore_from_redis(svc.engine, store)
        svc.persist.snapshot()  # durable baseline of the import
        return svc

    tail = stream(90, 1000)
    ref = boot("ref")
    _feed(ref, tail)
    ref.consumer.drain()

    crashed = boot("crash")
    _feed(crashed, tail)
    crashed.consumer.run_once()  # consume part of the tail, then die
    assert crashed.bus.order_queue.committed() > 0

    recovered = _make_svc(tmp_path / "crash", every_n=10**9)
    assert recovered.persist.restore_latest()
    recovered.consumer.drain()
    assert _stream(recovered) == _stream(ref)
    a = ref.engine.batch.export_state()
    b = recovered.engine.batch.export_state()
    assert a["symbols"] == b["symbols"] and a["oids"] == b["oids"]
    for leaf in ("lots", "count", "price"):
        assert (a["books"][leaf] == b["books"][leaf]).all()


# -- durability surface ------------------------------------------------------


def test_durability_payload_and_persist_telemetry(tmp_path):
    from gome_tpu.service.ops import OpsServer

    svc = _make_svc(tmp_path, every_n=1)
    svc.persist.restore_latest()
    _feed(svc, mixed_stream(n=40, seed=4, cancel_prob=0.2))
    svc.pump()
    assert svc.persist.snapshots_taken > 0

    payload = OpsServer(svc).durability_payload()
    assert payload["faults"]["enabled"] is False
    assert payload["persist"]["snapshots_taken"] == svc.persist.snapshots_taken
    assert payload["persist"]["last_restore"] == "none"  # fresh boot
    assert 0 <= payload["persist"]["snapshot_age_s"]
    assert payload["matchfeed"]["gaps"] == 0
    assert payload["consumer"]["match_seq"] == svc.consumer.match_seq
    q = payload["queues"]["order_queue"]
    assert q["end"] == q["committed"] > 0

    reg = Registry()
    svc.persist.export_metrics(registry=reg)
    text = reg.render()
    for name in (
        "gome_snapshot_age_seconds",
        "gome_snapshot_bytes",
        "gome_snapshots_taken_total",
        "gome_snapshots_skipped_total",
        "gome_recovery_seconds",
        "gome_wal_replay_frames",
    ):
        assert name in text

    probe = svc.persist.probe()
    assert set(probe) == {
        "snapshots_taken", "snapshots_skipped", "snapshot_age_s",
        "snapshot_bytes", "last_restore", "recovery_s", "replay_s",
        "wal_replay_frames",
    }


def test_timeline_registers_persist_probe(tmp_path):
    from gome_tpu.obs.timeline import TIMELINE, service_timeline

    svc = _make_svc(tmp_path, every_n=1)
    TIMELINE.install(registry=Registry())
    try:
        service_timeline(svc)
        sample = TIMELINE.sample()
        assert sample["persist"]["last_restore"] == "never"
        assert sample["persist"]["snapshots_taken"] == 0
    finally:
        TIMELINE.disable()


# -- faults config block -----------------------------------------------------


def test_faults_config_defaults_off_and_inline_points(tmp_path):
    assert Config().faults.enabled is False
    cfg_path = tmp_path / "config.yaml"
    cfg_path.write_text(
        "faults:\n"
        "  seed: 5\n"
        "  points:\n"
        "    - {point: consumer.commit, mode: raise, at: [2]}\n"
    )
    from gome_tpu.config import load_config

    cfg = load_config(str(cfg_path))
    assert cfg.faults.enabled is True  # a faults: section arms by default
    plan = cfg.faults.fault_plan()
    assert plan.seed == 5
    assert plan.faults == (
        FaultSpec("consumer.commit", mode="raise", at=(2,)),
    )
    with pytest.raises(ValueError):
        FaultsConfig(plan="x.json", points=({"point": "a"},))


def test_service_arms_faults_from_config():
    cfg = Config(
        engine=EngineConfig(cap=16, n_slots=4, max_t=4),
        faults=FaultsConfig(
            enabled=True, seed=3,
            points=({"point": "consumer.frame", "mode": "raise",
                     "at": [1]},),
        ),
    )
    svc = EngineService(cfg)
    assert FAULTS.enabled
    svc.bus.order_queue.publish(
        encode_order(Order(uuid="u", oid="o1", symbol="s", side=Side.BUY,
                           price=100, volume=1))
    )
    assert svc.consumer.step_with_policy() == 0  # injected, absorbed
    assert FAULTS.report()["fired"]
