#!/usr/bin/env python
"""Fleet chaos soak: seeded kill/restart cycles against a LIVE 2x2 fleet
with a machine-checked fault-tolerance verdict.

scripts/chaos.py answers "does one consumer recover bit-exactly?" against
a pre-seeded queue with no service in the loop. This script answers the
fleet-level question: when real processes die UNDER LIVE gRPC DRIVE —
a consumer killed mid-frame, a gateway killed mid-admit, the bus
"disconnected" under the gateway's feet — does the deployment as a whole
keep the invariants it advertises?

    - every death is an injected one (exit code 86, nothing else dies),
    - clients never lose an entry: gateway deaths are resubmitted
      duplicate-free (gateway.emit fires PRE-publish, so a killed chunk
      was never half-published), bus disconnects surface as the
      retryable status and the driver's backoff path absorbs them,
    - each partition's final book is BIT-EXACT against an uninterrupted
      oracle replay of the same order log (scripts/chaos.py --worker is
      the oracle: same consumer code, same engine geometry),
    - the fleet-wide match stream is exactly-once (per-partition seq
      audit anchored at first_seq=0, zero dupes, zero gaps),
    - recovery is bounded (p99 over all death->caught-up measurements),
    - aggregate accept throughput while a member is down stays above a
      floor (the degraded-window rate vs FLEET_r01's 410 orders/sec),
    - consumer failover rides the round-12 router tier: the dead
      member's partitions are reassigned (PartitionMap epoch bump via
      FailoverController) only AFTER the standby's durable-state
      recovery (Persister.restore_latest + WAL catch-up) completes.

Topology (parent drives everything; 4 long-lived children + respawns):

    parent                              children (this script, --worker)
    ------                              -----------------------------
    record sim GCO frames               gw0, gw1: OrderGateway + gRPC
    route via fleet.partition_of            (+ admission controller,
    drive rounds of namespaced               gateway.emit fault point)
      DoOrderBatch chunks, retrying    c0, c1: consumer + Persister +
      transport errors + code 14           MatchFeed over the partition
    kill cycles: rotate fault class        file bus (snapshots + WAL)
    failover via fleet router           oracle per partition:
    verdict -> FLEET_CHAOS_r01.json        scripts/chaos.py --worker

Kill rotation (cycle c, 1-indexed): fault class cycles through
consumer-kill / gateway-kill / bus-disconnect, victim partition
alternates. Faults are armed by restarting the victim with a FaultPlan
(the restart itself is part of the soak); `at=(K,)` counts events of
THAT lifetime, so the schedule is pinned in the verdict artifact.

The drive is paced rounds of the recorded sim flow with a per-round oid
namespace (keys never collide, cancels stay paired with their round's
adds), so the oracle needs no request list: it replays whatever the
gateways durably published. The verdict JSON (committed as
FLEET_CHAOS_r01.json, pinned by tests/test_fleet_chaos.py) records the
plans, per-cycle recovery, the degraded-window throughput table, the
router failover history, and a pass/fail per check. CI runs this with
``--seconds 30 --kills 3`` and fails the build on any breach.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

SCRIPTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(SCRIPTS)
sys.path.insert(0, REPO)
sys.path.insert(0, SCRIPTS)

# Must be set before anything imports jax (workers inherit it too).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from gome_tpu.utils.faults import EXIT_CODE, FaultPlan, FaultSpec  # noqa: E402

from chaos import (  # noqa: E402 — shared machinery (scripts/chaos.py)
    audit_seqs, book_digest, build_engine, pctl, read_match_stream,
)
from fleet_drill import (  # noqa: E402 — fleet topology machinery
    N_PARTITIONS, Worker, record_sim_frames, requests_from_frames,
    rusage_self, start_respserver, write_json,
)

SCHEMA = "gome-fleet-chaos-verdict-v1"

CLASSES = ("consumer-kill", "gateway-kill", "bus-disconnect")

#: Orders per DoOrderBatch chunk = N_LANES * T_BINS: one engine dispatch
#: per published frame, so fault-hit counters index whole frames.
DRIVE_CHUNK = 128
#: Pause between chunks: paces the drive to ~400 orders/sec/partition so
#: a degraded window holds live traffic without drowning the consumers.
PACE_S = 0.3
#: Event index (per victim lifetime) at which the armed fault fires.
HIT_K = 3
EVERY_N = 8  # snapshot cadence in committed consumer batches
SNAP_KEEP = 16

CODE_RETRYABLE = 14  # service.gateway.CODE_RETRYABLE
RETRY_AFTER_RE = re.compile(r"retry-after=([0-9.]+)s")


# -- workers -----------------------------------------------------------------
#
# Same protocol as fleet_drill workers: one "READY ops=<p> grpc=<p>" line
# on stdout once serving, then block on stdin; any line (or EOF) is the
# stop signal. Injected exit-mode faults hard-exit with EXIT_CODE first.


def _await_stop() -> None:
    try:
        sys.stdin.readline()
    except Exception:
        pass


def run_gateway_worker(args) -> int:
    """One partition's front door: OrderGateway + admission controller
    over the partition file bus. Arms the cycle's FaultPlan (if any)
    and registers the "disconnect" call-handler: a gateway.emit hit in
    call mode raises ConnectionError PRE-publish, which the batch funnel
    converts to CODE_RETRYABLE with accepted=0 — the client's retry path
    absorbs it with zero loss and zero duplicates."""
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig, Config, GrpcConfig
    from gome_tpu.engine.prepool import RespPrePool, make_marker
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.service.admission import AdmissionController
    from gome_tpu.service.gateway import OrderGateway, serve_gateway
    from gome_tpu.utils.faults import FAULTS

    bus = make_bus(
        BusConfig(backend="file", dir=args.bus_dir, match_wire="frame")
    )

    def _disconnect() -> None:
        raise ConnectionError("injected bus disconnect (fleet_chaos)")

    FAULTS.handler("disconnect", _disconnect)
    if args.plan:
        with open(args.plan) as f:
            FAULTS.install(FaultPlan.from_json(f.read()))
    admission = AdmissionController(
        bus.order_queue.depth, max_depth=args.max_depth
    )
    # Split-process marker store: marks must land in the partition's RESP
    # server BEFORE publish, or the consumer's admission drops the ADDs as
    # unmarked (engine/orchestrator pre-pool contract).
    pool = RespPrePool(RespClient(port=args.resp_port))
    gateway = OrderGateway(
        bus, accuracy=0, mark=make_marker(pool), admission=admission,
        mark_frame=pool.mark_frame, unmark_frame=pool.unmark_frame,
    )
    server = serve_gateway(
        gateway, Config(grpc=GrpcConfig(host="127.0.0.1", port=0))
    )
    print(f"READY ops=0 grpc={server.bound_port}", flush=True)
    _await_stop()
    result = {
        "role": "gateway",
        "partition": args.partition,
        "published": {"doOrder": bus.order_queue.end_offset()},
        "faults": FAULTS.report() if args.plan else None,
        "rusage": rusage_self(),
    }
    write_json(args.result, result)
    server.stop(grace=1).wait()
    return 0


def run_consumer_worker(args) -> int:
    """One partition's engine half for one process lifetime: restore
    durable state, (optionally) arm the cycle's FaultPlan, then consume
    live under the threaded consumer until told to stop. The graceful
    final lifetime writes the book digest the oracle comparison pins."""
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig, PersistConfig
    from gome_tpu.persist import Persister
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.matchfeed import MatchFeed
    from gome_tpu.utils.faults import FAULTS

    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient

    bus = make_bus(
        BusConfig(backend="file", dir=args.bus_dir, match_wire="frame")
    )
    engine = build_engine()
    # Same RESP store the partition's gateway marks into: consumption at
    # admission is the cross-process half of the exactly-once contract.
    # Assigned before attach/restore — restore_latest() rebuilds marks
    # into this pool in place (clear + update + WAL-tail reconstruct).
    engine.pre_pool = RespPrePool(RespClient(port=args.resp_port))
    persist = Persister(PersistConfig(
        enabled=True, dir=args.snap_dir, every_n_batches=EVERY_N,
        keep=SNAP_KEEP,
    ))
    consumer = OrderConsumer(
        engine, bus, batch_n=4, batch_wait_s=0.02, match_wire="frame",
    )
    feed = MatchFeed(bus, log_events=False)
    persist.attach(engine, bus, consumer=consumer)
    pre_committed = bus.order_queue.committed()
    persist.restore_latest()
    # Arm AFTER restore (chaos.py discipline): restore-time replay must
    # not consume fault hits, so at=(K,) indexes the K-th frame THIS
    # lifetime consumes live.
    if args.plan:
        with open(args.plan) as f:
            FAULTS.install(FaultPlan.from_json(f.read()))
    consumer.start()
    feed.start()
    print("READY ops=0 grpc=0", flush=True)
    _await_stop()
    consumer.stop()
    consumer.drain()  # any frames between the last poll and the stop
    feed.stop()
    feed.drain()
    persist.wait()  # a cut on its way is on disk
    oq, mq = bus.order_queue, bus.match_queue
    result = {
        "role": "consumer",
        "partition": args.partition,
        "pre_committed": pre_committed,
        "restore": persist.probe(),
        "book_digest": book_digest(engine),
        "match_seq": consumer.match_seq,
        "feed": feed.seq_state(),
        "faults": FAULTS.report() if args.plan else None,
        "oq": {"end": oq.end_offset(), "committed": oq.committed()},
        "mq": {"end": mq.end_offset(), "committed": mq.committed()},
        "rusage": rusage_self(),
    }
    write_json(args.result, result)
    return 0


# -- parent: fault plans -----------------------------------------------------


def class_for_cycle(cycle: int) -> tuple[str, int]:
    """(fault class, victim partition) for 1-indexed cycle: the class
    rotates through all three, the partition alternates."""
    return CLASSES[(cycle - 1) % 3], (cycle - 1) % N_PARTITIONS


def plan_for_cycle(cycle: int, seed: int, klass: str) -> FaultPlan:
    if klass == "consumer-kill":
        spec = FaultSpec("consumer.frame", mode="exit", at=(HIT_K,))
    elif klass == "gateway-kill":
        spec = FaultSpec("gateway.emit", mode="exit", at=(HIT_K,))
    else:  # bus-disconnect: three consecutive emit attempts fail soft
        spec = FaultSpec(
            "gateway.emit", mode="call", handler="disconnect",
            at=(HIT_K, HIT_K + 1, HIT_K + 2),
        )
    return FaultPlan(seed=seed * 1000 + cycle, faults=(spec,))


# -- parent: chaos-aware drive -----------------------------------------------


class DriveCtl:
    """Shared state between the parent and the per-partition driver
    threads: live gateway targets (the parent repoints a partition after
    a restart), per-partition tallies, and timestamped cumulative-accept
    samples for degraded-window throughput."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.targets: dict[int, str] = {}
        # Health-gated shedding, parent-side: while a partition's member is
        # down its driver parks between chunks (the router tier would shed
        # RouteUnavailable; the drill sheds at the source). `idle[p]` acks
        # that no chunk is in flight — the standby's restore can then
        # rebuild the shared mark store without racing live marking.
        self.pause = {p: threading.Event() for p in range(N_PARTITIONS)}
        self.idle = {p: threading.Event() for p in range(N_PARTITIONS)}
        self.stats = {
            p: {
                "accepted": 0, "rejected": 0, "aborted": 0,
                "transport_retries": 0, "shed_retries": 0,
                "disconnect_retries": 0,
            }
            for p in range(N_PARTITIONS)
        }
        # [(monotonic_t, cumulative_accepted)]  guarded by self.lock
        self.samples: dict[int, list] = {p: [] for p in range(N_PARTITIONS)}

    def stat(self, p: int, key: str) -> int:
        with self.lock:
            return self.stats[p][key]


def _ns_requests(base: list, ns: str) -> list:
    """Re-key one round of the recorded flow under a fresh oid namespace:
    (symbol, uuid, oid) keys never collide across rounds, and cancels
    stay paired with their own round's adds (both get the prefix)."""
    from gome_tpu.api import order_pb2 as pb

    out = []
    for is_cancel, r in base:
        q = pb.OrderRequest()
        q.CopyFrom(r)
        q.oid = f"{ns}.{r.oid}"
        out.append((is_cancel, q))
    return out


def _send_chunk(ctl: DriveCtl, p: int, chunk: list) -> None:
    """Deliver one chunk come what may: transport errors mean the
    gateway is down or restarting — the in-flight batch was NOT
    published (gateway.emit fires pre-publish), so resubmitting the
    whole chunk to the restarted gateway is duplicate-free. CODE_RETRYABLE
    means shed or disconnected: resubmit the unconsumed tail after the
    server's retry-after hint (the round-12 remainder contract)."""
    import grpc

    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.api.service import OrderStub

    while chunk:
        target = ctl.targets[p]
        breq = pb.OrderBatchRequest(
            orders=[r for _, r in chunk],
            cancel=[c for c, _ in chunk],
        )
        try:
            with grpc.insecure_channel(target) as channel:
                resp = OrderStub(channel).DoOrderBatch(breq, timeout=30)
        except grpc.RpcError:
            with ctl.lock:
                ctl.stats[p]["transport_retries"] += 1
            time.sleep(0.25)
            continue
        # Consumed prefix contract: every entry before an abort point was
        # either accepted or per-entry rejected (clients/doorder.py).
        consumed = resp.accepted + len(resp.reject_index)
        with ctl.lock:
            st = ctl.stats[p]
            st["accepted"] += resp.accepted
            st["rejected"] += len(resp.reject_index)
            ctl.samples[p].append((time.monotonic(), st["accepted"]))
        if resp.code == CODE_RETRYABLE:
            msg = resp.message or ""
            key = (
                "disconnect_retries" if "batch aborted" in msg
                else "shed_retries"
            )
            with ctl.lock:
                ctl.stats[p][key] += 1
            chunk = chunk[consumed:]
            m = RETRY_AFTER_RE.search(msg)
            time.sleep(max(float(m.group(1)) if m else 0.0, 0.2))
            continue
        if consumed < len(chunk):  # permanent abort: count, don't hide
            with ctl.lock:
                ctl.stats[p]["aborted"] += len(chunk) - consumed
        return


def _drive_partition(
    ctl: DriveCtl, p: int, base: list, phase: str, done: threading.Event,
    min_rounds: int,
) -> None:
    r = 0
    while r < min_rounds or not done.is_set():
        reqs = _ns_requests(base, f"{phase}.r{r}")
        for i in range(0, len(reqs), DRIVE_CHUNK):
            if ctl.pause[p].is_set():
                ctl.idle[p].set()
                while ctl.pause[p].is_set() and not done.is_set():
                    time.sleep(0.05)
                ctl.idle[p].clear()
            _send_chunk(ctl, p, reqs[i : i + DRIVE_CHUNK])
            time.sleep(PACE_S)
        r += 1


def drive_burst(
    ctl: DriveCtl, parts: list, phase: str, done: threading.Event,
    min_rounds: int = 1,
) -> list:
    threads = [
        threading.Thread(
            target=_drive_partition,
            args=(ctl, p, parts[p], phase, done, min_rounds),
            daemon=True,
        )
        for p in range(N_PARTITIONS)
    ]
    for t in threads:
        t.start()
    return threads


def window_rate(ctl: DriveCtl, t0: float, t1: float) -> dict:
    """Aggregate fleet accept throughput inside [t0, t1] from the
    cumulative samples (nearest sample at or before each edge)."""
    total = 0
    with ctl.lock:
        samples = {p: list(ctl.samples[p]) for p in range(N_PARTITIONS)}
    for p in range(N_PARTITIONS):
        a0 = a1 = 0
        for t, a in samples[p]:
            if t <= t0:
                a0 = a
            if t <= t1:
                a1 = a
            else:
                break
        total += a1 - a0
    dur = max(1e-9, t1 - t0)
    return {
        "orders": total,
        "window_s": round(t1 - t0, 3),
        "orders_per_s": round(total / dur, 1),
    }


# -- parent: durable-offset polling (sidecar reads, never FileQueue opens:
# opening a live queue from a second process could truncate a mid-append
# tail the writer is still fsyncing) --------------------------------------

_OFF_RE = re.compile(rb"\s*(\d+)")


def log_end(bus_dir: str) -> int:
    """Record count of the order log — the same unit the committed
    sidecar carries (FileQueue offsets are record indexes). Walks the
    4-byte-BE length prefixes; an incomplete tail record (live writer
    mid-append) is not counted, matching FileQueue's own tail rule."""
    path = os.path.join(bus_dir, "doOrder.log")
    n = 0
    try:
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            pos = 0
            while pos + 4 <= size:
                ln = int.from_bytes(f.read(4), "big")
                if pos + 4 + ln > size:
                    break  # torn/live tail: not yet a record
                f.seek(ln, os.SEEK_CUR)
                pos += 4 + ln
                n += 1
    except OSError:
        return 0
    return n


def committed(bus_dir: str) -> int:
    try:
        with open(os.path.join(bus_dir, "doOrder.offset"), "rb") as f:
            m = _OFF_RE.match(f.read())
        return int(m.group(1)) if m else 0
    except OSError:
        return 0


def await_committed(bus_dir: str, target: int, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if committed(bus_dir) >= target:
            return True
        time.sleep(0.2)
    return False


# -- parent ------------------------------------------------------------------


class Fleet:
    """Process bookkeeping: current worker per slot plus the full
    lifetime ledger (every spawn's armed class + observed exit code —
    the injected-deaths-only check reads this)."""

    def __init__(
        self, work: str, bus_dirs: list, snap_dirs: list, resp_ports: list,
    ):
        self.work = work
        self.bus_dirs = bus_dirs
        self.snap_dirs = snap_dirs
        self.resp_ports = resp_ports
        self.current: dict[str, Worker] = {}
        self.lifetimes: list[dict] = []
        self._n = 0

    def spawn(
        self, role: str, p: int, plan_path: str | None = None,
        armed: str | None = None, ready_timeout_s: float = 300.0,
    ) -> Worker:
        name = ("gw" if role == "gateway" else "c") + str(p)
        self._n += 1
        result = os.path.join(self.work, f"{name}_L{self._n}.json")
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--worker", role,
            "--bus-dir", self.bus_dirs[p],
            "--partition", str(p),
            "--result", result,
            "--resp-port", str(self.resp_ports[p]),
        ]
        if role == "consumer":
            cmd += ["--snap-dir", self.snap_dirs[p]]
        if plan_path:
            cmd += ["--plan", plan_path]
        w = Worker(name, cmd)
        w.await_ready(timeout_s=ready_timeout_s)
        self.current[name] = w
        self.lifetimes.append({
            "name": name, "role": role, "partition": p, "lifetime": self._n,
            "armed": armed, "result": result, "exit_code": None,
        })
        w.ledger = self.lifetimes[-1]
        return w

    def note_exit(self, w: Worker, rc: int) -> None:
        w.ledger["exit_code"] = rc

    def stop(self, name: str) -> int:
        w = self.current.pop(name, None)
        if w is None:
            return 0
        rc = w.stop(timeout_s=90.0)
        self.note_exit(w, rc)
        return rc

    def result_of(self, name: str) -> dict:
        for lt in reversed(self.lifetimes):
            if lt["name"] == name:
                try:
                    with open(lt["result"]) as f:
                        return json.load(f)
                except (OSError, ValueError):
                    return {}
        return {}


def run_oracle(work: str, bus_dir: str, p: int) -> tuple[int, dict, str]:
    """Uninterrupted replay of partition p's durable order log through
    scripts/chaos.py --worker (same consumer code path, same engine
    geometry, fresh snapshot dir) — the bit-exactness baseline."""
    obus = os.path.join(work, f"oracle{p}", "bus")
    osnap = os.path.join(work, f"oracle{p}", "snaps")
    os.makedirs(obus, exist_ok=True)
    os.makedirs(osnap, exist_ok=True)
    # Copy ONLY the log: no offset sidecar, so the oracle consumes from 0.
    shutil.copyfile(
        os.path.join(bus_dir, "doOrder.log"),
        os.path.join(obus, "doOrder.log"),
    )
    out = os.path.join(work, f"oracle{p}_result.json")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(SCRIPTS, "chaos.py"), "--worker",
            "--bus-dir", obus, "--snap-dir", osnap, "--out", out,
        ],
        timeout=1200,
    )
    try:
        with open(out) as f:
            return proc.returncode, json.load(f), obus
    except (OSError, ValueError):
        return proc.returncode, {}, obus


def run_parent(args) -> int:
    import tempfile

    from gome_tpu.fleet import FailoverController, HealthGate, PartitionMap

    work = args.workdir or tempfile.mkdtemp(prefix="gome-fleet-chaos-")
    os.makedirs(work, exist_ok=True)
    n_steps = max(32, min(480, args.seconds * 8))
    print(f"fleet-chaos: recording {n_steps} sim steps (seed {args.seed})...")
    frames = record_sim_frames(args.seed, n_steps)
    parts = requests_from_frames(frames)
    base_counts = [len(p) for p in parts]
    print(f"fleet-chaos: {len(frames)} frames -> base round "
          f"{base_counts} orders/partition in {work}")

    bus_dirs, snap_dirs = [], []
    for i in range(N_PARTITIONS):
        bus_dirs.append(os.path.join(work, f"p{i}", "bus"))
        snap_dirs.append(os.path.join(work, f"p{i}", "snaps"))
        os.makedirs(bus_dirs[i], exist_ok=True)
        os.makedirs(snap_dirs[i], exist_ok=True)

    # One marker store per partition (never a kill target: the store's own
    # failure mode is PR 9's supervised-client drill). Per-partition keeps
    # the book digest honest — pre-pool iteration is store-wide.
    resp = [start_respserver(work) for _ in range(N_PARTITIONS)]
    resp_ports = [r.resp_port for r in resp]
    print(f"fleet-chaos: marker stores on ports {resp_ports}")

    fleet = Fleet(work, bus_dirs, snap_dirs, resp_ports)
    ctl = DriveCtl()

    # Router tier state the failover drill runs over: consumer members
    # own partitions; health is ground truth from the parent's process
    # monitoring (mark_down on an observed death — the poll-debounce path
    # is unit-tested, a watched SIGKILL needs no debounce).
    pmap = PartitionMap(
        N_PARTITIONS, {i: f"m{i}" for i in range(N_PARTITIONS)}
    )
    gate = HealthGate()
    fc = FailoverController(pmap, gate)

    cycles: list[dict] = []
    recoveries: list[float] = []
    all_ready = False
    drained_final = [False] * N_PARTITIONS
    t_run0 = time.monotonic()

    def now() -> float:
        return round(time.monotonic() - t_run0, 3)

    try:
        for i in range(N_PARTITIONS):
            fleet.spawn("consumer", i)
            gw = fleet.spawn("gateway", i)
            ctl.targets[i] = f"127.0.0.1:{gw.ports['grpc']}"
        all_ready = True
        for i in range(N_PARTITIONS):
            gate.record(f"m{i}", True)
            gate.record(f"gw{i}", True)
        print("fleet-chaos: 2x2 fleet up "
              f"(targets {sorted(ctl.targets.items())})")

        # Warm round: trigger the consumers' first-dispatch compiles so
        # cycle recovery times measure recovery, not cold-start skew.
        done = threading.Event()
        done.set()
        for t in drive_burst(ctl, parts, "warm", done, min_rounds=1):
            t.join(timeout=300)
        for i in range(N_PARTITIONS):
            await_committed(bus_dirs[i], log_end(bus_dirs[i]), 240.0)
        print(f"fleet-chaos: warm round done at t={now()}s "
              f"(accepted {[ctl.stat(p, 'accepted') for p in range(2)]})")

        for c in range(1, args.kills + 1):
            klass, p = class_for_cycle(c)
            plan = plan_for_cycle(c, args.seed, klass)
            plan_path = os.path.join(work, f"plan_{c}.json")
            with open(plan_path, "w") as f:
                f.write(plan.to_json())
            victim_name = ("c" if klass == "consumer-kill" else "gw") + str(p)
            cyc: dict = {
                "cycle": c, "class": klass, "partition": p,
                "victim": victim_name, "plan": plan.to_dict(),
                "t_armed": now(),
            }
            print(f"fleet-chaos: cycle {c} [{klass}] partition {p} "
                  f"-> arming {victim_name}")

            # Re-arm by restart: graceful stop, spawn with the plan. No
            # drive is in flight between bursts, so the stop is clean.
            fleet.stop(victim_name)
            victim = fleet.spawn(
                "consumer" if klass == "consumer-kill" else "gateway",
                p, plan_path=plan_path, armed=klass,
            )
            if klass != "consumer-kill":
                ctl.targets[p] = f"127.0.0.1:{victim.ports['grpc']}"

            done = threading.Event()
            threads = drive_burst(ctl, parts, f"c{c}", done, min_rounds=1)
            try:
                if klass == "bus-disconnect":
                    # No death: the armed gateway soft-fails three emits
                    # (CODE_RETRYABLE); wait until the drivers' retry
                    # tallies show all three absorbed.
                    base_disc = ctl.stat(p, "disconnect_retries")
                    deadline = time.monotonic() + 180.0
                    while time.monotonic() < deadline:
                        if ctl.stat(p, "disconnect_retries") - base_disc >= 3:
                            break
                        time.sleep(0.25)
                    cyc["disconnect_retries"] = (
                        ctl.stat(p, "disconnect_retries") - base_disc
                    )
                    cyc["recovery_s"] = None
                    print(f"fleet-chaos: cycle {c} absorbed "
                          f"{cyc['disconnect_retries']} disconnects")
                else:
                    rc = victim.proc.wait(timeout=360)
                    t_death = time.monotonic()
                    fleet.note_exit(victim, rc)
                    fleet.current.pop(victim_name, None)
                    cyc["victim_exit"] = rc
                    cyc["t_death"] = now()
                    print(f"fleet-chaos: cycle {c} {victim_name} died "
                          f"rc={rc} at t={cyc['t_death']}s")
                    if klass == "consumer-kill":
                        dead = pmap.owner(p)
                        gate.mark_down(dead)
                        standby = f"m{p}s{c}"
                        # Park p's driver (health-gated shed) and wait for
                        # the in-flight chunk to land: the standby's restore
                        # rebuilds the shared mark store from the durable
                        # log, which must not race live gateway marking.
                        ctl.pause[p].set()
                        ctl.idle[p].wait(timeout=120.0)
                        target = log_end(bus_dirs[p])

                        def recover(dead_member, partitions):
                            fleet.spawn("consumer", p)
                            if not await_committed(
                                bus_dirs[p], target,
                                args.recovery_timeout,
                            ):
                                raise RuntimeError(
                                    f"standby for {dead_member} never "
                                    f"caught up to {target}"
                                )

                        # Reassignment ONLY after durable recovery: the
                        # claim->recover->commit protocol under test.
                        try:
                            epoch = fc.failover(dead, standby, recover)
                        finally:
                            ctl.pause[p].clear()
                        rec_s = time.monotonic() - t_death
                        gate.record(standby, True)
                        cyc["failover"] = {
                            "dead": dead, "standby": standby,
                            "epoch": epoch,
                        }
                    else:  # gateway-kill
                        gate.mark_down(f"gw{p}")
                        gw = fleet.spawn("gateway", p)
                        ctl.targets[p] = f"127.0.0.1:{gw.ports['grpc']}"
                        rec_s = time.monotonic() - t_death
                        gate.record(f"gw{p}", True)
                    cyc["recovery_s"] = round(rec_s, 3)
                    recoveries.append(rec_s)
                    cyc["degraded"] = window_rate(
                        ctl, t_death, t_death + rec_s
                    )
                    print(f"fleet-chaos: cycle {c} recovered in "
                          f"{rec_s:.1f}s (degraded window "
                          f"{cyc['degraded']['orders_per_s']} orders/s)")
            finally:
                done.set()
            for t in threads:
                t.join(timeout=300)
            cyc["t_done"] = now()
            cycles.append(cyc)

        # -- final drain: gateways are idle, ends are stable ------------
        for i in range(N_PARTITIONS):
            backlog = log_end(bus_dirs[i]) - committed(bus_dirs[i])
            drained_final[i] = await_committed(
                bus_dirs[i], log_end(bus_dirs[i]),
                120.0 + backlog / 4096.0,
            )
        print(f"fleet-chaos: final drain={drained_final} at t={now()}s")
    finally:
        for name in [f"gw{i}" for i in range(N_PARTITIONS)] + [
            f"c{i}" for i in range(N_PARTITIONS)
        ]:
            fleet.stop(name)
        # Any stragglers (distinct lifetimes) die hard.
        for w in list(fleet.current.values()):
            w.kill()
        # Marker stores outlive the consumers: the final graceful stop
        # reads the pool (book digest) through them.
        for rp in resp:
            rp.kill()

    # -- oracle replays + durable audits (everyone is dead now) ---------
    partitions = []
    for i in range(N_PARTITIONS):
        final = fleet.result_of(f"c{i}")
        orc, oracle, obus = run_oracle(work, bus_dirs[i], i)
        fleet_lines, fleet_seqs = read_match_stream(bus_dirs[i])
        oracle_lines, _ = read_match_stream(obus)
        partitions.append({
            "partition": i,
            "events": len(fleet_lines),
            "stamped": len(fleet_seqs),
            "seq_audit": audit_seqs(fleet_seqs),
            "book_digest": final.get("book_digest"),
            "oracle_digest": oracle.get("book_digest"),
            "digest_match": (
                bool(final.get("book_digest"))
                and final.get("book_digest") == oracle.get("book_digest")
            ),
            "match_stream_identical": (
                len(fleet_lines) > 0 and fleet_lines == oracle_lines
            ),
            "match_seq": final.get("match_seq"),
            "oracle_match_seq": oracle.get("match_seq"),
            "feed": final.get("feed"),
            "oracle_exit": orc,
        })
        print(f"fleet-chaos: partition {i} digest "
              f"{'MATCH' if partitions[-1]['digest_match'] else 'MISMATCH'} "
              f"({len(fleet_lines)} events)")

    # -- verdict --------------------------------------------------------
    death_cycles = [c for c in cycles if c["class"] != "bus-disconnect"]
    disc_cycles = [c for c in cycles if c["class"] == "bus-disconnect"]
    stats = {str(p): dict(ctl.stats[p]) for p in range(N_PARTITIONS)}
    checks = {
        "all_members_ready": all_ready,
        "injected_deaths_only": bool(fleet.lifetimes) and all(
            lt["exit_code"] == (
                EXIT_CODE
                if lt["armed"] in ("consumer-kill", "gateway-kill")
                else 0
            )
            for lt in fleet.lifetimes
        ),
        "covered_fault_classes": (
            {c["class"] for c in cycles} >= set(CLASSES)
        ),
        "disconnect_absorbed": bool(disc_cycles) and all(
            c.get("disconnect_retries", 0) >= 3 for c in disc_cycles
        ),
        "no_lost_entries": all(
            s["aborted"] == 0 for s in stats.values()
        ),
        "all_partitions_drained": all(drained_final),
        "book_digest_match": all(p["digest_match"] for p in partitions),
        "match_stream_identical": all(
            p["match_stream_identical"] for p in partitions
        ),
        "exactly_once_fleet": all(
            p["seq_audit"]["dupes"] == 0 and p["seq_audit"]["gaps"] == 0
            and (p["feed"] or {}).get("dupes") == 0
            and (p["feed"] or {}).get("gaps") == 0
            for p in partitions
        ),
        "failover_after_recovery": all(
            (c.get("failover") or {}).get("epoch") is not None
            for c in cycles if c["class"] == "consumer-kill"
        ) and any(c["class"] == "consumer-kill" for c in cycles),
        "recovery_measured": len(recoveries) == len(death_cycles),
        "recovery_bounded": (
            bool(recoveries)
            and pctl(recoveries, 99) <= args.recovery_bound
        ),
        "throughput_floor_degraded": bool(death_cycles) and all(
            c["degraded"]["orders_per_s"] >= args.floor
            for c in death_cycles
        ),
        "oracle_clean_exit": all(
            p["oracle_exit"] == 0 for p in partitions
        ),
    }
    verdict = {
        "schema": SCHEMA,
        "config": {
            "seed": args.seed,
            "seconds": args.seconds,
            "kills": args.kills,
            "n_steps": n_steps,
            "base_orders_per_partition": base_counts,
            "partitions": N_PARTITIONS,
            "drive_chunk": DRIVE_CHUNK,
            "pace_s": PACE_S,
            "hit_k": HIT_K,
            "floor_orders_per_s": args.floor,
            "recovery_bound_s": args.recovery_bound,
            "admission_max_depth": args.max_depth,
            "every_n_batches": EVERY_N,
        },
        "cycles": cycles,
        "recovery": {
            "samples_s": [round(r, 3) for r in recoveries],
            "p50_s": pctl(recoveries, 50),
            "p99_s": pctl(recoveries, 99),
        },
        "throughput": {
            "degraded_windows": {
                str(c["cycle"]): c["degraded"] for c in death_cycles
            },
            "floor_orders_per_s": args.floor,
            "fleet_r01_orders_per_s": 410.0,
        },
        "drivers": stats,
        "router": {
            "map": pmap.snapshot(),
            "failovers": fc.history(),
            "health": gate.snapshot(),
        },
        "partitions": partitions,
        "lifetimes": [
            {k: lt[k] for k in
             ("name", "role", "partition", "lifetime", "armed", "exit_code")}
            for lt in fleet.lifetimes
        ],
        "checks": checks,
        "pass": all(checks.values()),
    }
    write_json(args.out, verdict)
    status = "PASS" if verdict["pass"] else "FAIL"
    print(f"fleet-chaos: {status} -> {args.out}")
    for name, ok in checks.items():
        print(f"  [{'ok' if ok else 'BREACH'}] {name}")
    return 0 if verdict["pass"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=int, default=30,
                    help="soak scale knob: sim steps = seconds*8 (clamped)")
    ap.add_argument("--kills", type=int, default=3,
                    help="kill/restart cycles (fault class rotates)")
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--out", default="FLEET_CHAOS_r01.json",
                    help="verdict JSON path (parent mode)")
    ap.add_argument("--workdir", default="",
                    help="scratch dir (default: fresh tempdir)")
    ap.add_argument("--floor", type=float, default=100.0,
                    help="degraded-window aggregate floor, orders/sec "
                         "(~0.25x FLEET_r01's 410)")
    ap.add_argument("--recovery-bound", type=float, default=150.0,
                    help="p99 recovery ceiling, seconds (CPU compile "
                         "inclusive)")
    ap.add_argument("--recovery-timeout", type=float, default=300.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--max-depth", type=int, default=16384,
                    help="gateway admission depth ceiling")
    # worker mode (internal)
    ap.add_argument("--worker", choices=("gateway", "consumer"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--bus-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--snap-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--plan", default="", help=argparse.SUPPRESS)
    ap.add_argument("--partition", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--result", default="", help=argparse.SUPPRESS)
    ap.add_argument("--resp-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.worker == "gateway":
        return run_gateway_worker(args)
    if args.worker == "consumer":
        return run_consumer_worker(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
