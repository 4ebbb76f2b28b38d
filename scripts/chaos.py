#!/usr/bin/env python
"""Chaos soak: seeded kill/restart cycles with a machine-checked verdict.

The question this script answers: after N real process deaths injected at
the nastiest points we know (mid-frame, inside the at-least-once window,
torn sidecar writes, torn snapshot manifests), does recovery produce the
EXACT state and event stream an uninterrupted run produces?

Topology (everything file-backed, no gateway, no threads):

    parent                          worker child (this script, --worker)
    ------                          -----------------------------------
    record sim GCO frames  ──────>  doOrder FileQueue (pre-published)
    oracle child: clean run         boot -> Persister.restore_latest()
    kill cycle c = 1..N:            -> arm FAULTS from the cycle's plan
      write FaultPlan JSON          -> consume synchronously until the
      run child, expect exit 86        injected fault kills the process
    final child: clean run, exit 0     (exit EXIT_CODE) or queue drains
    compare: book digest,           -> MatchFeed.drain() + book digest
      match stream bytes,           -> result JSON (progressive write at
      seq audit, recovery p50/p99      WAL catch-up, full at completion)

Determinism: the worker is single-threaded (batch_n=1, per-message
commit), the fault registry is armed AFTER restore_latest() so a plan's
``at=(K,)`` indexes positions in THIS run's replay stream, and the sim
flow never reuses an (symbol, uuid, oid) key (flow.FlowState.next_oid is
monotonic) — so the recovery-time DEL-suppression refinement in
persist._reconstruct_marks cannot diverge replay from the oracle.

The verdict JSON (committed as CHAOS_r01.json, pinned by
tests/test_chaos.py) records the plans, per-cycle exit codes, recovery
times, the seq audit, and a pass/fail per check. CI runs this with
``--seconds 30 --kills 3`` and fails the build on any breach.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Must be set before anything imports jax (workers inherit it too).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from gome_tpu.utils.faults import EXIT_CODE, FaultPlan, FaultSpec  # noqa: E402

SCHEMA = "gome-chaos-verdict-v1"

# Worker geometry: small enough to compile in seconds on CPU, matched to
# the sim flow below (n_slots >= n_lanes, max_t >= t_bins).
N_LANES = 16
T_BINS = 8
EVERY_N = 2  # snapshot cadence in committed batches (= messages here)
SNAP_KEEP = 8  # torn snapshots accumulate; keep enough good history


# -- shared by parent and worker --------------------------------------------

def build_engine():
    import jax.numpy as jnp

    from gome_tpu.engine.book import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine

    return MatchEngine(
        config=BookConfig(cap=64, max_fills=8, dtype=jnp.int64),
        n_slots=N_LANES,
        max_t=T_BINS,
        auto_grow=True,
        kernel="scan",
    )


def book_digest(engine) -> str:
    """sha256 over the full exported engine state (arrays bit-exact,
    interners, geometry) + the pre-pool — the bit-for-bit equality the
    chaos verdict asserts between oracle and recovered runs."""
    import numpy as np

    state = engine.batch.export_state()
    h = hashlib.sha256()
    for key in sorted(state):
        val = state[key]
        h.update(key.encode())
        if key == "books":
            for name in sorted(val):
                arr = np.ascontiguousarray(val[name])
                h.update(name.encode())
                h.update(str(arr.dtype).encode())
                h.update(repr(arr.shape).encode())
                h.update(arr.tobytes())
        else:
            h.update(repr(val).encode())
    h.update(repr(sorted(engine.pre_pool)).encode())
    return h.hexdigest()


# -- worker ------------------------------------------------------------------

def run_worker(args) -> int:
    """One consumer-process lifetime: boot, restore, (optionally) arm the
    fault plan, consume the order queue synchronously, drain the feed,
    digest the book. An injected fault hard-exits with EXIT_CODE before
    this function returns."""
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig, PersistConfig
    from gome_tpu.persist import Persister
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.matchfeed import MatchFeed
    from gome_tpu.utils.faults import FAULTS

    bus = make_bus(
        BusConfig(backend="file", dir=args.bus_dir, match_wire="frame")
    )
    engine = build_engine()
    persist = Persister(PersistConfig(
        enabled=True, dir=args.snap_dir, every_n_batches=EVERY_N,
        keep=SNAP_KEEP,
    ))
    # batch_n=1: one message per step, commit per message — fault hit
    # counters then index individual frames, reproducibly.
    consumer = OrderConsumer(
        engine, bus, batch_n=1, batch_wait_s=0.0, match_wire="frame",
    )
    feed = MatchFeed(bus, log_events=False)
    persist.attach(engine, bus, consumer=consumer)  # the consumer's hooks

    oq = bus.order_queue
    pre_committed = oq.committed()  # the crashed predecessor's position
    t0 = time.monotonic()
    persist.restore_latest()

    # Arm AFTER restore: restore-time sidecar writes must not consume
    # fault hits, so a plan's at=(K,) means "the K-th <point> of THIS
    # run" — reproducible from the verdict artifact alone.
    if args.plan:
        with open(args.plan) as f:
            FAULTS.install(FaultPlan.from_json(f.read()))

    result: dict = {
        "pre_committed": pre_committed,
        "restore": persist.probe(),
        "completed": False,
    }

    def write_result() -> None:
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
        os.replace(tmp, args.out)

    # recovery_s = restore + WAL catch-up back to the pre-crash position,
    # cold process (includes the first dispatch's compile). Written as
    # soon as it is known so a later injected death cannot lose it.
    caught_up = oq.committed() >= pre_committed
    if caught_up:
        result["recovery_s"] = persist.last_recovery_seconds
        write_result()
    while oq.committed() < oq.end_offset():
        consumer.run_once()
        if not caught_up and oq.committed() >= pre_committed:
            caught_up = True
            result["recovery_s"] = time.monotonic() - t0
            write_result()
    feed.drain()
    persist.wait()  # a cut on its way is on disk (or its fault has fired)
    result.update({
        "completed": True,
        "book_digest": book_digest(engine),
        "match_seq": consumer.match_seq,
        "feed": feed.seq_state(),
        "faults": FAULTS.report() if args.plan else None,
        "oq": {"end": oq.end_offset(), "committed": oq.committed()},
        "mq": {
            "end": bus.match_queue.end_offset(),
            "committed": bus.match_queue.committed(),
        },
    })
    write_result()
    return 0


# -- parent ------------------------------------------------------------------

def plan_for_cycle(cycle: int, seed: int) -> FaultPlan:
    """The kill rotation. Cycle 1 always dies inside the at-least-once
    window at offset 0 (match events published, NOTHING committed, no
    snapshot yet — the stale-match-tail case); later cycles rotate
    through the remaining fault classes at hit K, chosen past the replay
    window (<= EVERY_N messages) so every cycle makes net progress."""
    k = EVERY_N + 2 + ((cycle - 1) % 3)  # 4..6
    if cycle == 1:
        spec = FaultSpec("consumer.commit", mode="exit", at=(1,))
    else:
        rot = (cycle - 2) % 4
        if rot == 0:
            spec = FaultSpec("consumer.frame", mode="exit", at=(k,))
        elif rot == 1:
            spec = FaultSpec("filelog.offset", mode="torn", at=(k,))
        elif rot == 2:
            # 2nd snapshot of the run: published torn, then death —
            # load_latest must fall back to the previous snapshot.
            spec = FaultSpec("snapshot.rename", mode="torn", at=(2,))
        else:
            spec = FaultSpec("filelog.append", mode="torn", at=(k,))
    return FaultPlan(seed=seed * 1000 + cycle, faults=(spec,))


def record_sim_frames(seed: int, n_steps: int) -> list[bytes]:
    from gome_tpu.sim.env import EnvConfig
    from gome_tpu.sim.flow import FlowConfig
    from gome_tpu.sim.replay import record_frames

    # Dense enough that (a) no step is empty and (b) most frames publish
    # match events — the filelog.append fault point needs real appends.
    cfg = EnvConfig(flow=FlowConfig(
        n_lanes=N_LANES, t_bins=T_BINS, dt=0.07,
        submit_rate=3.0, cancel_rate=1.5, market_rate=1.0,
    ))
    return record_frames(cfg, seed, n_steps)


def seed_queue(bus_dir: str, frames: list[bytes]) -> None:
    from gome_tpu.bus.filelog import FileQueue

    q = FileQueue("doOrder", os.path.join(bus_dir, "doOrder"))
    for fr in frames:
        q.publish(fr)
    q.close()


def read_match_stream(bus_dir: str) -> tuple[list[bytes], list[int]]:
    """The durable queue-level record: every event as its canonical JSON
    line (seq included) plus the raw seq sequence for the audit."""
    from gome_tpu.bus.colwire import decode_event_frame
    from gome_tpu.bus.filelog import FileQueue

    q = FileQueue("matchOrder", os.path.join(bus_dir, "matchOrder"))
    lines: list[bytes] = []
    seqs: list[int] = []
    for m in q.read_from(0, q.end_offset()):
        batch = decode_event_frame(m.body)
        lines.extend(batch.to_json_lines())
        for r in batch.to_results():
            if r.seq is not None:
                seqs.append(r.seq)
    q.close()
    return lines, seqs


def audit_seqs(seqs: list[int]) -> dict:
    """Full-stream exactly-once audit (SeqTracker anchored at seq 0)."""
    from gome_tpu.service.matchfeed import SeqTracker

    tracker = SeqTracker(first_seq=0)
    for s in seqs:
        tracker.observe(s)
    return tracker.state()


def pctl(xs: list[float], p: float) -> float | None:
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))]


def run_child(
    bus_dir: str, snap_dir: str, out: str, plan_path: str | None = None
) -> tuple[int, float]:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--worker",
        "--bus-dir", bus_dir, "--snap-dir", snap_dir, "--out", out,
    ]
    if plan_path:
        cmd += ["--plan", plan_path]
    t0 = time.monotonic()
    proc = subprocess.run(cmd, timeout=300)
    return proc.returncode, time.monotonic() - t0


def read_result(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def run_parent(args) -> int:
    import tempfile

    work = args.workdir or tempfile.mkdtemp(prefix="gome-chaos-")
    os.makedirs(work, exist_ok=True)
    n_steps = max(16, min(160, max(args.seconds, 8 * args.kills)))
    print(f"chaos: recording {n_steps} sim steps (seed {args.seed})...")
    frames = record_sim_frames(args.seed, n_steps)
    from gome_tpu.bus.colwire import decode_order_frame

    n_orders = sum(int(decode_order_frame(fr)["n"]) for fr in frames)
    print(f"chaos: {len(frames)} frames / {n_orders} orders -> {work}")

    dirs = {}
    for run in ("oracle", "chaos"):
        dirs[run] = {
            "bus": os.path.join(work, run, "bus"),
            "snaps": os.path.join(work, run, "snaps"),
        }
        os.makedirs(dirs[run]["bus"], exist_ok=True)
        os.makedirs(dirs[run]["snaps"], exist_ok=True)
        seed_queue(dirs[run]["bus"], frames)

    # -- oracle: one uninterrupted run ----------------------------------
    oracle_out = os.path.join(work, "oracle_result.json")
    oracle_rc, oracle_wall = run_child(
        dirs["oracle"]["bus"], dirs["oracle"]["snaps"], oracle_out
    )
    oracle = read_result(oracle_out) or {}
    print(f"chaos: oracle rc={oracle_rc} wall={oracle_wall:.1f}s "
          f"digest={oracle.get('book_digest', '?')[:12]}...")

    # -- chaos: N killed cycles, then one clean run to completion -------
    cycles = []
    for c in range(1, args.kills + 1):
        plan = plan_for_cycle(c, args.seed)
        plan_path = os.path.join(work, f"plan_{c}.json")
        with open(plan_path, "w") as f:
            f.write(plan.to_json())
        out_c = os.path.join(work, f"chaos_result_{c}.json")
        rc, wall = run_child(
            dirs["chaos"]["bus"], dirs["chaos"]["snaps"], out_c, plan_path
        )
        res = read_result(out_c) or {}
        spec = plan.faults[0]
        print(f"chaos: cycle {c} [{spec.point}/{spec.mode}@{spec.at}] "
              f"rc={rc} wall={wall:.1f}s "
              f"recovery={res.get('recovery_s', -1):.3f}s")
        cycles.append({
            "cycle": c,
            "plan": plan.to_dict(),
            "exit_code": rc,
            "wall_s": round(wall, 3),
            "pre_committed": res.get("pre_committed"),
            "recovery_s": res.get("recovery_s"),
            "restore": res.get("restore"),
        })
    final_out = os.path.join(work, "chaos_result_final.json")
    final_rc, final_wall = run_child(
        dirs["chaos"]["bus"], dirs["chaos"]["snaps"], final_out
    )
    final = read_result(final_out) or {}
    print(f"chaos: final rc={final_rc} wall={final_wall:.1f}s "
          f"digest={final.get('book_digest', '?')[:12]}...")

    # -- verdict --------------------------------------------------------
    oracle_lines, oracle_seqs = read_match_stream(dirs["oracle"]["bus"])
    chaos_lines, chaos_seqs = read_match_stream(dirs["chaos"]["bus"])
    seq_audit = audit_seqs(chaos_seqs)
    oracle_audit = audit_seqs(oracle_seqs)

    # Recovery samples: every boot that followed an injected death
    # (cycles 2..N and the final run). Cycle 1 boots fresh.
    recoveries = [
        c["recovery_s"] for c in cycles[1:] if c["recovery_s"] is not None
    ]
    if final.get("recovery_s") is not None:
        recoveries.append(final["recovery_s"])
    wal_frames = sum(
        (c["restore"] or {}).get("wal_replay_frames", 0) for c in cycles[1:]
    ) + (final.get("restore") or {}).get("wal_replay_frames", 0)
    total_rec = sum(recoveries)

    feed_state = final.get("feed") or {}
    checks = {
        "oracle_clean_exit": oracle_rc == 0,
        "all_kills_injected": all(
            c["exit_code"] == EXIT_CODE for c in cycles
        ),
        "final_clean_exit": final_rc == 0,
        "book_digest_match": (
            bool(oracle.get("book_digest"))
            and oracle.get("book_digest") == final.get("book_digest")
        ),
        "match_stream_identical": (
            len(oracle_lines) > 0 and oracle_lines == chaos_lines
        ),
        "queue_seq_no_dupes": seq_audit["dupes"] == 0,
        "queue_seq_no_gaps": seq_audit["gaps"] == 0,
        "feed_exactly_once": (
            feed_state.get("dupes") == 0 and feed_state.get("gaps") == 0
        ),
        "recovery_measured": len(recoveries) >= args.kills,
    }
    verdict = {
        "schema": SCHEMA,
        "config": {
            "seed": args.seed,
            "seconds": args.seconds,
            "kills": args.kills,
            "n_steps": n_steps,
            "frames": len(frames),
            "orders": n_orders,
            "every_n_batches": EVERY_N,
            "engine": {
                "n_slots": N_LANES, "max_t": T_BINS,
                "cap": 64, "max_fills": 8, "dtype": "int64",
            },
        },
        "oracle": {
            "exit_code": oracle_rc,
            "wall_s": round(oracle_wall, 3),
            "book_digest": oracle.get("book_digest"),
            "events": len(oracle_lines),
            "match_seq": oracle.get("match_seq"),
            "seq_audit": oracle_audit,
        },
        "cycles": cycles,
        "final": {
            "exit_code": final_rc,
            "wall_s": round(final_wall, 3),
            "book_digest": final.get("book_digest"),
            "events": len(chaos_lines),
            "match_seq": final.get("match_seq"),
            "feed": feed_state,
        },
        "matchfeed": {
            "events": len(chaos_lines),
            "stamped": len(chaos_seqs),
            "seq_audit": seq_audit,
        },
        "recovery": {
            "samples_s": [round(r, 4) for r in recoveries],
            "p50_s": pctl(recoveries, 50),
            "p99_s": pctl(recoveries, 99),
            "wal_replay_frames_total": wal_frames,
            "wal_replay_frames_per_s": (
                round(wal_frames / total_rec, 2) if total_rec > 0 else None
            ),
        },
        "checks": checks,
        "pass": all(checks.values()),
    }
    with open(args.out, "w") as f:
        json.dump(verdict, f, indent=1, sort_keys=True)
        f.write("\n")
    status = "PASS" if verdict["pass"] else "FAIL"
    print(f"chaos: {status} -> {args.out}")
    for name, ok in checks.items():
        print(f"  [{'ok' if ok else 'BREACH'}] {name}")
    return 0 if verdict["pass"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=int, default=30,
                    help="soak scale knob: sim steps to record (clamped)")
    ap.add_argument("--kills", type=int, default=3,
                    help="injected process deaths before the clean run")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default="CHAOS_r01.json",
                    help="verdict JSON path (parent mode)")
    ap.add_argument("--workdir", default="",
                    help="scratch dir (default: fresh tempdir)")
    # worker mode (internal)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--bus-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--snap-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--plan", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.worker:
        return run_worker(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
