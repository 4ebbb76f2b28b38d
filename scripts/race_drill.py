"""Race drill — the gomerace dynamic prong run against REAL service flow.

Boots a full EngineService with ``GOME_RACECHECK=1`` (the app-level hook
arms analysis.racecheck's Eraser-style lockset detector over the
matchfeed, its SeqTracker, the consumer's seq frontier, and the batcher
when present), then drives concurrent gateway→bus→consumer→matchfeed
traffic the way production sees it:

  * N gateway threads submitting mixed add/cancel flow through the real
    ``DoOrder``/``DeleteOrder`` handlers (no gRPC socket — the handlers
    ARE the concurrency surface; the wire adds nothing to lock
    discipline),
  * the consumer and matchfeed daemon loops running live,
  * one subscriber draining the fan-out stream (the SubscribeMatches
    path's queue handoff).

The run ends in a machine-checkable JSON verdict: orders accepted,
events fanned out, and every lockset violation the detector recorded —
both stacks, deduped by fingerprint. Exit 0 iff traffic actually flowed
AND no unsuppressed race was reported; a suppression (see
``RaceCheck.suppress``) must cite a documented benign-race
justification. CI (tier1.yml ``race`` job) runs this after the GL7xx
static sweep: the static pass proves the *contracts* are declared, the
drill proves the code *honors* them under real interleavings.

Usage:
    GOME_RACECHECK=1 python scripts/race_drill.py --seconds 6
    python scripts/race_drill.py --seconds 3 --threads 2   # smoke
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The drill IS the racecheck mode; set it before EngineService is built
# so the app-level hook arms the detector.
os.environ["GOME_RACECHECK"] = "1"

SYMBOL = "eth2usdt"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=6.0,
                    help="wall-clock traffic window")
    ap.add_argument("--threads", type=int, default=4,
                    help="concurrent gateway submitter threads")
    ap.add_argument("--out", default="",
                    help="write the JSON verdict here too")
    args = ap.parse_args(argv)

    from gome_tpu.analysis.racecheck import RACECHECK
    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.config import Config
    from gome_tpu.service.app import EngineService

    svc = EngineService(Config())
    assert RACECHECK.enabled, "GOME_RACECHECK hook did not arm"
    # Tens of thousands of per-fill INFO lines would bury the verdict.
    import logging

    logging.getLogger("gome_tpu.matchfeed").setLevel(logging.WARNING)
    svc.consumer.start()
    svc.feed.start()

    stop = threading.Event()
    accepted = [0] * args.threads
    rejected = [0] * args.threads
    sub_events = [0]

    def gateway_worker(i: int) -> None:
        rng = random.Random(0xACE + i)
        n = 0
        resting: list[str] = []
        while not stop.is_set():
            n += 1
            oid = f"o{i}-{n}"
            if resting and rng.random() < 0.3:
                # cancel flow rides the same handlers/batcher path
                dead = resting.pop(rng.randrange(len(resting)))
                svc.gateway.DeleteOrder(
                    pb.OrderRequest(
                        uuid=f"u{i}", oid=dead, symbol=SYMBOL,
                        transaction=pb.BUY, price=1.0, volume=1.0,
                    ),
                    None,
                )
                continue
            side = pb.BUY if rng.random() < 0.5 else pb.SALE
            r = svc.gateway.DoOrder(
                pb.OrderRequest(
                    uuid=f"u{i}", oid=oid, symbol=SYMBOL,
                    transaction=side,
                    price=round(rng.uniform(0.90, 1.10), 2),
                    volume=float(rng.randint(1, 5)),
                ),
                None,
            )
            if r.code == 0:
                accepted[i] += 1
                resting.append(oid)
            else:
                rejected[i] += 1

    def subscriber() -> None:
        # Real fan-out consumer: the generator's queue handoff (one item
        # per match message, yielded as one serialised MatchEvent per
        # event) is the SubscribeMatches path; it ends when the feed stops.
        for _ in svc.feed.subscribe():
            sub_events[0] += 1

    sub = threading.Thread(target=subscriber, name="drill-subscriber")
    sub.start()
    workers = [
        threading.Thread(target=gateway_worker, args=(i,),
                         name=f"drill-gateway-{i}")
        for i in range(args.threads)
    ]
    t0 = time.monotonic()
    for w in workers:
        w.start()
    time.sleep(args.seconds)
    stop.set()
    for w in workers:
        w.join(timeout=30)
    # Let the consumer/feed drain the tail before stopping the loops.
    deadline = time.monotonic() + 10
    while (svc.bus.order_queue.committed() < svc.bus.order_queue.end_offset()
           and time.monotonic() < deadline):
        time.sleep(0.05)
    svc.consumer.stop()
    svc.feed.stop()
    sub.join(timeout=10)
    RACECHECK.disable()

    reports = RACECHECK.reports()
    all_reports = RACECHECK.reports(include_suppressed=True)
    verdict = {
        "seconds": round(time.monotonic() - t0, 2),
        "gateway_threads": args.threads,
        "orders_accepted": sum(accepted),
        "orders_rejected": sum(rejected),
        "events_fanned_out": svc.feed.events_seen,
        "subscriber_events": sub_events[0],
        "matchfeed_seq": svc.feed.seq.state(),
        "race_reports_total": len(all_reports),
        "race_reports_suppressed": len(all_reports) - len(reports),
        "race_reports": [r.format() for r in reports],
        "race_report_stacks": [
            {"here": list(r.site_here), "prev": list(r.site_prev)}
            for r in reports
        ],
    }
    verdict["passed"] = (
        verdict["orders_accepted"] > 0
        and verdict["events_fanned_out"] > 0
        and not reports
    )
    text = json.dumps(verdict, indent=2, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    return 0 if verdict["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
