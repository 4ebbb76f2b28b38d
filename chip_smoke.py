#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives the system's main path once, through the entry points a user calls,
at the full width of the deployment the README headlines:

    EngineService(load_config(yaml))   10,240 symbols, cap 256, max_fills 16,
                                       max_t 32, int32, kernel pallas,
                                       pipeline_depth 2, memory bus, frame wire
    gRPC DoOrderBatch on 127.0.0.1:<ephemeral>, from a client PROCESS that
    imports no JAX, one request in flight
    -> gateway -> bus -> consumer thread -> device -> matchfeed

and checks what comes out by the repo's own means: the same stream through
gome_tpu.oracle.OracleEngine must give the same events, event for event,
and engine.verify_books() must pass. Before the service starts it builds
the native host library from the committed sources and runs the
compiled-kernel parity suite (scripts/tpu_parity_check.py) at the
deployment's own geometry.

    python chip_smoke.py [--seed N] [--mesh-devices 4]
    python chip_smoke.py --rehearsal [--symbols N --orders N --batches N ...]

Standard output ends with two JSON lines. The last is the verdict, exactly
{"ok": bool, "device": {"platform", "kind", "count"}} with the device as JAX
reports it; the line before it is the report the verdict was drawn from
(versions, geometry, orders and events against the oracle, grids by kernel,
escalations, compile count/seconds and cache, native library, seconds per
phase, failures).

Exit codes: 0 every phase passed; 1 a phase failed (the report's "failures"
says which); 2 the repository is not around this file; 3 JAX found no TPU.
With 2 and 3 nothing is printed on standard output. This process is the one
process that touches JAX (a chip belongs to one process); the client child
is JAX-free and is reaped before exit. Needs no network and no git.

--rehearsal is the only way to run it off the chip: CPU backend, the kernel
in Pallas interpret mode, shrunken sizes, every output line labelled. It
proves the command's control flow before chip time is spent; it proves
nothing about the chip (its two JSON lines carry a "cpu_rehearsal" key as
their label). No rate is printed under a metric's name: timing the system
is the benchmark's job (ROADMAP A1).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: The deployment (README; bench.py --service sizes the same engine).
CHIP = dict(symbols=10240, cap=256, max_fills=16, max_t=32,
            orders=240_000, batches=6)
#: --rehearsal defaults: same code, toy sizes (interpret mode is slow).
#: Requests of 94 orders, so that the first (94 of the listing's 128
#: symbols) takes the full grid and the rest, live on under half the
#: lanes, take dense ones: at 128 lanes a full grid may run 1,024 deep
#: and leaves no dense tail, as the chip's 10,240-lane one (64 deep) does.
REHEARSAL = dict(symbols=128, cap=16, max_fills=4, max_t=8,
                 orders=6_000, batches=64)

CONFIG_YAML = """\
grpc: {{host: 127.0.0.1, port: 0}}
engine:
  n_slots: {symbols}
  cap: {cap}
  max_fills: {max_fills}
  max_t: {max_t}
  dtype: int32
  kernel: pallas
  pipeline_depth: 2
  mesh_devices: {mesh_devices}
bus: {{backend: memory, match_wire: frame}}
"""

MID = 100_000_000  # price 1.0 at accuracy 8
BAND = 500_000  # +-0.5% of mid, as bench.py's flows
HOT_P = 0.25  # share of flow on symbol 0 (>= 20%: deep dense grids)
N_UUIDS = 64
MAX_REQUEST_BYTES = 4 << 20  # grpc's default receive limit (gateway sets none)


# --------------------------------------------------------------------------
# the order stream (the parent makes it and hands it to the client as .npz)


def make_stream(seed: int, n_symbols: int, n_orders: int, n_batches: int,
                cap: int):
    """The seeded order stream and what the oracle makes of it.

    Returns (batches, events, facts): per-batch numpy column dicts (the
    client builds its OrderRequests from them), the OracleEngine's
    MatchResult list for the whole stream, and a few facts about the
    stream for the report.

    Shape: the first n_symbols orders rest one limit order on EVERY
    symbol; after that symbol 0 takes HOT_P of the flow and the rest is
    uniform over the others. Limit orders (uniform in a +-0.5% band, or
    passive while the hot book builds), market orders, and cancels; a few
    hot-symbol market "whales" cross more resting orders than max_fills
    records hold. The generator runs the oracle as it goes and steers the
    hot book's depth from what is really resting: it builds past `cap`
    (forcing a storage cap escalation) and is then held under 3x cap (so
    exactly one 4x escalation covers it). Hot-symbol cancels hit resting
    orders exactly; the others target earlier limit orders and may miss.
    No cancel precedes its own ADD, so admission order == stream order is
    all the comparison needs."""
    import random

    import numpy as np

    from gome_tpu.oracle import OracleEngine
    from gome_tpu.types import Action, Order, OrderType, Side

    rng = random.Random(seed)
    oracle = OracleEngine()
    depth_lo, depth_hi = int(1.5 * cap), int(2.5 * cap)
    n_hot_expected = max(int((n_orders - n_symbols) * HOT_P), 1)
    whale_every = max(n_hot_expected // 5, 1)
    symbols = [f"s{i}" for i in range(n_symbols)]
    uuids = [f"u{i}" for i in range(N_UUIDS)]
    recent: list[tuple] = []  # non-hot limit ADDs: (sym, uuid, oid, side, price)

    cols = {k: [] for k in (
        "symbol_idx", "uuid_idx", "oid_num", "side", "kind", "cancel",
        "price", "volume",
    )}
    next_oid = 1
    hot_seen = 0
    hot_depth = (0, 0)
    peak_depth = 0
    whales = 0

    def emit(sym, uid, oid_num, side, kind, cancel, price, volume):
        for k, v in zip(
            cols, (sym, uid, oid_num, side, kind, cancel, price, volume)
        ):
            cols[k].append(v)
        oracle.process(Order(
            uuid=uuids[uid], oid=f"o{oid_num}", symbol=symbols[sym],
            side=Side(side), price=price, volume=volume,
            action=Action.DEL if cancel else Action.ADD,
            order_type=OrderType(kind),
        ))

    def uniform_price():
        return rng.randrange(MID - BAND, MID + BAND)

    for i in range(n_orders):
        uid = rng.randrange(N_UUIDS)
        side = rng.randrange(2)
        volume = rng.randrange(1, 101)
        if i < n_symbols:  # touch every symbol: one passive limit each
            price = MID - 1 - rng.randrange(BAND) if side == 0 \
                else MID + 1 + rng.randrange(BAND)
            emit(i, uid, next_oid, side, 0, False, price, volume)
            if i:
                recent.append((i, uid, next_oid, side, price))
            next_oid += 1
            continue
        if rng.random() < HOT_P:
            hot_seen += 1
            if hot_seen % 32 == 1:  # re-read the real book
                book = oracle.book(symbols[0])
                hot_depth = (
                    len(book.orders(Side.BUY)), len(book.orders(Side.SALE))
                )
                peak_depth = max(peak_depth, *hot_depth)
            d = max(hot_depth)
            if hot_seen % whale_every == 0 and d >= depth_lo:
                # cross ~30 resting orders of ~50 lots: n_fills > max_fills
                whales += 1
                emit(0, uid, next_oid, int(hot_depth[0] > hot_depth[1]), 1,
                     False, 0, 1500)
                next_oid += 1
                continue
            # (cancel, market, passive) probabilities; the rest is uniform
            p_cancel, p_market, p_passive = (
                (0.10, 0.05, 0.65) if d < depth_lo
                else (0.55, 0.15, 0.00) if d > depth_hi
                else (0.35, 0.15, 0.25)
            )
            r = rng.random()
            if r < p_cancel and d:
                deep = Side.BUY if hot_depth[0] >= hot_depth[1] else Side.SALE
                resting = oracle.book(symbols[0]).orders(deep)
                if resting:
                    o = resting[rng.randrange(len(resting))]
                    emit(0, int(o.uuid[1:]), int(o.oid[1:]), int(o.side), 0,
                         True, o.price, 1)
                    continue
            if r < p_cancel + p_market:
                emit(0, uid, next_oid, side, 1, False, 0, volume)
            elif r < p_cancel + p_market + p_passive:
                price = MID - 1 - rng.randrange(BAND) if side == 0 \
                    else MID + 1 + rng.randrange(BAND)
                emit(0, uid, next_oid, side, 0, False, price, volume)
            else:
                emit(0, uid, next_oid, side, 0, False, uniform_price(), volume)
            next_oid += 1
            continue
        r = rng.random()
        if r < 0.20 and recent:  # cancel a recent limit order (may miss)
            back = min(len(recent), 4096)
            sym, t_uid, t_oid, t_side, t_price = recent[-1 - rng.randrange(back)]
            emit(sym, t_uid, t_oid, t_side, 0, True, t_price, 1)
            continue
        sym = rng.randrange(1, n_symbols)
        if r < 0.30:
            emit(sym, uid, next_oid, side, 1, False, 0, volume)
        else:
            price = uniform_price()
            emit(sym, uid, next_oid, side, 0, False, price, volume)
            recent.append((sym, uid, next_oid, side, price))
        next_oid += 1

    dtypes = dict(symbol_idx=np.int32, uuid_idx=np.int32, oid_num=np.int64,
                  side=np.int8, kind=np.int8, cancel=np.bool_,
                  price=np.int64, volume=np.int64)
    arrays = {k: np.asarray(v, dtypes[k]) for k, v in cols.items()}
    per = -(-n_orders // n_batches)
    batches = [
        {k: a[lo:lo + per] for k, a in arrays.items()}
        for lo in range(0, n_orders, per)
    ]
    facts = dict(
        symbols_touched=int(len(np.unique(arrays["symbol_idx"]))),
        hot_symbol_share=round(float((arrays["symbol_idx"] == 0).mean()), 4),
        limit_orders=int(((arrays["kind"] == 0) & ~arrays["cancel"]).sum()),
        market_orders=int((arrays["kind"] == 1).sum()),
        cancels=int(arrays["cancel"].sum()),
        whales=whales,
        hot_book_peak_depth=peak_depth,
        oracle_cancels_missed=oracle.stats.cancels_missed,
    )
    return batches, oracle.events, facts


# --------------------------------------------------------------------------
# the client child: no JAX, the package's own stub, one request in flight


def client_main(target: str, stream_npz: str, accuracy: int) -> int:
    import grpc
    import numpy as np

    from gome_tpu.api import order_pb2 as pb
    from gome_tpu.api.service import OrderStub

    data = np.load(stream_npz)
    n_batches = int(data["n_batches"])
    unit = 10.0 ** accuracy
    out = dict(requests=0, sent=0, accepted=0, rejected=0, aborted=0,
               max_request_bytes=0)
    t0 = time.perf_counter()
    with grpc.insecure_channel(target) as channel:
        stub = OrderStub(channel)
        for b in range(n_batches):
            c = {k.split("/", 1)[1]: data[k]
                 for k in data.files if k.startswith(f"b{b}/")}
            orders = [
                pb.OrderRequest(
                    uuid=f"u{u}", oid=f"o{o}", symbol=f"s{s}",
                    transaction=t, price=p / unit, volume=v / unit, kind=k,
                )
                for u, o, s, t, p, v, k in zip(
                    c["uuid_idx"].tolist(), c["oid_num"].tolist(),
                    c["symbol_idx"].tolist(), c["side"].tolist(),
                    c["price"].tolist(), c["volume"].tolist(),
                    c["kind"].tolist(),
                )
            ]
            req = pb.OrderBatchRequest(
                orders=orders, cancel=c["cancel"].tolist()
            )
            size = req.ByteSize()
            out["max_request_bytes"] = max(out["max_request_bytes"], size)
            if size >= MAX_REQUEST_BYTES:
                print(f"request {b} is {size} bytes: over grpc's default "
                      "receive limit", file=sys.stderr)
                return 1
            resp = stub.DoOrderBatch(req, timeout=600)  # one in flight
            out["requests"] += 1
            out["sent"] += len(orders)
            out["accepted"] += resp.accepted
            out["rejected"] += len(resp.reject_index)
            if resp.code:
                out["aborted"] += (
                    len(orders) - resp.accepted - len(resp.reject_index)
                )
                print(f"batch {b}: code {resp.code}: {resp.message}",
                      file=sys.stderr)
    out["elapsed_s"] = round(time.perf_counter() - t0, 3)
    out["jax_imported"] = "jax" in sys.modules
    print(json.dumps(out), flush=True)
    return 0


# --------------------------------------------------------------------------
# the parent: the one process that holds the chip


class Smoke:
    def __init__(self, args, sizes):
        self.args = args
        self.sizes = sizes
        self.tag = (
            "[CPU REHEARSAL - not a chip result] " if args.rehearsal else ""
        )
        self.phases: dict[str, float] = {}
        self.failures: list[str] = []
        self.doc: dict = {}
        self.client = None
        self.svc = None

    def say(self, msg: str) -> None:
        print(f"{self.tag}{msg}", flush=True)

    def phase(self, name: str, fn) -> bool:
        """Run one phase. A failed check lets later phases run (the
        report names every failure); a raise returns False and ends the
        run, since later phases build on this one."""
        t0 = time.perf_counter()
        n_before = len(self.failures)
        raised = False
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — top-level phase boundary
            import traceback

            traceback.print_exc()
            self.failures.append(f"{name}: raised {type(e).__name__}: {e}")
            raised = True
        self.phases[name] = round(time.perf_counter() - t0, 3)
        self.say(f"phase {name}: {self.phases[name]} s"
                 + (" FAILED" if len(self.failures) > n_before else ""))
        return not raised

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            self.say(f"FAIL: {what}")

    # -- phases ------------------------------------------------------------
    def native(self) -> None:
        """Build the native host library from the three committed .cc
        files and load it: the frame hot path silently runs ~10x slower
        pure-Python code without it."""
        from gome_tpu.bus import native as bus_native
        from gome_tpu.engine import nativehost

        lib = os.path.join(HERE, "native", "build", "libgome_native.so")
        existed = os.path.exists(lib)
        loaded = nativehost.available()
        self.doc["native"] = dict(
            loaded=bool(loaded), built_this_run=bool(loaded and not existed),
            path=os.path.relpath(lib, HERE), error=bus_native._lib_err,
        )
        self.check(loaded, f"native library not loaded: {bus_native._lib_err}")

    def parity(self) -> None:
        """Compiled kernel == scan, leaf for leaf, at the deployment's own
        geometry, before any order is served."""
        if self.args.rehearsal:
            # Nothing to certify off the chip: the suite is about the
            # COMPILED kernel (the interpret kernel's parity is tier-1's).
            self.doc["parity"] = dict(skipped="cpu rehearsal")
            self.say("parity suite skipped: it certifies the compiled "
                     "kernel and needs the chip")
            return
        sys.path.insert(0, os.path.join(HERE, "scripts"))
        import tpu_parity_check as tpc

        z = self.sizes
        rc = tpc.run_suite(
            S=z["symbols"], T=z["max_t"], CAP=z["cap"], K=z["max_fills"],
            G=2, dense=tpc.DEPLOYMENT_DENSE, log=self.say,
        )
        self.doc["parity"] = dict(
            rc=rc, full=[z["symbols"], z["max_t"], z["cap"]],
            dense=[list(d) for d in tpc.DEPLOYMENT_DENSE],
        )
        self.check(rc == 0, f"kernel parity suite returned {rc}")

    def stream(self) -> None:
        import numpy as np

        z = self.sizes
        self.batches, self.want, facts = make_stream(
            self.args.seed, z["symbols"], z["orders"], z["batches"], z["cap"]
        )
        self.doc["stream"] = dict(
            seed=self.args.seed, orders=z["orders"], batches=len(self.batches),
            **facts,
        )
        self.check(facts["symbols_touched"] == z["symbols"],
                   "stream does not touch every symbol")
        self.check(facts["hot_symbol_share"] >= 0.20,
                   "hot symbol carries under 20% of the flow")
        self.check(min(facts["limit_orders"], facts["market_orders"],
                       facts["cancels"]) > 0,
                   "stream lacks limit orders, market orders or cancels")
        self.tmp = tempfile.mkdtemp(prefix="chip_smoke_")
        self.stream_npz = os.path.join(self.tmp, "stream.npz")
        flat = {f"b{i}/{k}": v for i, b in enumerate(self.batches)
                for k, v in b.items()}
        np.savez(self.stream_npz, n_batches=len(self.batches), **flat)

    def boot(self) -> None:
        """EngineService from a YAML file, as a deployment boots it."""
        import logging

        from gome_tpu.config import load_config
        from gome_tpu.service.app import EngineService

        path = os.path.join(self.tmp, "config.yaml")
        with open(path, "w") as f:
            f.write(CONFIG_YAML.format(
                mesh_devices=self.args.mesh_devices, **self.sizes
            ))
        self.config = load_config(path)
        self.svc = EngineService(self.config)
        if self.args.rehearsal:
            # EngineService has no interpret switch (a deployment never
            # wants one): set it on the engine it built.
            self.svc.engine.batch._pallas_interpret = True
        # One INFO line per match event is the reference's behaviour;
        # a quarter of a million of them bury this script's own output.
        logging.getLogger("gome_tpu.matchfeed").setLevel(logging.WARNING)
        self.svc.start()
        self.port = self.svc._server.bound_port
        e = self.config.engine
        self.doc["deployment"] = dict(
            n_slots=e.n_slots, cap=e.cap, max_fills=e.max_fills,
            max_t=e.max_t, dtype=e.dtype, kernel=e.kernel,
            pipeline_depth=e.pipeline_depth, mesh_devices=e.mesh_devices,
            bus=self.config.bus.backend, match_wire=self.config.bus.match_wire,
            accuracy=e.accuracy, grpc=f"127.0.0.1:{self.port}",
        )

    def serve(self) -> None:
        """Client child sends the stream; wait until every acknowledged
        order is consumed, matched, published and fed."""
        svc = self.svc
        self.client = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--client",
             f"127.0.0.1:{self.port}", self.stream_npz,
             str(self.config.engine.accuracy)],
            stdout=subprocess.PIPE, text=True, cwd=HERE,
        )
        oq, mq = svc.bus.order_queue, svc.bus.match_queue
        deadline = time.monotonic() + self.args.serve_timeout
        client_out = None
        while True:
            if svc.consumer.device_fault is not None:
                raise RuntimeError(
                    f"consumer stopped on a device fault: "
                    f"{svc.consumer.device_fault}"
                )
            if client_out is None and self.client.poll() is not None:
                client_out = self.client.stdout.read()
                if self.client.returncode != 0:
                    raise RuntimeError(
                        f"client exited {self.client.returncode}"
                    )
            if (
                client_out is not None
                and oq.committed() == oq.end_offset()
                and not len(svc.consumer._pipe or ())
                and mq.committed() == mq.end_offset()
            ):
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"served path did not drain in {self.args.serve_timeout}"
                    f" s (order queue {oq.committed()}/{oq.end_offset()})"
                )
            time.sleep(0.05)
        c = json.loads(client_out.strip().splitlines()[-1])
        self.doc["client"] = c
        self.check(not c["jax_imported"], "client process imported jax")
        self.check(c["sent"] == self.sizes["orders"],
                   f"client sent {c['sent']} of {self.sizes['orders']}")
        self.check(c["accepted"] == c["sent"] and not c["rejected"]
                   and not c["aborted"],
                   f"gateway did not acknowledge every order: {c}")

    def compare(self) -> None:
        """Match queue vs oracle, event for event; counters; books."""
        from gome_tpu.bus.colwire import decode_event_frame
        from gome_tpu.service.health import HealthMonitor
        from gome_tpu.utils.metrics import REGISTRY

        svc = self.svc
        got = []
        for m in svc.bus.match_queue.read_from(0, 1 << 30):
            got.extend(decode_event_frame(m.body).to_results())
        want = self.want
        first_diff = next(
            (i for i, (a, b) in enumerate(zip(got, want)) if a != b), None
        )
        if first_diff is None and len(got) != len(want):
            first_diff = min(len(got), len(want))
        equal = first_diff is None
        if not equal:
            self.say(f"first differing event #{first_diff}: "
                     f"got {got[first_diff:first_diff + 1]} "
                     f"want {want[first_diff:first_diff + 1]}")
        st = svc.engine.stats
        metric = lambda name: REGISTRY.counter(name).value()
        step_failures = int(metric("gome_consumer_step_failures_total"))
        poison = int(metric("gome_poison_orders_total"))
        feed = svc.feed.seq_state()
        self.doc["orders"] = dict(
            sent=self.doc.get("client", {}).get("sent"),
            acknowledged=self.doc.get("client", {}).get("accepted"),
            matched=st.orders, dropped_no_prepool=st.dropped_no_prepool,
            fills=st.fills, cancels=st.cancels,
            cancels_missed=st.cancels_missed,
        )
        self.doc["events"] = dict(
            oracle=len(want), match_queue=len(got),
            compared=min(len(got), len(want)), equal=equal,
            first_difference=first_diff,
            matchfeed_delivered=svc.feed.events_seen,
            matchfeed_dupes=feed["dupes"], matchfeed_gaps=feed["gaps"],
        )
        grids, ops = st.grids_by_kernel, st.ops_by_kernel
        self.doc["kernels"] = dict(
            grids=dict(grids), ops=dict(ops),
            scan_giveways=dict(st.scan_giveways),
            device_calls=st.device_calls,
        )
        self.doc["engine"] = dict(
            step_failures=step_failures, poison_orders=poison,
            frame_fallbacks=st.frame_fallbacks,
            cap_escalations=st.cap_escalations,
            grid_cap_escalations=st.grid_cap_escalations,
            fill_record_escalations=st.fill_record_escalations,
            lane_growths=st.lane_growths, final_cap=svc.engine.config.cap,
            shape_combos=svc.engine.batch.combo_count(),
        )
        self.check(equal, "events differ from the oracle's")
        self.check(len(got) >= len(want),
                   "match queue holds fewer events than the oracle produced")
        self.check(svc.feed.events_seen == len(want) and not feed["dupes"]
                   and not feed["gaps"], f"matchfeed delivery off: {feed}")
        self.check(step_failures == 0, f"step_failures = {step_failures}")
        self.check(poison == 0, f"poison_orders = {poison}")
        self.check(st.orders == self.sizes["orders"],
                   f"engine matched {st.orders} of {self.sizes['orders']}")
        kern = "interpret" if self.args.rehearsal else "pallas"
        for kind in ("full", "dense"):
            self.check(grids.get(f"{kern}_{kind}", 0) > 0,
                       f"no {kern}_{kind} grid was dispatched: {grids}")
        self.check(st.cap_escalations + st.grid_cap_escalations > 0,
                   "no cap-class escalation happened")
        health = HealthMonitor(svc).check()
        self.doc["healthy"] = health.healthy
        self.check(health.healthy, f"service unhealthy: {health.detail}")
        svc.engine.batch.verify_books()
        if self.args.mesh_devices:
            self.placement()

    def placement(self) -> None:
        """Does each chip's book block live on its own device?"""
        import jax

        books = self.svc.engine.batch.books
        shards = books.price.addressable_shards
        local = self.sizes["symbols"] // self.args.mesh_devices
        one_each = (
            len({s.device for s in shards}) == self.args.mesh_devices
            and all(s.data.shape[0] == local for s in shards)
        )
        self.doc["placement"] = dict(
            sharding=str(books.price.sharding.spec),
            shard_devices=[str(s.device) for s in shards],
            shard_shapes=[list(s.data.shape) for s in shards],
            one_block_per_device=one_each,
            device_bytes_in_use=[
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()
            ],
        )
        self.check(one_each, "book blocks are not one per device")

    def close(self) -> None:
        """Stop everything this process started."""
        if self.client is not None and self.client.poll() is None:
            self.client.kill()
        if self.client is not None:
            self.client.wait(timeout=30)
        if self.svc is not None:
            self.svc.stop()
        tmp = getattr(self, "tmp", None)
        if tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="shard the lane axis over N chips (4 on a "
                         "four-chip host); fewer devices is an error")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU + interpret mode + toy sizes; proves the "
                         "control flow, nothing about the chip")
    ap.add_argument("--serve-timeout", type=float, default=900.0)
    for k in REHEARSAL:  # sizes can only be set for a rehearsal
        ap.add_argument(f"--{k.replace('_', '-')}", type=int, default=None)
    args = ap.parse_args(argv)
    overrides = {k: getattr(args, k) for k in REHEARSAL
                 if getattr(args, k) is not None}
    if overrides and not args.rehearsal:
        ap.error("sizes are fixed on the chip; they can only be set with "
                 "--rehearsal")
    sizes = {**(REHEARSAL if args.rehearsal else CHIP), **overrides}

    try:
        import jax

        import gome_tpu.utils.jaxcache as jaxcache
    except ImportError as e:
        print(f"chip_smoke: the repository is not around this file ({e})",
              file=sys.stderr)
        return 2
    if args.rehearsal:
        jax.config.update("jax_platforms", "cpu")
        if args.mesh_devices:
            jax.config.update("jax_num_cpu_devices", args.mesh_devices)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "--rehearsal runs the CPU rehearsal", file=sys.stderr)
        return 3
    if args.mesh_devices > len(devices):
        print(f"chip_smoke: --mesh-devices {args.mesh_devices} but JAX "
              f"found {len(devices)} device(s)", file=sys.stderr)
        return 3

    # Compile accounting, before the first compile.
    comp = dict(count=0, seconds=0.0, cache_hits=0, cache_misses=0)

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            comp["count"] += 1
            comp["seconds"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            comp["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            comp["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache_dir = jaxcache.enable_compile_cache()
    entries_before = jaxcache.cache_entries(cache_dir)

    import importlib.metadata as md

    import jaxlib

    smoke = Smoke(args, sizes)
    smoke.say(f"device {dev.platform} / {dev.device_kind} x{len(devices)}; "
              f"compile cache {cache_dir} "
              f"({'cold' if not entries_before else 'warm'})")
    t0 = time.perf_counter()
    try:
        for name in ("native", "parity", "stream", "boot", "serve",
                     "compare"):
            if not smoke.phase(name, getattr(smoke, name)):
                break
    finally:
        smoke.phase("close", smoke.close)

    label = ({"cpu_rehearsal": "CPU REHEARSAL - not a chip result"}
             if args.rehearsal else {})
    verdict = {
        **label,
        "ok": not smoke.failures,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }
    report = {
        **verdict,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devices),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": md.version("libtpu"),
                     "python": sys.version.split()[0]},
        **smoke.doc,
        "compile": {
            "count": comp["count"], "seconds": round(comp["seconds"], 2),
            "cache_dir": cache_dir,
            "cache_placed_by_env": bool(os.environ.get(jaxcache.CACHE_ENV)),
            "cache_cold": not entries_before,
            "cache_entries_before": entries_before,
            "cache_entries_after": jaxcache.cache_entries(cache_dir),
            "cache_hits": comp["cache_hits"],
            "cache_misses": comp["cache_misses"],
        },
        "phase_seconds": smoke.phases,
        "total_seconds": round(time.perf_counter() - t0, 3),
        "failures": smoke.failures,
    }
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict), flush=True)  # the last line: these keys only
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--client":
        try:
            sys.exit(client_main(sys.argv[2], sys.argv[3], int(sys.argv[4])))
        except ImportError as e:
            print(f"chip_smoke client: the repository is not around this "
                  f"file ({e})", file=sys.stderr)
            sys.exit(2)
    sys.exit(main())
