#!/usr/bin/env python3
"""One run of one cell of the benchmark (BENCHMARK.json, PERF.md).

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process imports no JAX. It empties the run's directory
(.bench_run/<workload> under the root), starts the serving process
(benchmark/serve.py: the cell's deployment, alone on the chip, everything it
keeps on disk inside that directory), makes the cell's stream from the seed in
worker processes while that boots, the venue's own Book in the generator's loop,
then is the client: it sends the stream through gRPC DoOrderBatch, reads the
fills on SubscribeMatches, takes every end-to-end metric on its own clock, and
after the window compares the events with the plain reference, event for
event. The last line of standard output is the result: correct, attempted,
failed, metrics, device (and breakdown with --trace 1). --trace 0 reports the
cell's end-to-end metrics, --trace 1 its per-layer metrics. Everything else is
on earlier lines. A configuration with a `restart` block is then killed and
booted again on its directory, outside every timed number, and held to what it
acknowledged (restart_check).

Exit codes: 0 a result was printed; 1 the run broke; 2 the repository is not
around the benchmark; 3 JAX found no TPU, or fewer chips than the cell asks
for. With 1, 2 and 3 no result is printed. --rehearsal runs the same command on
the CPU at toy sizes with the kernel interpreted, every line labelled: it
proves the control flow and nothing about the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import threading
import time

T_IMPORT_NS = time.monotonic_ns()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import client as client_mod  # noqa: E402
from benchmark import compare, peaks, spec, stream, wire  # noqa: E402

now_ns = time.monotonic_ns


def process_start_ns() -> int:
    """When this process started, on CLOCK_MONOTONIC (Linux: starttime in
    /proc/self/stat counts clock ticks since boot, as the monotonic clock
    does); the moment this file was imported where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        start = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        if 0 < T_IMPORT_NS - start < 60_000_000_000:
            return start
    except (OSError, ValueError, IndexError):
        pass
    return T_IMPORT_NS


def core_plan() -> dict:
    """Which cores each process is held to: the serving process is kept off
    the client's and the generator workers' cores."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) >= 8:
        return dict(serve=cores[:-5], workers=cores[-5:-1], client=cores[-1:])
    if len(cores) >= 4:
        return dict(serve=cores[:-2], workers=cores[-2:-1], client=cores[-1:])
    return dict(serve=None, workers=None, client=None)


def _pin(cores) -> None:
    if cores:
        os.sched_setaffinity(0, set(cores))


class Serving:
    """The serving child, driven by lines on its stdin."""

    def __init__(self, args: dict):
        env = dict(os.environ, PYTHONHASHSEED="0")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "serve.py"), json.dumps(args)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1, cwd=ROOT, env=env,
        )

    def read(self, timeout_s: float | None = None) -> dict:
        """The child's next line; with a timeout, a child that has not
        written one by then is killed (and the read raises ServingExit)."""
        if timeout_s is None:
            line = self.proc.stdout.readline()
        else:
            timer = threading.Timer(max(timeout_s, 0), self.proc.kill)
            timer.start()
            try:
                line = self.proc.stdout.readline()
            finally:
                timer.cancel()
        if not line:
            code = self.proc.wait(timeout=60)
            raise ServingExit(code)
        return json.loads(line)

    def ask(self, line: str, timeout_s: float | None = None) -> dict:
        self.tell(line)
        return self.read(timeout_s)

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise ServingExit(self.proc.returncode)

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.tell("quit")
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait(timeout=30)


def wait_drained(serving: "Serving", timeout_s: float) -> bool:
    """Until the order queue, the frame pipeline and the match queue of the
    serving process are empty."""
    deadline = time.monotonic() + timeout_s
    while not serving.ask("drained")["drained"]:
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


class ServingExit(RuntimeError):
    def __init__(self, code):
        super().__init__(f"the serving process exited with code {code}")
        self.code = code


def restart_check(say, n_more: int, timeout_s: float, serving: Serving,
                  serve_args: dict, sender, loop, sub, n_sent: int,
                  cum_events, grpc) -> dict:
    """Kill the serving process at an acknowledgement and boot it again on
    the same directory: a durable configuration's guarantee, held outside
    every timed number. `n_more` more requests of the stream go out; at the
    acknowledgement of the last one the process gets SIGKILL,
    with events of acknowledged orders still on their way. The second process
    restores what the first left on disk and replays its log; the client
    subscribes again and waits until everything owed has arrived. Returns the
    requests acknowledged in all, and of the second process (None where it
    did not come up or drain inside timeout_s) the raw events, the
    seq of the first of them by the match feed's own count and its books'
    resting counts. `restart_s`, kill to first event delivered, goes on a
    line of its own."""
    out = dict(requests=n_sent + n_more, second=None)
    if out["requests"] > len(sender.requests):
        say("restart: the stream has no requests left to send before the kill")
        out["requests"] = n_sent
        return out
    for k in range(n_sent, n_sent + n_more):
        loop.released(k)
        sender.send(k)
    deadline = time.monotonic() + timeout_s
    while not sender.ack_ns[n_sent + n_more - 1]:
        serving.alive()
        if time.monotonic() > deadline:
            say("restart: the requests before the kill were not acknowledged")
            return out
        time.sleep(0.0005)
    t_kill = now_ns()
    serving.proc.kill()
    serving.proc.wait(timeout=30)
    sub.join(timeout=10)  # its stream ended with the process
    last = n_sent + n_more - 1
    say(f"restart: SIGKILL at the acknowledgement of request {last}, "
        f"{len(sub.raw)} of {int(cum_events[last])} events held; booting "
        f"again on {serve_args['run_dir']}")
    second = Serving(serve_args)
    channel = sub2 = None
    try:
        left = lambda: deadline - time.monotonic()
        deadline = time.monotonic() + timeout_s
        ready = second.read(left())
        channel = grpc.insecure_channel(f"127.0.0.1:{ready['port']}")
        grpc.channel_ready_future(channel).result(timeout=max(left(), 0.1))
        sub2 = client_mod.Subscriber(channel)
        sub2.start()
        while True:  # drained, and the client holds all that was fanned out
            if (second.ask("drained", left())["drained"] and len(sub2.raw)
                    == second.ask("counters", left())["feed_events"]):
                break
            if left() < 0:
                raise TimeoutError("the second process did not drain")
            time.sleep(0.02)
        books = second.ask("books", left())
        feed = books["feed"]
        n2 = len(sub2.raw)
        restart_s = (sub2.stamps[0] - t_kill) / 1e9 if n2 else None
        out.update(
            second=list(sub2.raw), counts=books["counts"],
            invariant_failures=books["invariant_failures"],
            second_from=(feed["last_seq"] + 1 - n2 - feed["gaps"]) if n2
            else None)
        say("restart " + json.dumps(dict(
            restart_s=restart_s, boot_s=ready["boot_s"], jax_s=ready["jax_s"],
            events_from_first_process=len(sub.raw), events_from_second=n2,
            second_from_seq=out["second_from"], feed=feed,
            what="restart_s: SIGKILL to the first event the second process "
                 "delivered; a reading, not a metric")))
    except (ServingExit, TimeoutError, OSError, grpc.FutureTimeoutError) as e:
        say(f"restart: the second process did not recover: {e!r}")
    finally:
        if sub2 is not None and sub2.is_alive():
            sub2.stop()
        if channel is not None:
            channel.close()
        second.close()
    return out


def result_line(rehearsal: bool, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, breakdown,
                compared: dict | None = None) -> dict:
    """The last line of standard output. A rehearsal carries no number under
    a metric's name: counts only, and its label. `compared`, each number
    compared with its limit, comes last."""
    if rehearsal:
        out = dict(cpu_rehearsal="CPU REHEARSAL - not a chip result",
                   correct=bool(correct), attempted=attempted, failed=failed,
                   metrics_that_a_chip_run_would_report=sorted(metrics),
                   device=dict(platform=device["platform"],
                               kind=device["kind"], count=device["count"]))
    else:
        out = dict(correct=bool(correct), attempted=attempted, failed=failed,
                   metrics=metrics, device=device)
        if breakdown is not None:
            out["breakdown"] = breakdown
    if compared is not None:
        out["compared"] = {name: dict(value=value, limit=0)
                           for name, value in compared.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also put the control (the reference with the "
                         "stated guarantee broken) in the program's place; "
                         "it has to come out not correct")
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--sabotage", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--rate", type=float, default=None,
                    help=argparse.SUPPRESS)  # sweep.py: an open loop's rate
    args = ap.parse_args(argv)
    tag = "[CPU REHEARSAL - not a chip result] " if args.rehearsal else ""
    t_start = process_start_ns()

    def say(msg: str) -> None:
        print(f"{tag}{msg}", flush=True)

    try:
        from gome_tpu.api import order_pb2 as pb
    except ImportError as e:
        print(f"benchmark: the repository is not around the benchmark ({e})",
              file=sys.stderr)
        return 2
    import grpc

    cell = spec.load_cell(args.workload, args.root, args.rehearsal)
    traffic, config = cell["traffic"], cell["config"]
    flow = config["flow"]
    closed = traffic["loop"] == "closed"
    if args.rate is not None:
        traffic["rate_orders_per_s"] = args.rate
    if closed:
        R = int(traffic["request_orders"])
        rate = float(traffic["provision_orders_per_s"])
    else:
        rate = float(traffic["rate_orders_per_s"])
        R = int(round(rate * traffic["tick_s"]))
    in_requests = lambda orders: -(-int(orders) // R)
    n_requests = int(
        in_requests(traffic["warmup_max_orders"]) + 8
        + rate * (args.seconds + traffic.get("pre_roll_s", 0)) / R
    )
    plan = core_plan()
    say(f"cpu_count {os.cpu_count()} usable {len(os.sched_getaffinity(0))} "
        f"pinning {json.dumps(plan)} loadavg {os.getloadavg()}")
    # A run owns its directory: whatever an earlier run of the cell left (a
    # durable deployment's log and snapshots, a trace) is gone before the
    # serving process boots.
    run_dir = os.path.join(args.root, ".bench_run", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    say(f"run directory {run_dir}: emptied")
    restart = config.get("restart")
    n_more = int(restart.get("requests", 4)) if restart else 0
    n_requests += n_more  # sent before the kill, after every timed number
    reference_path = os.path.join(args.root, config["reference"])
    serve_args = dict(
        service=config["service"], rehearsal=args.rehearsal,
        trace=bool(args.trace), chips=cell["chips"], cores=plan["serve"],
        run_dir=run_dir,
        scan_giveways_allowed=config.get("scan_giveways_allowed", []),
        sabotage=args.sabotage,
    )
    serving = Serving(serve_args)  # first: it boots while the stream is made
    sub = None
    channel = None
    try:
        # -- the stream, in worker processes kept off the serving cores ------
        t_gen = time.monotonic()
        n_workers = max(len(plan["workers"] or [0]), 1)
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(n_workers, initializer=_pin,
                      initargs=(plan["workers"],)) as pool:
            made = stream.generate(flow, args.seed, n_requests, R,
                                   workers=n_workers, pool=pool,
                                   reference_path=reference_path)
        # the workers are gone: their cores are the client's now
        _pin((plan["workers"] or []) + (plan["client"] or []))
        cols, events = made["cols"], made["events"]
        t_ser = time.monotonic()
        requests = wire.build_requests(
            cols, R, config["service"]["engine"].get("accuracy", 8))
        ev_order = events[:, 0]
        cum_events = np.cumsum(np.bincount(ev_order // R,
                                           minlength=n_requests))
        say("stream " + json.dumps(dict(
            stream.facts(made, R, flow), request_orders=R,
            request_bytes=len(requests[0]), workers=n_workers,
            generate_s=round(t_ser - t_gen, 3),
            serialise_s=round(time.monotonic() - t_ser, 3),
            jax_imported="jax" in sys.modules,
        )))
        sender = client_mod.Sender(requests, pb.OrderBatchResponse)

        ready = serving.read()
        say(f"serving ready: {json.dumps(ready)}")
        if not args.rehearsal:
            peaks.peaks(ready["kind"])  # an unknown device: an error
        channel = grpc.insecure_channel(f"127.0.0.1:{ready['port']}")
        grpc.channel_ready_future(channel).result(timeout=60)
        sender.connect(channel)
        sub = client_mod.Subscriber(channel)
        loop = client_mod.Loop(sender, sub, cum_events)
        sub.start()
        deadline = time.monotonic() + 30
        # (None: the program no longer shows its subscribers; go on, and a
        # subscription that came late shows as missing events)
        while serving.ask("counters")["subscribers"] == 0:
            if time.monotonic() > deadline:
                raise TimeoutError("the subscription did not register")
            time.sleep(0.01)
        gc.collect()
        gc.freeze()

        # -- warm-up: the same loop, from the stream's first request ----
        t_warm = now_ns()
        target = in_requests(traffic["warmup_orders"])
        quiet = in_requests(traffic["quiet_orders"])
        most = in_requests(traffic["warmup_max_orders"])
        stall_s = float(traffic.get("stall_timeout_s", 30))
        stalled = False

        def progress():
            """What the serving process has done: a silent subscription is a
            stall only while none of these moves (client.Loop.wait_done)."""
            c = serving.ask("counters")
            return tuple(c[k] for k in ("orders", "frames", "device_calls",
                                        "lowerings", "backend_compiles"))

        loop.open(int(traffic["outstanding"]))
        while True:
            stalled = not loop.wait_done(target - quiet, 900,
                                         serving.alive, stall_s, progress)
            before = serving.ask("counters")
            stalled = stalled or not loop.wait_done(
                target, 900, serving.alive, stall_s, progress)
            warm = serving.ask("counters")
            if (stalled or warm["lowerings"] == before["lowerings"]
                    or target >= most):
                break
            target = min(target + quiet, most)
        misses = warm["backend_compiles"] - warm["cache_hits"]
        say("warm-up " + json.dumps(dict(
            requests=target, orders=target * R,
            seconds=round((now_ns() - t_warm) / 1e9, 3),
            lowerings=warm["lowerings"],
            backend_compiles=warm["backend_compiles"],
            backend_compile_s=round(warm["backend_compile_s"], 3),
            cache_hits=warm["cache_hits"], cache_misses=misses,
            settled=warm["lowerings"] == before["lowerings"],
            longest_silence_s=round(loop.longest_silence_s, 3),
            lanes_by_class=warm["lanes_by_class"],
        )))
        if stalled:
            say("warm-up stalled: events stopped arriving and the serving "
                "process stopped moving; the run goes on to its comparison "
                "and cannot be correct")

        # -- the window ----------------------------------------------------
        seconds_ns = int(args.seconds * 1e9)
        drain_s = float(traffic.get("drain_timeout_s", 60))
        if stalled:  # no window: straight to the comparison
            t0 = t1 = now_ns()
            serving.tell(f"window {t0} {t1}")
            n_sent, closed = loop.close(), True
        elif closed:
            t0 = now_ns() + 20_000_000
            t1 = t0 + seconds_ns
            serving.tell(f"window {t0} {t1}")
            time.sleep(max((t1 - now_ns()) / 1e9, 0))
            n_sent = loop.close()
        else:
            n_warm = loop.close()
            if not loop.wait_done(n_warm, drain_s, serving.alive, stall_s,
                                  progress):
                raise TimeoutError("the system did not drain before the window")
            interval = int(traffic["tick_s"] * 1e9)
            pre = int(traffic.get("pre_roll_s", 0) / traffic["tick_s"])
            n_win = int(args.seconds / traffic["tick_s"])
            if n_warm + pre + n_win > n_requests:
                raise RuntimeError("the stream is too short for this window")
            start = now_ns() + 50_000_000
            t0 = start + pre * interval
            t1 = t0 + n_win * interval
            serving.tell(f"window {t0} {t1}")
            due = client_mod.paced(loop, n_warm, pre + n_win, start, interval)
            n_sent = n_warm + pre + n_win
        win = serving.read()
        sender.wait_acked(drain_s)
        drained = (loop.wait_done(n_sent, drain_s, serving.alive, stall_s,
                                  progress)
                   and wait_drained(serving, drain_s))
        setup_s = (t0 - t_start) / 1e9
        acknowledged = int(sum(sender.accepted[:n_sent]))
        spans = spec.span_names(cell["base"],
                                [m["name"] for m in cell["per_layer"]])
        fin = serving.ask(" ".join(
            ["finish", str(acknowledged), str(t0), str(t1)] + spans))
        n_got = again = None
        if restart:  # after every timed number; the run's own events end here
            n_got = len(sub.raw)
            again = restart_check(
                say, n_more, float(restart.get("timeout_s", 120)), serving,
                dict(serve_args, resumed=True, trace=False, sabotage=None),
                sender, loop, sub, n_sent, cum_events, grpc)
        sub.stop()
        channel.close()
        serving.close()

        # -- after the window: the comparison and the numbers ---------------
        t_check = time.monotonic()
        stamps = np.array(sub.stamps[:n_got], np.int64)
        got_all = wire.decode_events(sub.raw, pb)
        got = got_all[:n_got]
        expected = compare.expected_rows(events, n_sent * R)
        numbers = compare.compare_events(expected, got)
        first_diff = numbers.pop("_first_difference")
        if first_diff is not None:
            say(f"first difference at event {first_diff}: expected "
                f"{expected[first_diff].tolist()} got {got[first_diff].tolist()}")
        numbers.update(fin["numbers"])
        numbers["client.rpc_errors_or_rejects"] = sender.errors
        numbers["window.warmup_stalled"] = int(stalled)  # then no window was
        numbers["window.not_drained"] = int(not drained)
        numbers["window.stream_exhausted"] = int(n_sent >= n_requests - n_more)
        if again is not None:
            n_all = again["requests"] * R
            recovered = again["second"] is not None
            numbers.update(compare.restart_numbers(
                compare.expected_rows(events, n_all), got_all,
                wire.decode_events(again["second"] or [], pb),
                again.get("second_from"),
                compare.resting_counts(cols, n_all,
                                       stream.book_class(reference_path))
                if recovered else None,
                again.get("counts"), again.get("invariant_failures", 0),
                recovered))
        if args.control:
            ref = spec.load_reference(args.root, config)
            broken = compare.control(cols, n_sent * R, got,
                                     ref.CONTROL_PRIORITY, ref.run)
            broken.pop("_first_difference")
            for name, value in broken.items():
                say(f"control {cell['config_name']} ({ref.CONTROL_PRIORITY}) "
                    f"{name} = {value} (limit 0)")
            say(f"control_correct {all(v == 0 for v in broken.values())} "
                f"(has to be False)")
        correct = all(v == 0 for v in numbers.values())
        for name, value in numbers.items():
            say(f"compare {cell['config_name']} {name} = {value} (limit 0) "
                f"{'ok' if value == 0 else 'FAIL'}")

        # requests of the window (k_win): those sent (closed) or due (open) in it
        done_ns = np.array(loop.done_ns[:n_sent], np.int64)
        send_ns = np.array(sender.send_ns[:n_sent], np.int64)
        ack_ns = np.array(sender.ack_ns[:n_sent], np.int64)
        release_ns = np.array(sender.release_ns[:n_sent], np.int64)
        done_ns[done_ns == 0] = np.iinfo(np.int64).max  # never completed
        e2e = dict(setup_s=setup_s)
        detail = {}
        # orders complete by each second of the window (an order is complete
        # when every event up to its own has arrived), capped by what went out
        cum_by_order = np.cumsum(np.bincount(ev_order, minlength=n_sent * R)
                                 )[:n_sent * R]
        edges = t0 + (np.arange(int(args.seconds) + 1) * 1e9).astype(np.int64)
        edges = np.r_[edges[edges < t1], t1]
        got_by = np.searchsorted(stamps, edges, side="right")
        out_by = np.searchsorted(np.sort(ack_ns), edges, side="right") * R
        done_by = np.minimum(
            np.searchsorted(cum_by_order, got_by, side="right"), out_by)
        per_second = np.diff(done_by).tolist()
        if closed:
            k_win = np.flatnonzero((send_ns >= t0) & (send_ns < t1))
            e2e["orders_per_s"] = float(done_by[-1] - done_by[0]) / args.seconds
            detail["requests_completed"] = int(
                ((done_ns >= t0) & (done_ns < t1)).sum())
            unanswered = 0
            # Little's law over release and completion stamps
            busy = (np.minimum(done_ns, t1) - np.maximum(release_ns, t0))
            detail["outstanding_mean"] = float(
                busy[busy > 0].sum() / seconds_ns)
            n20 = min(20, len(per_second))
            detail["orders_per_s_first_20s"] = sum(per_second[:n20]) / max(
                n20, 1)
        else:
            k_win = np.arange(n_warm + pre, n_sent)
            lo, hi = k_win[0] * R, n_sent * R
            first_event = np.searchsorted(ev_order, np.arange(lo, hi))
            has = np.r_[ev_order, -1][first_event] == np.arange(lo, hi)
            fe = first_event[has]
            arrived = fe < len(stamps)
            due_of = np.array(due[pre:], np.int64)
            due_each = np.repeat(due_of, R)[has]
            lat_ms = (stamps[fe[arrived]] - due_each[arrived]) / 1e6
            unanswered = int((~arrived).sum())
            lat_all = np.r_[lat_ms, np.full(unanswered, np.inf)]
            e2e["fill_latency_p50_ms"] = float(np.quantile(lat_all, 0.50))
            detail["fill_latency_p95_ms"] = float(np.quantile(lat_all, 0.95))
            detail["fill_latency_samples"] = int(len(lat_all))
            detail["fill_latency_p99_ms"] = float(np.quantile(lat_all, 0.99))
            third = len(lat_ms) // 3
            detail["fill_latency_p50_ms_first_third"] = float(
                np.median(lat_ms[:third])) if third else None
            detail["fill_latency_p50_ms_last_third"] = float(
                np.median(lat_ms[-third:])) if third else None
            detail["rate_orders_per_s"] = rate
            # The tail over all orders, in every run, traced or not: it has no
            # bound (PERF.md, section 2) and is not to go unseen for that.
            say(f"tail {args.workload} fill_latency_p95_ms = "
                f"{detail['fill_latency_p95_ms']} fill_latency_p99_ms = "
                f"{detail['fill_latency_p99_ms']} over {len(lat_all)} orders "
                f"({unanswered} unanswered); no bound")
            late = (send_ns[k_win] - due_of) / 1e6
            detail["gen_late_p99_ms"] = float(np.quantile(late, 0.99))
            detail["gen_late_max_ms"] = float(late.max())
        if len(k_win) == 0:
            # No request went out inside a window (the warm-up stalled, or the
            # system stood still for all of it): every order sent is attempted,
            # and those of requests that never completed have failed.
            k_win = np.arange(n_sent)
            unanswered = int((done_ns[:n_sent] == np.iinfo(np.int64).max
                              ).sum()) * R
        attempted = len(k_win) * R
        rejected = attempted - int(sum(sender.accepted[k] for k in k_win))
        # an order both rejected and unanswered has failed once
        failed = min(rejected + unanswered, attempted)
        ack_us = (ack_ns[k_win] - send_ns[k_win]) / 1e3 if len(k_win) else \
            np.zeros(1)
        detail["admit_us_per_order"] = float(ack_us.mean() / R)
        detail["send_delay_p99_ms"] = float(np.quantile(
            (send_ns[k_win] - release_ns[k_win]) / 1e6, 0.99)) if len(k_win) \
            else 0.0
        c0, c1 = win["c0"], win["c1"]
        delta = {k: c1[k] - c0[k] for k in c0
                 if isinstance(c0[k], (int, float)) and k != "t_ns"}
        frames = max(delta["frames"], 1)
        k0 = int(k_win[0]) if len(k_win) else 0
        kmid, kend = (k0 + n_sent) // 2, n_sent - 1
        depths = {
            str(rank + 1): [tr[k, [2, 5]].tolist() for k in (k0, kmid, kend)]
            for rank, tr in sorted(made["traces"].items())[:8]
        }
        band_min = min((int(tr[k0:n_sent, [0, 3]].min())
                        for tr in made["traces"].values()), default=None)
        band_max = max((int(tr[k0:n_sent, [1, 4]].max())
                        for tr in made["traces"].values()), default=None)
        say("report " + json.dumps(dict(
            workload=args.workload, seed=args.seed, seconds=args.seconds,
            requests_sent=n_sent, first_window_request=k0,
            window=detail, orders_complete_per_second=per_second,
            events_received=len(stamps),
            events_per_order=round(len(stamps) / max(n_sent * R, 1), 4),
            device_calls_per_frame=delta["device_calls"] / frames,
            order_backlog_frames_start_end=[
                c0["published"] - c0["frames"], c1["published"] - c1["frames"]],
            frames=delta["frames"], lanes_by_class_start=c0["lanes_by_class"],
            lanes_by_class_end=c1["lanes_by_class"],
            steered_depth_start_mid_end=depths,
            steered_depth_min_max_from_window_start=[band_min, band_max],
            rewinds=dict(fallbacks=delta["fallbacks"],
                         escalations=delta["escalations"],
                         lowerings=delta["lowerings"],
                         lowered=win["lowered_in_window"]),
            gc_serving=win["gc"], gc_serving_total=fin["gc_total"],
            floors=fin["floors"], counters_window=delta,
            grids_by_kernel=fin["grids_by_kernel"],
            grids_noted_in_trace=len(fin.get("grids", [])),
            scan_giveways=fin["scan_giveways"],
            check_seconds=round(time.monotonic() - t_check, 3),
        )))

        # -- the result line -------------------------------------------------
        device = dict(platform=ready["platform"], kind=ready["kind"],
                      count=ready["count"],
                      memory_peak_bytes=fin["memory_peak_bytes"])
        metrics = {}
        breakdown = None
        if args.trace:
            reduced = fin.get("trace")
            if "trace_error" in fin:
                say(f"no trace to reduce: {fin['trace_error']}")
            else:
                say("trace lines: " + "; ".join(fin["trace_lines"]))
            run = dict(cell=cell, win=win, detail=detail, trace=reduced,
                       grids=fin.get("grids", []), device_kind=device["kind"],
                       backlog=fin.get("backlog", []),
                       rehearsal=args.rehearsal)
            for m in cell["per_layer"]:
                meta, read = spec.load_reader(cell["base"], m["name"])
                value = read(run, meta)
                if value is not None:
                    metrics[m["name"]] = dict(value=value, unit=m["unit"])
            if reduced and reduced.get("window_s"):
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = reduced["breakdown"]
        else:
            for m in cell["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = dict(value=e2e[m["name"]],
                                              unit=m["unit"])
        for name, value in numbers.items():  # the record's last lines
            print(f"{tag}compared {name} = {value} (limit 0)", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result_line(args.rehearsal, correct, attempted,
                                     failed, metrics, device, breakdown,
                                     numbers)),
              flush=True)
        return 0
    except ServingExit as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code if e.code in (2, 3) else 1
    finally:
        if sub is not None and sub.is_alive():
            sub.stop()
        if channel is not None:
            channel.close()
        serving.close()


if __name__ == "__main__":
    sys.exit(main())
