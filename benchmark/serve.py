"""The serving process: the one that holds the chip. It boots the cell's
deployment (build_service(load_config(file)): the service with its Persister
where the file enables one) and otherwise only answers the supervisor's lines
on stdin with JSON lines on stdout: the program's counters, the profiler for a
traced stretch, and after the window the guarantees it can check from inside.
No load is made here and nothing of the benchmark's runs on this interpreter
while the window is open but a sleep (and, in a traced run, a 10 ms backlog
sampler).

The run's directory (run.py hands it over empty) holds everything the
deployment keeps on disk: a relative bus.dir or persist.dir of the service
block is placed under it. A deployment that keeps anything there (a file or
cfile bus, a persist section) is durable: its `ready` line says what the
directory stands on (filesystem type, median fsync of a 64 KB append), and
when run.py boots it a second time on the same directory (`resumed`, the
restart check) the match feed is held until the client's subscription has
registered, since SubscribeMatches has no way to ask for events from a seq.

What the benchmark touches of the program: EngineStats, the bus queues'
offsets, MatchFeed.seq_state, the metrics registry, jax.monitoring's compile
events, verify_books(), and in traced runs a clock around MatchFeed.run_once
and a note of each kernel grid's geometry (BatchEngine._step). For want of a
public way it also reads four private members: the gateway server's bound
port, the match feed's subscriber list (its length), the consumer's frame
pipeline (its length) and the engine's grow-only floors (printed only).
"""

from __future__ import annotations

import gc
import inspect
import json
import logging
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

now_ns = time.monotonic_ns
MARK = "bench_mark"
TRACE_SECONDS = 6.0


def _size(private):
    """Length of a private member of the program that the benchmark reads for
    want of a public one (PERF.md, open questions); None where it is gone."""
    return None if private is None else len(private)


def kept_on_disk(service: dict) -> dict:
    """{block of the service: its directory's default name} for the blocks
    that keep anything on disk: a file or cfile bus, an enabled persist
    section. Empty for a deployment that keeps nothing across its death."""
    kept = {}
    if service.get("bus", {}).get("backend") in ("file", "cfile"):
        kept["bus"] = "bus_data"
    if service.get("persist", {}).get("enabled", "persist" in service):
        kept["persist"] = "snapshots"
    return kept


def place_under(service: dict, run_dir: str) -> dict:
    """The service block with what it keeps on disk placed in the run's
    directory: a relative bus.dir / persist.dir goes under it (the default
    name where the block names none); an absolute path stays. A block that
    keeps nothing on disk comes back as it is."""
    out = json.loads(json.dumps(service))
    for block, default in kept_on_disk(service).items():
        out[block]["dir"] = os.path.join(run_dir,
                                         out[block].get("dir", default))
    return out


def disk_facts(directory: str) -> dict:
    """What "durable" means here: the filesystem the directory stands on and
    the median of 32 fsyncs of a 64 KB append to a file in it."""
    real = os.path.realpath(directory)
    fs, mount = "unknown", ""
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, point, kind = line.split()[:3]
                inside = real == point or real.startswith(
                    point.rstrip("/") + "/")
                if inside and len(point) >= len(mount):
                    fs, mount = kind, point
    except OSError:
        pass
    path = os.path.join(directory, ".fsync_probe")
    block, took = os.urandom(65536), []
    with open(path, "ab", buffering=0) as f:
        for _ in range(32):
            f.write(block)
            t0 = now_ns()
            os.fsync(f.fileno())
            took.append(now_ns() - t0)
    os.remove(path)
    return dict(filesystem=fs, mount=mount,
                fsync_64k_median_ms=sorted(took)[len(took) // 2] / 1e6)


def build_service(config):
    """The deployment of a loaded Config: the service, with its Persister
    attached where the file enables one. What gome_tpu.service.app's `main`
    does by hand; the program owes a public function for it (PERF.md, open
    questions), and this one goes when it has one."""
    from gome_tpu.service.app import EngineService

    persist = None
    if config.persist.enabled:
        from gome_tpu.persist import Persister

        persist = Persister(config.persist)
    return EngineService(config, persist=persist)


def sleep_until(t_ns: int) -> None:
    left = (t_ns - now_ns()) / 1e9
    if left > 0:
        time.sleep(left)


class Compiles:
    """Counts what JAX lowers and compiles, from jax.monitoring. A lowering
    (jaxpr -> MLIR) happens for every program new to the process, whether or
    not the persistent cache then spares the backend compile."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.backend = self.cache_hits = 0
        self.backend_s = 0.0
        self.lowered: list[tuple[int, str]] = []  # (when, function)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        if event == self.LOWER:
            self.lowered.append((now_ns(), str(fun_name)))
        elif event == self.BACKEND:
            self.backend += 1
            self.backend_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcLog:
    """Garbage collections of this process: (start, seconds, generation)."""

    def __init__(self):
        self.runs: list[tuple[int, float, int]] = []
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = now_ns()
        else:
            self.runs.append((self._t0, (now_ns() - self._t0) / 1e9,
                              info["generation"]))

    def between(self, t0: int, t1: int) -> dict:
        inside = [r for r in self.runs if t0 <= r[0] <= t1]
        return dict(
            count=len(inside), seconds=sum(r[1] for r in inside),
            gen2=sum(1 for r in inside if r[2] == 2),
            longest_s=max((r[1] for r in inside), default=0.0),
        )


class Sampler(threading.Thread):
    """Order-queue backlog (published minus committed), every 10 ms."""

    def __init__(self, queue):
        super().__init__(name="bench-sampler", daemon=True)
        self.queue = queue
        self.samples: list[tuple[int, int]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(0.010):
            self.samples.append((now_ns(), self.queue.depth()))

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5)


class Served:
    def __init__(self, args: dict, jax):
        self.args = args
        self.jax = jax
        self.rehearsal = args["rehearsal"]
        self.compiles = Compiles(jax)
        self.gc = GcLog()
        self.grids: list[tuple] = []  # (t_ns, rows, t, cap, n_ops)
        self.feed_ns = self.feed_calls = 0
        self.sampler = None
        self.svc = None
        self.run_dir = args["run_dir"]

    def boot(self) -> int:
        from gome_tpu.config import load_config

        os.makedirs(self.run_dir, exist_ok=True)
        path = os.path.join(self.run_dir, "config.yaml")
        with open(path, "w") as f:  # JSON is YAML
            json.dump(place_under(self.args["service"], self.run_dir), f)
        self.svc = build_service(load_config(path))
        if self.rehearsal:
            self.svc.engine.batch._pallas_interpret = True
        # One INFO line per match event is the reference's behaviour; the
        # configuration's file asks for log level WARNING (assumed).
        logging.getLogger("gome_tpu").setLevel(logging.WARNING)
        if self.args["trace"]:
            self._wrap_for_trace()
        if self.args.get("sabotage"):  # the benchmark's own tests only
            from benchmark import faults

            faults.apply(self.args["sabotage"], self.svc)
        if self.args.get("resumed"):
            self._hold_feed_until_subscribed()
        self.svc.start()
        return self.svc._server.bound_port

    def _hold_feed_until_subscribed(self) -> None:
        """A second boot on the first one's directory replays its log from
        the moment it starts, and the match feed would hand the events to
        nobody: SubscribeMatches cannot ask for events from a seq, and the
        feed's cursor in the durable match queue is the only resume point
        there is. So the feed stands still until a subscription registers;
        the events wait in the match queue meanwhile."""
        feed = self.svc.feed
        inner = feed.run_once

        def run_once():
            if _size(getattr(feed, "_subs", None)) == 0:
                time.sleep(0.002)
                return 0
            return inner()

        feed.run_once = run_once

    def _wrap_for_trace(self) -> None:
        feed = self.svc.feed
        inner_once = feed.run_once

        def run_once():
            t0 = now_ns()
            n = inner_once()
            if n:
                self.feed_ns += now_ns() - t0
                self.feed_calls += 1
            return n

        feed.run_once = run_once
        # The kernel's events in the trace carry no geometry, so each grid's
        # rows, depth, cap class and real op count are noted where the engine
        # dispatches it. Whatever the method's arguments become, the call goes
        # through unchanged; a grid whose geometry cannot be read is not
        # noted, and the kernel's metrics then find nothing to read.
        eng = self.svc.engine.batch
        inner_step = getattr(eng, "_step", None)
        if inner_step is not None:
            try:
                signature = inspect.signature(inner_step)
            except (TypeError, ValueError):
                signature = None

            def step(*args, **kwargs):
                try:
                    given = signature.bind(*args, **kwargs).arguments
                    rows, t = given["ops"].action.shape
                    if given.get("n_ops") is not None:
                        self.grids.append((
                            now_ns(), int(rows), int(t),
                            int(given.get("cap_g") or eng.config.cap),
                            int(given["n_ops"])))
                except (AttributeError, KeyError, TypeError, ValueError):
                    pass
                return inner_step(*args, **kwargs)

            eng._step = step
        self.sampler = Sampler(self.svc.bus.order_queue)
        self.sampler.start()

    def counters(self) -> dict:
        import numpy as np
        from gome_tpu.engine.batch import _cap_ladder

        svc = self.svc
        st = svc.engine.stats
        oq = svc.bus.order_queue
        for _ in range(5):  # the consumer thread may be writing the dicts
            try:
                ops = dict(st.ops_by_kernel)
                grids = dict(st.grids_by_kernel)
                break
            except RuntimeError:
                continue
        kernel = ("pallas", "interpret")
        eng = svc.engine.batch
        ladder = _cap_ladder(eng.config.cap)
        ub = np.asarray(eng.count_ub())
        live = ub[ub > 0]
        cls = np.minimum(np.searchsorted(ladder, live), len(ladder) - 1)
        return dict(
            t_ns=now_ns(), orders=st.orders,
            device_calls=st.device_calls, fallbacks=st.frame_fallbacks,
            escalations=(st.cap_escalations + st.grid_cap_escalations
                         + st.fill_record_escalations + st.lane_growths),
            dropped_no_prepool=st.dropped_no_prepool,
            kernel_ops=sum(v for k, v in ops.items() if k.startswith(kernel)),
            kernel_grids=sum(v for k, v in grids.items()
                             if k.startswith(kernel)),
            all_ops=sum(ops.values()), grids_by_kernel=grids,
            scan_giveways=dict(st.scan_giveways),
            frames=oq.committed(), published=oq.end_offset(),
            lowerings=len(self.compiles.lowered),
            backend_compiles=self.compiles.backend,
            backend_compile_s=self.compiles.backend_s,
            cache_hits=self.compiles.cache_hits,
            rewinds=(st.frame_fallbacks + st.cap_escalations
                     + st.grid_cap_escalations + st.fill_record_escalations
                     + st.lane_growths + len(self.compiles.lowered)),
            feed_events=svc.feed.events_seen, feed_ns=self.feed_ns,
            feed_calls=self.feed_calls,
            subscribers=_size(getattr(svc.feed, "_subs", None)),
            storage_cap=int(eng.config.cap),
            lanes_by_class={str(ladder[int(c)]): int(n) for c, n in
                            zip(*np.unique(cls, return_counts=True))},
        )

    def window(self, t0: int, t1: int) -> dict:
        """Counter snapshots at both ends and, in a traced run, the profiler
        on for a stretch in the middle."""
        jax = self.jax
        sleep_until(t0)
        out = dict(t0_ns=t0, t1_ns=t1, c0=self.counters())
        if self.args["trace"]:
            seconds = (t1 - t0) / 1e9
            span = min(TRACE_SECONDS, seconds / 2)
            sleep_until(t0 + int((seconds - span) / 2 * 1e9))
            # The Python tracer would log every call of the service's host
            # code: it slows the host the window measures and is not read.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            trace_dir = os.path.join(self.run_dir, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            out["mark_ns"] = now_ns()
            with jax.profiler.TraceAnnotation(MARK):
                time.sleep(0.001)
            out["trace_c0"] = self.counters()
            time.sleep(span)
            out["trace_c1"] = self.counters()
            jax.profiler.stop_trace()
            out["trace_dir"] = trace_dir
        sleep_until(t1)
        out["c1"] = self.counters()
        out["gc"] = self.gc.between(t0, t1)
        out["lowered_in_window"] = [
            name for t, name in self.compiles.lowered if t0 <= t <= t1
        ]
        fault = self.svc.consumer.device_fault
        out["device_fault"] = None if fault is None else repr(fault)
        return out

    def drained(self) -> bool:
        svc = self.svc
        oq, mq = svc.bus.order_queue, svc.bus.match_queue
        return (oq.committed() == oq.end_offset()
                and not _size(getattr(svc.consumer, "_pipe", None))
                and mq.committed() == mq.end_offset())

    def _books_broken(self) -> int:
        try:
            self.svc.engine.batch.verify_books()
        except Exception as e:  # noqa: BLE001 - any failure is the finding
            print(f"verify_books: {e!r}", file=sys.stderr, flush=True)
            return 1
        return 0

    def books(self) -> dict:
        """After a drain: the resting count of every symbol and side, and
        whether the books hold their invariants (the restart check)."""
        eng = self.svc.engine.batch
        count = eng.lane_books().count
        return dict(
            invariant_failures=self._books_broken(),
            feed=self.svc.feed.seq_state(),
            counts={name: [int(c) for c in count[eng.symbol_lane(name)]]
                    for name in eng.symbols.to_list()})

    def finish(self, acknowledged: int, t0: int, t1: int, spans) -> dict:
        """After the drain: the guarantees (each held to 0), the peak, and in
        a traced run the trace's rows."""
        from gome_tpu.utils.metrics import REGISTRY

        svc = self.svc
        st = svc.engine.stats
        feed = svc.feed.seq_state()
        metric = lambda name: int(REGISTRY.counter(name).value())
        grids = dict(st.grids_by_kernel)
        listed = set(self.args.get("scan_giveways_allowed", []))
        numbers = {
            "orders.acknowledged_not_matched": acknowledged - st.orders,
            "orders.dropped_no_prepool": st.dropped_no_prepool,
            "matchfeed.gaps": feed["gaps"],
            "matchfeed.dupes": feed["dupes"],
            "consumer.step_failures":
                metric("gome_consumer_step_failures_total"),
            "consumer.poison_orders": metric("gome_poison_orders_total"),
            "consumer.device_fault": int(svc.consumer.device_fault is not None),
            "books.invariant_failures": self._books_broken(),
            "kernel.no_compiled_pallas_grid": int(not any(
                v > 0 for k, v in grids.items()
                if k.startswith("interpret" if self.rehearsal else "pallas"))),
            "kernel.scan_giveways_not_listed": sum(
                v for k, v in st.scan_giveways.items() if k not in listed),
        }
        out = dict(
            numbers=numbers, feed=feed, grids_by_kernel=grids,
            scan_giveways=dict(st.scan_giveways),
            memory_peak_bytes=int(max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.jax.devices())),
            floors={name: {str(k): v for k, v in getattr(
                svc.engine.batch, f"_dense_{name}_floor", {}).items()}
                for name in ("rows", "t")},
            gc_total=dict(count=len(self.gc.runs),
                          seconds=sum(r[1] for r in self.gc.runs)),
        )
        if self.args["trace"]:
            from benchmark import tracered

            self.sampler.stop()
            out["backlog"] = [d for t, d in self.sampler.samples
                              if t0 <= t <= t1]
            out["grids"] = [g for g in self.grids if t0 <= g[0] <= t1]
            try:
                raw = tracered.extract(
                    tracered.find_xplane(os.path.join(self.run_dir, "trace")),
                    list(spans) + [MARK])
                out["trace"] = tracered.reduce(raw)
                out["trace_lines"] = sorted(set(raw["lines"]))
            except FileNotFoundError as e:
                out["trace_error"] = str(e)
        return out

    def stop(self) -> None:
        if self.sampler is not None and self.sampler.is_alive():
            self.sampler.stop()
        if self.svc is not None:
            self.svc.stop()
            self.svc = None


def main(argv) -> int:
    args = json.loads(argv[1])
    if args.get("cores"):
        os.sched_setaffinity(0, set(args["cores"]))
    t_start = now_ns()
    try:
        import jax

        import gome_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"benchmark: the repository is not around the benchmark ({e})",
              file=sys.stderr)
        return 2
    if args["rehearsal"]:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if not args["rehearsal"] and (devices[0].platform != "tpu"
                                  or len(devices) < args["chips"]):
        print(f"benchmark: the cell needs {args['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    from gome_tpu.utils.jaxcache import enable_compile_cache

    # the env's, else <checkout>/.jax_cache; a CPU rehearsal caches nothing
    cache_dir = None if args["rehearsal"] else enable_compile_cache()
    # Cache every program, the quick ones too: each run is a new process and
    # the set-up of every later check pays for what is not cached.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say = lambda doc: print(json.dumps(doc), flush=True)
    served = Served(args, jax)
    try:
        t_jax = now_ns()
        disk = {}
        if kept_on_disk(args["service"]):
            os.makedirs(args["run_dir"], exist_ok=True)
            disk = dict(run_dir_disk=disk_facts(args["run_dir"]))
        port = served.boot()
        say(dict(ready=True, port=port, platform=devices[0].platform,
                 kind=devices[0].device_kind, count=len(devices),
                 jax_s=(t_jax - t_start) / 1e9,
                 boot_s=(now_ns() - t_jax) / 1e9,
                 cores=sorted(os.sched_getaffinity(0)), cache_dir=cache_dir,
                 **disk))
        for line in sys.stdin:
            cmd, *rest = line.split()
            if cmd == "counters":
                say(served.counters())
            elif cmd == "window":
                say(served.window(int(rest[0]), int(rest[1])))
            elif cmd == "drained":
                say(dict(drained=served.drained()))
            elif cmd == "books":
                say(served.books())
            elif cmd == "finish":
                say(served.finish(int(rest[0]), int(rest[1]), int(rest[2]),
                                  rest[3:]))
            elif cmd == "quit":
                break
    finally:
        served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
