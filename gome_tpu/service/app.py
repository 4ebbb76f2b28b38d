"""EngineService — the whole stack assembled from one Config.

The reference runs three processes wired by external RabbitMQ/Redis
(README.md run instructions; SURVEY §1): gRPC server, order consumer, match
consumer. Here the default deployment is one binary hosting all three
components around the in-process (or file) bus; the same components can
also run in separate processes against a shared `file`/`amqp` bus — a
`redis:` config section then puts the pre-pool markers in a
Redis-compatible store (the built-in RESP client + engine.prepool.
RespPrePool; persist/respserver.py is a standalone stand-in server), which
is exactly the reference's own trade (nodepool.go:14-28) and gives the
split topology reference race semantics (tested in
tests/test_multiprocess.py::test_three_process_prepool_reference_topology).
"""

from __future__ import annotations

import os

from ..bus import FileQueue, make_bus
from ..config import Config
from ..engine.orchestrator import MatchEngine
from ..types import OrderType
from ..utils import tracing
from ..utils.logging import configure as configure_logging, get_logger
from .consumer import OrderConsumer
from .gateway import OrderGateway, serve_gateway
from .matchfeed import MatchFeed

log = get_logger("app")


class EngineService:
    def __init__(self, config: Config | None = None, persist=None):
        self.config = config or Config()
        configure_logging()
        if self.config.faults.enabled:
            # Arm the deterministic fault-injection registry (utils.faults)
            # BEFORE the bus exists so boot-time injection points (torn
            # sidecar reads, first appends) are covered. Chaos/test
            # tooling only; without a `faults:` section FAULTS stays a
            # zero-allocation no-op.
            from ..utils.faults import FAULTS

            FAULTS.install(self.config.faults.fault_plan())
            log.warning(
                "fault injection ARMED (seed=%d, %d specs) — chaos/test "
                "mode, never production",
                self.config.faults.seed,
                len(self.config.faults.fault_plan().faults),
            )
        self.bus = make_bus(self.config.bus)
        from ..bus.base import export_queue_metrics

        # Per-queue depth/lag gauges (gome_bus_depth{queue=...}): scrape-
        # time reads of local queue state, registered for both queues on
        # every backend — the per-partition fan-in telemetry obs.fleet
        # aggregates.
        export_queue_metrics(self.bus.order_queue)
        export_queue_metrics(self.bus.match_queue)
        e = self.config.engine
        mesh = None
        if e.mesh_devices:
            from ..parallel import make_mesh

            mesh = make_mesh(e.mesh_devices)
        self.engine = MatchEngine(
            config=e.book_config(),
            n_slots=e.n_slots,
            max_t=e.max_t,
            auto_grow=e.auto_grow,
            kernel=e.kernel,
            mesh=mesh,
        )
        from ..engine import frames

        frames.export_metrics(self.engine.batch)
        if self.config.store.enabled:
            # A `redis:` config section puts the pre-pool markers in the
            # (Redis-compatible) store under the reference's exact schema —
            # split gateway/consumer processes then share marker state the
            # way the reference's three processes do (nodepool.go:14-28).
            # Like the amqp bus backend, an unreachable store must not stop
            # the engine from booting (the reference config.yaml names
            # local Redis/RabbitMQ that may not exist in this environment):
            # warn loudly and keep the in-process pool.
            from ..engine.prepool import RespPrePool
            from ..persist.resp import RespError, SupervisedRespClient

            st = self.config.store
            try:
                # Supervised client: a store restart mid-traffic reconnects
                # under backoff + breaker and replays the session
                # (utils.resilience) instead of killing the marker path.
                client = SupervisedRespClient(
                    st.host, st.port, password=st.password or None,
                    name="resp:store",
                )
                # Validate the session up front (a reachable-but-unusable
                # store, e.g. NOAUTH, must fall back at boot — not fail
                # on the first hot-path HSET).
                client.ping()
                self.engine.pre_pool = RespPrePool(client)
            except (OSError, RespError) as exc:
                log.warning(
                    "redis store %s:%d unusable (%s): pre-pool markers "
                    "stay IN-PROCESS — split gateway/consumer deployments "
                    "need the store up",
                    st.host, st.port, exc,
                )
        self.persist = persist  # gome_tpu.persist.Persister or None
        self.feed = MatchFeed(self.bus)
        self.consumer = OrderConsumer(
            self.engine,
            self.bus,
            batch_n=e.max_t * max(1, e.n_slots // 8),
            match_wire=self.config.bus.match_wire,
            pipeline_depth=e.pipeline_depth,
        )
        if persist is not None:
            # attach() gives the consumer its two persist hooks; the
            # consumer rides along so snapshots carry the matchfeed
            # seq at the cut and restore rebases it (exactly-once across
            # restarts); the durability gauges read from the Persister at
            # scrape time.
            persist.attach(self.engine, self.bus, consumer=self.consumer)
            persist.export_metrics()
        from ..engine.step import LOT_MAX32

        self.admission = None
        if self.config.admission.enabled:
            # End-to-end overload protection (round 12): the gateway
            # sheds retryable once order-queue consumer lag crosses the
            # configured ceiling — backpressure reaches the client
            # instead of piling into the bus.
            from .admission import AdmissionController

            a = self.config.admission
            self.admission = AdmissionController(
                self.bus.order_queue.depth,
                max_depth=a.max_depth,
                min_deadline_s=a.min_deadline_s,
                retry_after_s=a.retry_after_s,
                retry_after_max_s=a.retry_after_max_s,
                cache_s=a.cache_s,
            )
        self.gateway = OrderGateway(
            self.bus,
            accuracy=e.accuracy,
            mark=self.engine.mark,
            unmark=self.engine.unmark,
            mark_frame=self.engine.mark_frame,
            unmark_frame=self.engine.unmark_frame,
            match_feed=self.feed,
            max_volume=LOT_MAX32 if e.dtype == "int32" else None,
            admission=self.admission,
        )
        self._server = None
        self.ops = None
        if self.config.ops.enabled:
            from .ops import OpsServer

            if self.config.ops.trace:
                # Arm the order-lifecycle tracer (utils.trace): trace ids
                # at the gateway, per-stage histograms in /metrics, and
                # the flight recorder behind the ops /trace endpoint.
                from ..utils.trace import TRACER, FlightRecorder

                TRACER.install(
                    FlightRecorder(
                        keep_n=self.config.ops.trace_keep,
                        slow_threshold_s=self.config.ops.slow_ms / 1e3,
                    )
                )
            if self.config.ops.cost:
                # Arm the compile journal (gome_tpu.obs): first-seen
                # frame-dispatch combos land in gome_compile_seconds
                # metrics and the ops /cost endpoint.
                from ..obs.compile_journal import JOURNAL

                JOURNAL.install(keep_n=self.config.ops.cost_keep)
            if self.config.ops.timeline:
                # Arm the host-side timeline sampler (gome_tpu.obs.
                # timeline): RSS/rusage/live-buffer/compile/queue series
                # behind the ops /timeline endpoint and gome_timeline_*
                # gauges. The periodic thread runs only while the
                # service is start()ed; sample() also works on demand.
                from ..obs.timeline import TIMELINE, service_timeline

                TIMELINE.install(
                    interval_s=self.config.ops.timeline_interval_s,
                    keep_n=self.config.ops.timeline_keep,
                )
                service_timeline(self)
            if self.config.ops.profile:
                # Arm the measured-roofline profiler (gome_tpu.obs.
                # profiler): per-shard dispatch telemetry on the dense
                # mesh path, bounded jax.profiler captures behind the
                # ops /profile endpoint, gome_profile_* gauges.
                from ..obs.profiler import PROFILER

                PROFILER.install(keep_n=self.config.ops.profile_keep)
            if self.config.ops.hostprof:
                # Arm the host-CPU sampling profiler (gome_tpu.obs.
                # hostprof): gateway note_admit hook live, thread-mode
                # wall sampler behind the ops /hostprof endpoint and the
                # gome_hostprof_* gauges. The sampler thread runs only
                # while the service is start()ed.
                from ..obs.hostprof import HOSTPROF

                HOSTPROF.install(
                    hz=self.config.ops.hostprof_hz,
                    keep_n=self.config.ops.hostprof_keep,
                )
            if self.config.ops.placement:
                # Arm the placement observatory (gome_tpu.obs.placement):
                # gateway admit hooks feed the heavy-hitter symbol
                # sketch, the dense-dispatch hook keeps the occupancy
                # ledger, and the /placement endpoint serves the skew
                # attribution + the committed what-if verdict when one
                # is checked in next to the package.
                import numpy as np

                from ..engine.book import DeviceOp, GRID_I32_FIELDS
                from ..obs import placement as _placement

                itemsize = np.dtype(e.dtype).itemsize
                n_i32 = len(GRID_I32_FIELDS)
                n_val = len(DeviceOp._fields) - n_i32
                _placement.PLACEMENT.install(
                    topk=self.config.ops.placement_topk,
                    ewma_alpha=self.config.ops.placement_alpha,
                    row_bytes=(n_i32 * 4 + n_val * itemsize) * e.max_t,
                    partitions=self.config.ops.placement_partitions,
                    verdict=_placement.default_verdict(),
                )
            if self.config.fleet.enabled:
                # Arm the fleet aggregator (gome_tpu.obs.fleet): this
                # process polls the listed members' ops endpoints and
                # serves the merged view under its own /fleet. The
                # polling thread runs only while the service is
                # start()ed.
                from ..obs.fleet import FLEET

                FLEET.install(
                    self.config.fleet.member_map(),
                    interval_s=self.config.fleet.interval_s,
                    timeout_s=self.config.fleet.timeout_s,
                )
            self.ops = OpsServer(
                self, host=self.config.ops.host, port=self.config.ops.port
            )
        if os.environ.get("GOME_RACECHECK") == "1":
            # Arm the dynamic lockset race detector (analysis.racecheck)
            # over the service's cross-thread hotspots — the CI race
            # drill's hook. Local import behind the env check: a normal
            # boot neither imports nor pays for it.
            from ..analysis.racecheck import maybe_arm

            maybe_arm(self)

    def start(self):
        """Start gRPC server + consumer + feed threads (+ the ops HTTP
        endpoint when configured); returns self."""
        if self.persist is not None:
            self.persist.restore_latest()
        # The slow-span lines' absolute counters (throttling, context
        # switches, CPU time) at a known instant.
        tracing.log_baseline()
        self._server = serve_gateway(self.gateway, self.config)
        self.consumer.start()
        self.feed.start()
        if self.ops is not None:
            self.ops.start()
            if self.config.ops.timeline:
                from ..obs.timeline import TIMELINE

                TIMELINE.start()
            if self.config.ops.hostprof:
                from ..obs.hostprof import HOSTPROF

                HOSTPROF.start()
            if self.config.fleet.enabled:
                from ..obs.fleet import FLEET

                FLEET.start()
        return self

    def stop(self):
        if self._server is not None:
            self._server.stop(grace=2).wait()
            self._server = None
        self.consumer.stop()
        self.feed.stop()
        if self.persist is not None and not self.persist.wait(60):
            log.warning("a snapshot was still being written after 60 s")
        tracing.log_totals()
        # Beside the spans' totals, at their level: how the frames those
        # spans timed were handed their buffers and fetched.
        st = self.engine.stats
        (log.warning if tracing.slow() else log.info)(
            "fast-path frames: %d dispatched, %d on reused event buffers, "
            "%d fetched in one phase, %d with their cap classes merged into "
            "one grid; grids: %d dispatched, %d as one "
            "program; %d dispatch combos over %d step geometries; the "
            "book stack holds %d rows for the venue's %d lanes",
            st.fast_frames, st.fast_frames_reused, st.fast_frames_one_phase,
            st.fast_frames_merged, st.device_calls, st.fast_grids_one_program,
            self.engine.batch.combo_count(),
            len({c[:4] for c in self.engine.batch.combos()}),
            self.engine.batch.lane_rows, self.engine.batch.n_slots,
        )
        # Beside them what woke each queue's idle reader (bus/base.py:
        # "timer" is a reader that woke for nothing). A file queue also says
        # how often its reads looked at the log (bus/filelog.py): the count
        # stops at the queue's first append.
        (log.warning if tracing.slow() else log.info)(
            "polls that brought messages, by what ended their wait: %s",
            "; ".join(
                f"{q.name} {q.poll_returns()}, its idle reader woken by "
                f"{q.idle_wakeups()}"
                + (f", looks at the log {q.log_looks()}"
                   if isinstance(q, FileQueue) else "")
                for q in (self.bus.order_queue, self.bus.match_queue)
            ),
        )
        (log.warning if tracing.slow() else log.info)(
            "orders: %d applied; adds by kind %s; %d stopped at their "
            "owner's order (self_trade %s); expired: %d IOC "
            "remainders dropped, %d FOK killed, %d POST_ONLY blocked",
            st.orders,
            {OrderType(k).name: n for k, n in sorted(st.adds_by_kind.items())},
            st.stp_expired, self.engine.batch.config.self_trade,
            st.expired_ioc, st.fok_killed, st.post_only_blocked,
        )
        if self.persist is not None:
            kept = {
                name: int(counter.value())
                for name, counter in (
                    ("order", self.gateway.order_log_bytes),
                    ("match", self.consumer.match_log_bytes),
                ) if counter is not None
            }
            (log.warning if tracing.slow() else log.info)(
                "durability: %d snapshots taken, %d cadence ticks skipped "
                "(writer busy), %d cuts discarded (engine rewound), last "
                "snapshot %d bytes; log bytes appended %s",
                self.persist.snapshots_taken, self.persist.snapshots_skipped,
                self.persist.cuts_discarded,
                self.persist.last_snapshot_bytes, kept,
            )
        if self.ops is not None:
            self.ops.stop()
            if self.config.ops.timeline:
                from ..obs.timeline import TIMELINE

                TIMELINE.stop()
            if self.config.ops.hostprof:
                from ..obs.hostprof import HOSTPROF

                HOSTPROF.stop()
            if self.config.fleet.enabled:
                from ..obs.fleet import FLEET

                FLEET.stop()

    def wait(self):
        if self._server is not None:
            self._server.wait_for_termination()

    # -- synchronous conveniences (tests, embedded use) ----------------------
    def pump(self) -> int:
        """Drain order queue then match queue once, synchronously (no
        threads). Returns orders processed."""
        n = self.consumer.drain()
        self.feed.drain()
        if self.persist is not None:
            self.persist.wait()  # a cut taken on the way is on disk
        return n


def build_service(config) -> EngineService:
    """The deployment of a loaded Config: the service, with its Persister
    attached where the file enables one (not started)."""
    persist = None
    if config.persist.enabled:
        from ..persist import Persister

        persist = Persister(config.persist)
    return EngineService(config, persist=persist)


def main(argv=None):
    """CLI entry: `python -m gome_tpu.service.app [config.yaml]` — the
    single-binary replacement for the reference's three `go run` processes
    (README.md:11-15)."""
    import sys

    from ..config import load_config
    from ..utils.jaxcache import enable_compile_cache

    argv = sys.argv[1:] if argv is None else argv
    config = load_config(argv[0] if argv else None)
    # Every frame-geometry shape is a compile of seconds on the chip;
    # cached, a restart pays them once per machine.
    cache_dir = enable_compile_cache()
    svc = build_service(config).start()
    log.info(
        "engine service up (grpc %s:%d, compile cache %s)",
        config.grpc.host, config.grpc.port, cache_dir,
    )
    try:
        svc.wait()
    except KeyboardInterrupt:
        svc.stop()


if __name__ == "__main__":
    main()
