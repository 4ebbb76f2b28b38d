"""Failure detection & supervision (SURVEY §5.3 — the reference has only a
recover() in main and log.Fatalf on MQ errors; crash model: lose in-flight
messages, keep Redis book state).

This framework's stronger model: the consumer/feed loops already survive
per-batch exceptions (service/consumer.py), durability comes from
persist+file-bus replay, and this module adds the missing observability and
supervision:

  HealthMonitor — point-in-time health snapshot: thread liveness,
                  heartbeat age, queue lags, engine capacity pressure,
                  and per-connection resilience state (breaker state,
                  reconnect/retry counts, time degraded — every
                  utils.resilience.Supervised in the process) plus the
                  gateway's degraded-mode spill (service.batcher).
  Watchdog      — periodic checks with a restart policy for dead loops
                  (bounded restarts — persistent crash loops surface
                  instead of flapping forever).
"""

from __future__ import annotations

import dataclasses
import threading
import time

from ..utils.logging import get_logger
from ..utils.metrics import REGISTRY

log = get_logger("health")

_restarts = REGISTRY.counter(
    "gome_loop_restarts_total", "consumer/feed loops restarted by watchdog"
)


@dataclasses.dataclass
class Health:
    healthy: bool
    consumer_alive: bool
    feed_alive: bool
    heartbeat_age_s: float
    order_lag: int  # unconsumed messages in doOrder
    match_lag: int  # undelivered messages in matchOrder
    lane_pressure: float  # provisioned-lane utilization [0, 1]
    detail: dict

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class HealthMonitor:
    def __init__(self, service, stall_after_s: float = 30.0):
        """service: EngineService. stall_after_s: heartbeat age beyond which
        a *running* consumer thread counts as stalled (wedged device call,
        deadlock) — the failure mode liveness alone misses."""
        self.service = service
        self.stall_after_s = stall_after_s
        self._beat = time.monotonic()  # single-writer: heartbeat() — the consumer loop

    def heartbeat(self) -> None:
        self._beat = time.monotonic()

    def check(self) -> Health:
        svc = self.service
        consumer_thread = svc.consumer._thread
        feed_thread = svc.feed._thread
        consumer_alive = bool(consumer_thread and consumer_thread.is_alive())
        feed_alive = bool(feed_thread and feed_thread.is_alive())
        oq = svc.bus.order_queue
        mq = svc.bus.match_queue
        order_lag = oq.end_offset() - oq.committed()
        match_lag = mq.end_offset() - mq.committed()
        batch = svc.engine.batch
        lane_pressure = len(batch.symbols) / max(batch.max_slots, 1)
        age = time.monotonic() - self._beat
        stalled = consumer_alive and order_lag > 0 and age > self.stall_after_s
        # A consumer stopped by a compile/lowering/device-runtime error
        # (consumer.step_with_policy): unhealthy until an operator acts.
        device_fault = getattr(svc.consumer, "device_fault", None)
        healthy = (
            consumer_alive and feed_alive and not stalled
            and device_fault is None
        )
        from ..utils.resilience import resilience_snapshot

        connections = resilience_snapshot()
        degraded = any(c["breaker"] != "closed" for c in connections.values())
        gateway = {}
        batcher = getattr(svc.gateway, "_batcher", None)
        if batcher is not None:
            gateway = batcher.stats()
            degraded = degraded or gateway.get("degraded", False)
        return Health(
            healthy=healthy,
            consumer_alive=consumer_alive,
            feed_alive=feed_alive,
            heartbeat_age_s=age,
            order_lag=order_lag,
            match_lag=match_lag,
            lane_pressure=lane_pressure,
            detail={
                "stalled": stalled,
                "device_fault": device_fault,
                "orders_processed": batch.stats.orders,
                "cap_escalations": batch.stats.cap_escalations,
                "device_calls": batch.stats.device_calls,
                # Transport degradation is NOT unhealthy (matching keeps
                # running; durability covers the gap) but operators need
                # to see it: supervised-connection + spill state.
                "degraded": degraded,
                "connections": connections,
                "gateway": gateway,
            },
        )


class Watchdog:
    """Periodically checks health and restarts dead loops. Crash-looping
    components get max_restarts attempts within window_s, then the watchdog
    stops restarting and marks the service unhealthy (a supervisor above —
    systemd/k8s — takes over, with durability guaranteeing replay)."""

    def __init__(
        self,
        service,
        monitor: HealthMonitor | None = None,
        interval_s: float = 1.0,
        max_restarts: int = 5,
        window_s: float = 60.0,
    ):
        self.service = service
        self.monitor = monitor or HealthMonitor(service)
        self.interval_s = interval_s
        self.max_restarts = max_restarts
        self.window_s = window_s
        self._restart_times: list[float] = []  # single-writer: the watchdog thread (check_once)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None  # single-writer: start()/stop() caller
        self.gave_up = False  # single-writer: the watchdog thread (check_once)

    def check_once(self) -> Health:
        h = self.monitor.check()
        if (
            not h.consumer_alive
            and self.service.consumer._thread is not None
            # A device fault is not cured by a restart: the same kernel
            # fails to compile again. Leave it down and red.
            and h.detail["device_fault"] is None
        ):
            self._restart("consumer", self.service.consumer)
        if not h.feed_alive and self.service.feed._thread is not None:
            self._restart("feed", self.service.feed)
        return h

    def _restart(self, name: str, component) -> None:
        now = time.monotonic()
        self._restart_times = [
            t for t in self._restart_times if now - t < self.window_s
        ]
        if len(self._restart_times) >= self.max_restarts:
            if not self.gave_up:
                log.error(
                    "%s crash-looping (%d restarts in %.0fs); giving up — "
                    "escalate to the process supervisor",
                    name, len(self._restart_times), self.window_s,
                )
                self.gave_up = True
            return
        log.warning("restarting dead %s loop", name)
        self._restart_times.append(now)
        _restarts.inc()
        component.stop()
        component.start()

    # -- background loop -----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            raise RuntimeError("watchdog already started")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="watchdog", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.check_once()
            except Exception:
                log.exception("health check failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
