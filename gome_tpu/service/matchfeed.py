"""Match-event feed — the reference's consume_match_order process
(consume_match_order.go:7-10 → rabbitmq.go:132-177): drains the
"matchOrder" queue, logs each MatchResult (rabbitmq.go:162-171), and — where
the reference leaves a "your code..." stub (rabbitmq.go:169) — fans events
out to in-process subscribers (the gateway's SubscribeMatches stream).
"""

from __future__ import annotations

import queue
import threading

from ..api import order_pb2 as pb
from ..bus import QueueBus, decode_match_result
from ..fixed import unscale
from ..types import MatchResult, OrderSnapshot
from ..utils.logging import get_logger
from ..utils.metrics import REGISTRY
from ..utils.tracing import annotate, poll_span, span

log = get_logger("matchfeed")

_dupes_total = REGISTRY.counter(
    "gome_matchfeed_dupes_total",
    "duplicate matchfeed seqs observed (suppressed before fan-out)",
)
_gaps_total = REGISTRY.counter(
    "gome_matchfeed_gaps_total",
    "missing matchfeed seqs observed (events lost upstream)",
)


class SeqTracker:
    """Subscriber-side exactly-once guard over matchfeed seq numbers.

    ``observe(seq)`` returns False for an already-seen seq (the caller
    suppresses the event) and True otherwise, counting dupes and gaps as
    it goes. The baseline is the FIRST observed seq: a subscriber
    attaching mid-stream must not count everything before its attach
    point as a gap. Pass ``first_seq`` to anchor the stream start instead
    (e.g. 0 for a full-stream audit of a queue read from offset 0).

    A duplicate only rewinds, never re-counts: seqs at or below the
    high-water mark are dupes; anything above it contributes
    ``seq - last - 1`` gaps. Unstamped events (seq None) pass through
    untracked — mixed legacy streams stay deliverable.
    """

    def __init__(self, first_seq: int | None = None):
        # single-writer (all counters): the observe() caller — one
        # delivery thread per tracker (the matchfeed fan-out loop, or the
        # chaos verdict's replay walk). state() readers tolerate
        # staleness; ints rebind atomically under the GIL.
        self.last_seq: int | None = (  # single-writer: observe() caller
            None if first_seq is None else first_seq - 1
        )
        self.dupes = 0  # single-writer: observe() caller
        self.gaps = 0  # single-writer: observe() caller
        self.observed = 0  # single-writer: observe() caller

    def observe(self, seq: int) -> bool:
        self.observed += 1
        last = self.last_seq
        if last is None:
            self.last_seq = seq
            return True
        if seq <= last:
            self.dupes += 1
            _dupes_total.inc()
            return False
        if seq > last + 1:
            self.gaps += seq - last - 1
            _gaps_total.inc(seq - last - 1)
        self.last_seq = seq
        return True

    def state(self) -> dict:
        return {
            "last_seq": self.last_seq,
            "observed": self.observed,
            "dupes": self.dupes,
            "gaps": self.gaps,
        }


def snapshot_to_pb(s: OrderSnapshot) -> pb.OrderSnapshot:
    # Wire doubles carry the reference's observable values: the scaled
    # float64 (SURVEY §2.2 — events serialize post-scaling nodes).
    return pb.OrderSnapshot(
        uuid=s.uuid,
        oid=s.oid,
        symbol=s.symbol,
        transaction=int(s.side),
        price=unscale(s.price),
        volume=unscale(s.volume),
    )


def match_result_to_pb(mr: MatchResult) -> pb.MatchEvent:
    return pb.MatchEvent(
        node=snapshot_to_pb(mr.node),
        match_node=snapshot_to_pb(mr.match_node),
        match_volume=float(mr.match_volume),
    )


class MatchFeed:
    def __init__(self, bus: QueueBus, log_events: bool = True):
        self.bus = bus
        self.log_events = log_events
        self._subs: list[queue.Queue] = []  # guarded by self._lock
        self._lock = threading.Lock()
        self._life = threading.Lock()  # serializes start()/stop()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None  # guarded by self._life
        self.events_seen = 0  # single-writer: the feed thread (run_once)
        # Exactly-once guard: dupes (same event re-delivered by the
        # at-least-once replay window) are suppressed before fan-out, so
        # subscribers see each seq at most once; gaps are counted loudly
        # (a gap after recovery is a durability bug, never expected).
        self.seq = SeqTracker()
        self.suppressed = 0  # single-writer: the feed thread (run_once)
        self._poll = poll_span("feed_poll")  # owned by the feed thread

    def run_once(self) -> int:
        msgs = self._poll(self.bus.match_queue.poll_batch, 256, 0.002)
        if not msgs:
            return 0
        from ..bus.colwire import decode_event_frame, is_frame

        with annotate("feed_run_once"):
            with self._lock:
                subs = list(self._subs)
            i = 0
            while i < len(msgs):
                # One decode and one fan-out span per EVENT frame (one
                # message = a whole batch of MatchResults, bus.colwire) or
                # per run of JSON messages (one event each): never a span
                # per event.
                j = i + 1
                with span("feed_decode"):
                    if is_frame(msgs[i].body):
                        results = decode_event_frame(
                            msgs[i].body
                        ).to_results()
                    else:
                        while j < len(msgs) and not is_frame(msgs[j].body):
                            j += 1
                        results = [
                            decode_match_result(m.body) for m in msgs[i:j]
                        ]
                with span("feed_fanout", events=len(results),
                          subscribers=len(subs)):
                    self._fan_out(results, subs)
                i = j
            self.bus.match_queue.commit(msgs[-1].offset + 1)
        return len(msgs)

    def _fan_out(self, results, subs) -> None:
        for mr in results:
            if mr.seq is not None and not self.seq.observe(mr.seq):
                self.suppressed += 1
                continue
            self.events_seen += 1
            if self.log_events:
                # rabbitmq.go:170's util.Info.Printf of the result
                log.info(
                    "match %s: taker=%s maker=%s qty=%d",
                    "CANCEL" if mr.is_cancel else "FILL",
                    mr.node.oid,
                    mr.match_node.oid,
                    mr.match_volume,
                )
            ev = match_result_to_pb(mr)
            for q in subs:
                q.put(ev)

    def drain(self) -> int:
        total = 0
        while self.bus.match_queue.committed() < self.bus.match_queue.end_offset():
            total += self.run_once()
        return total

    def seq_state(self) -> dict:
        """Exactly-once state for /durability."""
        return {**self.seq.state(), "suppressed": self.suppressed}

    def subscribe(self, context=None):
        """Generator of pb.MatchEvent for one subscriber (gateway streaming
        handler). Ends when the gRPC context goes inactive or the feed
        stops."""
        q: queue.Queue = queue.Queue()
        with self._lock:
            self._subs.append(q)
        try:
            while not self._stop.is_set():
                if context is not None and not context.is_active():
                    return
                try:
                    ev = q.get_nowait()
                except queue.Empty:
                    # Only an empty queue opens a span: time outside
                    # stream_wait is gRPC serialising and sending.
                    with span("stream_wait"):
                        try:
                            ev = q.get(timeout=0.1)
                        except queue.Empty:
                            continue
                yield ev
        finally:
            with self._lock:
                self._subs.remove(q)

    # -- background loop -----------------------------------------------------
    def start(self) -> None:
        # Serialized with stop() under _life: the watchdog restarts a
        # dead feed from ITS thread while an operator (or service
        # shutdown) may be starting/stopping it from another — without
        # the lock two start() calls can both pass the None check and
        # spawn two fan-out loops (double delivery, lost joins).
        with self._life:
            if self._thread is not None:
                raise RuntimeError("feed already started")
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="match-feed", daemon=True
            )
            self._thread.start()

    def _loop(self) -> None:
        from ..utils.resilience import backoff_delays
        from .consumer import FAULT_BACKOFF

        delays = None  # backoff across consecutive failures (dead bus)
        while not self._stop.is_set():
            try:
                self.run_once()
                delays = None
            except Exception:
                log.exception("match feed batch failed")
                if delays is None:
                    delays = backoff_delays(FAULT_BACKOFF)
                self._stop.wait(next(delays, FAULT_BACKOFF.max_s))
        self._poll.close()

    def stop(self) -> None:
        # The feed loop never takes _life, so joining under it cannot
        # deadlock; concurrent stop()s serialize harmlessly.
        with self._life:
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=10)
                self._thread = None
