"""Match-event feed — the reference's consume_match_order process
(consume_match_order.go:7-10 → rabbitmq.go:132-177): drains the
"matchOrder" queue, logs each MatchResult (rabbitmq.go:162-171), and — where
the reference leaves a "your code..." stub (rabbitmq.go:169) — fans events
out to in-process subscribers (the gateway's SubscribeMatches stream).

The unit handed from the feed thread to a subscriber is the match message
(one EVENT frame, or one run of JSON messages as a poll brought them): a
list of serialised MatchEvent messages, one per surviving event, put on the
subscriber's queue once. The wire stays one MatchEvent per gRPC message; the
handler's thread only yields bytes that are already made.

The feed reads ahead of its cursor. A match message is committed on the
match queue only when every live subscriber's handler has handed the whole
of it to gRPC (it was asked for what follows the message's last event); with
no subscriber, when it has been fanned out. So what a subscriber had not
been handed when the process died lies above the cursor of a durable match
queue, and the next process delivers it (again, where gRPC had it in flight:
at-least-once across a death, each seq once within a process).
"""

from __future__ import annotations

import logging
import queue
import threading

import numpy as np

from ..api import order_pb2 as pb
from ..bus import QueueBus, decode_match_result
from ..fixed import unscale
from ..types import MatchResult
from ..utils.logging import get_logger
from ..utils.metrics import REGISTRY
from ..utils import tracing
from ..utils.tracing import poll_span, span

log = get_logger("matchfeed")

_dupes_total = REGISTRY.counter(
    "gome_matchfeed_dupes_total",
    "duplicate matchfeed seqs observed (suppressed before fan-out)",
)
_gaps_total = REGISTRY.counter(
    "gome_matchfeed_gaps_total",
    "missing matchfeed seqs observed (events lost upstream)",
)
# events over hand-offs = the size of the unit the subscribers are handed:
# a frame's events on the frame wire, 1 where JSON messages come one by one.
_handoffs_total = REGISTRY.counter(
    "gome_matchfeed_handoffs_total",
    "match messages (EVENT frames, runs of JSON messages) fanned out",
)
_events_total = REGISTRY.counter(
    "gome_matchfeed_events_total",
    "match events fanned out (after duplicate suppression)",
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

    def observe_run(self, seqs: range) -> int:
        """A frame's consecutive seqs at once, with the counts that
        ``observe`` on each in turn gives: returns how many leading seqs
        were already seen (the caller delivers only the tail after them)."""
        n = len(seqs)
        if not n:
            return 0
        self.observed += n
        last = self.last_seq
        seen = 0
        if last is not None:
            seen = min(max(last - seqs.start + 1, 0), n)
            if seen:
                self.dupes += seen
                _dupes_total.inc(seen)
            elif seqs.start > last + 1:
                self.gaps += seqs.start - last - 1
                _gaps_total.inc(seqs.start - last - 1)
        if seen < n:
            self.last_seq = seqs[-1]
        return seen

    def state(self) -> dict:
        return {
            "last_seq": self.last_seq,
            "observed": self.observed,
            "dupes": self.dupes,
            "gaps": self.gaps,
        }


def _row_of(mr: MatchResult) -> tuple:
    """A MatchResult as the 13 fields of its MatchEvent, in wire order."""
    # Wire doubles carry the reference's observable values: the scaled
    # float64 (SURVEY §2.2 — events serialize post-scaling nodes).
    n, m = mr.node, mr.match_node
    return (
        n.uuid, n.oid, n.symbol, int(n.side),
        unscale(n.price), unscale(n.volume),
        m.uuid, m.oid, m.symbol, int(m.side),
        unscale(m.price), unscale(m.volume),
        float(mr.match_volume),
    )


def _frame_rows(batch) -> list[tuple]:
    """An EVENT frame's events as ``_row_of(mr)`` gives them for
    ``batch.to_results()``, without the objects: each column is read once,
    the id tables are indexed once, and a cancel's match_node is its node
    (engine.events.EventBatch.to_results is the reference; a test holds the
    two together field by field)."""
    c = batch.columns
    cancel, side = c["is_cancel"], c["taker_side"]

    def names(table, ids):
        return [table[i] for i in ids.tolist()]

    def maker(col, taker_col):
        return np.where(cancel, taker_col, c[col])

    def scaled(col):
        return list(map(unscale, col.tolist()))

    symbol = names(batch.symbols, c["symbol_id"])
    return list(zip(
        names(batch.uid_table, c["taker_uid"]),
        names(batch.oid_table, c["taker_oid"]),
        symbol,
        side.tolist(),
        scaled(c["taker_price"]),
        scaled(c["taker_volume"]),
        names(batch.uid_table, maker("maker_uid", c["taker_uid"])),
        names(batch.oid_table, maker("maker_oid", c["taker_oid"])),
        symbol,
        np.where(cancel, side, 1 - side).tolist(),
        scaled(maker("fill_price", c["taker_price"])),
        scaled(maker("maker_volume", c["taker_volume"])),
        map(float, np.where(cancel, 0, c["match_volume"]).tolist()),
    ))


def match_result_to_pb(mr) -> pb.MatchEvent:
    """The one place a MatchEvent is built, from a MatchResult or from the
    row of one (``_row_of``, ``_frame_rows``). The feed looks it up through
    the module at every call, once per delivered event, so whoever replaces
    this attribute sees, and may alter, everything that is delivered."""
    ev = pb.MatchEvent()
    n, m = ev.node, ev.match_node
    (n.uuid, n.oid, n.symbol, n.transaction, n.price, n.volume,
     m.uuid, m.oid, m.symbol, m.transaction, m.price, m.volume,
     ev.match_volume) = mr if type(mr) is tuple else _row_of(mr)
    return ev


class _Chunk(list):
    """One match message's serialised events for a subscriber: `match`, the
    message's match-queue offset (the first's, of a run of JSON messages);
    `end`, the offset that committing it would move the cursor to; `put_ns`,
    the instant the feed put it on the subscribers' queues (the handler's
    get ends the hand-off: subscriber_queue_dwell, utils.tracing)."""

    __slots__ = ("match", "end", "put_ns")


class _Subscription(queue.Queue):
    """One subscriber's queue of _Chunks, with how far it got: `given`, the
    `end` of the last chunk put on it (the feed thread's), and `handed`, the
    `end` of the last chunk whose every event its handler has handed to gRPC
    (the handler thread's). Equal: it holds nothing it was given."""

    def __init__(self):
        super().__init__()
        self.given = 0  # single-writer: the feed thread
        self.handed = 0  # single-writer: the subscriber's handler thread


class MatchFeed:
    def __init__(self, bus: QueueBus, log_events: bool = True):
        self.bus = bus
        self.log_events = log_events
        self._subs: list[_Subscription] = []  # guarded by self._lock
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
        # The read cursor: the next match message to fan out, at or ahead of
        # the queue's committed offset; and that offset as the feed last
        # saw or set it (someone else moving it, a restore's rollback, sends
        # the read cursor back to it).
        self._next = 0  # single-writer: the feed thread (run_once)
        self._committed: int | None = None  # single-writer: the feed thread

    def _sync(self) -> int:
        """The read cursor, after a look at the queue's committed offset."""
        committed = self.bus.match_queue.committed()
        if committed != self._committed:
            self._next = self._committed = committed
        return self._next

    def _commit_handed(self) -> None:
        """Move the queue's cursor up to the first message that some live
        subscriber has not handed to gRPC whole (to the read cursor where
        all have, or there is none)."""
        if self._next <= self._committed:
            return  # nothing fanned out that is not committed
        with self._lock:
            subs = list(self._subs)
        upto = self._next
        for sub in subs:
            handed = sub.handed
            if handed != sub.given:
                upto = min(upto, handed)
        if upto > self._committed:
            self.bus.match_queue.commit(upto)
            self._committed = upto

    def run_once(self) -> int:
        start = self._sync()
        msgs = self._poll.batch(self.bus.match_queue, 256, 0.002, 0.001, start)
        if not msgs:
            self._commit_handed()
            return 0
        from ..bus.colwire import decode_event_frame, is_frame

        with self._lock:
            subs = list(self._subs)
        i = 0
        while i < len(msgs):
            # One decode and one fan-out span per EVENT frame (one message =
            # a whole batch of MatchResults, bus.colwire) or per run of JSON
            # messages (one event each): never a span per event. `seqs` is a
            # range for a stamped frame, whose duplicates and gaps are
            # decided on its ends.
            j = i + 1
            match = msgs[i].offset
            with span("feed_decode", match=match):
                if is_frame(msgs[i].body):
                    batch = decode_event_frame(msgs[i].body)
                    rows = _frame_rows(batch)
                    seqs = (
                        None if batch.seq0 is None
                        else range(batch.seq0, batch.seq0 + len(rows))
                    )
                else:
                    while j < len(msgs) and not is_frame(msgs[j].body):
                        j += 1
                    results = [
                        decode_match_result(m.body) for m in msgs[i:j]
                    ]
                    rows = [_row_of(mr) for mr in results]
                    seqs = [mr.seq for mr in results]
            with span("feed_fanout", match=match, events=len(rows),
                      subscribers=len(subs)):
                self._fan_out(rows, seqs, subs, match, msgs[j - 1].offset + 1)
            i = j
        self._next = msgs[-1].offset + 1
        self._commit_handed()
        return len(msgs)

    def _fan_out(self, rows, seqs, subs, match: int, end: int) -> None:
        """One match message's events, as rows, to every subscriber as ONE
        queue item: the serialised MatchEvent of each event not seen before,
        in order. `match`: the message's match-queue offset; `end`: the
        offset past it."""
        n = len(rows)
        if type(seqs) is range:
            rows = rows[self.seq.observe_run(seqs):]
        elif seqs is not None:
            observe = self.seq.observe
            rows = [
                row for row, seq in zip(rows, seqs)
                if seq is None or observe(seq)
            ]
        self.suppressed += n - len(rows)
        if not rows:
            return
        self.events_seen += len(rows)
        _handoffs_total.inc()
        _events_total.inc(len(rows))
        # rabbitmq.go:170's util.Info.Printf of each result, for a
        # deployment that logs at INFO; the level is looked at once here.
        if self.log_events and log.isEnabledFor(logging.INFO):
            for row in rows:  # wire order: 1, 7 the oids, 12 the volume
                log.info(
                    "match %s: taker=%s maker=%s qty=%d",
                    "FILL" if row[12] else "CANCEL", row[1], row[7], row[12],
                )
        chunk = _Chunk(
            match_result_to_pb(row).SerializeToString() for row in rows
        )
        chunk.match, chunk.end = match, end
        chunk.put_ns = tracing._wall_ns()
        for sub in subs:
            sub.put(chunk)
            sub.given = end

    def drain(self) -> int:
        """Fan out everything on the match queue (its cursor follows as the
        subscribers take it, at once where there is none)."""
        total = 0
        while self._sync() < self.bus.match_queue.end_offset():
            total += self.run_once()
        return total

    def seq_state(self) -> dict:
        """Exactly-once state for /durability."""
        return {**self.seq.state(), "suppressed": self.suppressed}

    def subscribe(self, context=None):
        """Generator of serialised pb.MatchEvent messages (bytes) for one
        subscriber (the gateway's streaming handler sends them as they
        are). Ends when the gRPC context goes inactive or the feed stops;
        both are looked at once per queue item, not per event."""
        q = _Subscription()
        q.given = q.handed = self._next  # owed nothing from before it came
        with self._lock:
            self._subs.append(q)
        try:
            while not self._stop.is_set():
                if context is not None and not context.is_active():
                    return
                # The handler's two leaves: stream_wait while its queue is
                # empty, stream_send while gRPC takes a chunk from it. The
                # hand-off's dwell rides on whichever closes with the chunk.
                try:
                    chunk = q.get_nowait()
                except queue.Empty:
                    with span("stream_wait") as waited:
                        try:
                            chunk = q.get(timeout=0.1)
                        except queue.Empty:
                            continue
                        waited.note(match=chunk.match,
                                    dwell_us=self._picked_up(chunk))
                    dwell = None
                else:
                    dwell = self._picked_up(chunk)
                # One span per queue item, never per event: it closes when
                # the generator is resumed after the chunk's last event (gRPC
                # has the whole of it), or when the subscriber goes away.
                with span("stream_send", match=chunk.match,
                          events=len(chunk)) as sent:
                    if dwell is not None:
                        sent.note(dwell_us=dwell)
                    events = iter(chunk)
                    yield next(events)  # a chunk holds at least one event
                    first = tracing._wall_ns() - sent.t0_ns
                    yield from events
                    sent.note(first_us=first // 1000)
                # Asked for what follows the chunk's last event: gRPC has
                # the whole of it, and the feed may commit past it; it may
                # be asleep on its queue (_loop).
                q.handed = chunk.end
                self.bus.match_queue.wake()
        finally:
            with self._lock:
                self._subs.remove(q)

    @staticmethod
    def _picked_up(chunk: _Chunk) -> int:
        """A handler has taken `chunk` off its queue: the hand-off's dwell,
        recorded once per chunk and subscriber; returns it in microseconds."""
        dwell = tracing._wall_ns() - chunk.put_ns
        tracing.record("subscriber_queue_dwell", dwell)
        return dwell // 1000

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
        q = self.bus.match_queue
        while not self._stop.is_set():
            try:
                # Looked up on the instance at every pass: a harness may
                # have put its own run_once there. One that brought nothing
                # is followed by a sleep until the queue has a message past
                # the read cursor (at once if one stands there: a wrapper
                # may hold them back), a handler's hand-over or stop() wakes
                # the feed, or the span's bound has passed; the next pass
                # commits what was handed over meanwhile.
                if self.run_once() == 0:
                    self._poll.idle(q, self._sync())
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
            self.bus.match_queue.wake()  # the loop may sleep on its queue
            if self._thread is not None:
                self._thread.join(timeout=10)
                self._thread = None
