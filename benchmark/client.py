"""The load client: sender and subscriber, in a process that imports no JAX.

Orders go in through DoOrderBatch, pre-serialised; events come back on
SubscribeMatches and are kept raw with a CLOCK_MONOTONIC stamp each. The
generator's reference gives every request's cumulative event count and events
reach a subscriber in admission order, so request k is complete when that many
events have arrived and it was acknowledged: no marker from the program is
needed.

Requests enter the gateway one at a time, in stream order: a released request
goes out the moment the previous one is acknowledged (at once when none is
out). That is a session's ordering; gRPC gives none between concurrent calls,
and a cancel that overtook its own add would legitimately change the result.
Acknowledgement follows admission, not matching, so several requests can be
admitted and not yet complete.
"""

from __future__ import annotations

import collections
import sys
import threading
import time

from . import wire

now_ns = time.monotonic_ns
STALL_POLL_S = 2.0  # how often a silent serving process is asked for progress


class Sender:
    def __init__(self, requests: list, response_type):
        self.requests = requests
        self.response_type = response_type
        self.reset()
        self.lock = threading.Lock()
        self.call = None
        self.on_ack = None

    def reset(self) -> None:
        n = len(self.requests)
        self.release_ns = [0] * n  # when the loop or the schedule let it go
        self.send_ns = [0] * n
        self.ack_ns = [0] * n
        self.accepted = [0] * n
        self.errors = 0
        self.unacked = 0
        self.queue = collections.deque()
        self.in_flight = False

    def connect(self, channel) -> None:
        self.call = channel.unary_unary(
            wire.DO_ORDER_BATCH, request_serializer=None,
            response_deserializer=self.response_type.FromString,
        )

    def send(self, k: int, release_ns: int | None = None) -> None:
        """Release request k: it goes out when its turn comes."""
        self.release_ns[k] = release_ns or now_ns()
        with self.lock:
            self.unacked += 1
            self.queue.append(k)
        self._pump()

    def _pump(self) -> None:
        # The call is made outside the lock: a future that is already done
        # runs its callback, and so _acked, on this very thread.
        with self.lock:
            if self.in_flight or not self.queue:
                return
            k = self.queue.popleft()
            self.in_flight = True
        self.send_ns[k] = now_ns()
        fut = self.call.future(self.requests[k], timeout=600)
        fut.add_done_callback(lambda f, k=k: self._acked(k, f))

    def _acked(self, k: int, fut) -> None:
        self.ack_ns[k] = now_ns()
        try:
            resp = fut.result()
            self.accepted[k] = resp.accepted
            if resp.code or resp.reject_index:
                self.errors += 1
        except Exception as e:  # noqa: BLE001 - an RPC failure is a result
            self.errors += 1
            print(f"client: request {k} failed: {e!r}", file=sys.stderr)
        with self.lock:
            self.unacked -= 1
            self.in_flight = False
        if self.on_ack is not None:
            self.on_ack(k, self.ack_ns[k])
        self._pump()

    def wait_acked(self, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        while self.unacked and time.monotonic() < deadline:
            time.sleep(0.002)
        return not self.unacked


class Subscriber(threading.Thread):
    """Reads SubscribeMatches, keeps every event raw with its arrival stamp,
    and calls `on_target(n, t_ns)` when the count reaches `target`."""

    def __init__(self, channel):
        super().__init__(name="bench-subscriber", daemon=True)
        self.stream = channel.unary_stream(
            wire.SUBSCRIBE, request_serializer=None,
            response_deserializer=None,
        )(b"")
        self.raw: list[bytes] = []
        self.stamps: list[int] = []
        self.target = 1 << 62
        self.on_target = None
        self.error = None

    def run(self) -> None:
        raw_append, stamp_append = self.raw.append, self.stamps.append
        n = 0
        try:
            for raw in self.stream:
                t = now_ns()
                raw_append(raw)
                stamp_append(t)
                n += 1
                if n >= self.target:
                    self.on_target(n, t)
        except Exception as e:  # noqa: BLE001 - cancelled at the end, or lost
            self.error = e

    def stop(self) -> None:
        self.stream.cancel()
        self.join(timeout=10)


class Loop:
    """Completion tracking and the closed loop: at most `outstanding` requests
    between release and completion, the next released when one completes."""

    def __init__(self, sender: Sender, sub: Subscriber, cum_events):
        self.sender, self.sub = sender, sub
        self.cum = [int(c) for c in cum_events]
        self.n_requests = len(self.cum)
        self.sent = self.done = self.acked = 0
        self.done_ns = [0] * self.n_requests
        self.outstanding = 0
        self.stop_at = 0
        self.longest_silence_s = 0.0  # events owed, none arriving
        self.lock = threading.Lock()
        sub.on_target = self._reached
        sub.target = self.cum[0] if self.cum else 1 << 62
        sender.on_ack = self._acked

    def _acked(self, k: int, t_ns: int) -> None:  # a gRPC callback thread
        """A request whose events have all arrived already (it made none
        that were not there) completes on its acknowledgement."""
        with self.lock:
            self.acked = k + 1
        self._reached(len(self.sub.stamps), t_ns)

    def _reached(self, n: int, t_ns: int) -> None:  # subscriber thread
        with self.lock:
            while True:
                before = self.done
                while (self.done < self.acked
                       and n >= self.cum[self.done]):
                    self.done_ns[self.done] = t_ns
                    self.done += 1
                self._top_up(t_ns)  # a request without events completes now
                if self.done == before:
                    break
            self.sub.target = (self.cum[self.done]
                               if self.done < self.n_requests else 1 << 62)

    def _top_up(self, t_ns: int) -> None:
        while (self.sent - self.done < self.outstanding
               and self.sent < self.stop_at):
            k = self.sent
            self.sent += 1
            self.sender.send(k, t_ns)

    def open(self, outstanding: int, stop_at: int | None = None) -> None:
        with self.lock:
            self.outstanding = outstanding
            self.stop_at = self.n_requests if stop_at is None else stop_at
            self._top_up(now_ns())

    def close(self) -> int:
        """No further releases; returns how many requests went out."""
        with self.lock:
            self.stop_at = self.sent
            return self.sent

    def released(self, k: int) -> None:
        """Request k goes out on the schedule's own clock (open loop)."""
        with self.lock:
            self.sent = max(self.sent, k + 1)

    def wait_done(self, k: int, timeout_s: float, alive=None,
                  stall_s: float | None = None, progress=None) -> bool:
        """Until requests below k are complete. False on the timeout, or when
        events are owed (acknowledged requests' counts not reached), none has
        arrived for stall_s and the serving process has not moved either: an
        event was lost, and the count a request waits for is never reached.
        `progress()` gives the serving process's own counters (orders, frames,
        lowerings, compiles); it is asked every STALL_POLL_S of silence only,
        so a process that compiles its programs for minutes before its first
        event, as the first run in a checkout does, is not taken for lost."""
        deadline = time.monotonic() + timeout_s
        seen, since = len(self.sub.stamps), time.monotonic()
        quiet_from, asked, mark = since, since, None
        while self.done < k:
            now = time.monotonic()
            if now > deadline:
                return False
            owed = self.acked and self.cum[self.acked - 1] > seen
            if len(self.sub.stamps) != seen or not owed:
                seen, since, quiet_from = len(self.sub.stamps), now, now
            else:
                self.longest_silence_s = max(self.longest_silence_s,
                                             now - quiet_from)
                if (progress is not None
                        and now - max(asked, quiet_from) > STALL_POLL_S):
                    moved = progress()  # the answer itself may take a while
                    now = asked = time.monotonic()
                    if moved != mark:
                        mark, since = moved, now
                if stall_s is not None and now - since > stall_s:
                    return False
            if alive is not None:
                alive()
            time.sleep(0.005)
        return True


def paced(loop: Loop, k0: int, n: int, t0_ns: int, interval_ns: int) -> list:
    """Request k0+i is due at t0 + i*interval and released then (at once when
    the schedule ran late), whatever became of the ones before; it is timed
    from when it was due. Returns the due times."""
    due = []
    for i in range(n):
        t = t0_ns + i * interval_ns
        due.append(t)
        while True:
            left = t - now_ns()
            if left <= 0:
                break
            if left > 300_000:  # sleep the bulk, spin the last stretch
                time.sleep((left - 200_000) / 1e9)
        loop.released(k0 + i)
        loop.sender.send(k0 + i)
    return due
