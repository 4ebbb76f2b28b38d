"""Snapshot store + the Persister that wires it into the service loop.

A snapshot is a CUT of the engine (engine.batch.BookCut, BatchEngine.take_cut): the book stack as
it stood right after one frame's last dispatch, taken by the consumer's
thread without waiting for the device and whatever is in flight, with the
bus cursors of that frame beside it. Carrying it to disk (the transfer from
the device, the files, the fsyncs) is the Persister's writer thread's.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time

import numpy as np

from ..utils import tracing
from ..utils.faults import FAULTS
from ..utils.logging import get_logger
from ..utils.tracing import span

log = get_logger("persist")

_MANIFEST = "manifest.json"
_BOOKS = "books.npz"
#: The interners whose tables a snapshot needs, and the per-lane vectors a
#: version 2 snapshot keeps beside the books (a version 1 manifest has them
#: as JSON lists).
_INTERNERS = ("symbols", "oids", "uids")
_LANES = ("price_base", "base_set", "env_lo", "env_hi")


def _id_blob(strings) -> bytes:
    """Strings (str, or bytes already encoded) as 4-byte little-endian
    length + bytes each: the id files' format, and the native interner's."""
    parts = []
    for s in strings:
        b = s if isinstance(s, bytes) else s.encode()
        parts.append(len(b).to_bytes(4, "little"))
        parts.append(b)
    return b"".join(parts)


def _id_strings(blob: bytes, n: int) -> list[str]:
    out, pos = [], 0
    for _ in range(n):
        ln = int.from_bytes(blob[pos : pos + 4], "little")
        pos += 4
        out.append(blob[pos : pos + ln].decode())
        pos += ln
    if len(out) != n or pos != len(blob):
        raise ValueError("id file does not hold what the manifest says")
    return out


class SnapshotStore:
    """Atomic, versioned snapshot directory.

    Layout: <dir>/snap-<n>/ containing manifest.json (everything JSON-able:
    cursors, pre-pool, geometry) + books.npz (the array state), and beside
    them <dir>/ids.<interner>: every string an interner has handed an id, in
    id order, appended to at each snapshot (the interners only grow) and
    fsynced before the manifest that records how far it reaches. So a
    snapshot costs what the venue holds plus what is new since the last
    one, never what the process has admitted since it started.

    A snapshot is written to a temp dir then os.rename'd — a crash mid-write
    leaves no torn snapshot, and restore picks the newest directory with a
    valid manifest ("DONE" marker is the manifest itself, written last).
    Bytes of an id file past the restored manifest's mark (a snapshot whose
    manifest never landed) are cut off by the next save.
    """

    def __init__(self, directory: str, keep: int = 4):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        #: interner -> [strings, bytes] of its id file that the newest
        #: snapshot of this lineage vouches for (save and load move it).
        self.ids = {name: [0, 0] for name in _INTERNERS}  # single-writer: the save()/load_latest() caller, never both at once
        # what the last save wrote, id files included
        self.last_bytes = 0  # single-writer: the save() caller
        # the snapshot load_latest last took ("snap-<n>")
        self.loaded = ""  # single-writer: the load_latest() caller

    def _ids(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("snap-"):
                try:
                    out.append(int(name.split("-", 1)[1]))
                except ValueError:
                    continue
        return sorted(out)

    def _id_path(self, name: str) -> str:
        return os.path.join(self.dir, "ids." + name)

    def _append_ids(self, new_ids: dict) -> tuple[dict, int]:
        """Append each interner's new strings to its id file, fsynced;
        returns ({interner: [strings, bytes]} as the manifest records it,
        bytes written)."""
        written = 0
        for name, strings in new_ids.items():
            count, size = self.ids[name]
            path = self._id_path(name)
            blob = _id_blob(strings)
            with open(path, "r+b" if os.path.exists(path) else "w+b") as f:
                f.truncate(size)
                f.seek(size)
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            self.ids[name] = [count + len(strings), size + len(blob)]
            written += len(blob)
        return {name: list(mark) for name, mark in self.ids.items()}, written

    def save(self, manifest: dict, books: dict[str, np.ndarray],
             new_ids: dict | None = None) -> str:
        """`new_ids`: {interner: the strings it has interned since the last
        save}; with it the manifest records the id files' reach ("ids")."""
        written = 0
        if new_ids is not None:
            manifest = dict(manifest)
            manifest["ids"], written = self._append_ids(new_ids)
        ids = self._ids()
        snap_id = (ids[-1] + 1) if ids else 0
        final = os.path.join(self.dir, f"snap-{snap_id}")
        tmp = tempfile.mkdtemp(prefix=".tmp-snap-", dir=self.dir)
        try:
            books_path = os.path.join(tmp, _BOOKS)
            np.savez(books_path, **books)
            with open(books_path, "rb+") as f:
                os.fsync(f.fileno())
            # manifest last: its presence marks the snapshot complete
            mpath = os.path.join(tmp, _MANIFEST)
            with open(mpath, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            self.last_bytes = (written + os.path.getsize(books_path)
                               + os.path.getsize(mpath))
            cut = FAULTS.fire("snapshot.rename")
            if cut:
                # Torn publish: truncate the manifest inside tmp, complete
                # the rename anyway, and die — load_latest must skip the
                # unreadable snapshot and fall back to the previous one.
                with open(mpath, "rb+") as f:
                    f.truncate(cut % os.path.getsize(mpath))
                os.rename(tmp, final)
                FAULTS.hard_exit()
            os.rename(tmp, final)
            # fsync the parent dir so the rename itself survives power loss
            dirfd = os.open(self.dir, os.O_RDONLY)
            try:
                os.fsync(dirfd)
            finally:
                os.close(dirfd)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._prune()
        return final

    def _prune(self) -> None:
        ids = self._ids()
        for old in ids[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.dir, f"snap-{old}"), ignore_errors=True
            )

    def _read_ids(self, marks: dict) -> dict[str, list[str]]:
        out = {}
        for name, (count, size) in marks.items():
            blob = b""
            if size:
                with open(self._id_path(name), "rb") as f:
                    blob = f.read(size)
            out[name] = _id_strings(blob, count)
        return out

    def load_latest(self) -> tuple[dict, dict[str, np.ndarray]] | None:
        """Newest snapshot with a valid manifest, or None: (manifest,
        arrays). A version 2 manifest comes back with its interners' tables
        read from the id files under their names, as a version 1 manifest
        holds them, and the store's marks moved to that snapshot's."""
        for snap_id in reversed(self._ids()):
            path = os.path.join(self.dir, f"snap-{snap_id}")
            try:
                with open(os.path.join(path, _MANIFEST)) as f:
                    manifest = json.load(f)
                with np.load(os.path.join(path, _BOOKS)) as z:
                    books = {k: z[k] for k in z.files}
                if "ids" in manifest:
                    manifest.update(self._read_ids(manifest["ids"]))
                    self.ids = {name: list(manifest["ids"][name])
                                for name in _INTERNERS}
                self.loaded = os.path.basename(path)
                return manifest, books
            except Exception as e:  # torn npz raises BadZipFile etc.; any
                # unreadable snapshot must fall back to the previous one
                log.warning("skipping unreadable snapshot %s: %s", path, e)
        return None


class _Cut:
    """A cut on its way to disk: the engine's (BookCut), the pre-pool's
    marks and the interners' new strings as the consumer's thread took them
    at the same instant, and the bus cursors of the cut's frame, known once
    that frame has committed."""

    __slots__ = ("books", "pre_pool", "new_ids", "ids_before", "end_offset",
                 "cursors")

    def __init__(self, books, pre_pool, new_ids, ids_before, end_offset):
        self.books = books
        self.pre_pool = pre_pool
        self.new_ids = new_ids
        self.ids_before = ids_before
        self.end_offset = end_offset
        self.cursors = None


class Persister:
    """Service-loop integration: cadence counting, the cut, the writer
    thread, restore + replay rewind. Attach via EngineService(persist=...).

    `every_n_batches` counts committed frames (commits of the order queue),
    pipeline empty or not. The consumer tells the Persister when a frame has
    been dispatched (on_dispatch) and when one has committed (on_batch); the
    frame that brings the count to the cadence is cut right after its last
    dispatch, the cut's cursors are noted when that frame commits, and the
    writer thread carries it to disk. At most one cut is on its way: a tick
    that finds the writer busy takes no cut and is counted."""

    def __init__(self, config):
        """config: gome_tpu.config.PersistConfig."""
        self.store = SnapshotStore(config.dir, keep=config.keep)
        self.every_n = config.every_n_batches
        self.engine = None  # MatchEngine  # single-writer: attach() caller
        self.bus = None  # single-writer: attach() caller
        self.consumer = None  # single-writer: attach() caller (matchfeed seq recovery)
        self._since = 0  # commits since the last cut  # single-writer: the consuming thread
        self._pending: _Cut | None = None  # cut, frame not committed yet  # single-writer: the consuming thread
        self._tick_counted = False  # single-writer: the consuming thread
        #: interner -> strings already in a cut (the next cut takes the rest)
        self._ids_cut = {name: 0 for name in _INTERNERS}  # single-writer: the consuming thread
        # The writer: one job at a time, handed over under _cond.
        self._cond = threading.Condition()
        self._job: _Cut | None = None  # guarded by self._cond
        self._writing = False  # guarded by self._cond
        self._writer: threading.Thread | None = None  # guarded by self._cond
        self._last_path = ""  # guarded by self._cond
        self._write_error: BaseException | None = None  # guarded by self._cond
        self.restored = False  # single-writer: restore_latest() caller
        # Durability telemetry (/durability payload, gome_* gauges, the
        # timeline probe). Written from the thread named; the ops HTTP
        # thread reads it off-lock (floats and small ints are
        # single-bytecode loads — stale at worst, never torn).
        self.snapshots_taken = 0  # single-writer: the writer thread
        self.snapshots_skipped = 0  # single-writer: the consuming thread
        self.cuts_discarded = 0  # single-writer: the consuming thread
        self.last_snapshot_unix = 0.0  # single-writer: the writer thread
        self.last_snapshot_bytes = 0  # single-writer: the writer thread
        self.last_restore = "never"  # single-writer: restore_latest() caller
        self.last_recovery_seconds = 0.0  # single-writer: restore_latest() caller
        self.restored_bytes = 0  # single-writer: restore_latest() caller
        self.wal_replay_frames = 0  # single-writer: restore_latest() caller
        # The replay the last restore left to the consumer: where it ends,
        # when it began, what it has applied (on_batch closes it).
        self._replay_to = 0  # single-writer: restore_latest(), then the consuming thread
        self._replay_t0 = 0  # single-writer: restore_latest() caller
        self._replay_orders = 0  # single-writer: the consuming thread
        self.last_replay_seconds = 0.0  # single-writer: the consuming thread

    def attach(self, engine, bus, consumer=None) -> None:
        self.engine = engine
        self.bus = bus
        if consumer is not None:
            self.consumer = consumer
            consumer.on_batch = self.on_batch
            consumer.on_dispatch = self.on_dispatch

    # -- called by OrderConsumer ---------------------------------------------
    def on_dispatch(self, end_offset: int, in_flight: int) -> None:
        """The frame whose commit will move the order queue's offset to
        `end_offset` has had its last dispatch; `in_flight` frames, that one
        among them, are dispatched and not committed. If it is the frame
        that brings the commits since the last cut to the cadence, the
        books are cut here, behind it."""
        if self._pending is not None or self._since + in_flight < self.every_n:
            return
        with self._cond:
            busy = self._writing or self._job is not None
        if busy:
            if not self._tick_counted:
                self._tick_counted = True
                self.snapshots_skipped += 1
            return
        with span("snapshot_cut"):
            self._pending = self._cut(end_offset)

    def on_batch(self, n_orders: int, n_events: int) -> None:
        """A frame's events are published and its offset committed."""
        self._since += 1
        committed = self.bus.order_queue.committed()
        cut = self._pending
        if cut is not None and committed >= cut.end_offset:
            self._pending = None
            if (committed == cut.end_offset
                    and self.engine.batch.cut_is_current(cut.books)):
                self._since = 0
                self._tick_counted = False
                self._hand_over(cut)
            else:
                # The engine was rewound under the cut (a frame re-run on
                # the exact path, an aborted span): its books are not
                # those of its frame. The next frame is cut instead.
                self.cuts_discarded += 1
                self._ids_cut = cut.ids_before
        if self._replay_to:
            self._replay_orders += n_orders
            if committed >= self._replay_to:
                self._replay_done()

    def _cut(self, end_offset: int) -> _Cut:
        books = self.engine.batch.take_cut()
        # The gateway thread mutates pre_pool concurrently; retry the copy on
        # the (tiny) window where iteration observes a mutation. Marks of
        # orders published after the cut are reconciled from the order log
        # on restore.
        pool = self.engine.pre_pool
        freeze = getattr(pool, "frozen", None)
        for _ in range(100):
            try:
                pre_pool = freeze() if freeze is not None else list(pool)
                break
            except RuntimeError:
                continue
        else:
            raise RuntimeError(
                "could not copy pre_pool after 100 attempts (pathological "
                "concurrent marking); snapshot aborted"
            )
        before = self._ids_cut
        new_ids = {
            name: getattr(self.engine.batch, name).since(before[name] + 1)
            for name in _INTERNERS
        }
        self._ids_cut = {
            name: before[name] + len(new_ids[name]) for name in _INTERNERS
        }
        return _Cut(books, pre_pool, new_ids, before, end_offset)

    def _cursors(self) -> dict:
        return {
            "order_committed": self.bus.order_queue.committed(),
            "match_end": self.bus.match_queue.end_offset(),
            # Matchfeed seq at the cut: every event below match_end carries
            # a seq below this (exactly-once suppression after restore).
            "match_seq": (
                self.consumer.match_seq if self.consumer is not None else 0
            ),
        }

    def _hand_over(self, cut: _Cut) -> None:
        cut.cursors = self._cursors()
        with self._cond:
            if self._writer is None or not self._writer.is_alive():
                self._writer = threading.Thread(
                    target=self._write_loop, name="snapshot-writer",
                    daemon=True,
                )
                self._writer.start()
            self._job = cut
            self._cond.notify_all()

    # -- the writer thread ---------------------------------------------------
    def _write_loop(self) -> None:
        while True:
            with self._cond:
                while self._job is None:
                    self._cond.wait()
                cut, self._job = self._job, None
                self._writing = True
            error = None
            path = ""
            try:
                path = self._write(cut)
            except BaseException as e:  # the venue goes on; the next cut retries
                error = e
                log.exception("snapshot write failed")
            del cut  # the device's copy of the books, and the host's
            with self._cond:
                self._writing = False
                self._last_path, self._write_error = path, error
                self._cond.notify_all()
            if error is not None and not isinstance(error, Exception):
                raise error  # not a failed write: the thread is told to end

    def _write(self, cut: _Cut) -> str:
        with span("snapshot_write") as wrote:
            arrays = cut.books.arrays()  # waits for the device's copy
            manifest = {
                "version": 2,
                **cut.cursors,
                "pre_pool": sorted(cut.pre_pool),
                **cut.books.meta,
            }
            path = self.store.save(manifest, arrays, cut.new_ids)
            wrote.note(bytes=self.store.last_bytes)
        self.snapshots_taken += 1
        self.last_snapshot_unix = time.time()
        self.last_snapshot_bytes = self.store.last_bytes
        log.info(
            "snapshot %s (orders<%d, matches<%d, %d bytes)",
            os.path.basename(path),
            manifest["order_committed"],
            manifest["match_end"],
            self.last_snapshot_bytes,
        )
        return path

    def wait(self, timeout_s: float | None = None) -> bool:
        """Until the writer has nothing on its way (shutdown, tests); False
        where that took longer than `timeout_s`."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        with self._cond:
            while self._writing or self._job is not None:
                left = None if deadline is None else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cond.wait(left)
        return True

    def snapshot(self) -> str:
        """Cut now, then wait for the writer: the same cut and the same
        write as the cadence's. Must run from the consumer thread or with
        the consumer idle and nothing in flight: the cursors are read here,
        and 'books == orders below the committed offset' only holds then."""
        self.wait()
        self._pending = None
        cut = self._cut(self.bus.order_queue.committed())
        self._since = 0
        self._hand_over(cut)
        self.wait()
        with self._cond:
            if self._write_error is not None:
                raise self._write_error
            return self._last_path

    def restore_latest(self) -> bool:
        """Restore books + pre-pool and rewind the bus to the snapshot cut.
        After this, the NORMAL consumer loop replays the order-log tail
        deterministically, regenerating the truncated match-queue tail
        exactly (see package docstring). Returns True if a snapshot was
        applied."""
        t0 = time.monotonic()
        with span("recover_restore") as restoring:
            loaded = self._restore()
            restoring.note(bytes=self.restored_bytes,
                           frames=self.wal_replay_frames)
        self.last_recovery_seconds = time.monotonic() - t0
        replayed = self.wal_replay_frames
        self.last_restore = (
            "restored"
            if loaded
            else ("replayed" if replayed else "none")
        )
        self._replay_to = self.bus.order_queue.end_offset() if replayed else 0
        self._replay_t0 = time.monotonic_ns()
        self._replay_orders = 0
        if loaded and not replayed:
            self._replay_done()
        return loaded

    def _replay_done(self) -> None:
        """The consumer has committed the last frame the restore left it
        (or there was none): `recover_replay`, from the end of the restore
        to here, and the one line that says what the boot cost."""
        wall_ns = time.monotonic_ns() - self._replay_t0
        if self._replay_to:
            tracing.record("recover_replay", wall_ns)
            self.last_replay_seconds = wall_ns / 1e9
        self._replay_to = 0
        log.warning(
            "recovery: snapshot=%s, %d bytes restored in %.3f s; %d frames "
            "(%d orders) of the order log replayed in %.3f s",
            self.store.loaded if self.restored else "none",
            self.restored_bytes,
            self.last_recovery_seconds, self.wal_replay_frames,
            self._replay_orders, self.last_replay_seconds,
        )

    def _restore(self) -> bool:
        loaded = self.store.load_latest()
        oq = self.bus.order_queue
        mq = self.bus.match_queue
        # The pre-crash consumer position: tail messages below it were
        # consumed by the crashed process (their effects may have been
        # observable), messages at/above it never were.
        consumed_to = oq.committed()
        if loaded is not None:
            manifest, arrays = loaded
            state = dict(manifest)
            state["books"] = {
                k: v for k, v in arrays.items() if k not in _LANES
            }
            # version 2 keeps the per-lane vectors beside the books
            state.update({k: arrays[k] for k in _LANES if k in arrays})
            self.engine.batch.import_state(state)
            self.restored_bytes = sum(a.nbytes for a in arrays.values()) + sum(
                size for _count, size in manifest.get("ids", {}).values())
            self._ids_cut = {
                name: manifest["ids"][name][0] if "ids" in manifest else 0
                for name in _INTERNERS
            }
            # In place, not reassignment: the pool object may be a shared
            # remote marker store (prepool.RespPrePool) the gateway also
            # holds.
            self.engine.pre_pool.clear()
            self.engine.pre_pool.update(tuple(k) for k in manifest["pre_pool"])
            # The snapshot is the authority on the cut. Normally the cut is
            # at/below the committed offset (rollback); after a TORN
            # .offset sidecar the recovered committed offset can sit BELOW
            # the cut (FileQueue falls back to a conservative digit
            # prefix) — the snapshot proves orders below the cut are fully
            # applied, so seek forward instead of replaying them onto
            # restored books (found by scripts/chaos.py's torn-sidecar
            # schedule).
            cut = manifest["order_committed"]
            if cut > oq.end_offset():
                # The snapshots outlived their log (it was lost, or the
                # directory was given a new one): the books are all there
                # is. Take them and consume the log that is there from its
                # end; what the old log held past the cut is gone with it.
                log.warning(
                    "snapshot cut at order offset %d lies past the end of "
                    "the order log (%d): the log was lost or replaced; "
                    "booting on the snapshot's books, the order cursor "
                    "starts at the log's end",
                    cut, oq.end_offset(),
                )
                cut = oq.end_offset()
            if cut <= oq.committed():
                oq.rollback(cut)
            else:
                oq.commit(cut)
            # The feed may have committed past the cut before the crash;
            # replay regenerates byte-identical events, so rewind its cursor
            # and drop the stale tail.
            mq.rollback(min(mq.committed(), manifest["match_end"]))
            mq.truncate_to(manifest["match_end"])
            if self.consumer is not None:
                # Replay regenerates the truncated match tail with the
                # SAME seqs it had pre-crash (exactly-once across restarts).
                self.consumer.reset_seq(int(manifest.get("match_seq", 0)))
            self.restored = True
        elif oq.committed() > 0 or mq.end_offset() > 0:
            # Durable order log but no snapshot yet (crash before the first
            # cadence tick): the engine is fresh/empty, so the only
            # consistent cut is offset 0 — rewind and replay the ENTIRE log;
            # the truncated match queue is regenerated deterministically.
            # The mq conditions cover a crash BEFORE the first order-queue
            # commit but AFTER a match publish (the at-least-once window at
            # offset 0): without truncation the replay would re-publish
            # those events as queue-level duplicates (found by
            # scripts/chaos.py's first-frame kill).
            oq.rollback(0)
            mq.rollback(0)
            mq.truncate_to(0)
            if self.consumer is not None:
                self.consumer.reset_seq(0)
        self.wal_replay_frames = self._reconstruct_marks(
            cut=oq.committed(), consumed_to=consumed_to
        )
        return loaded is not None

    def _reconstruct_marks(self, cut: int, consumed_to: int) -> int:
        """Rebuild pre-pool marks for ADDs queued at/after `cut` (they were
        marked in the crashed process's memory: the gateway marks BEFORE
        publishing, main.go:44-45 ordering — so every queued ADD carried a
        mark).

        One refinement separates two cases by `consumed_to` (the pre-crash
        consumer position):

        * ADD consumed pre-crash (offset < consumed_to): its admission
          decision may already be observable (fills delivered to live
          subscribers), so replay must re-admit — always re-mark. The
          realizable serialization: the mark was placed at publish time,
          after every DEL consumed before it.
        * ADD never consumed (offset >= consumed_to): no decision was made,
          so any realizable interleaving is valid; we choose NOT to re-mark
          when the key's latest committed message below the cut is a DEL —
          that DEL's cancel semantics were observable (event below
          match_end), and resurrecting a cancelled order would surprise
          (SURVEY §2.3.3's race, resolved deterministically at recovery).

        Residual ambiguity (documented, not resolvable from the log alone):
        a DEL *inside* the consumed tail followed by a same-key ADD replays
        as drop, while the crashed process may have raced to admit. Both
        outcomes are realizable serializations of the reference's racy
        pre-pool; eliminating the race entirely would need a durable mark
        log (fsync per gateway mark — rejected as the wrong latency trade).
        """
        from ..bus import decode_message_orders
        from ..types import Action

        def orders_in(m):
            # A frame's whole batch shares the message offset (it consumes
            # atomically), so the offset-based logic below is unchanged.
            return decode_message_orders(m.body)

        oq = self.bus.order_queue
        tail = oq.read_from(cut, oq.end_offset() - cut)
        suppressible = set()  # keys of never-consumed ADDs
        tail_adds: list[tuple[int, tuple]] = []
        for m in tail:
            for order in orders_in(m):
                if order.action is Action.ADD:
                    key = (order.symbol, order.uuid, order.oid)
                    tail_adds.append((m.offset, key))
                    if m.offset >= consumed_to:
                        suppressible.add(key)
        if not tail_adds:
            return len(tail)
        # Last committed action per suppressible key (recovery-only scan).
        last_committed: dict[tuple, Action] = {}
        pos = 0
        while pos < cut and suppressible:
            for m in oq.read_from(pos, min(4096, cut - pos)):
                for order in orders_in(m):
                    key = (order.symbol, order.uuid, order.oid)
                    if key in suppressible:
                        last_committed[key] = order.action
                pos = m.offset + 1
        remark = [
            key
            for offset, key in tail_adds
            if not (
                offset >= consumed_to
                and last_committed.get(key) is Action.DEL
            )
        ]
        # One batched update: with a remote marker store this is a single
        # pipelined round trip instead of one HSET per queued ADD (a tail
        # of 256K-order frames would otherwise take minutes to re-mark).
        self.engine.pre_pool.update(remark)
        return len(tail)

    # -- observability -------------------------------------------------------
    def snapshot_age_seconds(self) -> float:
        """Seconds since the last snapshot; -1 before the first one."""
        if not self.last_snapshot_unix:
            return -1.0
        return max(0.0, time.time() - self.last_snapshot_unix)

    def export_metrics(self, registry=None) -> None:
        """Register the durability gauges (callback gauges: values are read
        from this Persister at scrape time; re-registering rebinds)."""
        if registry is None:
            from ..utils.metrics import REGISTRY as registry  # noqa: N811
        registry.callback_gauge(
            "gome_snapshot_age_seconds",
            "Seconds since the last snapshot (-1 before the first)",
            self.snapshot_age_seconds,
        )
        registry.callback_gauge(
            "gome_snapshot_bytes",
            "On-disk size of the last snapshot",
            lambda: float(self.last_snapshot_bytes),
        )
        registry.callback_gauge(
            "gome_snapshots_taken_total",
            "Snapshots taken by this process",
            lambda: float(self.snapshots_taken),
        )
        registry.callback_gauge(
            "gome_snapshots_skipped_total",
            "Cadence ticks that found the writer busy and took no cut",
            lambda: float(self.snapshots_skipped),
        )
        registry.callback_gauge(
            "gome_recovery_seconds",
            "Duration of the last restore_latest (restore + mark rebuild)",
            lambda: self.last_recovery_seconds,
        )
        registry.callback_gauge(
            "gome_wal_replay_frames",
            "Order-log messages rewound for replay by the last restore",
            lambda: float(self.wal_replay_frames),
        )

    def probe(self) -> dict:
        """TimelineSampler probe: snapshot cadence + recovery state."""
        return {
            "snapshots_taken": self.snapshots_taken,
            "snapshots_skipped": self.snapshots_skipped,
            "snapshot_age_s": round(self.snapshot_age_seconds(), 3),
            "snapshot_bytes": self.last_snapshot_bytes,
            "last_restore": self.last_restore,
            "recovery_s": round(self.last_recovery_seconds, 6),
            "replay_s": round(self.last_replay_seconds, 6),
            "wal_replay_frames": self.wal_replay_frames,
        }
