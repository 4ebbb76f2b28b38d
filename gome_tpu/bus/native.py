"""ctypes binding for the native (C++) file-log queue backend.

`NativeFileQueue` is drop-in interchangeable with the Python `FileQueue` —
same Queue interface AND the same on-disk format, so a directory written by
one can be reopened by the other (tested both directions). Selected via
bus.backend = "cfile"; falls back to the Python backend with a warning when
the native library cannot be built (no toolchain).

Native additions over the Python backend: `publish_batch` amortizes one
write+fsync over a whole micro-batch of events (the consumer publishes all
of a batch's MatchResults in one call), and the record scan/read paths run
without interpreter overhead.
"""

from __future__ import annotations

import ctypes
import os
import threading

from ..utils.tracing import span
from .base import Message, Queue, _Waitable

_lib = None
_lib_err: str | None = None


def _load():
    global _lib, _lib_err
    if _lib is not None or _lib_err is not None:
        return _lib
    try:
        import importlib.util

        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        build_py = os.path.join(repo_root, "native", "build.py")
        spec = importlib.util.spec_from_file_location(
            "gome_tpu._native_build", build_py
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        path = mod.build()
        if path is None:
            raise OSError("g++ unavailable or compile failed")
        lib = ctypes.CDLL(path)
        lib.gq_open.restype = ctypes.c_void_p
        lib.gq_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.gq_close.argtypes = [ctypes.c_void_p]
        lib.gq_publish_batch.restype = ctypes.c_int64
        lib.gq_publish_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_ubyte),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32,
        ]
        lib.gq_end_offset.restype = ctypes.c_int64
        lib.gq_end_offset.argtypes = [ctypes.c_void_p]
        lib.gq_committed.restype = ctypes.c_int64
        lib.gq_committed.argtypes = [ctypes.c_void_p]
        lib.gq_read_from.restype = ctypes.c_int64
        lib.gq_read_from.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_ubyte),
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        for name in ("gq_commit", "gq_rollback", "gq_truncate_to"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        _lib = lib
    except Exception as e:  # pragma: no cover - environment-specific
        _lib_err = str(e)
    if _lib is None:
        # Once per process (the outcome is memoized): every caller falls
        # back to pure Python — same behaviour, ~10x the host time per
        # order on the frame path — and should not do so without a word.
        from ..utils.logging import get_logger

        get_logger("native").warning(
            "native library unavailable (%s): pure-Python fallbacks in use",
            _lib_err,
        )
    return _lib


def native_available() -> bool:
    return _load() is not None


class NativeFileQueue(_Waitable, Queue):
    def __init__(self, name: str, path_base: str, fsync: bool = True):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native queue unavailable: {_lib_err}")
        self.name = name
        self._lib = lib
        os.makedirs(os.path.dirname(path_base) or ".", exist_ok=True)
        self._h = lib.gq_open(path_base.encode(), 1 if fsync else 0)
        if not self._h:
            raise RuntimeError(f"gq_open failed for {path_base}")
        self._lock = threading.Lock()
        # FileQueue's rule (bus.filelog): True while this object's last
        # write to the log was an append, so that it is the log's writer.
        self._wrote = False  # single-writer: the publisher, or recovery
        self._init_wait()

    def _hears_publisher(self) -> bool:
        return self._wrote

    def _handle(self):
        """The open native handle; raises (instead of passing NULL into C,
        which would segfault) if the queue was closed. Serialization of the
        actual operations happens in the C library (Queue::mu); the Python
        lock exists only to make close() atomic vs this check. Contract (as
        for the Python backend): stop consumers before close() — a call
        racing close() may still reach a freed handle."""
        with self._lock:
            h = self._h
        if not h:
            raise ValueError(f"queue {self.name!r} is closed")
        return h

    # -- Queue interface -----------------------------------------------------
    def publish(self, body: bytes) -> int:
        return self.publish_batch([body])

    def publish_batch(self, bodies: list[bytes]) -> int:
        """Append many records with ONE write+fsync; returns the offset of
        the first. (The native fast path the Python backend lacks.)"""
        blob = b"".join(bodies)
        n = len(bodies)
        lengths = (ctypes.c_uint32 * n)(*[len(b) for b in bodies])
        buf = (ctypes.c_ubyte * len(blob)).from_buffer_copy(blob)
        first = self._lib.gq_publish_batch(self._handle(), buf, lengths, n)
        if first < 0:
            raise OSError("native publish failed")
        self._wrote = True
        self._notify_publish(int(first), n)
        return int(first)

    def read_from(self, offset: int, max_n: int) -> list[Message]:
        # Only a read that returns messages opens a span (bus.filelog): the
        # index says whether this one will.
        if max_n <= 0 or self._lib.gq_end_offset(self._handle()) <= offset:
            return []
        with span("log_read", queue=self.name) as reading:
            out, payload = self._read(offset, max_n)
            reading.note(messages=len(out), bytes=payload + 4 * len(out))
        return out

    def _read(self, offset: int, max_n: int) -> tuple[list[Message], int]:
        """The messages and their bodies' bytes together."""
        cap = 1 << 16
        while True:
            bodies = (ctypes.c_ubyte * cap)()
            lengths = (ctypes.c_uint32 * max_n)()
            n = self._lib.gq_read_from(
                self._handle(), offset, max_n, bodies, cap, lengths
            )
            if n == -2:
                raise OSError(
                    f"native read I/O error on queue {self.name!r} (log "
                    "file unreadable)"
                )
            if n >= 0:
                out = []
                pos = 0
                for i in range(n):
                    ln = lengths[i]
                    out.append(
                        Message(
                            offset=offset + i,
                            body=bytes(bodies[pos : pos + ln]),
                        )
                    )
                    pos += ln
                return out, pos
            cap *= 4  # n == -1: caller buffer too small; grow and retry
            if cap > 1 << 30:
                raise OSError("native read: record set exceeds 1 GiB buffer")

    def end_offset(self) -> int:
        return int(self._lib.gq_end_offset(self._handle()))

    def committed(self) -> int:
        return int(self._lib.gq_committed(self._handle()))

    def commit(self, offset: int) -> None:
        with span("cursor_commit", queue=self.name, offset=offset):
            rc = self._lib.gq_commit(self._handle(), offset)
        if rc == -1:
            raise ValueError(
                f"commit out of range: {offset} (committed={self.committed()},"
                f" end={self.end_offset()})"
            )
        if rc != 0:
            raise OSError("native commit failed")

    def rollback(self, offset: int) -> None:
        with span("cursor_commit", queue=self.name, offset=offset):
            rc = self._lib.gq_rollback(self._handle(), offset)
        if rc == -1:
            raise ValueError(f"rollback going forwards: {offset}")
        if rc != 0:
            raise OSError("native rollback failed")

    def truncate_to(self, offset: int) -> None:
        if offset < self.end_offset():  # a tail goes: as FileQueue
            self._wrote = False  # proves itself again at its next append
        rc = self._lib.gq_truncate_to(self._handle(), offset)
        if rc == -1:
            raise ValueError(f"cannot truncate below committed: {offset}")
        if rc != 0:
            raise OSError("native truncate failed")

    def close(self) -> None:
        with self._lock:
            if self._h:
                self._lib.gq_close(self._h)
                self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
