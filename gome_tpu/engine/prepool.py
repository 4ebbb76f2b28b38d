"""Pre-pool marker stores — the shared state between gateway and consumer.

The reference keeps the pre-pool in Redis so its three processes agree on
which ADDs are still live: the gateway marks at accept
(main.go:44-45 -> nodepool.go:14-16, HSET S:comparison S:U:O 1), the
consumer consumes the mark at SetOrder (engine.go:58-62, exists+delete)
and a cancel clears it first (engine.go:88-90) — that is what makes the
cancel-before-consume race drop the queued ADD (SURVEY §2.3.3).

Two implementations of one contract:

  LocalPrePool — a set subclass; single-process deployments (gateway and
      consumer sharing the MatchEngine) need nothing more.
  RespPrePool  — the markers live in a Redis-compatible server via the
      dependency-free RESP client (persist.resp), under the reference's
      EXACT schema, so (a) split-process topologies get reference
      semantics, and (b) a live gome deployment's S:comparison hashes are
      directly this pool's state during migration.

The contract the engine uses (beyond set-ish add/discard/contains/iter):

  consume_batch(keys) -> list[bool]   pop each (symbol, uuid, oid) key in
      order; True where the key existed. Admission consumes marks through
      this — ONE pipelined round trip per frame for the RESP pool instead
      of 2 RTTs per order (the reference's exists+delete pair collapses to
      HDEL's return value, same observable semantics single-consumer).
"""

from __future__ import annotations

import numpy as np

from ..types import Action

Key = tuple[str, str, str]  # (symbol, uuid, oid) — S:U:O, ordernode.go:89-92


class LocalPrePool(set):
    """In-process marker store: a plain set of (symbol, uuid, oid)."""

    def consume_batch(self, keys: list[Key]) -> list[bool]:
        out = []
        discard = self.discard
        for k in keys:
            if k in self:
                discard(k)
                out.append(True)
            else:
                out.append(False)
        return out

    def _frame_keys(self, cols: dict):
        """Key tuples of the frame's ADD rows (the numpy fallback of the
        native marker's fused pass: one vectorized row select, then
        C-speed zip/update — no per-order Python function calls)."""
        act = np.ascontiguousarray(cols["action"])
        sel = np.nonzero(act == int(Action.ADD))[0]
        if not len(sel):
            return None
        syms, uuids = cols["symbols"], cols["uuids"]
        sidx = np.asarray(cols["symbol_idx"])[sel].tolist()
        uidx = np.asarray(cols["uuid_idx"])[sel].tolist()
        oids = np.asarray(cols["oids"])[sel].tolist()
        return zip(
            map(syms.__getitem__, sidx),
            map(uuids.__getitem__, uidx),
            (o.decode() for o in oids),
        )

    def mark_frame(self, cols: dict) -> None:  # gomelint: hotpath
        """Gateway-side bulk marking of a built ORDER block's ADDs
        (main.go:42-45 for a whole frame) — the columnar admit path's
        numpy fallback when native host ops are unavailable."""
        keys = self._frame_keys(cols)
        if keys is not None:
            self.update(keys)

    def unmark_frame(self, cols: dict) -> None:
        """Undo mark_frame (emit failed after marking: the frame never
        entered the pipeline, so no marker may dangle)."""
        keys = self._frame_keys(cols)
        if keys is not None:
            self.difference_update(keys)


def consume_batch_of(pool, keys: list[Key]) -> list[bool]:
    """consume_batch for any pool object — uses the pool's own batched
    implementation when present, else the generic set-protocol fallback
    (covers plain sets assigned by older persistence snapshots)."""
    consume = getattr(pool, "consume_batch", None)
    if consume is not None:
        return consume(keys)
    return LocalPrePool.consume_batch(pool, keys)  # set-protocol fallback


class RespPrePool:
    """Markers in a Redis-compatible server, reference schema:
    hash `S:comparison`, field `S:U:O`, value "1" (nodepool.go:14-28).

    Implements enough of the set protocol for the engine's rollback
    (`pool |= consumed`), the persistence layer's snapshot (iteration) and
    restore (clear/update), plus the batched consume the admission hot
    path uses.

    With a persist.resp.SupervisedRespClient, a store restart mid-traffic
    reconnects + retries under the hood: mark_frame/add/__ior__ (HSET) are
    idempotent under retry; consume_batch (HDEL) inherits the lost-reply
    ambiguity window every Redis deployment has (documented on the
    client), which maps onto the consumer's at-least-once replay."""

    def __init__(self, client):
        self.client = client  # resp.RespClient / SupervisedRespClient / redis-py

    def resilience(self) -> dict | None:
        """The supervised client's state snapshot (breaker, reconnects,
        time degraded) for health surfaces; None for a raw client."""
        sup = getattr(self.client, "supervisor", None)
        return sup().snapshot() if sup is not None else None

    # -- schema ------------------------------------------------------------
    @staticmethod
    def _loc(key: Key) -> tuple[str, str]:
        symbol, uuid, oid = key
        return f"{symbol}:comparison", f"{symbol}:{uuid}:{oid}"

    # -- set protocol ------------------------------------------------------
    def add(self, key: Key) -> None:
        k, f = self._loc(key)
        self.client.execute_command("HSET", k, f, "1")

    def discard(self, key: Key) -> None:
        k, f = self._loc(key)
        self.client.execute_command("HDEL", k, f)

    def __contains__(self, key: Key) -> bool:
        k, f = self._loc(key)
        return self.client.execute_command("HEXISTS", k, f) == 1

    def __ior__(self, keys):
        cmds = []
        for key in keys:
            k, f = self._loc(key)
            cmds.append(("HSET", k, f, "1"))
        if cmds:
            self._check(self.client.pipeline(cmds))
        return self

    def update(self, keys) -> None:
        self.__ior__(keys)

    def __iter__(self):
        for hkey in self.client.keys("*:comparison"):
            symbol = hkey[: -len(":comparison")]
            for field in self.client.hgetall(hkey):
                rest = field[len(symbol) + 1 :]  # strip "S:"
                uuid, _, oid = rest.partition(":")
                yield (symbol, uuid, oid)

    def __len__(self) -> int:
        return sum(
            self.client.execute_command("HLEN", k)
            for k in self.client.keys("*:comparison")
        )

    def clear(self) -> None:
        keys = self.client.keys("*:comparison")
        if keys:
            self.client.execute_command("DEL", *keys)

    # -- the admission hot path -------------------------------------------
    def consume_batch(self, keys: list[Key]) -> list[bool]:
        cmds = []
        for key in keys:
            k, f = self._loc(key)
            cmds.append(("HDEL", k, f))
        replies = self._check(self.client.pipeline(cmds))
        return [r == 1 for r in replies]

    def mark_frame(self, cols: dict) -> None:
        """Gateway-side bulk marking of a decoded/built ORDER frame's ADDs
        (main.go:42-45): one pipelined round trip, fields grouped into one
        variadic HSET per symbol hash key (same keyspace effect as
        per-mark HSETs; ~10x fewer commands for the server to parse)."""
        syms, uuids = cols["symbols"], cols["uuids"]
        sidx = cols["symbol_idx"].tolist()
        uidx = cols["uuid_idx"].tolist()
        oids = cols["oids"].tolist()
        ADD = int(Action.ADD)
        by_key: dict[str, list[str]] = {}
        for a, k, u, o in zip(cols["action"].tolist(), sidx, uidx, oids):
            if a != ADD:
                continue
            sym = syms[k]
            fv = by_key.setdefault(f"{sym}:comparison", [])
            fv.append(f"{sym}:{uuids[u]}:{o.decode()}")
            fv.append("1")
        if by_key:
            self._check(
                self.client.pipeline(
                    [("HSET", k, *fv) for k, fv in by_key.items()]
                )
            )

    def unmark_frame(self, cols: dict) -> None:
        """Undo mark_frame for the frame's ADD rows (columnar emit failed
        after marking): one pipelined round trip of HDELs — the bulk
        mirror of the gateway's per-order unmark."""
        syms, uuids = cols["symbols"], cols["uuids"]
        sidx = cols["symbol_idx"].tolist()
        uidx = cols["uuid_idx"].tolist()
        oids = cols["oids"].tolist()
        ADD = int(Action.ADD)
        cmds = []
        for a, k, u, o in zip(cols["action"].tolist(), sidx, uidx, oids):
            if a != ADD:
                continue
            sym = syms[k]
            cmds.append((
                "HDEL", f"{sym}:comparison",
                f"{sym}:{uuids[u]}:{o.decode()}",
            ))
        if cmds:
            self._check(self.client.pipeline(cmds))

    @staticmethod
    def _check(replies: list) -> list:
        """An error reply must RAISE, never read as 'mark absent': treating
        a store error (-LOADING, -OOM, -WRONGTYPE) as a missing mark would
        silently drop acknowledged ADDs; raising lets the at-least-once
        consumer replay the batch once the store recovers. Likewise a
        failed mark RESTORE (__ior__) must not pass silently — the replay
        depends on those marks being back."""
        for r in replies:
            if isinstance(r, Exception):
                raise r
        return replies


class NativeConsumed:
    """The marks one frame admission consumed, represented compactly: the
    frame's columns plus the per-row consumed mask — restoring them
    (`pool |= consumed`, the failed-batch rollback) replays the same fused
    C++ pass in mark mode instead of materializing per-order key tuples."""

    __slots__ = ("cols", "sel")

    def __init__(self, cols: dict, sel):
        self.cols = cols
        self.sel = sel  # uint8[n]: 1 where this row's mark was consumed

    def __len__(self) -> int:
        return int(self.sel.sum())

    def __iter__(self):
        """Key tuples of the consumed rows (snapshot/debug; not hot)."""
        import numpy as np

        c = self.cols
        syms, uuids = c["symbols"], c["uuids"]
        for i in np.nonzero(self.sel)[0].tolist():
            yield (
                syms[int(c["symbol_idx"][i])],
                uuids[int(c["uuid_idx"][i])],
                c["oids"][i].decode(),
            )


class PoolDump:
    """A NativePrePool's marks at one instant, length-prefixed as the C set
    dumps them; iterating decodes the keys."""

    __slots__ = ("raw",)

    def __init__(self, raw: bytes):
        self.raw = raw

    def __iter__(self):
        raw, pos = self.raw, 0
        while pos < len(raw):
            ln = int.from_bytes(raw[pos : pos + 4], "little")
            pos += 4
            yield tuple(raw[pos : pos + ln].decode().split(NativePrePool.SEP))
            pos += ln


class NativePrePool:
    """In-process marker store backed by the C++ set (native/hostops.cc):
    same semantics as LocalPrePool, but admission of a whole decoded ORDER
    frame is ONE C call (compose key + pop marker + keep/existed masks)
    instead of a per-order Python loop — the difference between ~1.5 and
    ~0.1 us/order on the consumer hot path. Construction raises when the
    native library is unavailable (callers fall back to LocalPrePool)."""

    SEP = "\x1f"  # ASCII unit separator; ids on the reference JSON wire
    #               contract never contain control bytes

    def __init__(self):
        from . import nativehost

        self._nh = nativehost
        self._lib = nativehost.load()
        if self._lib is None:
            raise RuntimeError("native host ops unavailable")
        import ctypes

        self._h = ctypes.c_void_p(self._lib.gp_new())
        # String-list -> packed (data, offs) for the C call, keyed by list
        # identity: the wire decoder returns the same list object for a
        # repeated dictionary (bus.colwire), so a stable symbol universe
        # encodes its 10K+ strings once, not once per frame. Decoded
        # dictionaries are shared/immutable by contract (colwire).
        from ..utils.cache import IdentityCache

        self._packed_cache = IdentityCache()

    def __del__(self):
        h, self._h = self._h, None
        if h and getattr(self, "_lib", None) is not None:
            self._lib.gp_free(h)

    # -- set protocol ------------------------------------------------------
    def _ckey(self, key: Key) -> bytes:
        return self.SEP.join(key).encode()

    def add(self, key: Key) -> None:
        b = self._ckey(key)
        self._lib.gp_add(self._h, b, len(b))

    def discard(self, key: Key) -> None:
        b = self._ckey(key)
        self._lib.gp_discard(self._h, b, len(b))

    def __contains__(self, key: Key) -> bool:
        b = self._ckey(key)
        return bool(self._lib.gp_contains(self._h, b, len(b)))

    def __len__(self) -> int:
        return int(self._lib.gp_len(self._h))

    def frozen(self) -> "PoolDump":
        """The marks now, as one C copy: decoded only when iterated, on
        whatever thread does that (the snapshot writer's)."""
        import ctypes

        need = self._lib.gp_dump(self._h, None, 0)
        buf = ctypes.create_string_buffer(max(int(need), 1))
        got = self._lib.gp_dump(self._h, buf, need)
        if got != need:
            # A concurrent mark grew the pool between the size probe and
            # the fill (each takes the C mutex separately). RuntimeError is
            # the set-mutated-during-iteration contract the snapshot layer
            # retries on (persist/snapshot.py) — never yield garbage.
            raise RuntimeError("pre-pool changed size during iteration")
        return PoolDump(buf.raw[:need])

    def __iter__(self):
        return iter(self.frozen())

    def clear(self) -> None:
        self._lib.gp_clear(self._h)

    def __eq__(self, other):
        if isinstance(other, (set, frozenset, NativePrePool, RespPrePool)):
            return set(self) == set(other)
        return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return NotImplemented if eq is NotImplemented else not eq

    def __ior__(self, other):
        if isinstance(other, NativeConsumed):
            self._frame(other.cols, mode=2, sel=other.sel)
        else:
            for key in other:
                self.add(key)
        return self

    def update(self, keys) -> None:
        self.__ior__(keys)

    def consume_batch(self, keys: list[Key]) -> list[bool]:
        lib, h = self._lib, self._h
        out = []
        for key in keys:
            b = self._ckey(key)
            out.append(bool(lib.gp_discard(h, b, len(b))))
        return out

    # -- fused frame passes ------------------------------------------------
    def _packed(self, strs):
        ent = self._packed_cache.get(strs)
        if ent is None:
            ent = self._packed_cache.put(strs, self._nh.pack_strlist(strs))
        return ent

    def _frame(self, cols: dict, mode: int, sel=None):
        import ctypes

        nh = self._nh
        n = int(cols["n"])
        action = np.ascontiguousarray(cols["action"], np.uint8)
        sym_data, sym_offs = self._packed(cols["symbols"])
        uuid_data, uuid_offs = self._packed(cols["uuids"])
        sym_idx = np.ascontiguousarray(cols["symbol_idx"], np.uint32)
        uuid_idx = np.ascontiguousarray(cols["uuid_idx"], np.uint32)
        # The C pass indexes the offset tables unchecked; a frame whose
        # index column exceeds its dictionary must fail HERE, loudly.
        if n and (
            int(sym_idx.max()) >= len(cols["symbols"])
            or int(uuid_idx.max()) >= len(cols["uuids"])
        ):
            raise ValueError(
                "ORDER frame index column exceeds its dictionary "
                f"(symbols {len(cols['symbols'])}, uuids "
                f"{len(cols['uuids'])})"
            )
        oids = np.ascontiguousarray(cols["oids"])
        keep = np.empty(n, np.uint8) if mode == 0 else None
        existed = sel if sel is not None else (
            np.empty(n, np.uint8) if mode == 0 else None
        )
        c_void = ctypes.c_void_p
        as_p = lambda a: a.ctypes.data_as(c_void) if a is not None else None
        rc = self._lib.gp_frame(
            self._h, n, as_p(action),
            sym_data, sym_offs.ctypes.data_as(nh._p_i64), as_p(sym_idx),
            uuid_data, uuid_offs.ctypes.data_as(nh._p_i64), as_p(uuid_idx),
            as_p(oids), oids.dtype.itemsize,
            int(Action.ADD), int(Action.DEL),
            as_p(keep), as_p(existed), mode,
        )
        if rc != 0:
            raise RuntimeError("native pre-pool frame pass failed")
        return keep, existed

    def consume_frame(self, cols: dict):
        """Fused frame admission: returns (keep mask (bool[n]), consumed) —
        the engine.go:58-62/88-90 semantics in one native pass."""
        keep, existed = self._frame(cols, mode=0)
        return keep.view(np.bool_), NativeConsumed(cols, existed)

    def mark_frame(self, cols: dict) -> None:
        """Gateway-side bulk marking (main.go:42-45 for a whole frame)."""
        self._frame(cols, mode=1)

    def unmark_frame(self, cols: dict) -> None:
        """Undo mark_frame for the frame's ADD rows. Emit-failure path
        (rare by construction), so a per-row gp_discard loop is fine —
        no fused C mode needed."""
        act = np.ascontiguousarray(cols["action"])
        sel = np.nonzero(act == int(Action.ADD))[0]
        if not len(sel):
            return
        syms, uuids = cols["symbols"], cols["uuids"]
        sidx = np.asarray(cols["symbol_idx"])[sel].tolist()
        uidx = np.asarray(cols["uuid_idx"])[sel].tolist()
        oids = np.asarray(cols["oids"])[sel].tolist()
        lib, h = self._lib, self._h
        for s, u, o in zip(sidx, uidx, oids):
            b = self._ckey((syms[s], uuids[u], o.decode()))
            lib.gp_discard(h, b, len(b))


def make_prepool():
    """A NativePrePool when the toolchain allows, else LocalPrePool."""
    try:
        return NativePrePool()
    except RuntimeError:
        return LocalPrePool()


def make_marker(pool):
    """Gateway-side mark callable for a pool NOT attached to an engine —
    the split-process gateway's equivalent of MatchEngine.mark
    (main.go:42-45: ADDs mark, cancels never do)."""

    def mark(order) -> None:
        if order.action is Action.ADD:
            pool.add((order.symbol, order.uuid, order.oid))

    return mark
