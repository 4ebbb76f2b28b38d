"""Columnar binary wire frames — the high-throughput order/event transport.

The reference ships one JSON document per order (engine.go:36
`json.Marshal(node)`) and one per fill (engine.go:149-158). At the 1M+
orders/sec the TPU engine sustains, per-message JSON costs more than the
matching itself (~1-2 µs/order for encode+decode+object churn vs ~0.07 µs
of device time). These frames carry a whole micro-batch as numpy columns:

  * ORDER frame ("GCO1"): one bus message holding N orders — fixed-width
    numeric columns plus dictionary-encoded symbols/uuids and
    padded-fixed-width oids, all decodable with `np.frombuffer` (no
    per-order Python).
  * EVENT frame ("GCE1"): one bus message holding an EventBatch's columns
    plus the id-table slices it references — the matchOrder feed at
    device speed. `decode_event_frame(...).to_results()` recovers the
    exact MatchResult objects, and `EventBatch.to_json_lines()` the exact
    reference JSON, so parity surfaces are unchanged; the binary hop is an
    internal transport choice (config: service.match_wire).

Frames are self-describing (magic + version); the consumer sniffs the
first byte to distinguish them from reference-parity JSON messages ('{'),
so both producers can share one queue during migration.

Layout conventions: little-endian, u32 lengths, arrays written back to
back in column order. Strings: `dict` columns are a u32 count + packed
(u16 len + bytes) uniques + u32 idx[n]; `padded` columns are a u16 width +
n*width bytes (numpy 'S{width}' — embedded NULs cannot occur in ids that
round-trip the reference's JSON contract).
"""

from __future__ import annotations

import struct
from collections import OrderedDict

import numpy as np

from ..utils.cache import IdentityCache

ORDER_MAGIC = b"GCO2"
ORDER_MAGIC_V1 = b"GCO1"  # decode-compat: pre-cache dict-column layout
#: GCO2 + one trailing padded per-order trace-context column (utils.trace
#: "<id>@<t>" strings; '' = untraced). Emitted only when at least one
#: order carries a context, so tracing-off traffic stays byte-identical
#: GCO2 — zero wire overhead on the hot path.
ORDER_MAGIC_TRACED = b"GCO3"
#: The columnar-front-door layout (round 11): a HEADER (u32 total order
#: count + u32 block count) followed by back-to-back GCO2-style BODIES
#: ("blocks"), each with its own count/dictionaries. The gateway's
#: columnar admit encodes one block per gRPC batch on the handler thread;
#: the batcher's flush is then a pure byte-join — no decode/re-encode
#: round-trip, no per-order Python anywhere between proto and frame.
#: Single-block frames decode through the exact GCO2 body reader (same
#: dict-cache identity semantics); multi-block frames merge on the
#: consumer side, which has ~13x the gateway's CPU headroom (HOSTPROF).
ORDER_MAGIC_BLOCKS = b"GCO4"
EVENT_MAGIC = b"GCE1"
#: GCE1 + one u64 base sequence number after the count: event i in the
#: frame is matchfeed seq ``seq0 + i`` (exactly-once across restarts —
#: ISSUE 11). Emitted only when the publisher stamps seqs, so legacy
#: traffic stays byte-identical GCE1 (the GCO3 migration story again).
EVENT_MAGIC_SEQ = b"GCE2"

# Order columns: (name, dtype) fixed-width part.
_ORDER_NUM = (
    ("action", np.uint8),
    ("side", np.uint8),
    ("kind", np.uint8),
    ("price", np.int64),
    ("volume", np.int64),
)

_EVENT_NUM = (
    # mirrors gome_tpu.engine.events._COLUMNS minus arrival (frame-local
    # order IS arrival order)
    ("is_cancel", np.uint8),
    ("symbol_id", np.int64),
    ("taker_uid", np.int64),
    ("taker_oid", np.int64),
    ("taker_side", np.int8),
    ("taker_price", np.int64),
    ("taker_volume", np.int64),
    ("maker_uid", np.int64),
    ("maker_oid", np.int64),
    ("fill_price", np.int64),
    ("maker_volume", np.int64),
    ("match_volume", np.int64),
    # the event's taker was a MARKET order; not the order's kind: an IOC,
    # FOK or POST_ONLY taker reads 0 (engine/events.py)
    ("is_market", np.uint8),
)


# Decoded dict-column uniques, content-addressed by their raw wire bytes.
# Real order flow re-sends the same symbol/uuid dictionary frame after
# frame (exchange symbol universes are stable); decoding 10K+ strings per
# frame costs ~0.1 us/order, so the decoder hashes the uniques region and
# reuses the previously decoded list. HITS RETURN THE *SAME LIST OBJECT*,
# which downstream hot paths use as their own IdentityCache key (the
# engine's symbol->lane map, the pre-pool's packed key bytes) — decoded
# dicts are shared and must be treated as immutable.
# The cache is module-global and SHARED across all engines/threads in the
# process: values are immutable decoded lists (see above), so cross-thread
# reuse is safe; mutation relies on the GIL's per-op atomicity plus
# KeyError-tolerant eviction below. Eviction is one-entry LRU (oldest
# insertion out, hits refreshed), so a workload with >32 live dictionaries
# degrades to re-decoding only its coldest dict per frame instead of the
# wholesale clear() this used to do (which evicted every hot entry too).
_dict_cache: "OrderedDict[bytes, list[str]]" = OrderedDict()
_DICT_CACHE_MAX = 32

# Writer-side mirror: list object -> encoded uniques region (the gateway
# re-encodes the same dictionary every frame).
_pack_cache = IdentityCache()


def _dict_uniques_bytes(values) -> bytes:
    parts = [struct.pack("<I", len(values))]
    for s in values:
        b = s.encode() if isinstance(s, str) else s
        parts.append(struct.pack("<H", len(b)))
        parts.append(b)
    return b"".join(parts)


def _pack_dict_column(values: list[str], idx: np.ndarray) -> bytes:
    uniques = _pack_cache.get(values)
    if uniques is None:
        uniques = _pack_cache.put(values, _dict_uniques_bytes(values))
    return (
        struct.pack("<I", len(uniques))
        + uniques
        + np.ascontiguousarray(idx, np.uint32).tobytes()
    )


def _parse_dict_uniques(region: bytes) -> list[str]:
    (count,) = struct.unpack_from("<I", region, 0)
    off = 4
    values = []
    for _ in range(count):
        (ln,) = struct.unpack_from("<H", region, off)
        off += 2
        values.append(region[off : off + ln].decode())
        off += ln
    return values


def _read_dict_column(buf: memoryview, off: int, n: int):
    (nbytes,) = struct.unpack_from("<I", buf, off)
    off += 4
    region = bytes(buf[off : off + nbytes])
    off += nbytes
    values = _dict_cache.get(region)
    if values is None:
        values = _parse_dict_uniques(region)
        while len(_dict_cache) >= _DICT_CACHE_MAX:
            try:
                _dict_cache.popitem(last=False)  # LRU: evict oldest only
            except KeyError:  # concurrent evictor got there first
                break
        _dict_cache[region] = values
    else:
        try:
            _dict_cache.move_to_end(region)
        except KeyError:  # concurrently evicted; value is still valid
            pass
    idx = np.frombuffer(buf, np.uint32, n, off)
    off += 4 * n
    return values, idx, off


def _read_dict_column_v1(buf: memoryview, off: int, n: int):
    """GCO1 layout: no region-length prefix — walk the per-string lengths."""
    (count,) = struct.unpack_from("<I", buf, off)
    off += 4
    values = []
    for _ in range(count):
        (ln,) = struct.unpack_from("<H", buf, off)
        off += 2
        values.append(bytes(buf[off : off + ln]).decode())
        off += ln
    idx = np.frombuffer(buf, np.uint32, n, off)
    off += 4 * n
    return values, idx, off


def _padded_array(strs) -> np.ndarray:
    """strs: list[str] (or np 'S' array) -> the 'S{width}' array a padded
    column holds, width the batch's longest. str inputs are encoded to
    UTF-8 bytes FIRST — np.array(dtype='S') on str objects is ASCII-only
    and would crash on in-contract non-ASCII ids."""
    if isinstance(strs, np.ndarray) and strs.dtype.kind == "S":
        return np.ascontiguousarray(strs)
    arr = np.array(
        [s if isinstance(s, bytes) else s.encode() for s in strs],
        dtype="S",
    )
    if arr.dtype.itemsize == 0:  # all-empty edge
        arr = arr.astype("S1")
    return arr


def _pack_padded_column(strs) -> bytes:
    arr = _padded_array(strs)
    return struct.pack("<H", arr.dtype.itemsize) + arr.tobytes()


def _read_padded_column(buf: memoryview, off: int, n: int):
    (width,) = struct.unpack_from("<H", buf, off)
    off += 2
    arr = np.frombuffer(buf, f"S{width}", n, off)
    off += width * n
    return arr, off


def encode_order_block(
    n: int,
    action: np.ndarray,
    side: np.ndarray,
    kind: np.ndarray,
    price: np.ndarray,
    volume: np.ndarray,
    symbols: list[str],
    symbol_idx: np.ndarray,
    uuids: list[str],
    uuid_idx: np.ndarray,
    oids,
) -> bytes:  # gomelint: hotpath
    """One ORDER block BODY (no magic): u32 count + numeric columns +
    dict-encoded symbols/uuids + padded oids — exactly a GCO2 body, so a
    single block prefixed with ORDER_MAGIC is a valid GCO2 frame and
    GCO4 is a pure framing of these. This is what the columnar gateway
    encodes per gRPC batch (array inputs straight from the admit masks,
    never per-order Python)."""
    parts = [struct.pack("<I", n)]
    for (_name, dt), col in zip(
        _ORDER_NUM, (action, side, kind, price, volume)
    ):
        parts.append(np.ascontiguousarray(col, dt).tobytes())
    parts.append(_pack_dict_column(symbols, symbol_idx))
    parts.append(_pack_dict_column(uuids, uuid_idx))
    parts.append(_pack_padded_column(oids))
    return b"".join(parts)


def encode_order_frame(
    n: int,
    action: np.ndarray,
    side: np.ndarray,
    kind: np.ndarray,
    price: np.ndarray,
    volume: np.ndarray,
    symbols: list[str],
    symbol_idx: np.ndarray,
    uuids: list[str],
    uuid_idx: np.ndarray,
    oids,
    traces=None,
) -> bytes:
    """Build one ORDER frame. symbols/uuids are per-batch dictionaries with
    u32 index columns; oids are raw per-order strings (padded column).
    traces: optional per-order trace-context strings ('' = untraced) —
    selects the GCO3 layout (a trailing padded column)."""
    magic = ORDER_MAGIC if traces is None else ORDER_MAGIC_TRACED
    body = encode_order_block(
        n, action, side, kind, price, volume, symbols, symbol_idx,
        uuids, uuid_idx, oids,
    )
    if traces is None:
        return magic + body
    return b"".join((magic, body, _pack_padded_column(traces)))


def encode_order_frame_blocks(blocks: list[bytes]) -> bytes:  # gomelint: hotpath
    """Pre-encoded ORDER blocks -> one GCO4 frame: magic + u32 total
    order count + u32 block count + the blocks back to back. The total
    is read off each block's leading u32 — the flush path stays a byte
    join, never a decode."""
    if not blocks:
        raise ValueError("GCO4 frame needs at least one block")
    n_total = 0
    for b in blocks:
        (n,) = struct.unpack_from("<I", b, 0)
        n_total += n
    return b"".join(
        [ORDER_MAGIC_BLOCKS, struct.pack("<II", n_total, len(blocks))]
        + list(blocks)
    )


def orders_to_cols(orders) -> dict:
    """A list of Order objects -> the column dict decode_order_frame
    returns for the same orders (same keys and dtypes, dictionaries in
    first-occurrence order, `trace` only when an order carries one),
    built without bytes: how a list of Orders enters the engine's frame
    path (engine.frames)."""
    n = len(orders)
    syms: list[str] = []
    uuids: list[str] = []
    sym_ix: dict[str, int] = {}
    uuid_ix: dict[str, int] = {}
    sym_idx = np.empty(n, np.uint32)
    uuid_idx = np.empty(n, np.uint32)
    action = np.empty(n, np.uint8)
    side = np.empty(n, np.uint8)
    kind = np.empty(n, np.uint8)
    price = np.empty(n, np.int64)
    volume = np.empty(n, np.int64)
    oids = []
    for i, o in enumerate(orders):
        action[i] = int(o.action)
        side[i] = int(o.side)
        kind[i] = int(o.order_type)
        price[i] = o.price
        volume[i] = o.volume
        if o.symbol not in sym_ix:
            sym_ix[o.symbol] = len(syms)
            syms.append(o.symbol)
        sym_idx[i] = sym_ix[o.symbol]
        if o.uuid not in uuid_ix:
            uuid_ix[o.uuid] = len(uuids)
            uuids.append(o.uuid)
        uuid_idx[i] = uuid_ix[o.uuid]
        oids.append(o.oid)
    cols = dict(
        n=n, action=action, side=side, kind=kind, price=price,
        volume=volume, symbols=syms, symbol_idx=sym_idx, uuids=uuids,
        uuid_idx=uuid_idx, oids=_padded_array(oids),
    )
    if any(o.trace is not None for o in orders):
        cols["trace"] = _padded_array([o.trace or "" for o in orders])
    return cols


def encode_orders(orders) -> bytes:
    """Convenience: a list of Order objects -> one ORDER frame (what a
    batching gateway produces; shared by tests, the fuzzer, and examples)."""
    c = orders_to_cols(orders)
    return encode_order_frame(
        c["n"], c["action"], c["side"], c["kind"], c["price"], c["volume"],
        c["symbols"], c["symbol_idx"], c["uuids"], c["uuid_idx"], c["oids"],
        traces=c.get("trace"),
    )


def _read_order_body(buf: memoryview, off: int, read_dict):
    """One ORDER body (u32 count + columns) -> (cols dict, new offset) —
    shared by the GCO1/GCO2/GCO3 frame decoders and the per-block GCO4
    loop, so every layout funnels through identical column parsing (and
    the same dict-column identity cache)."""
    (n,) = struct.unpack_from("<I", buf, off)
    off += 4
    out: dict = {"n": n}
    for name, dt in _ORDER_NUM:
        out[name] = np.frombuffer(buf, dt, n, off)
        off += np.dtype(dt).itemsize * n
    out["symbols"], out["symbol_idx"], off = read_dict(buf, off, n)
    out["uuids"], out["uuid_idx"], off = read_dict(buf, off, n)
    out["oids"], off = _read_padded_column(buf, off, n)
    return out, off


# Merged multi-block dictionaries, keyed on the identity of the per-block
# uniques lists (which the _dict_cache keeps stable for a stable symbol
# universe), so a steady flow of same-shaped GCO4 frames reuses one merged
# list object — downstream identity caches (the engine's symbol->lane map,
# the native pre-pool's packed tables) keep hitting. Values pin the part
# lists so an id() can never be recycled while its key is live; the
# whole-tuple identity is re-verified on hit anyway (IdentityCache's
# discipline). Same GIL-atomicity + LRU reasoning as _dict_cache above.
_merge_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
_MERGE_CACHE_MAX = 32


def _merge_dicts(parts: list) -> tuple:
    """Per-block uniques lists -> (merged uniques list, per-block u32
    remap arrays): remap[i] is the merged id of part value i, so a
    block's index column remaps in one vectorized gather."""
    key = tuple(map(id, parts))
    hit = _merge_cache.get(key)
    if hit is not None and all(
        a is b for a, b in zip(hit[0], parts)
    ):
        try:
            _merge_cache.move_to_end(key)
        except KeyError:  # concurrently evicted; value is still valid
            pass
        return hit[1], hit[2]
    ix: dict = {}
    merged: list = []
    remaps = []
    for vals in parts:
        remap = np.empty(len(vals), np.uint32)
        for j, s in enumerate(vals):
            k = ix.get(s)
            if k is None:
                k = ix[s] = len(merged)
                merged.append(s)
            remap[j] = k
        remaps.append(remap)
    while len(_merge_cache) >= _MERGE_CACHE_MAX:
        try:
            _merge_cache.popitem(last=False)
        except KeyError:  # concurrent evictor got there first
            break
    _merge_cache[key] = (list(parts), merged, remaps)
    return merged, remaps


def _merge_order_blocks(blocks: list) -> dict:
    """Decoded GCO4 blocks -> one standard cols dict: numeric columns
    concatenate, dictionary columns merge through _merge_dicts (stable
    merged-list identity), oids concatenate with 'S' width promotion."""
    out: dict = {"n": int(sum(b["n"] for b in blocks))}
    for name, _dt in _ORDER_NUM:
        out[name] = np.concatenate([b[name] for b in blocks])
    for values_key, idx_key in (
        ("symbols", "symbol_idx"), ("uuids", "uuid_idx")
    ):
        merged, remaps = _merge_dicts([b[values_key] for b in blocks])
        out[values_key] = merged
        out[idx_key] = np.concatenate(
            [remap[b[idx_key]] for remap, b in zip(remaps, blocks)]
        )
    out["oids"] = np.concatenate([b["oids"] for b in blocks])
    return out


def decode_order_frame(payload: bytes) -> dict:
    """ORDER frame -> dict of numpy columns + string dictionaries:
    {action,side,kind,price,volume: np arrays; symbols: list[str],
    symbol_idx: u32 array; uuids, uuid_idx; oids: np 'S' array}. All
    layouts (GCO1-GCO4) normalize to this one contract, so the consumer
    and engine frame path never see the wire version."""
    buf = memoryview(payload)
    magic = bytes(buf[:4])
    if magic == ORDER_MAGIC_BLOCKS:
        n_total, n_blocks = struct.unpack_from("<II", buf, 4)
        off = 12
        blocks = []
        for _ in range(n_blocks):
            block, off = _read_order_body(buf, off, _read_dict_column)
            blocks.append(block)
        if n_blocks == 1:
            out = blocks[0]  # the GCO2-identical fast path
        else:
            out = _merge_order_blocks(blocks)
        if out["n"] != n_total:
            raise ValueError(
                f"GCO4 header count {n_total} != block sum {out['n']}"
            )
        return out
    if magic not in (ORDER_MAGIC, ORDER_MAGIC_V1, ORDER_MAGIC_TRACED):
        raise ValueError("not an ORDER frame")
    read_dict = (
        _read_dict_column_v1 if magic == ORDER_MAGIC_V1 else _read_dict_column
    )
    out, off = _read_order_body(buf, 4, read_dict)
    if magic == ORDER_MAGIC_TRACED:
        # Per-order trace contexts ride the frame; engine code never reads
        # this key (the consumer peels it off before processing).
        out["trace"], off = _read_padded_column(buf, off, out["n"])
    return out


def is_frame(body: bytes) -> bool:
    return body[:1] == b"G"


def _pack_id_table(table, used: np.ndarray) -> bytes:
    """Frame-local id table: u32 count + padded 'S' column of the USED
    strings. A native-interner table (gather_padded) packs without
    materializing ANY Python strings; Python-list tables gather via
    operator.itemgetter at C speed."""
    count = len(used)
    gather = getattr(table, "gather_padded", None)
    if gather is not None and count:
        arr = gather(np.ascontiguousarray(used, np.int64))
        return (
            struct.pack("<I", count)
            + struct.pack("<H", arr.dtype.itemsize)
            + arr.tobytes()
        )
    import operator

    if count == 0:
        gathered = []
    elif count == 1:
        gathered = [table[int(used[0])]]
    else:
        gathered = list(operator.itemgetter(*used.tolist())(table))
    return struct.pack("<I", count) + _pack_padded_column(gathered)


def _read_id_table(buf: memoryview, off: int):
    (count,) = struct.unpack_from("<I", buf, off)
    off += 4
    arr, off = _read_padded_column(buf, off, count)
    return [s.decode() for s in arr.tolist()], off


def encode_event_frame(batch, seq0: int | None = None) -> bytes:
    """EventBatch -> one EVENT frame. Only the id-table entries the batch
    references are shipped (remapped to frame-local ids), so frame size
    tracks the batch, not the process-lifetime interners. All column and
    table packing is vectorized — no per-event or per-string Python.

    With ``seq0`` (defaults to the batch's own stamp) the frame is GCE2:
    a u64 base seq follows the count and event i is seq ``seq0 + i``.
    Without one it stays byte-identical GCE1."""
    c = batch.columns
    n = len(batch)
    if seq0 is None:
        seq0 = getattr(batch, "seq0", None)
    if seq0 is None:
        parts = [EVENT_MAGIC, struct.pack("<I", n)]
    else:
        parts = [EVENT_MAGIC_SEQ, struct.pack("<IQ", n, seq0)]
    local_cols: dict[str, np.ndarray] = {}
    tables = []
    for table, cols in (
        (batch.symbols, ("symbol_id",)),
        (batch.uid_table, ("taker_uid", "maker_uid")),
        (batch.oid_table, ("taker_oid", "maker_oid")),
    ):
        if n:
            cat = np.concatenate([c[k] for k in cols])
            top = int(cat.max()) if len(cat) else 0
            lo = int(cat.min()) if len(cat) else 0
            span = top - lo
            if 0 <= lo and span < max(16 * len(cat), 1 << 16):
                # Dense ids (interner-assigned): a flag-scatter + nonzero
                # over the batch's [lo, top] id RANGE replaces the
                # O(n log n) sort inside np.unique — ~2x less host CPU at
                # frame shape. Unlike the remap below (lazy np.empty, only
                # touched pages materialize), nonzero READS the whole flag
                # array, so it is sized to the batch's span (a frame's oid
                # ids are recent neighbors even when the interner holds
                # hundreds of millions); spans sparser than 16x the batch
                # degrade to np.unique.
                seen = np.zeros(span + 1, np.bool_)
                seen[cat - lo] = True
                used = np.nonzero(seen)[0] + lo
            else:
                used = np.unique(cat)
        else:
            used = np.zeros(0, np.int64)
        tables.append(_pack_id_table(table, used))
        if n and len(used):
            top = int(used[-1])
            if top < (1 << 28):
                # Dense O(1) remap instead of per-column searchsorted:
                # scatter frame-local ids into a position-indexed map.
                # np.empty is a lazy mmap and only the touched pages
                # materialize, but the map still scales with the LARGEST
                # id (the oid interner grows one id per order for the
                # process lifetime) — so cap it at 2^28 ids (1 GB u32,
                # ~270M orders) and degrade to searchsorted beyond, which
                # keeps scratch O(batch).
                remap = np.empty(top + 1, np.uint32)
                remap[used] = np.arange(len(used), dtype=np.uint32)
                for k in cols:
                    local_cols[k] = remap[c[k]]
            else:
                for k in cols:
                    local_cols[k] = np.searchsorted(used, c[k])
        else:
            for k in cols:
                local_cols[k] = np.zeros(0, np.int64)
    for name, dt in _EVENT_NUM:
        col = local_cols.get(name, c.get(name))
        parts.append(np.ascontiguousarray(col, dt).tobytes())
    parts.extend(tables)
    return b"".join(parts)


def decode_event_frame(payload: bytes):
    """EVENT frame -> EventBatch (frame-local tables)."""
    from ..engine.events import EventBatch

    buf = memoryview(payload)
    magic = bytes(buf[:4])
    seq0: int | None = None
    if magic == EVENT_MAGIC:
        (n,) = struct.unpack_from("<I", buf, 4)
        off = 8
    elif magic == EVENT_MAGIC_SEQ:
        n, seq0 = struct.unpack_from("<IQ", buf, 4)
        off = 16
    else:
        raise ValueError("not an EVENT frame")
    cols: dict = {}
    for name, dt in _EVENT_NUM:
        cols[name] = np.frombuffer(buf, dt, n, off).astype(
            np.bool_ if name in ("is_cancel", "is_market") else np.int64
        )
        off += np.dtype(dt).itemsize * n
    cols["taker_side"] = cols["taker_side"].astype(np.int8)
    symbols, off = _read_id_table(buf, off)
    uids, off = _read_id_table(buf, off)
    oids, off = _read_id_table(buf, off)
    cols["arrival"] = np.arange(n, dtype=np.int64)
    return EventBatch(
        columns=cols, symbols=symbols, oid_table=oids, uid_table=uids,
        seq0=seq0,
    )
