"""The wire, as the benchmark's client writes and reads it: DoOrderBatch
requests encoded with numpy during set-up, SubscribeMatches events decoded
after the window. api/order.proto is the program's public interface; nothing
else of the program is used here.
"""

from __future__ import annotations

import numpy as np

DO_ORDER_BATCH = "/gome_tpu.api.Order/DoOrderBatch"
SUBSCRIBE = "/gome_tpu.api.Order/SubscribeMatches"
UID_DIGITS, OID_DIGITS, SYM_DIGITS = 3, 9, 5
ORDER_BYTES = (2 + (2 + 1 + UID_DIGITS) + (2 + 1 + OID_DIGITS)
               + (2 + 1 + SYM_DIGITS) + 2 + 9 + 9 + 2)


def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """[n, width] ASCII digits of non-negative ints, zero-padded."""
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((values[:, None] // powers) % 10 + 48).astype(np.uint8)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def encode_orders(cols: dict, accuracy: int) -> np.ndarray:
    """[n, ORDER_BYTES] uint8: each row one `orders` entry of an
    OrderBatchRequest on the wire (tag, length, then the OrderRequest's seven
    fields). Ids have fixed widths (u%03d, o%09d, s%05d), so every row has the
    same length and the whole stream is encoded with numpy, no message object
    per order. Zero-valued enums are written out, which the wire format
    allows. Prices and volumes go out in external units (scaled / 10^accuracy),
    as a client of the reference sends them."""
    n = len(cols["sym"])
    unit = 10.0 ** accuracy
    i64 = lambda a: np.asarray(a).astype(np.int64)
    f64 = lambda a: (np.asarray(a) / unit).astype("<f8").view(np.uint8).reshape(n, 8)
    parts = [
        0x0A, ORDER_BYTES - 2,
        0x0A, 1 + UID_DIGITS, ord("u"), _digits(i64(cols["uid"]), UID_DIGITS),
        0x12, 1 + OID_DIGITS, ord("o"), _digits(i64(cols["oid"]), OID_DIGITS),
        0x1A, 1 + SYM_DIGITS, ord("s"), _digits(i64(cols["sym"]), SYM_DIGITS),
        0x20, np.asarray(cols["side"]).astype(np.uint8)[:, None],
        0x29, f64(cols["price"]),
        0x31, f64(cols["volume"]),
        0x38, np.asarray(cols["kind"]).astype(np.uint8)[:, None],
    ]
    out = np.empty((n, ORDER_BYTES), np.uint8)
    at = 0
    for part in parts:
        if isinstance(part, int):
            out[:, at] = part
            at += 1
        else:
            out[:, at:at + part.shape[1]] = part
            at += part.shape[1]
    if at != ORDER_BYTES:
        raise AssertionError(f"encoded {at} bytes per order, not {ORDER_BYTES}")
    return out


def build_requests(cols: dict, request_orders: int, accuracy: int) -> list:
    """One serialised OrderBatchRequest per `request_orders` orders: the
    orders' rows, then the packed `cancel` mask."""
    if (np.max(cols["uid"]) >= 10 ** UID_DIGITS
            or np.max(cols["oid"]) >= 10 ** OID_DIGITS
            or np.max(cols["sym"]) >= 10 ** SYM_DIGITS):
        raise ValueError("an id is wider than the fixed-width encoding")
    rows = encode_orders(cols, accuracy)
    cancel = np.asarray(cols["cancel"]).astype(np.uint8)
    head = b"\x12" + _varint(request_orders)
    return [
        rows[lo:lo + request_orders].tobytes() + head
        + cancel[lo:lo + request_orders].tobytes()
        for lo in range(0, len(rows) - request_orders + 1, request_orders)
    ]


def decode_events(raws: list, pb=None) -> np.ndarray:
    """int64 [n, 13]: each MatchEvent as (maker symbol, taker symbol, then
    reference.EVENT_FIELDS[2:]), scaled integers."""
    if pb is None:
        from gome_tpu.api import order_pb2 as pb
    parse = pb.MatchEvent.FromString
    out = np.empty((len(raws), 13), np.int64)
    rows = []
    for raw in raws:
        e = parse(raw)
        a, b = e.node, e.match_node
        rows.append((
            int(b.symbol[1:]), int(a.symbol[1:]), int(a.uuid[1:]),
            int(a.oid[1:]), a.transaction, a.price, a.volume,
            int(b.uuid[1:]), int(b.oid[1:]), b.transaction, b.price,
            b.volume, e.match_volume,
        ))
    if rows:
        out[:] = np.array(rows, np.float64).round().astype(np.int64)
    return out
