"""Batch order decoding: native C++ fast path with json.loads fallback.

The consumer decodes every inbound doOrder message; `decode_orders_batch`
parses a whole micro-batch in one native call (native/ordercodec.cc),
returning the same Order objects `codec.decode_order` would. Messages the
native parser declines (escaped strings, unknown keys, no toolchain) fall
back to the json path — the fast path can only be faster, never different.
"""

from __future__ import annotations

import ctypes

import numpy as np

from ..types import ORDER_KINDS, Action, Order, OrderType, Side, known_kinds
from .codec import decode_order

# Index tables beat Enum.__call__ (~10x) on the per-message hot path.
_SIDES = (Side.BUY, Side.SALE)
_ACTIONS = (Action.NOP, Action.ADD, Action.DEL)
#: By the kind's own number; None at the unassigned ones (2, 5), which the
#: range check below sends to the json path like any unknown kind.
_KINDS = tuple(
    OrderType(k) if k in ORDER_KINDS else None
    for k in range(max(ORDER_KINDS) + 1)
)

_fn = None
_fn_err = False


def _load():
    global _fn, _fn_err
    if _fn is not None or _fn_err:
        return _fn
    try:
        from .native import _load as _load_lib

        lib = _load_lib()
        if lib is None:
            _fn_err = True
            return None
        fn = lib.gome_parse_orders
        fn.restype = ctypes.c_int64
        fn.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                       ctypes.c_int64] + [
            ctypes.POINTER(ctypes.c_int64)
        ] * 11
        _fn = fn
    except Exception:
        _fn_err = True
        return None
    return _fn


def decode_orders_batch(bodies: list[bytes]) -> list[Order]:
    """Decode a batch of doOrder message bodies. Semantics identical to
    [decode_order(b) for b in bodies]."""
    n = len(bodies)
    if n == 0:
        return []
    fn = _load()
    if fn is None:
        return [decode_order(b) for b in bodies]

    buf = b"".join(bodies)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum([len(b) for b in bodies], out=offs[1:])
    cols = [np.empty(n, np.int64) for _ in range(11)]
    ptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    parsed = int(
        fn(buf, ptr(offs), n, *(ptr(c) for c in cols))
    )
    (action, transaction, price, volume, kind,
     u_off, u_len, o_off, o_len, s_off, s_len) = cols

    orders: list[Order] = []
    sv = buf.decode()  # one decode; offsets are byte==char offsets (ASCII
    # fast path — any non-ASCII byte makes len(sv) != len(buf) and we fall
    # back below rather than slice at wrong positions)
    if len(sv) != len(buf):
        return [decode_order(b) for b in bodies]
    # Out-of-range enum codes decline to the json path (which raises the
    # same ValueError decode_order would).
    ok = (
        (transaction[:parsed] >= 0) & (transaction[:parsed] <= 1)
        & (action[:parsed] >= 0) & (action[:parsed] <= 2)
        & known_kinds(kind[:parsed])
    )
    if not ok.all():
        parsed = int(np.argmin(ok))

    uo, ul = u_off.tolist(), u_len.tolist()
    oo, ol = o_off.tolist(), o_len.tolist()
    so, sl = s_off.tolist(), s_len.tolist()
    tr, pr, vo = transaction.tolist(), price.tolist(), volume.tolist()
    ac, kn = action.tolist(), kind.tolist()
    append = orders.append
    for i in range(parsed):
        append(
            Order(
                uuid=sv[uo[i] : uo[i] + ul[i]],
                oid=sv[oo[i] : oo[i] + ol[i]],
                symbol=sv[so[i] : so[i] + sl[i]],
                side=_SIDES[tr[i]],
                price=pr[i],
                volume=vo[i],
                action=_ACTIONS[ac[i]],
                order_type=_KINDS[kn[i]],
            )
        )
    for i in range(parsed, n):  # native declined: exact json fallback
        orders.append(decode_order(bodies[i]))
    return orders
