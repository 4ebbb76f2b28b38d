"""Domain types shared by the oracle, the JAX engine, and the bridge.

Mirrors the reference wire contract (api/order.proto:4-29) and the internal
order node / match-result shapes (gomengine/engine/ordernode.go:9-36,
gomengine/engine/engine.go:24-28) — re-expressed as integer tick/lot
quantities so the TPU hot path is exact integer arithmetic rather than the
reference's float64-on-scaled-values model (SURVEY §2.2).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field, replace

import numpy as np


# Device execution strategies for one [S, T] op grid (single source of
# truth for config validation and BatchEngine selection).
KERNELS = ("scan", "pallas")

# The self-trade prevention rules a venue can state (engine.self_trade in
# the config, BookConfig.self_trade on the device, OracleEngine's argument):
# "none", the reference's, lets an account trade with itself;
# "expire_taker" stops an add at its owner's first resting order and
# expires what is left of it (oracle/book.py's docstring has the rule).
SELF_TRADE_RULES = ("none", "expire_taker")


def check_self_trade(rule) -> None:
    if rule not in SELF_TRADE_RULES:
        raise ValueError(
            f"engine.self_trade must be one of {SELF_TRADE_RULES}, "
            f"got {rule!r}"
        )


class Side(enum.IntEnum):
    """api/order.proto:4-7 — TransactionType {BUY=0, SALE=1}."""

    BUY = 0
    SALE = 1

    @property
    def opposite(self) -> "Side":
        return Side.SALE if self is Side.BUY else Side.BUY


class Action(enum.IntEnum):
    """gomengine/main.go:14-18 — iota consts: ADD=1, DEL=2. NOP=0 is ours
    (padding slot in fixed-shape device op grids)."""

    NOP = 0
    ADD = 1
    DEL = 2


class OrderType(enum.IntEnum):
    """Extension beyond the reference: the proto has no order-type field, so
    every reference order is implicitly a limit order (api/order.proto:10-17;
    SURVEY §1 L5). MARKET is required by BASELINE.json config 5.

    IOC, FOK and POST_ONLY (PR 34) carry FIX 4.4's own numbers: tag 59
    TimeInForce 3 = Immediate or Cancel, 4 = Fill or Kill; tag 18 ExecInst
    6 = Participate don't initiate (post-only). 2 (tag 59 "at the opening":
    this venue has no auction) and 5 stay unassigned and are rejected at the
    gateway like any unknown kind. The rules of each kind are in
    oracle/book.py's docstring. The number is the `kind` byte of the order
    frame and the word the device op carries."""

    LIMIT = 0
    MARKET = 1
    IOC = 3
    FOK = 4
    POST_ONLY = 6


#: Every kind the program knows, ascending: the one list the gateway's
#: columnar check, the order codecs and the fuzzers share.
ORDER_KINDS = tuple(int(k) for k in OrderType)
assert max(ORDER_KINDS) < 8  # known_kinds' table below has eight entries
_KIND_KNOWN = np.isin(np.arange(8), ORDER_KINDS)


def known_kinds(kind: np.ndarray) -> np.ndarray:
    """Elementwise: is this entry of an integer array one of ORDER_KINDS?
    Two compares and a take off an 8-entry table: np.isin costs ~15 us a
    call whatever the size, which every admitted request would pay."""
    return (kind >= 0) & (kind < 8) & _KIND_KNOWN[kind & 7]


def may_rest(kind):
    """True for the add kinds that can leave an order in the book: LIMIT
    and POST_ONLY. A MARKET, IOC or FOK add never rests, so it neither
    counts toward a lane's resting-count bound nor needs its price inside
    the lane's admitted price envelope. Works on an int, an OrderType or a
    numpy array of kinds (elementwise)."""
    return (kind == OrderType.LIMIT) | (kind == OrderType.POST_ONLY)


@dataclass(frozen=True)
class Order:
    """An order in engine-internal form: prices/volumes are *scaled integers*
    (ticks/lots — the value after the reference's 10^accuracy scaling,
    ordernode.go:76-87, held exactly as int instead of float64).
    """

    uuid: str
    oid: str
    symbol: str
    side: Side
    price: int  # scaled ticks; ignored for MARKET
    volume: int  # scaled lots
    action: Action = Action.ADD
    order_type: OrderType = OrderType.LIMIT
    # Order-lifecycle trace context (utils.trace encode_context wire form,
    # "<id>@<t>"). None when tracing is off; excluded from equality so a
    # traced order still compares equal to its untraced twin (replay,
    # oracle parity).
    trace: str | None = field(default=None, compare=False, repr=False)

    def with_volume(self, volume: int) -> "Order":
        return replace(self, volume=volume)


@dataclass(frozen=True)
class OrderSnapshot:
    """The observable fields of an OrderNode as they appear in a MatchResult
    event (engine.go:24-28 serializes whole OrderNodes; the parity surface is
    the subset below — uuid/oid/symbol/side/price/volume; SURVEY §3.4)."""

    uuid: str
    oid: str
    symbol: str
    side: Side
    price: int
    volume: int  # remaining volume at event time (see MatchResult docstring)


@dataclass(frozen=True)
class MatchResult:
    """One fill or cancel event — the parity surface vs the reference.

    Field semantics (engine.go:138-198, engine.go:109-113; SURVEY §3.4):
      * node        — the taker, with volume = remaining AFTER this fill.
      * match_node  — the maker. For a FULL maker fill its volume is the
                      maker's PRE-fill volume (== match_volume); for a
                      PARTIAL maker fill it is the maker's remaining volume
                      after the fill (engine.go:154,171 vs engine.go:178-190).
      * match_volume — traded quantity; 0 ⇒ this is a cancel notice, and
                      node == match_node == the cancelled order with its
                      remaining resting volume (engine.go:109-113).
    Fill price is implicit: match_node.price (the maker's level).
    """

    node: OrderSnapshot
    match_node: OrderSnapshot
    match_volume: int
    # Matchfeed sequence number (monotonic per book epoch; ISSUE 11
    # exactly-once). None when the producer predates seq stamping —
    # excluded from equality so a stamped event still compares equal to
    # its unstamped twin (replay, oracle parity), like Order.trace.
    seq: int | None = field(default=None, compare=False, repr=False)

    @property
    def is_cancel(self) -> bool:
        return self.match_volume == 0


@dataclass
class StepStats:
    """Oracle-side diagnostics (new instrumentation; the reference has none —
    SURVEY §5.5). The device engine's counters live in
    gome_tpu.engine.batch.EngineStats."""

    dropped_no_prepool: int = 0
    cancels_missed: int = 0
    fills: int = 0
    # Adds that expired by their kind's rule (oracle/book.py docstring).
    expired_ioc: int = 0
    fok_killed: int = 0
    post_only_blocked: int = 0
    # Adds of any kind that stopped at their owner's resting order with
    # volume left (self-trade prevention, rule expire_taker).
    stp_expired: int = 0


def snapshot_of(order: Order, volume: int | None = None) -> OrderSnapshot:
    return OrderSnapshot(
        uuid=order.uuid,
        oid=order.oid,
        symbol=order.symbol,
        side=order.side,
        price=order.price,
        volume=order.volume if volume is None else volume,
    )
