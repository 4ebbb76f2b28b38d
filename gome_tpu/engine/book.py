"""Fixed-shape, array-resident order-book state for one symbol.

This is the TPU re-expression of the reference's Redis schema (SURVEY §2.1):
the S:BUY/S:SALE price zsets, the S:depth volume hash, and the S:link:P
hash-encoded FIFO linked lists (gomengine/engine/nodepool.go,
gomengine/engine/nodelink.go) all collapse into five [2, CAP] integer arrays
kept sorted in *priority order* per side:

  * side 0 (BUY bids):  descending price, FIFO (ascending seq) within price
  * side 1 (SALE asks): ascending price,  FIFO (ascending seq) within price

Active orders occupy a contiguous prefix of length ``count[side]``; slot 0 is
always the best-priority resting order. Keeping the invariant "sorted,
prefix-packed" turns the reference's O(levels x orders) pointer-chasing match
loop (engine.go:118-198) into branch-free vector ops: a crossing mask is a
prefix, fill quantities are one exclusive cumsum, removals are a left-shift
gather, and inserts are a right-shift gather — no `lax.while_loop`, no
data-dependent shapes, fully `vmap`-able across thousands of symbols.

Prices and volumes are scaled integer ticks/lots (see gome_tpu.fixed);
oid/uid are integer handles interned by the host bridge (the string ids of
api/order.proto:11-12 never reach the device).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..types import check_self_trade

BUY = 0
SALE = 1


@dataclasses.dataclass(frozen=True)
class BookConfig:
    """Static (compile-time) book geometry.

    cap      — max resting orders per side per symbol. The reference's book
               is unbounded (Redis); fixed capacity is the §5.7 "windowed
               ladder" trade: overflow is reported and spilled to the host
               slow path, never silently dropped.
    max_fills — fill records emitted per op (K). An op crossing more than K
               resting orders still mutates the book exactly; records beyond
               K are counted in `fill_overflow` and recovered by the host
               slow path (SURVEY §7 hard part (c)).
    dtype    — lot/price dtype. int64 (default) matches the reference's
               exact-integer envelope at accuracy=8 (SURVEY §2.2); int32 is
               available when tick/lot ranges allow, halving HBM traffic.
    self_trade — the venue's self-trade prevention rule
               (types.SELF_TRADE_RULES; step._match has it): "none", the
               reference's, lets an account trade with itself;
               "expire_taker" stops an add at its owner's first resting
               order and expires what is left. A rule of the venue, as the
               accuracy is: the two give different events. Static, so a
               venue without the rule traces the step it always traced.
    """

    cap: int = 256
    max_fills: int = 16
    dtype: jnp.dtype = jnp.int64
    self_trade: str = "none"

    def __post_init__(self) -> None:
        check_self_trade(self.self_trade)

    @property
    def seq_dtype(self):
        return jnp.int32


class BookState(NamedTuple):
    """One symbol's book. All arrays [2, cap] except count [2] and the
    per-symbol arrival counter next_seq [] (the time-priority stamp that the
    reference keeps implicitly as linked-list position, nodelink.go:53-64)."""

    price: jax.Array
    lots: jax.Array  # remaining lots; 0 <=> slot empty (beyond count)
    seq: jax.Array
    oid: jax.Array
    uid: jax.Array
    count: jax.Array
    next_seq: jax.Array


class DeviceOp(NamedTuple):
    """One operation in device form (the OrderNode fields that matter on
    device; ordernode.go:9-36 minus the Redis key plumbing). Scalars here;
    batched versions carry leading axes."""

    action: jax.Array  # i32: 0=NOP, 1=ADD, 2=DEL (gomengine/main.go:14-18)
    side: jax.Array  # i32: 0=BUY, 1=SALE (api/order.proto:4-7)
    kind: jax.Array  # i32: types.OrderType, the wire's number (0 on a DEL/NOP)
    price: jax.Array  # dtype ticks
    volume: jax.Array  # dtype lots
    oid: jax.Array  # dtype interned order id
    uid: jax.Array  # dtype interned user id


#: DeviceOp fields carried as int32 regardless of the book value dtype.
#: Grid packers (the numpy path in engine.frames and the native
#: nativehost.pack_grid) share this rule so both produce identically
#: typed DeviceOp grids.
GRID_I32_FIELDS = ("action", "side", "kind")


class StepOutput(NamedTuple):
    """Fixed-shape per-op result — everything the host needs to reconstruct
    the reference's MatchResult event stream (SURVEY §3.4) for this op.

    Fill j (j < min(n_fills, K)) reconstructs to one fill event:
      maker volume field = maker_prefill[j] if maker_remaining[j]==0 (full
      fill, engine.go:154,171) else maker_remaining[j] (partial,
      engine.go:190); taker volume field = taker_after[j].
    """

    fill_price: jax.Array  # [K] maker level price (the fill price)
    fill_qty: jax.Array  # [K] traded lots
    maker_oid: jax.Array  # [K]
    maker_uid: jax.Array  # [K]
    maker_prefill: jax.Array  # [K] maker lots before this fill
    maker_remaining: jax.Array  # [K] maker lots after this fill
    taker_after: jax.Array  # [K] taker remaining after fill j
    n_fills: jax.Array  # i32 total fills (may exceed K)
    fill_overflow: jax.Array  # i32 fills not captured in records
    taker_remaining: jax.Array  # taker lots left after matching
    rested: jax.Array  # i32 bool: remainder rested in the book
    book_overflow: jax.Array  # i32 bool: rest dropped, side full
    cancel_found: jax.Array  # i32 bool: DEL matched a resting order
    cancel_volume: jax.Array  # lots remaining at cancel (engine.go:100)
    # i32: 0, or the kind of an add that expired by its kind's rule (IOC:
    # a remainder was dropped; FOK: killed; POST_ONLY: blocked), or
    # step.EXPIRED_STP for an add of any kind that stopped at its owner's
    # resting order with volume left (BookConfig.self_trade). A code and
    # not a flag, so that the frame's totals count each by a compare.
    expired: jax.Array


def ensure_dtype_usable(dtype) -> None:
    """int64 books silently degrade to int32 when jax's x64 mode is off —
    wrong matching arithmetic (depth prefix sums overflow), not an error.
    Enable x64 on the user's behalf (with a warning, since it is global
    config) rather than let that happen.

    Exception: once the Pallas kernel module has traced anything, flipping
    jax_enable_x64 mid-process can send a later retrace into infinite
    recursion through the dtype-promotion cache (documented in
    scripts/fuzz.py, observed on TPU). In that state the flip is refused
    with an actionable error instead — set JAX_ENABLE_X64=1 before startup."""
    if jnp.dtype(dtype).itemsize == 8 and not jax.config.jax_enable_x64:
        import sys

        if "gome_tpu.ops.pallas_match" in sys.modules:
            raise RuntimeError(
                "BookConfig dtype is 64-bit but jax_enable_x64 is off, and "
                "the Pallas kernel module is already loaded — flipping x64 "
                "now can corrupt jax's trace caches. Set JAX_ENABLE_X64=1 "
                "before process start (or use an int32 BookConfig)."
            )
        import warnings

        warnings.warn(
            "BookConfig dtype is 64-bit but jax_enable_x64 is off; enabling "
            "it globally (set JAX_ENABLE_X64=1 or use an int32 BookConfig "
            "to silence this)",
            stacklevel=3,
        )
        jax.config.update("jax_enable_x64", True)


def init_book(config: BookConfig) -> BookState:
    ensure_dtype_usable(config.dtype)
    shape = (2, config.cap)
    # One jnp.zeros call PER field: sharing a single zeros array across
    # leaves would alias their device buffers, and a donated book (the
    # single-op `step` entry donates its input, gomelint GL6xx) then trips
    # XLA's "attempt to donate the same buffer twice".
    z = lambda: jnp.zeros(shape, config.dtype)
    return BookState(
        price=z(),
        lots=z(),
        seq=jnp.zeros(shape, config.seq_dtype),
        oid=z(),
        uid=z(),
        count=jnp.zeros((2,), jnp.int32),
        next_seq=jnp.zeros((), config.seq_dtype),
    )


def init_books(config: BookConfig, n_symbols: int) -> BookState:
    """A stacked [n_symbols, ...] book pytree (leading symbol axis — the
    vmap/sharding axis; SURVEY §2.1 "symbol isolation")."""
    one = init_book(config)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_symbols,) + x.shape), one
    )


def grow_books(books: BookState, new_cap: int) -> BookState:
    """Widen the slot axis of a book (or stacked-book) pytree to `new_cap`,
    zero-padding the tail. Active slots are a prefix (book.py invariant), so
    padding on the right preserves every book exactly — this is the host
    "spill" escape hatch for the fixed-width ladder (SURVEY §5.7): when a
    side fills up (`book_overflow`), the engine re-runs the batch from the
    pre-batch snapshot on grown books instead of dropping the insert.
    """
    cap = books.price.shape[-1]
    if new_cap < cap:
        raise ValueError(f"cannot shrink cap {cap} -> {new_cap}")
    if new_cap == cap:
        return books
    pad = [(0, 0)] * (books.price.ndim - 1) + [(0, new_cap - cap)]

    def widen(a):
        return jnp.pad(a, pad)

    return books._replace(
        price=widen(books.price),
        lots=widen(books.lots),
        seq=widen(books.seq),
        oid=widen(books.oid),
        uid=widen(books.uid),
    )


def book_depth(book: BookState, side: int, max_levels: int):
    """Aggregate [price, volume] depth view, best-first — the observable
    equivalent of the reference's S:BUY/S:SALE zset + S:depth hash
    (nodepool.go:61-83). Returns (prices[max_levels], volumes[max_levels],
    n_levels) as int64 numpy arrays; unused slots are zero.

    A host-side view: the caller typically passes a
    BatchEngine.lane_books() book whose price leaf is already absolute
    int64 — running this through jnp with x64 off would silently truncate
    rebased-absolute prices back to 32 bits. Device-resident books are
    pulled host-side in one transfer up front.
    """
    count, price, lots = jax.device_get(
        (book.count[side], book.price[side], book.lots[side])
    )
    n_active = int(count)
    price = np.asarray(price[:n_active], dtype=np.int64)
    lots = np.asarray(lots[:n_active], dtype=np.int64)
    prices = np.zeros(max_levels, np.int64)
    volumes = np.zeros(max_levels, np.int64)
    # slots are priority-sorted, so equal prices are contiguous runs
    n = 0
    i = 0
    while i < n_active and n < max_levels:
        j = i
        while j < n_active and price[j] == price[i]:
            j += 1
        prices[n] = price[i]
        volumes[n] = lots[i:j].sum()
        n += 1
        i = j
    # n is clipped to max_levels: a book with more distinct levels than
    # max_levels is truncated (best-first).
    return prices, volumes, np.int32(n)
