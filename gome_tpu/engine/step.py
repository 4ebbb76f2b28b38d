"""The single-op book transition: ADD (match + rest), DEL (cancel), NOP.

This one function replaces the reference's entire consumer hot path —
SetOrder/Match/MatchOrder/DeleteOrder (gomengine/engine/engine.go:56-198) and
all the Redis round trips behind them (SURVEY §3.2: ~6 + 2·levels + 4·fills
RTTs per order) — with a fixed number of O(cap) vector operations:

  match   = prefix mask + one exclusive cumsum + clip      (engine.go:118-198)
  removal = left-shift of the filled prefix                (nodelink.go:124-166)
  rest    = right-shift insert at the priority slot        (nodepool.go:31-46)
  cancel  = masked locate + left-shift                     (engine.go:87-116)

Everything is branch-free (ADD and DEL paths are both computed and selected
by mask) so the function vmaps cleanly across the symbol axis and compiles
to a static XLA graph — no data-dependent control flow, per the TPU design
rules.

TPU lowering discipline — the entire step is gather/scatter-free:

  * Side selection (`own` = the taker's side, `opp` = the opposing side) is
    NOT a dynamic index into the [2, cap] axis (under vmap that lowers to a
    per-row gather, and the write-back to a per-row scatter — both serialize
    badly on TPU). Both rows are read with static slices and selected
    elementwise by the side mask; write-back re-stacks two static rows.
  * The match compaction ("drop the fully-filled prefix of length n") is NOT
    a dynamic-offset gather. It is decomposed into log2(cap) static
    shift-by-2^k passes, each enabled by one bit of n — every pass is a
    static slice + pad + select, which XLA fuses into the surrounding
    elementwise work.
  * Insert/cancel shifts are static shift-by-one selects; the cancel-volume
    read is a masked sum, not a dynamic scalar index.

Scalar semantics are checked against the Python oracle in
tests/test_engine_step.py; the oracle is the spec (SURVEY §7 step 1).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..types import Action, OrderType, may_rest
from .book import BUY, BookConfig, BookState, DeviceOp, StepOutput

# Device-side action codes are the types.Action values (single source of
# truth; they mirror gomengine/main.go:14-18's iota consts).
ACTION_NOP = int(Action.NOP)
ACTION_ADD = int(Action.ADD)
ACTION_DEL = int(Action.DEL)
# The op's kind word holds the wire's number (types.OrderType).
KIND_LIMIT = int(OrderType.LIMIT)
KIND_MARKET = int(OrderType.MARKET)
KIND_IOC = int(OrderType.IOC)
KIND_FOK = int(OrderType.FOK)
KIND_POST_ONLY = int(OrderType.POST_ONLY)
# StepOutput.expired of an add that self-trade prevention stopped: a number
# no kind has (2 and 5 may yet be kinds; 7 lies past FIX's tables).
EXPIRED_STP = 7


def _bsel(c, a, b):
    """Select `a` where `c` else `b`, for a scalar-per-lane bool `c` and
    vector operands. Written as an integer blend (m*a + (1-m)*b) instead of
    jnp.where: under vmap a scalar predicate broadcasts to a [B, cap] i1
    vector, and Mosaic (Pallas TPU) cannot relayout 1-bit vectors across
    the minor dims — the i32 mask broadcast is supported everywhere and
    fuses identically under XLA."""
    m = jnp.asarray(c, a.dtype)
    return m * a + (1 - m) * b


# Saturation ceiling for 32-bit depth prefix sums: with every addend
# clamped here, one Hillis-Steele add of two partials stays below 2^31.
# Exactness argument (int32 operating contract, per-order lots <= LOT_MAX):
# a fill only reads cum_excl through clip(volume - cum_excl, 0, lots), so
# any clamped value >= volume yields the same (zero) fill as the true sum,
# and partials below the clamp are exact.
SAT32_MAX = (1 << 30) - 1
LOT_MAX32 = SAT32_MAX  # documented int32-mode per-order lot ceiling

# Where the packers clamp the rebased limit price of an add in int32 mode.
# An add that can rest keeps its price inside the lane's admitted envelope
# (|price - base| <= BatchEngine._INT32_SAFE = 2^31 - 2), so the clamp only
# ever moves the limit of an add that cannot rest (IOC, FOK; types.may_rest),
# which feeds no envelope: one past the farthest representable resting price,
# it crosses exactly the resting orders the true limit crosses.
TAKER_PRICE_MAX32 = (1 << 31) - 1


def _prefix_sum(a):
    """Inclusive prefix sum along the last axis via Hillis-Steele log-shift
    passes (static slice + pad + add). Used instead of jnp.cumsum because
    Mosaic (Pallas TPU) has no cumsum lowering; XLA fuses the passes into the
    surrounding elementwise work either way.

    32-bit inputs saturate at SAT32_MAX instead of wrapping — fills stay
    exact (see SAT32_MAX) no matter how deep the crossed book is."""
    n = a.shape[-1]
    sat = jnp.dtype(a.dtype).itemsize <= 4
    if sat:
        a = jnp.minimum(a, SAT32_MAX)
    k = 1
    while k < n:
        pad = [(0, 0)] * (a.ndim - 1) + [(k, 0)]
        a = a + jnp.pad(a[..., :-k], pad)
        if sat:
            a = jnp.minimum(a, SAT32_MAX)
        k *= 2
    return a


def _shl1(a):
    """Static shift-by-one toward index 0, zero-filling the tail."""
    return jnp.pad(a[1:], (0, 1))


def _shr1_last(a):
    """Shift-by-one away from index 0 along the LAST axis (any rank),
    zero-filling the head."""
    pad = [(0, 0)] * (a.ndim - 1) + [(1, 0)]
    return jnp.pad(a[..., :-1], pad)


def _shr1(a):
    """Static shift-by-one away from index 0, zero-filling the head."""
    return jnp.pad(a[:-1], (1, 0))


class _Side(NamedTuple):
    """One side's slot arrays (a row of each BookState array)."""

    price: jax.Array
    lots: jax.Array
    seq: jax.Array
    oid: jax.Array
    uid: jax.Array

    def shift_left(self, by, cap: int) -> "_Side":
        """Drop `by` leading slots (removals always form a prefix after a
        match; an arbitrary slot for cancels is handled by _remove).

        `by` is data-dependent, so a direct a[i + by] lowers to a per-lane
        gather under vmap. Instead: binary-decompose the shift into static
        shift-by-2^k slices, each selected by bit k of `by` — O(log cap)
        fused elementwise passes, no gather (SURVEY §7 hard part (a), done
        the XLA-friendly way).
        """
        out = list(self)
        k = 0
        while (1 << k) <= cap:
            sh = 1 << k
            on = ((by >> k) & 1) != 0

            def g(a, sh=sh, on=on):
                if sh >= cap:
                    # Whole-array shift: avoid the zero-size slice a[cap:]
                    # (Mosaic rejects 0-length vectors).
                    shifted = jnp.zeros_like(a)
                else:
                    shifted = jnp.pad(a[sh:], (0, sh))
                return _bsel(on, shifted, a)

            out = [g(a) for a in out]
            k += 1
        return _Side(*out)


def _match(
    config: BookConfig, opp: _Side, opp_count, side, price, volume, kind, uid
):
    """Fill the crossing prefix of the opposing side, as the add's kind
    and the venue's self-trade rule allow.

    Crossing rule (nodepool.go:86-115): BUY taker hits asks with price <=
    limit; SALE taker hits bids with price >= limit; MARKET (extension)
    hits every active order. Because the side is priority-sorted, crossing
    slots are a contiguous prefix, so "walk levels best-first, FIFO within
    level" (engine.go:118-136) degenerates to elementwise arithmetic.

    Two kinds fill all or nothing (oracle/book.py has the rules): a FOK
    add that the whole crossing prefix cannot fill, and a POST_ONLY add
    that would take anything, leave the book as it was. Both are decided
    from the fill itself and blended in as an i32 mask (as _bsel does): no
    branch, no new reduction. Returns `killed` (bool) beside the usual.

    Who may trade with whom (config.self_trade, static): under "none", the
    reference's, the owners are never compared and an account fills its own
    resting order like any other. Under "expire_taker" the crossing prefix
    is cut at its first slot whose uid is the taker's: the add fills the
    slots ahead of it as it always did, and if volume is left when it
    arrives there, what is left expires (`stp`: it does not trade with
    that order, does not pass it and does not rest; step_rows_impl drops
    it). The own order is never touched. A FOK add counts only the lots
    ahead of the cut; a POST_ONLY add is blocked by the uncut prefix,
    whoever owns its first order. The cut prefix is still a prefix, so the
    fill records, fill_overflow and the compaction below do not change;
    the cost is one compare and one masked integer minimum over [cap].
    Returns `stp` (bool, or None where the venue has no rule).
    """
    cap = config.cap
    k = config.max_fills
    idx = jnp.arange(cap, dtype=jnp.int32)
    active = idx < opp_count
    # The side/market predicates are scalar-per-lane; combine them with the
    # [cap] masks through i32 blends (_bsel) — a scalar i1 broadcast against
    # a vector has no Mosaic relayout.
    le = (opp.price <= price).astype(jnp.int32)
    ge = (opp.price >= price).astype(jnp.int32)
    mkt = (kind == KIND_MARKET).astype(jnp.int32)
    crosses = jnp.maximum(_bsel(side == BUY, le, ge), mkt)
    crossing = active & (crosses != 0)
    own_in_c = None
    if config.self_trade == "expire_taker":
        # The first own slot of C, cap where C holds none. An integer
        # minimum, as _remove's reductions are integer sums (Mosaic lowers
        # a boolean reduction through a float).
        first_own = jnp.min(
            jnp.where(crossing & (opp.uid == uid), idx, cap)
        ).astype(jnp.int32)
        own_in_c = first_own < cap
        crossing = crossing & (idx < first_own)

    clots = jnp.where(crossing, opp.lots, 0)
    # Exclusive prefix = inclusive prefix of the shifted array — computed
    # directly (not incl - clots) so the 32-bit saturating scan stays
    # consistent: subtracting an unclamped addend from a clamped total
    # would under-report the depth ahead of a slot.
    cum_excl = _prefix_sum(_shr1_last(clots))
    fill = jnp.clip(volume - cum_excl, 0, clots)
    # What the whole crossing prefix C can give this add: min(volume,
    # avail), exactly. Where the 32-bit prefix sum saturated it read at
    # least SAT32_MAX >= volume (volume <= LOT_MAX32), so the slots behind
    # fill nothing, as under the true sum; below the clamp it is exact.
    total = jnp.sum(fill)
    # FOK: avail >= volume  <=>  total == volume. POST_ONLY: C is not
    # empty  <=>  total > 0 (resting lots and volumes are positive, so the
    # first crossing slot always fills something).
    fok_short = (kind == KIND_FOK) & (total < volume)
    is_post_only = kind == KIND_POST_ONLY
    took = total > 0
    if own_in_c is not None:
        # The uncut C is not empty where the cut one is not, or where it
        # held an own order at all (at its head, the cut one is empty).
        took = took | own_in_c
    killed = fok_short | (is_post_only & took)
    live = 1 - killed.astype(fill.dtype)
    fill = fill * live
    total = total * live
    remaining = volume - total
    stp = None
    if own_in_c is not None:
        stp = own_in_c & (remaining > 0) & ~killed

    new_lots = opp.lots - fill
    fully_filled = (fill > 0) & (new_lots == 0)  # a prefix of the array
    n_removed = jnp.sum(fully_filled).astype(jnp.int32)
    n_fills = jnp.sum(fill > 0).astype(jnp.int32)

    # Fill records: fills occupy slots [0, n_fills) pre-compaction.
    rec = slice(0, k)
    taker_after = volume - (cum_excl[rec] + fill[rec])
    out = dict(
        fill_price=opp.price[rec],
        fill_qty=fill[rec],
        maker_oid=opp.oid[rec],
        maker_uid=opp.uid[rec],
        maker_prefill=opp.lots[rec],
        maker_remaining=new_lots[rec],
        taker_after=jnp.where(fill[rec] > 0, taker_after, 0),
        n_fills=n_fills,
        fill_overflow=jnp.maximum(n_fills - k, 0).astype(jnp.int32),
    )

    compacted = opp._replace(lots=new_lots).shift_left(n_removed, cap)
    return compacted, opp_count - n_removed, remaining, killed, stp, out


def _insert(config: BookConfig, own: _Side, own_count, entry: _Side, side):
    """Rest the remainder at its own limit price (engine.go:69-83): insert
    at the last slot whose priority beats or equals the new order — existing
    same-price orders keep time priority (nodelink.go:53-64)."""
    cap = config.cap
    idx = jnp.arange(cap, dtype=jnp.int32)
    active = idx < own_count
    ge = (own.price >= entry.price).astype(jnp.int32)
    le = (own.price <= entry.price).astype(jnp.int32)
    beats = _bsel(side == BUY, ge, le) != 0
    pos = jnp.sum(active & beats).astype(jnp.int32)
    overflow = own_count >= cap

    def ins(a, v):
        shifted = jnp.where(idx > pos, _shr1(a), a)
        return jnp.where(idx == pos, jnp.asarray(v, a.dtype), shifted)

    new = _Side(*(ins(a, v) for a, v in zip(own, entry)))
    new = jax.tree.map(lambda n, o: _bsel(overflow, o, n), new, own)
    return new, jnp.where(overflow, own_count, own_count + 1), overflow


def _remove(config: BookConfig, own: _Side, own_count, oid, price):
    """Cancel lookup + unlink (engine.go:87-116): requires the exact resting
    price (SURVEY §2.3.2 — the reference looks up S:link:P by price); no
    ownership check (uid is deliberately not compared, under either
    self-trade rule: the rule is about who trades with whom, and a cancel
    trades with no one)."""
    cap = config.cap
    idx = jnp.arange(cap, dtype=jnp.int32)
    active = idx < own_count
    hit = active & (own.oid == oid) & (own.price == price)
    # Integer reduction, not jnp.any: Mosaic lowers boolean reductions
    # through a float max, which is unsupported for some widths.
    found = jnp.sum(hit.astype(jnp.int32)) > 0
    # oids unique by contract, so the hit mask has at most one set slot:
    # masked sums replace the dynamic argmax-index reads (gather-free).
    pos = jnp.sum(jnp.where(hit, idx, 0)).astype(jnp.int32)
    volume = jnp.sum(jnp.where(hit, own.lots, 0))

    def rm(a):
        return jnp.where(idx >= pos, _shl1(a), a)

    removed = _Side(*(rm(a) for a in own))
    new = jax.tree.map(lambda n, o: _bsel(found, n, o), removed, own)
    return new, jnp.where(found, own_count - 1, own_count), found, volume


def step_rows_impl(
    config: BookConfig,
    buy: _Side,
    sale: _Side,
    buy_count,
    sale_count,
    next_seq,
    op: DeviceOp,
) -> tuple[_Side, _Side, jax.Array, jax.Array, jax.Array, StepOutput]:
    """Apply one op to one symbol's book, given as separate per-side rows.

    This is the core the Pallas kernel calls directly (per-side [cap] rows
    tile densely in VMEM; a [2, cap] side axis would stack/unstack every
    step). step_impl wraps it for the [2, cap] BookState representation.

    Both the ADD path (match + rest) and the DEL path (cancel) are computed
    unconditionally and mask-selected — under vmap over symbols `lax.cond`
    would degenerate to the same thing, and branch-free code keeps the XLA
    graph static (TPU design rule: no data-dependent control flow).
    """
    s = op.side
    is_add = op.action == ACTION_ADD
    is_del = op.action == ACTION_DEL
    is_buy = s == BUY

    own0 = _Side(*(_bsel(is_buy, b, a) for b, a in zip(buy, sale)))
    opp0 = _Side(*(_bsel(is_buy, a, b) for b, a in zip(buy, sale)))
    own_count0 = jnp.where(is_buy, buy_count, sale_count)
    opp_count0 = jnp.where(is_buy, sale_count, buy_count)

    # --- ADD: match against the opposing side -------------------------------
    opp1, opp_count1, remaining, killed, stp, fills = _match(
        config, opp0, opp_count0, s, op.price, op.volume, op.kind, op.uid
    )

    # --- ADD: rest the remainder: a LIMIT add's, or a POST_ONLY add that
    # took nothing (types.may_rest); a MARKET or IOC remainder is dropped
    # and a killed FOK leaves nothing to rest (extensions: the reference
    # has limit orders only); nor does what self-trade prevention stopped --
    do_rest = is_add & (remaining > 0) & may_rest(op.kind) & ~killed
    expired = is_add & (
        killed | ((op.kind == KIND_IOC) & (remaining > 0))
    )
    if stp is not None:
        # Stopped at its owner's order with volume left: whatever the
        # kind, the remainder does not rest.
        do_rest = do_rest & ~stp
    entry = _Side(
        price=op.price,
        lots=remaining,
        seq=next_seq + 1,
        oid=op.oid,
        uid=op.uid,
    )
    own1, own_count1, overflow = _insert(config, own0, own_count0, entry, s)

    # --- DEL: cancel --------------------------------------------------------
    own2, own_count2, found, cancel_volume = _remove(
        config, own0, own_count0, op.oid, op.price
    )

    # --- select & write back ------------------------------------------------
    def sel(add_side, del_side, nop_side):
        return jax.tree.map(
            lambda a, d, n: _bsel(is_add, a, _bsel(is_del, d, n)),
            add_side,
            del_side,
            nop_side,
        )

    own_final = sel(
        jax.tree.map(lambda r, o_: _bsel(do_rest, r, o_), own1, own0),
        own2,
        own0,
    )
    own_count_final = jnp.where(
        is_add,
        jnp.where(do_rest, own_count1, own_count0),
        jnp.where(is_del, own_count2, own_count0),
    )
    opp_final = sel(opp1, opp0, opp0)
    opp_count_final = jnp.where(is_add, opp_count1, opp_count0)

    new_buy = _Side(
        *(_bsel(is_buy, o_, p) for o_, p in zip(own_final, opp_final))
    )
    new_sale = _Side(
        *(_bsel(is_buy, p, o_) for o_, p in zip(own_final, opp_final))
    )
    new_buy_count = jnp.where(is_buy, own_count_final, opp_count_final)
    new_sale_count = jnp.where(is_buy, opp_count_final, own_count_final)
    new_next_seq = jnp.where(do_rest, next_seq + 1, next_seq)

    zero = jnp.zeros((), config.dtype)
    out = StepOutput(
        fill_price=_bsel(is_add, fills["fill_price"], 0),
        fill_qty=_bsel(is_add, fills["fill_qty"], 0),
        maker_oid=_bsel(is_add, fills["maker_oid"], 0),
        maker_uid=_bsel(is_add, fills["maker_uid"], 0),
        maker_prefill=_bsel(is_add, fills["maker_prefill"], 0),
        maker_remaining=_bsel(is_add, fills["maker_remaining"], 0),
        taker_after=_bsel(is_add, fills["taker_after"], 0),
        n_fills=jnp.where(is_add, fills["n_fills"], 0),
        fill_overflow=jnp.where(is_add, fills["fill_overflow"], 0),
        taker_remaining=jnp.where(is_add, remaining, zero),
        rested=(do_rest & ~overflow).astype(jnp.int32),
        book_overflow=(do_rest & overflow).astype(jnp.int32),
        cancel_found=(is_del & found).astype(jnp.int32),
        cancel_volume=jnp.where(is_del, cancel_volume, zero),
        expired=jnp.where(expired, op.kind, 0).astype(jnp.int32),
    )
    if stp is not None:
        # Such an add counts under the venue's rule alone, whatever its
        # kind (a killed add did nothing and keeps its kind's count).
        out = out._replace(
            expired=jnp.where(is_add & stp, EXPIRED_STP, out.expired)
        )
    return new_buy, new_sale, new_buy_count, new_sale_count, new_next_seq, out


def step_impl(
    config: BookConfig, book: BookState, op: DeviceOp
) -> tuple[BookState, StepOutput]:
    """Apply one op to one symbol's [2, cap] BookState. Pure, jittable,
    vmap-able. Thin wrapper over step_rows_impl: unstack the side axis with
    static slices, run the rows core, restack (the stack is XLA-only — the
    Pallas kernel keeps per-side rows and never pays it)."""
    buy = _Side(*(getattr(book, n)[0] for n in _Side._fields))
    sale = _Side(*(getattr(book, n)[1] for n in _Side._fields))
    new_buy, new_sale, nb, ns, nseq, out = step_rows_impl(
        config, buy, sale, book.count[0], book.count[1], book.next_seq, op
    )
    new_book = BookState(
        price=jnp.stack([new_buy.price, new_sale.price]),
        lots=jnp.stack([new_buy.lots, new_sale.lots]),
        seq=jnp.stack([new_buy.seq, new_sale.seq]),
        oid=jnp.stack([new_buy.oid, new_sale.oid]),
        uid=jnp.stack([new_buy.uid, new_sale.uid]),
        count=jnp.stack([nb, ns]),
        next_seq=nseq,
    )
    return new_book, out


# Jitted entry point for single-op use (tests, debugging). Batched execution
# nests step_impl under scan/vmap instead (gome_tpu.engine.batch). The book
# is donated (gomelint GL601): callers thread it through (`book, out =
# step(config, book, op)`), so the input book is dead on return — without
# donation every single-op step double-buffers the book. The scalar op is
# NOT donated: its leaves mostly cannot alias an output (XLA would warn
# "donated buffers were not usable" on every compile) and the win is a few
# bytes. Do NOT reuse a book object across step calls (gomelint GL603
# flags it; donation-supporting backends raise "Array has been deleted").
step = functools.partial(jax.jit, static_argnums=0,
                         donate_argnums=(1,))(step_impl)
