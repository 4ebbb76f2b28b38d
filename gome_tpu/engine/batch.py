"""Batched execution: scan over time within a symbol, vmap across symbols.

This is the execution model that replaces the reference's one-order-at-a-time
consumer loop (rabbitmq.go:116-125): the host packs a micro-batch of orders
into a dense [S, T] op grid — S symbol lanes, T time slots, NOP-padded — and
the device applies all of it in one compiled call:

    books'[s], outs[s, :] = scan(step, books[s], ops[s, :])   for all s (vmap)

Two invariants make this exactly equivalent to sequential processing:
  * same-symbol operations never split across concurrent lanes and keep
    arrival order within the lane (SURVEY §5.2: the serialized-per-symbol
    invariant, the reference's correctness-by-single-threadedness);
  * symbols share nothing (SURVEY §2.1), so cross-symbol interleaving is
    irrelevant to book state — the host re-sorts decoded events by original
    arrival index to reproduce the reference's global emission order.

Fixed device budgets (book capacity, K fill records) never cost exactness:
the engine keeps the pre-batch book snapshot and, when a budget trips,
escalates — grows the book slot axis and re-runs the whole grid, or re-runs
one lane with a larger record budget — before decoding (SURVEY §7 hard
parts (a)/(c): overflow is recovered, never silently dropped; the reference
has no budgets because Redis is unbounded).

The [S] symbol axis is also the sharding axis: lanes are independent, so
pjit partitions the whole grid across chips with zero collectives
(gome_tpu.parallel).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.placement import PLACEMENT
from ..obs.profiler import PROFILER
from ..types import KERNELS, MatchResult, Order
from ..utils.metrics import REGISTRY
from ..utils.tracing import span
from . import placement
from .book import (
    BUY,
    BookConfig,
    BookState,
    DeviceOp,
    StepOutput,
    ensure_dtype_usable,
    grow_books,
    init_books,
)
from .host import Interner
from .step import ACTION_ADD, _Side, step_rows_impl

# The donating twins donate the whole ops pytree; XLA reuses most of its
# buffers for the [S, T] outputs but (depending on layout/CSE) not all,
# and warns "Some donated buffers were not usable" once per compiled
# shape. That partial reuse is the intended trade (jax FAQ: filter the
# warning when donation is deliberate); the unusable buffers are simply
# freed.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

#: Dense-dispatch skew telemetry (ROADMAP open item 2): each dense grid
#: observes dispatched-rows / live-lanes — the row-padding tax the pow2
#: bucketing (and, under a mesh, the per-shard MAX bucketing that
#: `scripts/mesh_overhead.py --skew` measures at 3.7x for D=8 Zipf) makes
#: the device pay. The p50 gauge is the placement target the ROADMAP sets
#: (<= 2.0); the histogram carries the tail. Ratio buckets, not seconds.
_ROWS_PER_LANE_BUCKETS = (
    1.0, 1.25, 1.5, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0,
)
_rows_per_live_lane = REGISTRY.histogram(
    "gome_dispatched_rows_per_live_lane",
    "dense-grid dispatched rows per live lane (row-padding/skew tax)",
    buckets=_ROWS_PER_LANE_BUCKETS,
)
REGISTRY.callback_gauge(
    "gome_dispatched_rows_per_live_lane_p50",
    "median dispatched-rows/live-lane across dense dispatches "
    "(ROADMAP open item 2 targets <= 2.0)",
    lambda: _rows_per_live_lane.quantile(0.5),
)
#: Per-shard skew companion (measured axis of the same open item): each
#: dense MESH dispatch observes max-shard-live / mean-shard-live — 1.0 is
#: perfectly balanced; the per-shard MAX bucketing makes dispatched rows
#: (and so device time) scale with this ratio, not with total live work.
_dense_shard_skew = REGISTRY.histogram(
    "gome_dense_shard_skew",
    "dense mesh dispatch max/mean live lanes per shard (1.0 = balanced)",
    buckets=_ROWS_PER_LANE_BUCKETS,
)
REGISTRY.callback_gauge(
    "gome_dense_shard_skew_p50",
    "median per-shard live-lane skew across dense mesh dispatches",
    lambda: _dense_shard_skew.quantile(0.5),
)


def _book_to_rows(book: BookState):
    """BookState -> per-side rows carry (static slices, done ONCE per grid).
    The scan carries rows so no step pays the [2, cap] side-axis restack
    (5 jnp.stack materializations per step in the naive form)."""
    buy = _Side(*(getattr(book, n)[..., 0, :] for n in _Side._fields))
    sale = _Side(*(getattr(book, n)[..., 1, :] for n in _Side._fields))
    return (buy, sale, book.count[..., 0], book.count[..., 1], book.next_seq)


def _rows_to_book(rows) -> BookState:
    buy, sale, nb, ns, nseq = rows
    pair = lambda b, a: jnp.stack([b, a], axis=-2)
    return BookState(
        price=pair(buy.price, sale.price),
        lots=pair(buy.lots, sale.lots),
        seq=pair(buy.seq, sale.seq),
        oid=pair(buy.oid, sale.oid),
        uid=pair(buy.uid, sale.uid),
        count=jnp.stack([nb, ns], axis=-1),
        next_seq=nseq,
    )


def _lane_scan_impl(config: BookConfig, book: BookState, ops_lane: DeviceOp):
    """One symbol's op sequence on one (unstacked) book — the single shared
    scan body for both the full grid (under vmap) and escalation re-runs."""

    def body(rows, op):
        buy, sale, nb, ns, nseq = rows
        buy, sale, nb, ns, nseq, out = step_rows_impl(
            config, buy, sale, nb, ns, nseq, op
        )
        return (buy, sale, nb, ns, nseq), out

    rows, outs = jax.lax.scan(body, _book_to_rows(book), ops_lane)
    return _rows_to_book(rows), outs


def _batch_step_impl(
    config: BookConfig, books: BookState, ops: DeviceOp
) -> tuple[BookState, StepOutput]:
    """books: [S, ...] stacked BookState; ops: DeviceOp with [S, T] leaves.
    Returns updated books and [S, T]-shaped StepOutputs.

    The stack's slot axis may be WIDER than config.cap (a per-grid cap
    class, VERDICT r4 #2): the step then runs on the [.., :cap] slice —
    per-step cost tracks the grid's own depth class, not the storage cap
    one hot lane escalated — and writes the slice back. Exactness is
    guarded by _guard_capped."""
    cap = config.cap
    sub = _slice_books_cap(books, cap)
    pre_counts = books.count
    sub, outs = jax.vmap(lambda b, o: _lane_scan_impl(config, b, o))(sub, ops)
    outs = _guard_capped(outs, pre_counts, cap, ops)
    if books.price.shape[-1] == cap:
        return sub, outs
    return _writeback_full_cap(books, sub, cap), outs


# Two jit wrappers per entry, one trace cache each (both precompiled the
# same way — shape combos are recorded per wrapper identity):
#
#   * the PUBLIC entry donates nothing: parity tests/benches replay the
#     same books/ops through several kernels, and the books argument is
#     retained by _run_exact for escalation replay and by _checkpoint for
#     the transactional rollback (the "double-buffer" the GL6xx audit
#     flags IS the transaction mechanism — see ARCHITECTURE.md);
#   * the `_donating` twin donates the ops-grid transfer buffers. _step
#     dispatches to it exactly when the grid is HOST-sourced (numpy):
#     every dispatch then re-transfers, so the device copy is provably
#     dead and XLA reuses it for the [S, T] outputs instead of allocating
#     fresh ones. The one packer (frames.pack_frame_grids) builds its
#     grids on the device and they stay undonated: the escalation path
#     re-dispatches the same arrays. No caller hands _step a host grid
#     any more (ROADMAP C5: the twins and the switch go together).
batch_step = functools.partial(  # gomelint: disable=GL601 — see note above
    jax.jit, static_argnums=0
)(_batch_step_impl)
batch_step_donating = functools.partial(  # gomelint: disable=GL601 — see above
    jax.jit, static_argnums=0, donate_argnums=(2,)
)(_batch_step_impl)


lane_scan = functools.partial(  # gomelint: disable=GL601 — parity entry
    jax.jit, static_argnums=0
)(_lane_scan_impl)
#: Escalation re-runs (_run_exact phase 2) build a fresh one-lane book
#: slice and op row per call — both dead on return, so both donate.
lane_scan_donating = functools.partial(
    jax.jit, static_argnums=0, donate_argnums=(1, 2)
)(_lane_scan_impl)


def _dense_batch_step_impl(
    config: BookConfig, books: BookState, lane_ids, ops: DeviceOp
):
    """Gather→scan→scatter over a compact set of LIVE lanes.

    Skewed real-world flow (BASELINE config 4: Zipf arrivals over 10K
    symbols) leaves most of a full [S, T] grid as NOP padding — the device
    would spend >99% of its work stepping idle books. This step instead
    gathers the R live lanes' books into a dense [R, ...] sub-stack, scans
    a compact [R, T] op grid (T can be much deeper than max_t,
    amortizing dispatch for hot symbols — the config 1-2 latency path;
    depth is BatchEngine._grid_depth's, for full grids too), and scatters
    the sub-stack back. Cost: one O(S) copy for the
    scatter (XLA preserves the un-donated input) plus O(R·T) matching work,
    vs O(S·T) matching work for the full grid.

    lane_ids: [R] int32, padded to the compile-bucketed row count with an
    out-of-range sentinel (>= S). Sentinel rows gather zero books
    (mode="fill"), scan pure-NOP op rows (the packer guarantees this), and
    are dropped by the scatter (mode="drop") — no aliasing, no branches.

    Like batch_step, the gather restricts the slot axis to config.cap —
    the grid's cap class — so tail-lane grids never pay a hot lane's
    escalated storage depth (_guard_capped covers mis-classed lanes).
    """
    cap = config.cap
    base = _slice_books_cap(books, cap)
    sub = jax.tree.map(
        lambda a: jnp.take(a, lane_ids, axis=0, mode="fill", fill_value=0),
        base,
    )
    pre_counts = sub.count
    sub, outs = jax.vmap(lambda b, o: _lane_scan_impl(config, b, o))(sub, ops)
    outs = _guard_capped(outs, pre_counts, cap, ops)
    new_books = _scatter_books_cap(books, lane_ids, sub, cap)
    return new_books, outs


dense_batch_step = functools.partial(  # gomelint: disable=GL601 — see batch_step
    jax.jit, static_argnums=0
)(_dense_batch_step_impl)
dense_batch_step_donating = functools.partial(  # gomelint: disable=GL601 — ibid.
    jax.jit, static_argnums=0, donate_argnums=(3,)
)(_dense_batch_step_impl)


def _dense_kernel_step_impl(
    config: BookConfig,
    books: BookState,
    lane_ids,
    ops: DeviceOp,
    block_s: int,
    interpret: bool = False,
):
    """dense_batch_step with the VMEM-resident Pallas kernel as the inner
    step (gome_tpu.ops.pallas_match) instead of scan x vmap. For few-lane
    deep grids this is the difference between ~40us/op (every scan step
    pays XLA kernel-launch overhead on a sequential dependency chain) and
    the in-kernel fori_loop running entirely out of VMEM — the single-hot-
    symbol latency path lives here. Row count must satisfy the kernel's
    blocking rule (the packer pads rows to >= 8, a power of two).

    Cap-class slicing as in dense_batch_step; a shallower class also
    shrinks the kernel's VMEM book tile, letting wider lane blocks fit."""
    from ..ops import pallas_batch_step

    cap = config.cap
    base = _slice_books_cap(books, cap)
    sub = jax.tree.map(
        lambda a: jnp.take(a, lane_ids, axis=0, mode="fill", fill_value=0),
        base,
    )
    pre_counts = sub.count
    sub, outs = pallas_batch_step(
        config, sub, ops, block_s=block_s, interpret=interpret,
        grid_kind="dense",
    )
    outs = _guard_capped(outs, pre_counts, cap, ops)
    new_books = _scatter_books_cap(books, lane_ids, sub, cap)
    return new_books, outs


dense_kernel_step = functools.partial(  # gomelint: disable=GL601 — see batch_step
    jax.jit, static_argnums=(0, 4, 5)
)(_dense_kernel_step_impl)
dense_kernel_step_donating = functools.partial(  # gomelint: disable=GL601 — ibid.
    jax.jit, static_argnums=(0, 4, 5), donate_argnums=(3,)
)(_dense_kernel_step_impl)


def _full_kernel_step_impl(
    config: BookConfig,
    books: BookState,
    ops: DeviceOp,
    block_s: int,
    interpret: bool = False,
):
    """Full-grid (row == lane) Pallas step with the cap-class slice/guard/
    write-back of batch_step — pallas_batch_step itself requires the book
    arrays at exactly config.cap."""
    from ..ops import pallas_batch_step

    cap = config.cap
    sub = _slice_books_cap(books, cap)
    pre_counts = books.count
    sub, outs = pallas_batch_step(
        config, sub, ops, block_s=block_s, interpret=interpret
    )
    outs = _guard_capped(outs, pre_counts, cap, ops)
    if books.price.shape[-1] == cap:
        return sub, outs
    return _writeback_full_cap(books, sub, cap), outs


full_kernel_step = functools.partial(  # gomelint: disable=GL601 — see batch_step
    jax.jit, static_argnums=(0, 3, 4)
)(_full_kernel_step_impl)
full_kernel_step_donating = functools.partial(  # gomelint: disable=GL601 — ibid.
    jax.jit, static_argnums=(0, 3, 4), donate_argnums=(2,)
)(_full_kernel_step_impl)


class StepPlan(NamedTuple):
    """Which step runs one grid, as BatchEngine._grid_plan decided it:
    hashable, so a program traced round the step (frames._grid_program) is
    keyed by it and shared by every engine that decides the same."""

    cfg: BookConfig  # at the grid's cap class
    dense: bool
    block_s: int | None  # ops.kernel_plan's lane block; None = scan path
    interpret: bool


def step_in_program(plan: StepPlan, books: BookState, ops: DeviceOp, ids):
    """The step BatchEngine._step dispatches for `plan` on one chip, for a
    caller that is being traced itself: the same bodies, no program of
    their own. ids are a dense grid's int32 lane ids, None on a full
    grid."""
    cfg = plan.cfg
    if plan.dense:
        if plan.block_s is not None:
            return _dense_kernel_step_impl(
                cfg, books, ids, ops, plan.block_s, plan.interpret
            )
        return _dense_batch_step_impl(cfg, books, ids, ops)
    if plan.block_s is not None:
        return _full_kernel_step_impl(
            cfg, books, ops, plan.block_s, plan.interpret
        )
    return _batch_step_impl(cfg, books, ops)


def _nop_grid(config: BookConfig, n_slots: int, t: int) -> dict[str, np.ndarray]:
    i32 = lambda: np.zeros((n_slots, t), np.int32)
    val = lambda: np.zeros((n_slots, t), np.dtype(config.dtype))
    return dict(
        action=i32(), side=i32(), kind=i32(),
        price=val(), volume=val(), oid=val(), uid=val(),
    )


# gomesurface: quantizer
def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


#: Smallest per-grid cap class. Below this the fixed per-step cost dominates
#: (the roofline in ARCHITECTURE.md prices slot work at ~11 cycles/slot past
#: 128 and ~nothing below), so finer classes would only multiply compiled
#: shapes. Also keeps every class >= the default max_fills record budget.
CAP_CLASS_MIN = 64


# gomesurface: quantizer
def _cap_ladder(cap: int) -> list[int]:
    """The per-grid cap classes available under a storage cap: pow4 steps
    from CAP_CLASS_MIN (64, 256, 1024, ...) strictly below `cap`, plus
    `cap` itself. Pow4 bounds the compiled-shape count at <=4x padding —
    the same trade _next_pow4 makes for train-grid rows. A storage cap at
    or below CAP_CLASS_MIN yields a single class (today's behavior:
    every grid runs at the storage cap)."""
    if cap <= CAP_CLASS_MIN:
        return [cap]
    out = []
    c = CAP_CLASS_MIN
    while c < cap:
        out.append(c)
        c *= 4
    out.append(cap)
    return out


def merged_floor_key(cls: int) -> int:
    """The key, in the rows and depth floors, of a merged small frame's
    grid at cap class `cls` (frames.pack_frame_grids): the class negated.
    Such a grid carries every lane of its frame, a few dozen rows at the
    deepest class present, where that class's own train in a large frame
    carries its deep lanes alone; under one key the one would pad the
    other for the life of the process. An int like the classes, so a
    saved manifest's JSON keys read back the same way
    (orchestrator.load_geometry)."""
    return -cls


def _slice_books_cap(books: BookState, cap: int) -> BookState:
    """Restrict the slot axis to the leading `cap` slots (no-op at the
    storage width). Exact for every lane whose resting count <= cap —
    active slots are a prefix — and _guard_capped turns any deeper lane
    into a book_overflow so the escalation machinery re-runs the grid at
    a deeper class instead of silently dropping its tail."""
    if books.price.shape[-1] == cap:
        return books
    cut = lambda a: a[..., :cap]
    return books._replace(
        price=cut(books.price), lots=cut(books.lots), seq=cut(books.seq),
        oid=cut(books.oid), uid=cut(books.uid),
    )


def _guard_capped(outs: StepOutput, pre_counts, cap: int,
                  ops: DeviceOp) -> StepOutput:
    """Flag rows whose PRE-step resting count exceeds the grid's cap class:
    their books were truncated by the slice, so the grid's result for them
    is not trustworthy. Folding the flag into book_overflow reuses the
    exact escalation/fallback path — a stale host-side depth estimate
    costs a re-run, never correctness. (Growth DURING the grid past cap is
    the ordinary insert overflow and needs no guard.)

    Rows with no real op are exempt: NOPs never read or write book slots,
    so a deep lane riding a shallow-class grid as padding is exact — in a
    class-partitioned full grid (engine.frames._class_partitions) every
    OTHER class's lanes are exactly such rows."""
    touched = jnp.any(ops.action != 0, axis=-1)
    bad = (
        touched & (jnp.max(pre_counts, axis=-1) > cap)
    ).astype(outs.book_overflow.dtype)
    return outs._replace(
        book_overflow=jnp.maximum(outs.book_overflow, bad[:, None])
    )


def _writeback_full_cap(books: BookState, sub: BookState, cap: int):
    """Write a cap-sliced full-grid result back into the storage-width
    stack (row == lane; slots beyond `cap` were untouched by the grid)."""
    put = lambda a, s: a.at[..., :cap].set(s)
    return books._replace(
        price=put(books.price, sub.price), lots=put(books.lots, sub.lots),
        seq=put(books.seq, sub.seq), oid=put(books.oid, sub.oid),
        uid=put(books.uid, sub.uid), count=sub.count,
        next_seq=sub.next_seq,
    )


def _scatter_books_cap(books: BookState, lane_ids, sub: BookState, cap: int):
    """Scatter a dense grid's sub-stack back, writing only the leading
    `cap` slots of each touched lane (sentinel rows drop). Lanes in a
    cap-class grid hold nothing beyond `cap` (guarded above), so the
    untouched tail slots stay zero and every book invariant holds."""
    if books.price.shape[-1] == cap:
        return jax.tree.map(
            lambda a, s: a.at[lane_ids].set(s, mode="drop"), books, sub
        )
    put3 = lambda a, s: a.at[lane_ids, :, :cap].set(s, mode="drop")
    put = lambda a, s: a.at[lane_ids].set(s, mode="drop")
    return books._replace(
        price=put3(books.price, sub.price), lots=put3(books.lots, sub.lots),
        seq=put3(books.seq, sub.seq), oid=put3(books.oid, sub.oid),
        uid=put3(books.uid, sub.uid), count=put(books.count, sub.count),
        next_seq=put(books.next_seq, sub.next_seq),
    )


#: Per-grid record-tensor element budget (T*K*R per record array; 5 record
#: arrays x 4 B => 16M elements ~ 320 MB of step outputs). Bounds the
#: rows-x-depth product of every grid (BatchEngine._grid_depth), so deep
#: time axes are reserved for few-row grids.
_REC_ELEM_BUDGET = 1 << 24


# gomesurface: quantizer
def _next_pow4(n: int) -> int:
    """Coarser shape bucket for a frame's train grids: every distinct
    compiled shape costs a trace, and the train's later grids see
    stochastic live counts/depths — pow4 classes (8, 32, 128, ...) visit
    4x fewer shapes for at most 4x padding on SMALL grids."""
    p = 1
    while p < n:
        p *= 4
    return p


def _merge_buf_floor(dst: dict, src) -> None:
    """Raise per-class buffer floors: src is {pow2 class: slots} or an
    int (interpreted as a floor for its own pow2 class)."""
    items = (
        src.items() if isinstance(src, dict)
        else [(_next_pow2(max(int(src), 64)), int(src))]
    )
    for b, v in items:
        v = _next_pow2(max(int(v), 64))
        dst[b] = max(dst.get(b, 0), v)


def splice_outs(outs, overrides):
    """Build the `outs_at(field, rows, ts)` accessor decode_grid_columnar
    needs: reads StepOutput columns at packed (row, t) coordinates and
    splices in per-row escalation re-runs (each with its own record budget
    K', padded to align)."""

    def outs_at(field, rows, ts):
        base = np.asarray(getattr(outs, field))[rows, ts]
        for r, src in overrides.items():
            m = rows == r
            if not m.any():
                continue
            ov = np.asarray(getattr(src, field))[ts[m]]
            if base.ndim > 1:
                k_base, k_ov = base.shape[1], ov.shape[1]
                if k_ov > k_base:
                    base = np.pad(base, [(0, 0), (0, k_ov - k_base)])
                elif k_ov < k_base:
                    ov = np.pad(ov, [(0, 0), (0, k_base - k_ov)])
            base[m] = ov
        return base

    return outs_at


def is_device_fault(exc: BaseException) -> bool:
    """True for a compile, lowering or device-runtime error: a property of
    the program and the chip, never of one order's data. Callers that
    tolerate bad INPUT (the consumer's poison-batch quarantine, the
    best-effort geometry replay) must let these through — a kernel the
    chip refuses, swallowed there, looks like a venue that silently drops
    every order."""
    if isinstance(exc, jax.errors.JaxRuntimeError):
        return True
    # Pallas lowering failures. The class lives in a module only loaded
    # with the kernel, and none can be raised before it is.
    pltpu = sys.modules.get("jax.experimental.pallas.tpu")
    return pltpu is not None and isinstance(exc, pltpu.LoweringException)


class CapacityError(RuntimeError):
    """A configured growth ceiling (max_slots / max_cap) was hit. The book
    state is unchanged for the op that tripped it; callers may shed load or
    re-shard rather than exhaust device memory."""


class BookInvariantError(RuntimeError):
    """verify_books found device book state violating a structural
    invariant — an engine bug or external state corruption, never a
    recoverable input condition."""


@dataclasses.dataclass
class EngineStats:
    """Host-side engine counters (new instrumentation — the reference has
    none, SURVEY §5.5). Escalations are exact-but-slow events worth watching:
    frequent cap growth means the configured book geometry is undersized."""

    orders: int = 0
    fills: int = 0
    cancels: int = 0
    cancels_missed: int = 0
    dropped_no_prepool: int = 0  # incremented by the orchestrator facade
    device_calls: int = 0
    # Fast-path frames dispatched with device grids (frames.submit_frame);
    # of them, those whose event buffers were an earlier frame's, and those
    # whose events came back with their totals (frames.ONE_PHASE_MAX_BYTES).
    fast_frames: int = 0
    fast_frames_reused: int = 0
    fast_frames_one_phase: int = 0
    # One-phase frames whose lanes spanned more than one cap class and were
    # packed as ONE grid at the deepest class present
    # (frames.pack_frame_grids). Over fast_frames: near 1 where small frames
    # mix deep and shallow lanes, 0 where a frame's lanes are of one class
    # or frames are large.
    fast_frames_merged: int = 0
    # Grids of one-phase frames, which cost the host ONE dispatch each:
    # scatter, step, compaction and the count reduction in one program
    # (frames._grid_program). Over device_calls: near 1 where small frames
    # flow, 0 where large ones do.
    fast_grids_one_program: int = 0
    cap_escalations: int = 0
    # Confined escalations: one GRID's cap class deepened (re-sliced from
    # the same storage) without growing the [S]-wide stack — the cheap
    # recovery per-grid cap classes buy (cap_escalations = storage grew).
    grid_cap_escalations: int = 0
    fill_record_escalations: int = 0
    frame_fallbacks: int = 0  # fast-path frames re-run on the exact path
    # Adds applied, by the kind's wire number (types.OrderType), and those
    # of them that expired by their kind's rule (oracle/book.py docstring):
    # an IOC add whose remainder was dropped, a FOK add killed, a POST_ONLY
    # add blocked. Counted on the device (StepOutput.expired) and summed
    # into the totals a frame already fetches.
    adds_by_kind: dict[int, int] = dataclasses.field(default_factory=dict)
    expired_ioc: int = 0
    fok_killed: int = 0
    post_only_blocked: int = 0
    # Adds of any kind that stopped at their owner's resting order with
    # volume left and expired there (BookConfig.self_trade "expire_taker";
    # 0 on a venue without the rule). Counted like the three above.
    stp_expired: int = 0
    lane_growths: int = 0
    # Grids dispatched and the real (non-padding) ops through them, keyed
    # by the kernel that ACTUALLY ran (BatchEngine._step): "pallas_full" /
    # "pallas_dense" (compiled), "interpret_full" / "interpret_dense" (the
    # Pallas interpreter — CPU tests, the chip_smoke rehearsal),
    # "scan_full" / "scan_dense".
    grids_by_kernel: dict[str, int] = dataclasses.field(default_factory=dict)
    ops_by_kernel: dict[str, int] = dataclasses.field(default_factory=dict)
    # kernel="pallas" grids that ran on the scan path instead, by reason
    # (ops.pallas_match.kernel_plan): a deployment that asks for the
    # kernel can tell whether it got it.
    scan_giveways: dict[str, int] = dataclasses.field(default_factory=dict)


class BookCut:
    """The engine's state at one instant between two dispatches
    (BatchEngine.take_cut): what a snapshot holds of it.

    The step never donates the book stack, so the stack as it stood right
    after a frame's last dispatch IS the books after exactly that frame,
    whatever is dispatched on top of it: the cut keeps that reference (one
    more live version of the stack until the cut is dropped) and starts its
    transfer to the host at once. The per-lane host vectors are copied at
    the same instant. The interners are not part of it: they only grow, so
    whoever reads their tables later reads a superset with the same ids.
    `arrays()` waits for the transfer: the snapshot writer's thread calls
    it, not the consumer's."""

    __slots__ = ("books", "lanes", "rows", "meta", "rewinds")

    def __init__(self, books, lanes, rows, meta, rewinds):
        self.books = books
        self.lanes = lanes
        self.rows = rows
        self.meta = meta
        self.rewinds = rewinds

    def arrays(self) -> dict[str, np.ndarray]:
        """Books and per-lane vectors of the venue's lanes as host arrays
        in the one-chip order (lane = interner id - 1), whatever row of
        whatever chip holds a lane and however many rows the stack was
        provisioned to: a snapshot restores into any mesh, or none."""
        out = {k: np.asarray(v) for k, v in self.books._asdict().items()}
        out.update(self.lanes)
        if self.rows is not None:
            out = {k: v[self.rows] for k, v in out.items()}
        return out


class BatchEngine:
    """Host-side driver for the batched device engine.

    Owns the device-resident [S] book stack, the symbol->lane mapping, the
    id interners and the grid geometry, and runs one op grid exactly
    (_run_exact) or without a host sync (_step). How a batch of orders
    becomes grids, and grids' outputs events, is engine.frames.

    This layer assumes orders already passed admission (pre-pool checks live
    in the orchestrator above — gome_tpu.engine.orchestrator); every ADD
    given here hits the book.
    """

    def __init__(
        self,
        config: BookConfig,
        n_slots: int,
        max_t: int = 32,
        auto_grow: bool = True,
        max_slots: int = 1 << 16,
        max_cap: int = 1 << 14,
        kernel: str = "scan",
        pallas_interpret: bool = False,
        mesh=None,
        dense: bool = True,
        dense_t_max: int = 1024,
    ):
        """max_slots / max_cap bound auto-grow (symbol lanes / per-side book
        capacity). Growth past a ceiling raises CapacityError instead of
        exhausting HBM — explicit backpressure the caller can surface
        (the reference has no such ceiling because Redis pages to disk).

        kernel: "scan" (XLA scan x vmap) or "pallas" (VMEM-resident Pallas
        grid kernel, gome_tpu.ops.pallas_match). A "pallas" grid the
        compiled kernel cannot run (off-TPU, int64 books, unblockable lane
        counts, book tile over the VMEM budget) gives way to the scan path
        — identical semantics — and EngineStats counts every grid by the
        kernel that ran it and every give-way by its reason
        (ops.pallas_match.kernel_plan). pallas_interpret=True selects the
        (slow) Pallas interpreter where the compiled kernel is unavailable;
        it exists so CPU tests and the chip_smoke rehearsal can exercise
        the kernel's code path.

        dense: allow the packer to put batches touching few symbols
        into compact gather/scatter grids over just the live lanes
        (dense_batch_step) instead of the full n_slots-row grid —
        throughput then tracks APPLIED ops, not provisioned lanes (Zipf
        flows). Semantics identical. It decides rows only.

        max_t / dense_t_max: the shallowest and the deepest time axis of a
        grid. Depth is chosen per grid, dense or full, from its row count
        and its deepest lane (_grid_depth): a hot symbol's stream runs
        dense_t_max deep per device call where the rows allow it (the
        single-symbol latency path), and max_t is the depth of a grid
        whose lanes all fit it.

        mesh: an optional 1-D jax.sharding.Mesh (gome_tpu.parallel.make_mesh)
        partitioning the symbol-lane axis across chips. Matching needs zero
        collectives (symbols share nothing, SURVEY §2.1), so the sharded
        step is the same graph with shardings pinned; lane counts stay
        multiples of the mesh size (growth rounds up). kernel="pallas"
        under a mesh runs the compiled VMEM kernel per chip inside a
        shard_map (gome_tpu.parallel.mesh.sharded_batch_step), preserving
        the kernel's throughput win at multi-chip scale. Under a mesh a
        symbol's lane (its row of the book stack, what _lane returns and
        every packer and per-lane array indexes) is its PLACEMENT: symbols
        are dealt round-robin over the shards in arrival order
        (engine.placement), so a Zipf head that arrives first spreads
        evenly. What leaves the engine keeps the one-chip order, lane =
        interner id - 1: the events' symbol_id, export_state / import_state,
        lane_books and symbol_lane.

        n_slots is the venue's lanes: one a symbol, what the interner,
        growth, count_ub, snapshots and every view count in. Under
        kernel="pallas" the device's lane axis (the book stack, a full
        grid) is provisioned to lane_rows, the compiled kernel's row floor
        at or over it (ops.blockable_rows; under a mesh per shard), so
        every n_slots has a compiled full grid. The rows past the venue's
        lanes hold empty books and never take an op; where n_slots is
        itself blockable (8, 10,240) lane_rows is n_slots, as it is for an
        engine that never runs the kernel."""
        if kernel not in KERNELS:
            raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
        if config.cap > max_cap:
            raise ValueError(f"cap {config.cap} exceeds max_cap {max_cap}")
        if n_slots > max_slots:
            raise ValueError(f"n_slots {n_slots} exceeds max_slots {max_slots}")
        # (before the row floor below brings the kernel's module in: see
        # ensure_dtype_usable)
        ensure_dtype_usable(config.dtype)
        self.config = config
        self.n_slots = n_slots
        self.max_t = max_t
        self.auto_grow = auto_grow
        self.max_slots = max_slots
        self.max_cap = max_cap
        self.kernel = kernel
        self._pallas_interpret = pallas_interpret
        self.mesh = mesh
        self.dense = dense
        self.dense_t_max = dense_t_max
        # Grow-only geometry ratchets (see _grid_geometry / frame packing):
        # compiled grid shapes must not oscillate across pow2 buckets.
        # Keyed by CAP CLASS (_cap_ladder): each class runs its own grid
        # train with its own row/depth profile — the tail class's 10K-row
        # floor must never inflate the hot class's 8-row grids (and vice
        # versa for depth). A small frame's merged grid has a profile of
        # its own again, under merged_floor_key(class).
        self._dense_rows_floor: dict[int, int] = {}
        self._dense_t_floor: dict[int, int] = {}
        # Per-lane resting-count upper bound, the host-side input to cap-
        # class selection (frames._class_partitions): ub = _ub_base (true
        # per-lane max-side counts at the last device fetch) + _ub_extra
        # (limit-ADDs packed since — each can rest at most once, and
        # nothing else ever raises a count, so base+extra is provably an
        # upper bound). It is a PERFORMANCE hint only: an underestimate is
        # caught on device by _guard_capped and re-run deeper.
        span = self.lane_span
        self._ub_base = np.zeros(span, np.int64)
        self._ub_extra = np.zeros(span, np.int64)
        # Compaction-buffer ratchets (frames._compact_sizes): grow-only
        # fetch-buffer sizes, keyed by the grid's pow2 op-count class. A
        # frame can contain grids of wildly different sizes (a Zipf flow
        # packs one 256K-op full grid plus a train of small deep dense
        # grids), so a single global floor would make every small grid
        # fetch the big grid's buffer; per-class floors keep each grid's
        # transfer proportional to its ops while still pinning compiled
        # shapes within a class. The fills floor additionally grows when
        # a grid's fill count overflows its buffer (the exact-path
        # fallback keeps that safe).
        self._fills_buf_floor: dict[int, int] = {}
        self._cancels_buf_floor: dict[int, int] = {}
        # Every fast-path (grid geometry, compact-buffer) shape combo this
        # engine has DISPATCHED (frames.submit_frame records; tuples of
        # (n_rows, t_grid, cap_g, dense, m_pad, k_rec, e_fills, e_cancels,
        # totals_len)). A deployment persists these alongside the floors
        # (shape_manifest / orchestrator.save_geometry) and replays them
        # with all-padding inputs at boot (frames.precompile_combos), so
        # the very first live frame runs fully traced+compiled — the
        # trace cost (which the XLA persistent cache does NOT cover: it
        # caches compiles, not traces) moves off every hot path.
        self._seen_combos: set[tuple] = set()
        # Resolved frames' event buffers, handed to the next frame of the
        # same shapes: (e_fills, e_cancels, totals_len) -> sets of
        # (fills, cancels, totals) device arrays no frame in flight holds
        # (frames._take_buffers / _give_buffers).
        self._event_buffers: dict[tuple, list] = {}
        if mesh is not None:
            # Every place n_slots can be set (init, growth, restore) must
            # produce a mesh multiple; enforcing the two static bounds here
            # and rounding growth up lets _place assume divisibility.
            for name, v in (("n_slots", n_slots), ("max_slots", max_slots)):
                if v % mesh.size != 0:
                    raise ValueError(
                        f"{name} {v} must be a multiple of the mesh size "
                        f"{mesh.size}"
                    )
        self._sharded_steppers: dict = {}  # BookConfig -> jitted step
        self._sharded_dense_steppers: dict = {}  # BookConfig -> dense step
        self.books = self._place(init_books(config, self.lane_rows))
        from .nativehost import make_interner

        from ..utils.cache import IdentityCache

        self.symbols = Interner()  # symbol -> lane id + 1 offset handled below
        # symbol-dictionary object -> (lane-id array, max lane); hits are
        # revalidated against n_slots (frames._lane_map).
        self._lane_map_cache = IdentityCache()
        # oids are the one per-order-unique string column — interned in C++
        # when the toolchain allows (nativehost; ~10x the dict loop).
        self.oids = make_interner()
        self.uids = Interner()
        self.stats = EngineStats()
        # Price rebasing (32-bit books only): device prices are stored
        # relative to a per-lane int64 base, so absolute tick magnitudes are
        # unbounded while each symbol's ACTIVE window is +-2^31 ticks — the
        # windowed-ladder re-centering of SURVEY §5.7, done at the host
        # boundary where it costs one subtract. int64 books keep base 0.
        self._rebase = jnp.dtype(config.dtype).itemsize <= 4
        self.price_base = np.zeros(span, np.int64)
        self._base_set = np.zeros(span, bool)
        # Conservative absolute-price envelope per lane (grows only): the
        # recenter check proves every price the lane has EVER admitted still
        # fits the int32 window under a new base, without a device scan.
        self._env_lo = np.zeros(span, np.int64)
        self._env_hi = np.zeros(span, np.int64)
        # _restore() calls: a cut taken before one no longer says what the
        # books were after its frame (cut_is_current).
        self._rewinds = 0

    # Admission window around the current base; recenter when exceeded.
    REBASE_LIMIT = 1 << 30
    _INT32_SAFE = (1 << 31) - 2

    def _rows_for(self, n_slots: int) -> int:
        """Rows of the device's lane axis that hold n_slots lanes: where the
        engine runs the kernel, its row floor (ops.blockable_rows), under a
        mesh for each shard's block."""
        if self.kernel != "pallas":
            return n_slots
        from ..ops import blockable_rows

        d = 1 if self.mesh is None else self.mesh.size
        return d * blockable_rows(n_slots // d)

    def _span_for(self, n_slots: int) -> int:
        """Length of the per-lane host vectors of n_slots lanes: what a
        lane indexes. Without a mesh a lane is its symbol's place in
        arrival order, under one its row of the stack (_lane_of)."""
        return n_slots if self.mesh is None else self._rows_for(n_slots)

    @property
    def lane_rows(self) -> int:
        """Provisioned rows of the book stack and of a full grid."""
        return self._rows_for(self.n_slots)

    @property
    def lane_span(self) -> int:
        """Length of price_base, count_ub and the other per-lane host
        vectors (_span_for the venue's lanes)."""
        return self._span_for(self.n_slots)

    def _venue_rows(self):
        """The stack's rows that hold the venue's lanes, in the one-chip
        order (lane = interner id - 1): what a cut, a view or a restore
        indexes the stack by. None where the stack is just those, in that
        order (no mesh, n_slots its own row floor)."""
        if self.mesh is None and self.lane_rows == self.n_slots:
            return None
        return self._lane_of(np.arange(self.n_slots))

    def _place(self, books: BookState) -> BookState:
        """Pin the lane axis across the mesh (no-op without one)."""
        if self.mesh is None:
            return books
        from ..parallel.mesh import shard_batch

        return shard_batch(self.mesh, books)

    # -- placement under a mesh (engine.placement) --------------------------
    def _lane_of(self, arrival):
        """Lane (row of the book stack) of the k-th symbol to arrive, int or
        array; the identity without a mesh."""
        if self.mesh is None:
            return arrival
        return placement.lane_of(arrival, self.lane_rows, self.mesh.size)

    def _symbol_ids(self, lanes):
        """Inverse of _lane_of: the one-chip lane (interner id - 1) that
        events and snapshots name a symbol by."""
        if self.mesh is None:
            return lanes
        return placement.arrival_of(lanes, self.lane_rows, self.mesh.size)

    def _relayout(self, a, rows: int, xp=np):
        """A per-lane array laid out for a stack of len(a) rows, laid out
        for one of `rows` (the same array where they are equal: a venue
        that grows inside its row floor moves nothing): padded (or cut) at
        the end without a mesh; under one every shard's block widens, so
        every lane moves, through arrival order. Host arrays, or with
        xp=jnp a leaf of the device book stack."""
        old = len(a)
        if old == rows:
            return a
        if self.mesh is not None:
            d = self.mesh.size
            a = a[placement.lane_of(np.arange(old), old, d)]
        a = a[:rows] if old > rows else xp.pad(
            a, [(0, rows - old)] + [(0, 0)] * (a.ndim - 1)
        )
        if self.mesh is not None:
            a = a[placement.arrival_of(np.arange(rows), rows, d)]
        return a

    def _grow_base_arrays(self, rows: int) -> None:
        for name in ("price_base", "_base_set", "_env_lo", "_env_hi",
                     "_ub_base", "_ub_extra"):
            setattr(self, name, self._relayout(getattr(self, name), rows))

    # -- resting-count upper bound (cap-class selection) -------------------
    def count_ub(self) -> np.ndarray:
        """Current per-lane upper bound on max-side resting count."""
        return self._ub_base + self._ub_extra

    def note_packed_adds(self, add_counts: np.ndarray) -> None:
        """Record a packed batch's per-lane limit-ADD counts (each may rest
        at most once, keeping count_ub an upper bound). add_counts is
        [lane_span] at pack time; callers keep it for _note_exact_counts."""
        self._ub_extra[: len(add_counts)] += add_counts

    def _note_exact_counts(self, counts_max, resolved_adds=None) -> None:
        """Reset the estimate from a device fetch of true per-lane max-side
        counts (taken AFTER some batch B executed). resolved_adds = B's own
        note_packed_adds increments when later batches are already packed
        on top (the frame pipeline resolves FIFO, so extra minus B's share
        is exactly the still-in-flight sum); None asserts nothing is in
        flight and zeroes extra."""
        n = self.lane_span
        # (a fetch or a pack from before a lane growth is laid out for the
        # stack as it was then; a fetch has the stack's rows, of which the
        # venue's lanes come first in every shard's block)
        self._ub_base = self._relayout(np.array(counts_max, np.int64), n)
        if resolved_adds is None:
            self._ub_extra = np.zeros(n, np.int64)
        else:
            extra = self._ub_extra - self._relayout(
                np.asarray(resolved_adds, np.int64), n
            )
            np.maximum(extra, 0, out=extra)
            self._ub_extra = extra

    # Buffer-floor helpers (shared with frames._compact_sizes): floors
    # are {pow2 op-class: slot count}; an int means "this size, in its
    # own class".
    # gomesurface: quantizer
    @staticmethod
    def _buf_class(n: int) -> int:
        return _next_pow2(max(n, 64))

    def prewarm_geometry(
        self,
        rows_floor: int | None = None,
        t_floor: int | None = None,
        fills_buf: int | None = None,
        cancels_buf: int | None = None,
    ) -> None:
        """Pre-set the grow-only shape ratchets to known steady-state
        values (each rounds up to a power of two; existing floors never
        shrink). fills_buf/cancels_buf accept an int (a floor for its own
        pow2 op-class) or a {pow2 op-class: slots} dict as returned by
        geometry_floors(). Every distinct compiled shape costs a
        trace+compile the first time it appears; a deployment that knows
        its flow's geometry (from a previous run or a staging soak)
        pre-warms here so every shape compiles during warmup instead of
        mid-traffic. Purely a performance knob — untouched ratchets grow
        on demand exactly as before.

        rows_floor/t_floor accept an int (a floor for the storage-cap
        class — the pre-cap-class behavior) or a {cap class: floor} dict
        as returned by geometry_floors() (merged small frames' floors under
        merged_floor_key(class))."""

        def merge(dst: dict, src, cap: int) -> None:
            """Merge grow-only, clamped to `cap`: a floor beyond the
            usable range (rows past n_slots, depth past the dense
            ceiling) carries no information — it just forces every grid
            to the degenerate fallback — and persisting it would let a
            compounding margin (e.g. 2x per run through a saved
            manifest) poison geometry forever."""
            items = (
                src.items() if isinstance(src, dict)
                else [(self.config.cap, src)]
            )
            for c, v in items:
                v = min(_next_pow2(max(int(v), 8)), cap)
                dst[c] = max(dst.get(c, 8), v)

        if rows_floor is not None:
            merge(
                self._dense_rows_floor, rows_floor, _next_pow2(self.n_slots)
            )
        if t_floor is not None:
            merge(
                self._dense_t_floor, t_floor,
                _next_pow2(max(self.dense_t_max, self.max_t)),
            )
        if fills_buf is not None:
            _merge_buf_floor(self._fills_buf_floor, fills_buf)
        if cancels_buf is not None:
            _merge_buf_floor(self._cancels_buf_floor, cancels_buf)

    def reset_geometry_floors(self, combos: bool = False) -> None:
        """Forget every grow-only geometry ratchet (rows/depth floors,
        compaction-buffer floors). Correctness-neutral — floors are
        performance hints — but sometimes necessary for performance:
        ratchets latched during a WARMUP TRANSIENT (e.g. count_ub
        overestimates while books fill from empty send hundreds of lanes
        into a deep cap class exactly once) would otherwise pin a
        pathologically wide-and-deep grid for the life of the process. A
        warmup loop calls this once the flow reaches steady state, lets
        the next frames re-ratchet from honest geometry, and THEN pins
        margins / saves the manifest.

        combos=True also forgets the recorded shape combos: the transient
        frames' shapes would otherwise ride save_geometry into the
        manifest and every later boot would precompile grids the
        steady-state flow never dispatches."""
        self._dense_rows_floor.clear()
        self._dense_t_floor.clear()
        self._fills_buf_floor.clear()
        self._cancels_buf_floor.clear()
        if combos:
            self._seen_combos.clear()

    def ensure_cap(self, cap: int) -> None:
        """Pre-size book storage to `cap` slots/side (pow2-snapped,
        grow-only, bounded by max_cap) — a deployment that knows its
        flow's stationary depth (e.g. from a persisted geometry manifest)
        escalates ONCE at boot instead of paying the mid-traffic
        grow+replay, and makes deep-cap shape combos replayable by
        precompile_combos."""
        cap = _next_pow2(max(int(cap), self.config.cap))
        if cap == self.config.cap:
            return
        if cap > self.max_cap:
            raise CapacityError(
                f"ensure_cap({cap}) exceeds max_cap={self.max_cap}"
            )
        self.books = self._place(grow_books(self.books, cap))
        self.config = dataclasses.replace(self.config, cap=cap)

    def geometry_floors(self) -> dict:
        """The current grow-only shape ratchets (see prewarm_geometry) —
        what a warmup loop watches to decide the flow's compiled shapes
        have stabilized, and what a deployment records to pre-warm the
        next process. rows_floor/t_floor are {cap class: floor} dicts (a
        merged small frame's grids under merged_floor_key(class)), the
        buffer floors {pow2 op-class: slots} dicts; everything is copied
        (safe to hold across further frames)."""
        return dict(
            rows_floor=dict(self._dense_rows_floor),
            t_floor=dict(self._dense_t_floor),
            fills_buf=dict(self._fills_buf_floor),
            cancels_buf=dict(self._cancels_buf_floor),
            cap=self.config.cap,
        )

    # gomesurface: combo(persist)
    def shape_manifest(self) -> dict:
        """Everything a future process needs to run this flow's fast path
        with ZERO first-seen traces: the grow-only floors (so the same
        shapes are CHOSEN) plus every dispatched shape combo (so they are
        TRACED+COMPILED off-clock via frames.precompile_combos). The XLA
        persistent cache already makes compiles one-time across processes;
        traces are per-process and this closes that gap."""
        return dict(
            floors=self.geometry_floors(),
            combos=self.combos(),
        )

    # Dispatch-combo chokepoint: the ONLY writer of the recorded shape
    # set. Everything outside this class — the frame dispatch, geometry
    # replay, observability probes, benches — goes through these four
    # accessors; gomesurface GL902 flags any `_seen_combos` reach-through
    # so a new reader/writer can't silently fork the combo bookkeeping
    # the steady-state (zero-recompile) contract hangs off.
    def record_combo(self, combo) -> bool:
        """Record one dispatched shape combo (tuple-ified). Returns True
        when the combo is first-seen — i.e. the dispatch that produced it
        just paid (or, for precompile replay, just prepaid) a jit
        trace+compile."""
        combo = tuple(combo)
        if combo in self._seen_combos:
            return False
        self._seen_combos.add(combo)
        return True

    def combo_seen(self, combo) -> bool:
        """Whether this shape combo has already been traced+compiled."""
        return tuple(combo) in self._seen_combos

    def combo_count(self) -> int:
        """How many distinct dispatch shape combos this engine compiled —
        the number the perf ratchet gates for the scripted drill."""
        return len(self._seen_combos)

    def combos(self) -> list:
        """The recorded dispatch combos, sorted (stable across runs for
        manifests and tests)."""
        return sorted(self._seen_combos)

    def _grid_geometry(self, live: np.ndarray, first: bool = True,
                       cls: int | None = None):
        """The ROWS of a grid, for the frame packer (engine.frames). A
        grid's geometry is two independent decisions: this one (which
        lanes get a row, and whether rows are indirected) and
        _grid_depth (how long the time axis is). When the
        batch touches few of the provisioned lanes, pack a compact grid
        over just the live lanes (row -> lane indirection, executed by
        dense_batch_step / parallel.mesh.sharded_dense_step); rows bucket
        to powers of two (min 8 — the Pallas kernel's sublane floor;
        sentinel padding rows are free) to bound compile shapes. Once the
        row bucket reaches n_slots the gather buys nothing and the grid is
        the full one, row == lane, at the stack's lane_rows — which says
        nothing about its depth.

        `first` marks the first dense grid of a frame's train. Only it
        consults/advances the grow-only row ratchet: the train's DEEPER
        grids (lanes outliving earlier grids' time axes — a Zipf flow
        drains its hot lanes through a geometrically shrinking train)
        use raw pow2 buckets, because pinning them to the first grid's
        floor would run every tail grid at the head grid's width —
        hundreds of times the live work. Their shapes converge to a
        small set (the shrink is geometric), each compiled once.

        Under a mesh the row axis is laid out PER SHARD: shard d's live
        lanes occupy the contiguous row block [d*R_s, (d+1)*R_s), so the
        standard symbol-axis sharding of the [D*R_s, T] grid hands each
        chip exactly the rows naming its own lanes — the dense gather
        stays shard-local and needs zero collectives (per-symbol key
        isolation, ordernode.go:89-117). R_s buckets to the max per-shard
        live count, so the dense win shrinks as skew concentrates on one
        shard — which is the true cost surface on hardware.

        `cls` keys the grow-only floors by the grid's cap class (per-class
        trains have independent row/depth profiles; a merged small frame's
        grid comes with merged_floor_key of its class); None = the storage
        cap class (the single-class behavior).

        Returns (use_dense, n_rows, lane_ids, row_of): lane_ids [n_rows]
        GLOBAL lane ids with sentinel lane_rows on padding rows (the device
        step localizes under a mesh); row_of [lane_rows] maps live lane ->
        row (valid only at live positions). Both None for full grids."""
        rows = self.lane_rows
        if not (self.dense and len(live) > 0):
            return False, rows, None, None
        cls = self.config.cap if cls is None else cls
        floor = self._dense_rows_floor.get(cls, 8) if first else 8
        bucket = _next_pow2 if first else _next_pow4
        if self.mesh is None:
            n_rows = max(8, bucket(len(live)), floor)
            if n_rows >= self.n_slots:
                return False, rows, None, None
            # Grow-only row bucket ("ratchet"): live-lane counts hovering
            # at a pow2 boundary would otherwise flip the compiled grid
            # shape frame to frame — and one fresh XLA compile costs more
            # than thousands of frames of matching.
            if first:
                self._dense_rows_floor[cls] = n_rows
            lane_ids = np.full(n_rows, rows, np.int64)
            lane_ids[: len(live)] = live
            rows_for_live = np.arange(len(live), dtype=np.int64)
            # Occupancy ledger (obs.placement): dispatched-vs-live rows
            # for the unsharded dense grid, values already in hand.
            PLACEMENT.note_dispatch(n_rows, live)
        else:
            d = self.mesh.size
            local = rows // d
            shard = live // local  # live is sorted (np.unique upstream)
            counts = np.bincount(shard, minlength=d)
            # Uniform R_s = global max is structural for now: shard_map's
            # even split hands every chip the same [R_s, T] block, so one
            # hot shard pads ALL shards (MULTICHIP_r06 skew 3.64).
            # Per-shard geometry is ROADMAP item 2's refactor.
            r_s = max(8, bucket(int(counts.max())), floor)  # gomelint: disable=GL802 — owning workstream: ROADMAP item 2 (per-shard geometry)
            if r_s * d >= self.n_slots:
                return False, rows, None, None
            if first:
                self._dense_rows_floor[cls] = r_s
            n_rows = r_s * d
            lane_ids = np.full(n_rows, rows, np.int64)
            starts = np.zeros(d, np.int64)
            np.cumsum(counts[:-1], out=starts[1:])
            rank = np.arange(len(live), dtype=np.int64) - starts[shard]
            rows_for_live = shard * r_s + rank
            lane_ids[rows_for_live] = live
            # Per-shard telemetry (always-on histogram + the armed
            # profiler's dispatch ring) from values already in hand.
            _dense_shard_skew.observe(int(counts.max()) * d / len(live))
            PROFILER.note_shard_dispatch(d, r_s, counts)
            PLACEMENT.note_dispatch(n_rows, live, counts, r_s)
        row_of = np.empty(rows, np.int64)
        row_of[live] = rows_for_live
        # Skew telemetry: what row padding (pow2 bucket, grow-only floor,
        # and per-shard MAX bucketing under a mesh) costs THIS dispatch.
        _rows_per_live_lane.observe(n_rows / len(live))
        return True, n_rows, lane_ids, row_of

    # gomesurface: quantizer
    def _grid_depth(self, n_rows: int, need: int, cls: int, first: bool,
                    dense: bool) -> int:
        """The DEPTH (time-axis length) of a grid, for either kind of row
        layout _grid_geometry chose: from the grid's row count and the
        deepest lane it has to carry (`need` ops).

        Depth is budgeted against rows: the step's record tensors are
        [T, K, R], so a wide grid must stay shallow (2048 rows x 8192 deep
        x K=16 is a 10+ GB allocation) while a few-row grid can run
        dense_t_max deep — cap_t below. A full grid of 10,240 lanes at
        K=16 is held to 64 by that arithmetic, one of 8 lanes gets the
        ceiling. Under a mesh a full grid's rows are split evenly, so its
        budget is per chip.

        A dense train's FIRST grid takes _next_pow2(need) over a grow-only
        floor keyed by cap class `cls` (like the row ratchet: a hot lane's
        depth hovering at a pow2 boundary must not flip the compiled shape
        frame to frame). Dense TAIL grids snap to four fixed classes
        (shallow / 8x-shallow / quarter-ceiling / ceiling): every distinct
        (rows, depth) is a compiled shape, and per-frame depth noise would
        otherwise keep minting buckets for the life of the process (~1 s
        of host re-trace each); the 8x class plugs the geometric hole
        between max_t and cap_t//4, so padding stays <= 8x.

        A FULL grid's rows are fixed at n_slots, so depth is its only free
        dimension: it takes the smallest of the same fixed classes that
        covers `need` and is not shallower than max_t, first grid and tails
        alike, with NO floor — there is no second dimension for a floor to
        steady, and one would pad every later small frame to the deepest
        frame the process ever saw. max_t is the shallowest class: a full
        grid whose lanes all fit it has the shape it always had."""
        if not dense and self.mesh is not None:
            n_rows = n_rows // self.mesh.size
        t_mem = max(
            self.max_t,
            _next_pow2(
                _REC_ELEM_BUDGET // max(n_rows * self.config.max_fills, 1)
                + 1
            )
            // 2,
        )
        cap_t = max(8, min(max(self.dense_t_max, self.max_t), t_mem))
        if dense and first:
            t_floor = self._dense_t_floor.get(cls, 8)
            t_grid = min(max(_next_pow2(need), t_floor), cap_t)
            # Grow-only; a mem-clamped wide grid leaves the floor for
            # future narrower (deeper-capable) first grids.
            self._dense_t_floor[cls] = max(t_floor, t_grid)
            return t_grid
        # cap_t >= max(8, max_t), so the shallowest class needs no clamp.
        classes = sorted({
            max(8, self.max_t) if dense else self.max_t,
            min(max(8, 8 * self.max_t), cap_t),
            min(max(8, cap_t // 4), cap_t),
            cap_t,
        })
        if not dense:
            # Where the row budget clamps cap_t//4 under max_t (10,240
            # rows: cap_t 64, quarter 16) that class drops out.
            classes = [c for c in classes if c >= self.max_t]
        return next((c for c in classes if c >= min(need, cap_t)), cap_t)

    def _admit_lane_range(self, lane: int, l: int, h: int) -> None:
        """Admit the ADD-limit price range [l, h] into `lane`'s grow-only
        envelope, seeding or recentering the base as needed (the scalar
        step of frames._prepare_bases_vec). Raises CapacityError —
        committing NOTHING — when the admitted envelope cannot fit an
        int32 window."""
        if not self._base_set[lane]:
            nb = (l + h) // 2
            if max(h - nb, nb - l) > self._INT32_SAFE:
                raise CapacityError(
                    f"lane {lane}: batch price range [{l}, {h}] spans "
                    "more than 2^31 ticks — int32 books cannot window "
                    "it; use coarser ticks or an int64 BookConfig"
                )
            self.price_base[lane] = nb
            self._base_set[lane] = True
            self._env_lo[lane] = l
            self._env_hi[lane] = h
            return
        el = min(int(self._env_lo[lane]), l)
        eh = max(int(self._env_hi[lane]), h)
        b = int(self.price_base[lane])
        if max(abs(l - b), abs(h - b)) > self.REBASE_LIMIT:
            nb = (el + eh) // 2
            if max(eh - nb, nb - el) > self._INT32_SAFE:
                raise CapacityError(
                    f"lane {lane}: admitted price range [{el}, {eh}] "
                    "spans more than 2^31 ticks — int32 books cannot "
                    "window it; use coarser ticks or an int64 BookConfig"
                )
            self._shift_lane_prices(lane, b - nb)
            self.price_base[lane] = nb
        # Commit the envelope only after every check passed: a raised
        # batch leaves no trace (the device books are unchanged too), so
        # retrying without the offending order cannot inherit a widened
        # window.
        self._env_lo[lane] = el
        self._env_hi[lane] = eh

    def _shift_lane_prices(self, lane: int, delta: int) -> None:
        """Recenter: stored rebased price -> absolute - new_base =
        stored + (old_base - new_base). Inactive slots shift too, harmlessly
        (matching masks everything beyond count; inserts overwrite)."""
        d = jnp.asarray(delta, self.config.dtype)
        self.books = self.books._replace(
            price=self.books.price.at[lane].add(d)
        )

    def _arrival(self, symbol: str) -> int:
        """The symbol's place in arrival order (interner id - 1: the lane a
        one-chip engine gives it), interning a new symbol and growing the
        book stack to hold it."""
        k = self.symbols.intern(symbol) - 1  # Interner ids start at 1
        if k >= self.n_slots:
            if not self.auto_grow:
                raise CapacityError(
                    f"symbol {symbol!r} needs lane {k} but engine has "
                    f"n_slots={self.n_slots} (auto_grow disabled)"
                )
            new_slots = min(max(self.n_slots * 2, k + 1), self.max_slots)
            if self.mesh is not None:
                m = self.mesh.size
                new_slots = min(((new_slots + m - 1) // m) * m, self.max_slots)
            if k >= new_slots:
                raise CapacityError(
                    f"symbol {symbol!r} needs lane {k} but max_slots="
                    f"{self.max_slots}; raise max_slots or shard symbols "
                    "across more engines"
                )
            self._grow_lanes(new_slots)
        return k

    def _grow_lanes(self, new_slots: int) -> None:
        """The venue's lanes double; the stack is provisioned anew only
        where the new count passes its row floor."""
        rows = self._rows_for(new_slots)
        if rows != self.lane_rows:
            # (under a mesh the one gather across chips the engine ever does)
            self.books = self._place(jax.tree.map(
                lambda a: self._relayout(a, rows, jnp), self.books
            ))
        self._grow_base_arrays(self._span_for(new_slots))
        self.n_slots = new_slots
        self.stats.lane_growths += 1

    def _lane(self, symbol: str) -> int:
        """The symbol's lane: its row of the book stack (_lane_of)."""
        return self._lane_of(self._arrival(symbol))

    def _checkpoint(self):
        """Everything a failed batch must roll back: the device book stack
        (immutable on device — retaining the reference is free) plus the
        host-side rebasing state and geometry that packing mutates. Interner
        growth is deliberately NOT rolled back (grow-only and idempotent:
        a replay re-interns the same strings to the same ids, and restored
        books only reference ids that already existed)."""
        return (
            self.books, self.config, self.n_slots,
            self.price_base.copy(), self._base_set.copy(),
            self._env_lo.copy(), self._env_hi.copy(),
            self._ub_base.copy(), self._ub_extra.copy(),
        )

    def _restore(self, cp) -> None:
        """Restore MUST copy the mutable arrays: a checkpoint may be
        restored more than once (restore -> exact re-run mutates rebasing
        state in place -> re-run fails -> restore the SAME checkpoint
        again, e.g. FramePipeline's recovery); assigning by reference would
        let the interim mutations corrupt the checkpoint itself."""
        (
            self.books, self.config, self.n_slots,
            price_base, base_set, env_lo, env_hi, ub_base, ub_extra,
        ) = cp
        self.price_base = price_base.copy()
        self._base_set = base_set.copy()
        self._env_lo = env_lo.copy()
        self._env_hi = env_hi.copy()
        self._ub_base = ub_base.copy()
        self._ub_extra = ub_extra.copy()
        self._rewinds += 1

    def process(self, orders: list[Order]) -> list[MatchResult]:
        """process_columnar() as MatchResult objects, in the reference's
        global emission order."""
        return self.process_columnar(orders).to_results()

    def process_columnar(self, orders: list[Order]):
        """Apply a micro-batch given as Order objects: a convenience over
        the frame path (engine.frames.process_frame on the orders' columns
        — the exact, synchronous form). Returns a columnar EventBatch
        (gome_tpu.engine.events) in original arrival order; device-budget
        overflows are escalated internally (see module docstring), so
        results are always exact. Transactional: a raised batch rolls the
        engine back to its pre-batch state (multi-grid batches commit
        device books per grid — without the rollback, replaying a batch
        that failed on grid 2 would double-apply grid 1's orders)."""
        from ..bus.colwire import orders_to_cols
        from . import frames

        return frames.process_frame(self, orders_to_cols(orders))

    def _run_exact(self, ops: DeviceOp, contexts, lane_ids=None,
                   cap_g: int | None = None):
        """Run one grid, escalating device budgets until nothing overflowed.

        Returns (outs, lane_overrides): the committed [R, T] outputs plus,
        for rows whose fill records were truncated at the grid's K, a
        re-decoded [T] StepOutput with a large-enough record budget.

        lane_ids: for a dense grid, the [R] row -> lane mapping (sentinel
        >= n_slots on padding rows); None for full grids (row == lane).

        cap_g: the grid's cap class (None = the storage cap). Overflow
        first deepens the CLASS — a re-slice of the same storage, confined
        to this grid — and only grows the [S]-wide storage once the grid
        already runs at the full storage cap.
        """
        books_before = self.books  # immutable on device; cheap to retain
        if cap_g is None:
            cap_g = self.config.cap

        def lane_of(row: int) -> int:
            return row if lane_ids is None else int(lane_ids[row])

        # Phase 1: book capacity. A tripped `book_overflow` means a resting
        # insert was dropped (or the grid's cap class sliced away a lane's
        # resting tail — _guard_capped) — the result is NOT what the
        # sequential semantics require, so deepen and replay the whole grid
        # from the snapshot (exact: active slots are a prefix; padding is
        # invisible to matching). The new cap targets the host-side bound
        # (current resting count plus the ADDs packed into the lane) but
        # grows at most 4x per replay — see the clamp below — so deep
        # grids converge in a few exact replays instead of one wildly
        # oversized jump.
        while True:
            # Two spans per attempt: the dispatch, then the blocking
            # overflow fetch (the fetch drains the step, so it is the
            # device wait: an armed TRACER records it as device_execute).
            with span("grid_dispatch", rows=ops.action.shape[0],
                      t=ops.action.shape[1], cap=int(cap_g),
                      n_ops=len(contexts),
                      **self.grid_note(lane_ids is not None)):
                new_books, outs = self._step(
                    books_before, ops, lane_ids, cap_g, n_ops=len(contexts)
                )
                self.stats.device_calls += 1
            with span("frame_fetch"):
                host_flags = np.asarray(jax.device_get(outs.book_overflow))
            if not host_flags.any():
                break
            counts = np.asarray(jax.device_get(books_before.count))  # [S, 2]
            adds_per_row = np.sum(
                np.asarray(ops.action) == ACTION_ADD, axis=1
            )  # [R]
            if lane_ids is None:
                row_counts = counts.max(axis=1)
            else:
                ids = np.asarray(lane_ids)
                valid = ids < counts.shape[0]
                row_counts = np.where(
                    valid,
                    counts.max(axis=1)[np.clip(ids, 0, counts.shape[0] - 1)],
                    0,
                )
            bound = int((row_counts + adds_per_row).max())
            if cap_g < self.config.cap:
                # Confined escalation: this grid re-runs on a deeper slice
                # of the SAME storage; the other grids and the stack are
                # untouched. Snap to the class ladder so the replay reuses
                # a compiled shape.
                self.stats.grid_cap_escalations += 1
                target = max(min(bound, 4 * cap_g), cap_g + 1)
                cap_g = next(
                    (c for c in _cap_ladder(self.config.cap) if c >= target),
                    self.config.cap,
                )
                continue
            # The bound assumes EVERY packed ADD rests — with deep dense
            # grids (thousands of ADDs on a hot row) that overshoots the
            # true requirement by orders of magnitude, and cap is global
            # across all S lanes (one 16K-cap escalation on a 10K-lane
            # stack is gigabytes). Grow at most 4x per escalation: the
            # replay loop converges in log4 steps to the smallest
            # sufficient pow2, each step exact.
            self.stats.cap_escalations += 1
            new_cap = _next_pow2(
                max(min(bound, 4 * self.config.cap), self.config.cap + 1)
            )
            if new_cap > self.max_cap:
                raise CapacityError(
                    f"book cap escalation to {new_cap} exceeds max_cap="
                    f"{self.max_cap} (a side is holding >{self.config.cap} "
                    "resting orders); raise max_cap or shed load"
                )
            books_before = self._place(grow_books(books_before, new_cap))
            self.config = dataclasses.replace(self.config, cap=new_cap)
            cap_g = new_cap
        self.books = new_books
        outs = jax.device_get(outs)

        # Phase 2: fill records. n_fills > K truncated this op's *records*
        # only — the book transition is exact either way — so re-run just the
        # affected rows from the snapshot with K' >= max fills observed.
        # n_fills <= resting orders crossed <= cap, so K' <= cap and the
        # set of escalated compile shapes is bounded by log2(cap).
        lane_overrides: dict[int, StepOutput] = {}
        n_fills = np.asarray(outs.n_fills)
        overflowed = sorted(
            {
                row
                for (row, t) in contexts
                if n_fills[row, t] > self.config.max_fills
            }
        )
        for row in overflowed:
            self.stats.fill_record_escalations += 1
            k = min(_next_pow2(int(n_fills[row].max())), self.config.cap)
            big = dataclasses.replace(self.config, max_fills=k)
            lane = lane_of(row)
            lane_book = jax.tree.map(lambda a: a[lane], books_before)
            lane_ops = jax.tree.map(lambda a: a[row], ops)
            # Donating twin: the one-lane book slice and op row are built
            # fresh above and dead after this call on both grid paths.
            _, lane_out = lane_scan_donating(big, lane_book, lane_ops)
            self.stats.device_calls += 1
            lane_overrides[row] = jax.device_get(lane_out)
        return outs, lane_overrides

    def grid_note(self, dense: bool) -> dict:
        """What a grid_dispatch span notes of the grid's kind: under a full
        grid's rows (the provisioned lane_rows) the venue's lanes."""
        if dense:
            return {"grid": "dense"}
        return {"grid": "full", "lanes": self.n_slots}

    def _plan_step(self, rows: int, cfg: BookConfig, dense: bool,
                   n_ops: int | None):
        """Choose the kernel for one grid of `rows` (per-chip) lanes and
        attribute the grid to it in EngineStats. Returns kernel_plan's
        (block_s, interpret): block_s None means the scan path. n_ops is
        the grid's real op count; None (precompile replay) counts
        nothing."""
        block_s, interpret, reason = None, False, None
        if self.kernel == "pallas":
            from ..ops import kernel_plan

            block_s, interpret, reason = kernel_plan(
                rows, cfg.cap, cfg.dtype, self._pallas_interpret
            )
        if n_ops is not None:
            st = self.stats
            ran = (
                "scan" if block_s is None
                else "interpret" if interpret else "pallas"
            ) + ("_dense" if dense else "_full")
            st.grids_by_kernel[ran] = st.grids_by_kernel.get(ran, 0) + 1
            st.ops_by_kernel[ran] = st.ops_by_kernel.get(ran, 0) + n_ops
            if reason is not None:
                st.scan_giveways[reason] = st.scan_giveways.get(reason, 0) + 1
        return block_s, interpret

    def _grid_plan(self, n_rows: int, dense: bool, cap_g: int | None,
                   n_ops: int | None) -> StepPlan:
        """The step of one grid of n_rows (global) rows at cap class cap_g
        (None/equal = storage cap), counted once in EngineStats
        (_plan_step)."""
        cfg = self.config
        if cap_g is not None and cap_g != cfg.cap:
            cfg = dataclasses.replace(cfg, cap=cap_g)
        # Per-chip rows: under a mesh each chip blocks its own local slice
        # (parallel.mesh makes the same kernel_plan call at trace time).
        rows = n_rows // (1 if self.mesh is None else self.mesh.size)
        block_s, interpret = self._plan_step(rows, cfg, dense, n_ops)
        return StepPlan(cfg, dense, block_s, interpret)

    def _step(self, books: BookState, ops: DeviceOp, lane_ids=None,
              cap_g: int | None = None, n_ops: int | None = None):
        """Run one [R, T] grid with the configured kernel. lane_ids selects
        the dense gather/scatter step (compact grid over live lanes; under
        a mesh the rows are laid out per shard and the gather runs inside
        shard_map — parallel.mesh.sharded_dense_step). kernel="pallas"
        grids run the Pallas kernel where ops.kernel_plan finds a blocking
        and give way to the scan path otherwise (_plan_step counts which);
        escalation re-runs (lane_scan) stay on the scan path — they are
        rare and per-lane.

        cap_g: the grid's cap class (None/equal = storage cap). Every step
        variant slices the slot axis to it, so the per-step cost tracks
        this grid's own depth class.

        n_ops: the grid's real op count, given by live dispatches so
        EngineStats attributes the grid to the kernel that ran it."""
        dense = lane_ids is not None
        plan = self._grid_plan(ops.action.shape[0], dense, cap_g, n_ops)
        cfg, block_s, interpret = plan.cfg, plan.block_s, plan.interpret
        # Donation policy (GL6xx): a HOST-sourced grid (numpy)
        # re-transfers on every dispatch, so its device buffers are dead
        # after the call and the donating twins let XLA reuse them for
        # the outputs. Device-built grids (frames._scatter_grid_fn, all
        # the packer makes) must NOT donate: escalation replays
        # re-dispatch the same arrays (_run_exact's phase-1 loop).
        donate = isinstance(ops.action, np.ndarray)
        _batch = batch_step_donating if donate else batch_step
        _dense = dense_batch_step_donating if donate else dense_batch_step
        _densek = dense_kernel_step_donating if donate else dense_kernel_step
        _fullk = full_kernel_step_donating if donate else full_kernel_step
        if dense and self.mesh is not None:
            from ..parallel.mesh import shard_batch, sharded_dense_step

            # Localize: global lane -> shard-local index (each chip's row
            # block names only its own lanes, so lane % local IS the
            # local index); sentinel rows map to `local` (out of range on
            # every chip: gathered as zero books, dropped by the scatter).
            rows = self.lane_rows
            local = rows // self.mesh.size
            ids_np = np.asarray(lane_ids)
            ids_local = np.where(
                ids_np >= rows, local, ids_np % local
            ).astype(np.int32)
            stepper = self._sharded_dense_steppers.get(cfg)
            if stepper is None:
                stepper = sharded_dense_step(
                    cfg,
                    self.mesh,
                    kernel=self.kernel,
                    pallas_interpret=self._pallas_interpret,
                )
                self._sharded_dense_steppers[cfg] = stepper
            # (live: each shard's live rows of this grid, for the trace's
            # reader; every shard is padded to the largest one's bucket)
            with span(
                "shard_put", rows=len(ids_np) // self.mesh.size,
                live="/".join(map(str, (
                    ids_np.reshape(self.mesh.size, -1) < rows
                ).sum(axis=1))),
            ):
                ids_local = shard_batch(self.mesh, jnp.asarray(ids_local))
                ops = shard_batch(self.mesh, ops)
            return stepper(books, ids_local, ops)
        if dense:
            ids = jnp.asarray(lane_ids, jnp.int32)
            if block_s is not None:
                return _densek(cfg, books, ids, ops, block_s, interpret)
            return _dense(cfg, books, ids, ops)
        if self.mesh is not None:
            from ..parallel.mesh import shard_batch, sharded_batch_step

            stepper = self._sharded_steppers.get(cfg)
            if stepper is None:
                stepper = sharded_batch_step(
                    cfg,
                    self.mesh,
                    kernel=self.kernel,
                    pallas_interpret=self._pallas_interpret,
                )
                self._sharded_steppers[cfg] = stepper
            with span("shard_put"):
                ops = shard_batch(self.mesh, ops)
            return stepper(books, ops)
        if block_s is not None:
            return _fullk(cfg, books, ops, block_s, interpret)
        return _batch(cfg, books, ops)

    # -- snapshot support ----------------------------------------------------
    def take_cut(self) -> BookCut:
        """The engine's state now, between two dispatches, without waiting
        for the device: the current book stack (its transfer to the host
        started) and copies of the per-lane host vectors. Called right after a frame's last dispatch it is the state
        after exactly that frame, with later frames free to be packed and
        dispatched meanwhile (BookCut)."""
        books = self.books
        for leaf in books:
            leaf.copy_to_host_async()
        return BookCut(
            books=books,
            lanes={
                "price_base": self.price_base.copy(),
                "base_set": self._base_set.copy(),
                "env_lo": self._env_lo.copy(),
                "env_hi": self._env_hi.copy(),
            },
            rows=self._venue_rows(),
            meta={
                "cap": self.config.cap,
                "max_fills": self.config.max_fills,
                "dtype": np.dtype(self.config.dtype).name,
                "self_trade": self.config.self_trade,
                "n_slots": self.n_slots,
                "max_t": self.max_t,
            },
            rewinds=self._rewinds,
        )

    def cut_is_current(self, cut: BookCut) -> bool:
        """False once the engine has been rewound since `cut` was taken: a
        frame the cut counted on was re-run or dropped."""
        return cut.rewinds == self._rewinds

    def export_state(self) -> dict:
        """Host-side copy of all mutable engine state (books + interners +
        geometry) for the durability layer (gome_tpu.persist): a cut,
        waited for, with the interners' tables beside it."""
        cut = self.take_cut()
        arrays = cut.arrays()
        return {
            "books": {k: arrays[k] for k in BookState._fields},
            "symbols": self.symbols.to_list(),
            "oids": self.oids.to_list(),
            "uids": self.uids.to_list(),
            **cut.meta,
            # JSON-safe lists, as a version 1 snapshot's manifest has them.
            "price_base": arrays["price_base"].tolist(),
            "base_set": arrays["base_set"].astype(int).tolist(),
            "env_lo": arrays["env_lo"].tolist(),
            "env_hi": arrays["env_hi"].tolist(),
        }

    def import_state(self, state: dict) -> None:
        """Restore a state exported by export_state (snapshot recovery).
        Replaces books, interners, and geometry; stats are NOT restored
        (counters describe a process lifetime, not book state). The
        venue's self-trade rule is the one piece of the BookConfig that is
        not taken from the state: it is compared, and a state written
        under another rule is refused (one from before the rule existed was
        written under "none"). The books would load, but whoever replays
        the frames behind a snapshot has to make the events the first
        process made, and the other rule makes others."""
        rule = state.get("self_trade", "none")
        if rule != self.config.self_trade:
            raise ValueError(
                f"snapshot was written under engine.self_trade {rule!r}, "
                f"this engine runs {self.config.self_trade!r}: restore "
                "under the rule it was written under"
            )
        self.config = dataclasses.replace(
            self.config,
            cap=int(state["cap"]),
            max_fills=int(state["max_fills"]),
            dtype=jnp.dtype(state["dtype"]),
        )
        # Restoring an int64 snapshot into a process that never built an
        # int64 book would silently device_put int32 arrays (x64 off) —
        # the exact failure ensure_dtype_usable exists to prevent.
        from .book import ensure_dtype_usable

        ensure_dtype_usable(self.config.dtype)
        self.n_slots = int(state["n_slots"])
        if self.mesh is not None and self.n_slots % self.mesh.size != 0:
            raise ValueError(
                f"snapshot n_slots {self.n_slots} is not a multiple of the "
                f"mesh size {self.mesh.size}; restore into a non-mesh "
                "engine or re-snapshot from a mesh-aligned one"
            )
        self.max_t = int(state["max_t"])
        # A snapshot holds the venue's n_slots lanes in the one-chip order
        # (export_state): each goes to the row this engine's placement
        # gives it, and the rows past them (the row floor's) stay empty.
        rows = self._venue_rows()

        def placed(a, n=self.lane_span):
            if rows is None:
                return np.array(a)  # (a copy: the state stays the caller's)
            a = np.asarray(a)
            out = np.zeros((n,) + a.shape[1:], a.dtype)
            out[rows] = a
            return out

        b = {k: placed(v, self.lane_rows) for k, v in state["books"].items()}
        books = BookState(**b)
        # _place device_puts with the mesh sharding directly from host
        # arrays; an inner device_put first would materialize the whole
        # stack on one chip (the OOM the mesh exists to avoid).
        self.books = (
            self._place(books) if self.mesh is not None
            else jax.device_put(books)
        )
        from .nativehost import make_interner

        self.symbols = Interner.from_list(list(state["symbols"]))
        self._lane_map_cache.clear()  # lane ids come from the new interner
        self.oids = make_interner(from_list=list(state["oids"]))
        self.uids = Interner.from_list(list(state["uids"]))
        self._rebase = jnp.dtype(self.config.dtype).itemsize <= 4
        n = self.lane_span
        # count_ub restarts exact from the restored books (nothing in
        # flight after a restore).
        counts = np.asarray(state["books"]["count"])  # [n_slots, 2]
        self._ub_base = placed(counts.astype(np.int64).max(axis=1))
        self._ub_extra = np.zeros(n, np.int64)
        if "price_base" in state:
            self.price_base = placed(np.asarray(state["price_base"], np.int64))
            self._base_set = placed(np.asarray(state["base_set"], bool))
            self._env_lo = placed(np.asarray(state["env_lo"], np.int64))
            self._env_hi = placed(np.asarray(state["env_hi"], np.int64))
        else:
            # Pre-rebasing snapshot: stored prices are absolute, i.e. base 0.
            # Lanes holding resting orders MUST be marked base-set at 0 —
            # otherwise the next batch seeds a fresh base and encodes takers
            # relative to it while the restored book stays absolute (silent
            # non-matching). Envelope from the restored books themselves.
            self.price_base = np.zeros(n, np.int64)
            occupied = counts.sum(axis=1) > 0
            self._base_set = placed(occupied)
            prices = np.asarray(state["books"]["price"]).astype(np.int64)
            cap = prices.shape[-1]  # [n_slots, 2, cap]
            slot = np.arange(cap)
            active = slot[None, None, :] < counts[:, :, None]
            self._env_lo = placed(np.where(
                occupied,
                np.where(active, prices, np.iinfo(np.int64).max).min((1, 2)),
                0,
            ))
            self._env_hi = placed(np.where(
                occupied, np.where(active, prices, 0).max((1, 2)), 0
            ))

    def verify_books(self) -> None:
        """Check every lane against the book invariants (priority-sorted
        slots, positive resting lots, zeroed tails, FIFO seq within price
        levels). O(S*cap) host work — a debug/ops API, not a hot-path check
        (the reference's equivalent was panics sprinkled through the
        linked-list code, nodelink.go:132-157). Raises BookInvariantError
        with the offending lane/side on violation (explicit raises, not
        asserts — python -O must not strip an ops check)."""

        def check(cond, lane, side, what):
            if not cond:
                raise BookInvariantError(
                    f"lane {lane} side {side}: {what}"
                )

        books = jax.device_get(self.books)
        price = np.asarray(books.price)
        lots = np.asarray(books.lots)
        seq = np.asarray(books.seq)
        counts = np.asarray(books.count)
        cap = price.shape[-1]
        for lane in range(counts.shape[0]):
            for side in (0, 1):
                n = int(counts[lane, side])
                check(0 <= n <= cap, lane, side, f"count {n} out of range")
                p, l, s = (a[lane, side] for a in (price, lots, seq))
                check(bool((l[:n] > 0).all()), lane, side, "empty slot in prefix")
                check(bool((l[n:] == 0).all()), lane, side, "lots beyond count")
                if n > 1:
                    dp = np.diff(p[:n].astype(np.int64))
                    ordered = (dp <= 0) if side == BUY else (dp >= 0)
                    check(bool(ordered.all()), lane, side, "priority order broken")
                    same = dp == 0
                    check(
                        bool((np.diff(s[:n])[same] > 0).all()),
                        lane, side, "FIFO seq order broken",
                    )

    # -- views -------------------------------------------------------------
    def lane_books(self) -> BookState:
        """Host copy of the books with ABSOLUTE prices (per-lane rebasing
        offsets added back; the price leaf widens to int64 when bases are in
        play), in the one-chip order: row k is the k-th symbol to arrive
        (symbol_lane), under a mesh too. Consumers of raw device state use
        export_state instead."""
        books = jax.device_get(self.books)
        base = self.price_base
        rows = self._venue_rows()
        if rows is not None:
            books = jax.tree.map(lambda a: np.asarray(a)[rows], books)
            base = base[rows]
        if self._rebase and self._base_set.any():
            price = np.asarray(books.price).astype(np.int64)
            books = books._replace(price=price + base[:, None, None])
        return books

    def symbol_lane(self, symbol: str) -> int:
        """Read-only lookup: the lane owning `symbol` in the one-chip order
        (its row of lane_books and of a snapshot; interner id - 1). Raises
        KeyError for a symbol the engine has never processed (unlike _lane,
        this never interns or grows device state)."""
        i = self.symbols.get(symbol)
        if i is None:
            raise KeyError(f"unknown symbol {symbol!r}")
        return i - 1
