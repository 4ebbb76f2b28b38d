"""The frame packer: how a batch of orders becomes op grids, and the grids'
outputs events, with ZERO per-order Python.

The engine's one way in is a decoded ORDER frame's columns
(gome_tpu.bus.colwire: decode_order_frame on the wire, orders_to_cols for
a list of Order objects); everything here works on them as numpy arrays:

  * interning is vectorized: the interner dict is touched once per UNIQUE
    symbol and uuid of the frame's dictionaries, and a take() broadcasts
    ids back to all N orders;
  * the rebasing envelope (_prepare_bases_vec), the unrepresentable-DEL
    drop mask, and the per-lane time-slot assignment are all numpy
    (sort/segment tricks) or one native pass (nativehost);
  * grid packing splits the frame into per-cap-class grid trains, takes
    each grid's rows and depth from BatchEngine (_grid_geometry: dense
    gather/scatter grids vs full grids; _grid_depth) and builds the
    padded grid on the device from O(ops) bytes (_scatter_grid_fn).

Two ways to run a frame's grids, on the same BatchEngine state:

  * `apply_frame` / `process_frame` — exact, synchronous: each grid runs
    through BatchEngine._run_exact (device budgets escalate in-line) and
    decodes through events.decode_grid_columnar. One device round trip
    per grid. The list-of-Order conveniences (BatchEngine.process /
    process_columnar, MatchEngine's) are this form.
  * `apply_frame_fast` (submit_frame + resolve_frame; across frames,
    engine.pipeline.FramePipeline) — the production hot path: every grid
    of the frame is DISPATCHED back-to-back with a device-side
    event-compaction kernel (_compact_accum) appended, and the frame
    resolves from its own fetch. A frame costs the device one scatter,
    one step and one compaction per grid (and the count_ub reduction),
    nothing else: its event buffers are an earlier frame's, handed back
    at resolve and donated into the first compaction, which reads the
    totals as zero (_take_buffers), so no op makes or clears them. When
    its event matrices are small (_one_phase, ONE_PHASE_MAX_BYTES) it
    costs the host ONE dispatch a grid, a program that holds all four
    (_grid_program; on one chip: a mesh places every grid through
    BatchEngine._step), and ONE wait: the matrices' copy starts with the
    totals' at submit. A large frame dispatches scatter, step and
    compaction apart, since a program keyed by its wandering buffer
    classes would lower the kernel again for each, and fetches the totals
    first and then the used prefixes (resolve_frame). The compaction
    reduces the transfer from O(S*T*K)
    record tensors (~500 B/order) to O(events) (~30 B/order). If any
    device budget tripped (book overflow, record truncation, compaction
    buffer), the frame transactionally rolls back and re-runs on the
    exact path — rare by construction, never wrong.

Event content and ordering of both are pinned to the oracle's by
differential tests (tests/test_frames.py).
"""

from __future__ import annotations

import functools
import time
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.compile_journal import JOURNAL, frame_combo_detail
from ..obs.timeline import TIMELINE
from ..types import Action, OrderType, may_rest
from ..utils.trace import TRACER
from ..utils.tracing import span
from .batch import (
    BatchEngine,
    _next_pow2,
    _next_pow4,
    is_device_fault,
    merged_floor_key,
    splice_outs,
    step_in_program,
)
from .book import GRID_I32_FIELDS, DeviceOp
from .step import ACTION_ADD, EXPIRED_STP, LOT_MAX32, TAKER_PRICE_MAX32

#: Cumulative wall-clock seconds apply_frame_fast spent BLOCKED on the
#: device->host fetch of compacted events. Blocking there also drains the
#: dispatched grids, so this is the device wait as the host sees it; the
#: service bench reports it beside the measured number.
FETCH_SECONDS = 0.0

ACTION_DEL = int(Action.DEL)
MARKET = int(OrderType.MARKET)
#: What StepOutput.expired can say, in the order of the totals' columns 4..
#: (compact_accum) and of a frame's `expired` array: the code, the
#: EngineStats field that counts it, and its label on /metrics
#: gome_orders_expired_total. The kinds' three, then the one of a venue's
#: self-trade rule, which only such a venue's totals hold (expired_codes),
#: so that a venue without a rule runs the programs it always ran.
_EXPIRED = (
    (int(OrderType.IOC), "expired_ioc", "ioc"),
    (int(OrderType.FOK), "fok_killed", "fok"),
    (int(OrderType.POST_ONLY), "post_only_blocked", "post_only"),
    (EXPIRED_STP, "stp_expired", "stp"),
)
#: Columns of a frame's per-grid totals: fills, cancels, book overflows,
#: max n_fills, then the expired adds of each of expired_codes(config):
#: n_totals(config) in all.
_N_COUNTS = 4


def expired_codes(config) -> tuple:
    """The codes of StepOutput.expired that a step under `config` can emit,
    in the order of the totals' columns 4.. and of a frame's `expired`."""
    codes = tuple(code for code, _field, _label in _EXPIRED)
    return codes[:-1] if config.self_trade == "none" else codes


def n_totals(config) -> int:
    """The width of the per-grid totals of an engine under `config`."""
    return _N_COUNTS + len(expired_codes(config))


_GRID_FIELDS = DeviceOp._fields  # one canonical field list + order

#: Hard per-frame op ceiling (wire contract, enforced in _frame_arrays).
#: This is what makes the m_pad / e_fills / e_cancels / totals_len combo
#: dimensions FINITE: every one of them is a quantized function of the
#: frame's op count, so bounding the op count bounds the compile surface
#: (analysis.surface GL905 derives the committed combo universe from it).
#: 1M ops/frame is ~100x the largest replay burst; a frame this large is
#: a producer bug, not traffic.
MAX_FRAME_OPS = 1 << 20

#: The frame-dispatch combo key, field by field, in tuple order. This is
#: the spine of the gomesurface GL902 site-agreement check: the build
#: tuple (submit_frame), every replay unpack (precompile_combos,
#: obs.compile_journal.frame_combo_detail), and the persisted manifest
#: (BatchEngine.shape_manifest) must all agree with THIS declaration —
#: adding a dimension means updating every site in one commit, and lint
#: fails until they line up.
COMBO_FIELDS = (
    "n_rows",      # grid rows (live-lane bucket or the full lane_rows)
    "t_grid",      # grid time-axis depth (packed-train class)
    "cap_g",       # book capacity class dispatched against
    "dense",       # full-grid (False) vs compact gather/scatter (True=
                   # lane_ids present) dispatch path
    "m_pad",       # packed-op axis length (pow4 of the frame op count)
    "k_rec",       # step record depth min(max_fills, cap)
    "e_fills",     # fills compaction buffer width (pow2 + grow-only floor)
    "e_cancels",   # cancels compaction buffer width
    "totals_len",  # per-grid totals buffer length
)


def _lane_map(eng: BatchEngine, symbols) -> np.ndarray:
    """symbol-dictionary -> lane-id array, cached by dictionary identity.

    The wire decoder (bus.colwire) returns the SAME list object for a
    dictionary region it has seen before, so a stable symbol universe
    resolves its per-unique interner walk once, not once per frame. What
    is cached is each symbol's place in arrival order, which is permanent
    (the interner is grow-only); its lane follows from the engine's
    placement (the same number without a mesh). BUT a cached map is
    only usable while every lane fits the CURRENT book stack: _arrival()'s
    side effect is auto-growing n_slots, and a transactional rollback
    (_restore after a failed/overflowed frame) shrinks n_slots back — a
    blind cache hit on the retry would skip the re-growth and index past
    the restored books. Hence the max-lane revalidation; a stale hit
    recomputes, re-growing exactly as the first attempt did. The cache
    resets when the engine's interners are replaced (import_state)."""
    ent = eng._lane_map_cache.get(symbols)
    if ent is not None and ent[1] < eng.n_slots:
        return eng._lane_of(ent[0])
    arrival_of_sym = np.empty(len(symbols), np.int64)
    for i, s in enumerate(symbols):
        arrival_of_sym[i] = eng._arrival(s)  # may auto-grow the book stack
    max_lane = int(arrival_of_sym.max()) if len(arrival_of_sym) else -1
    eng._lane_map_cache.put(symbols, (arrival_of_sym, max_lane))
    return eng._lane_of(arrival_of_sym)


def intern_column(interner, uniques) -> np.ndarray:
    """Intern a column's per-batch unique strings; returns int64 ids
    aligned with `uniques`. The only Python loop is over uniques."""
    ids = np.empty(len(uniques), np.int64)
    intern = interner.intern
    for i, s in enumerate(uniques):
        ids[i] = intern(s if isinstance(s, str) else s.decode())
    return ids


def _frame_arrays(eng: BatchEngine, cols: dict) -> dict:
    """Stage 1: vectorized interning, contract checks, envelope/drop mask,
    and per-lane slot assignment. Returns the arrays grid packing needs."""
    n = int(cols["n"])
    if n > MAX_FRAME_OPS:
        raise ValueError(
            f"frame has {n} ops, above the MAX_FRAME_OPS contract ceiling "
            f"({MAX_FRAME_OPS}); split the frame — the compile-surface "
            "bound (analysis/combo_universe.json) is derived from this "
            "limit"
        )
    action = np.ascontiguousarray(cols["action"], np.int64)
    side = np.ascontiguousarray(cols["side"], np.int64)
    kind = np.ascontiguousarray(cols["kind"], np.int64)
    price = np.ascontiguousarray(cols["price"], np.int64)
    volume = np.ascontiguousarray(cols["volume"], np.int64)

    lane_of_sym = _lane_map(eng, cols["symbols"])
    lanes = lane_of_sym[cols["symbol_idx"]]

    uid_of = intern_column(eng.uids, cols["uuids"])
    uid_ids = uid_of[cols["uuid_idx"]]
    # oids are raw per-order strings and typically (in exchange flow)
    # almost all NEW — a dedup sort would cost more than it saves; intern
    # directly (the interner handles repeats). One native call when the
    # C++ interner backs eng.oids.
    intern_batch = getattr(eng.oids, "intern_batch", None)
    if intern_batch is not None:
        oid_ids = intern_batch(cols["oids"])
    else:
        intern = eng.oids.intern
        oid_ids = np.fromiter(
            (intern(o.decode()) for o in cols["oids"].tolist()), np.int64, n
        )

    is_add = action == ACTION_ADD
    bad = is_add & (volume <= 0)
    if bad.any():
        i = int(np.nonzero(bad)[0][0])
        raise ValueError(
            f"volume must be positive, got {volume[i]}; volume<=0 is out "
            "of contract"
        )
    if np.dtype(eng.config.dtype).itemsize <= 4:
        over = is_add & (volume > LOT_MAX32)
        if over.any():
            i = int(np.nonzero(over)[0][0])
            raise ValueError(
                f"volume {volume[i]} exceeds the int32-mode per-order lot "
                f"ceiling {LOT_MAX32}; use coarser lot units or an int64 "
                "BookConfig"
            )

    drop = _prepare_bases_vec(eng, lanes, action, kind, price)
    bases = eng.price_base[lanes]

    # Occurrence index of each op within its lane, in arrival order. One
    # native linear pass when available; else the numpy stable-sort trick
    # (sort by lane groups each lane's ops contiguously with arrival order
    # preserved; index-in-group = arange minus the group's start).
    keep = ~drop
    from . import nativehost

    if nativehost.available():
        t = nativehost.occurrences(
            lanes, None if keep.all() else keep, eng.lane_rows
        )
    else:
        t = np.full(n, -1, np.int64)
        if keep.any():
            ki = np.nonzero(keep)[0]
            order = np.argsort(lanes[ki], kind="stable")
            sorted_lanes = lanes[ki][order]
            starts = np.concatenate(
                ([0], np.nonzero(np.diff(sorted_lanes))[0] + 1)
            )
            group_start = np.zeros(len(sorted_lanes), np.int64)
            group_start[starts] = starts
            group_start = np.maximum.accumulate(group_start)
            occ = np.arange(len(sorted_lanes)) - group_start
            t[ki[order]] = occ

    # count_ub upkeep (cap-class selection, batch.py): every kept ADD of
    # a kind that can rest (LIMIT, POST_ONLY) may rest at most once; a
    # MARKET, IOC or FOK add never does, so a taker-heavy lane climbs no
    # cap class it never needs. The increment happens at PACK time — the
    # classes chosen below then cover this frame's own worst case.
    kept_add = keep & is_add
    rest_mask = kept_add & may_rest(kind)
    add_counts = np.bincount(
        lanes[rest_mask], minlength=eng.lane_span
    ).astype(np.int64)
    eng.note_packed_adds(add_counts)
    adds_by_kind = np.bincount(kind[kept_add])

    return dict(
        n=n, action=action, side=side, kind=kind, price=price,
        volume=volume, lanes=lanes, uid_ids=uid_ids, oid_ids=oid_ids,
        keep=keep, t=t, bases=bases,
        dels_total=int((action == ACTION_DEL).sum()),
        add_counts=add_counts,
        # EngineStats upkeep (_assemble): kept adds by kind, of them those
        # that cannot rest, and the expired adds per expired_codes, which
        # the frame's totals (fast path) or outputs (exact path) add up.
        adds_by_kind=adds_by_kind,
        takers=int(kept_add.sum()) - int(add_counts.sum()),
        expired=np.zeros(len(expired_codes(eng.config)), np.int64),
    )


def _scatter_grid(dtype, n_rows: int, t_grid: int, cols, flat) -> DeviceOp:
    """Packed op columns [7, m_pad] + flat positions [m_pad] -> a padded
    [n_rows, t_grid] DeviceOp grid (traceable). Padding columns carry
    flat == R*T and drop."""
    rt = n_rows * t_grid
    fields = {}
    for i, name in enumerate(_GRID_FIELDS):
        want = jnp.int32 if name in GRID_I32_FIELDS else dtype
        fields[name] = (
            jnp.zeros((rt,), want)
            .at[flat]
            .set(cols[i].astype(want), mode="drop")
            .reshape(n_rows, t_grid)
        )
    return DeviceOp(**fields)


@functools.lru_cache(maxsize=256)  # a cap-class train set (rows x depth
# classes x caps) can exceed 64 live shapes; eviction = silent re-trace
def _scatter_grid_fn(dtype_name: str, n_rows: int, t_grid: int):
    """Jitted device-side grid builder for one (dtype, R, T) shape
    (_scatter_grid). The host uploads O(ops) bytes regardless of the
    grid's occupancy — a Zipf train's deep tail grids are ~1% occupied,
    and shipping their NOP padding over the device link cost more than
    the matching itself."""
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def scatter(cols, flat):
        return _scatter_grid(dtype, n_rows, t_grid, cols, flat)

    return scatter


class HostGrid(NamedTuple):
    """A grid still on the host, as the packer leaves it for a frame whose
    grids run as one program each (_grid_program): the scatter's two
    arguments and the shape it builds."""

    cols: np.ndarray  # [7, m_pad] packed op columns
    flat: np.ndarray  # [m_pad] flat (row, t) positions
    n_rows: int
    t_grid: int

    def on_device(self, dtype) -> DeviceOp:
        return _scatter_grid_fn(
            np.dtype(dtype).name, self.n_rows, self.t_grid
        )(self.cols, self.flat)


def _class_partitions(eng: BatchEngine, a: dict, active_idx):
    """Split a frame's kept ops into per-cap-class partitions by LANE
    (VERDICT r4 #2: stop taxing 10K shallow lanes for one hot lane's
    escalated cap). A lane's class is the smallest ladder cap covering its
    resting-count upper bound — count_ub already includes this frame's
    packed ADDs (note_packed_adds runs at pack time), so within-frame
    growth is covered and a well-estimated lane can never overflow its
    class. Same-lane ops stay in one partition: per-symbol FIFO is
    preserved exactly as in a single train.

    Returns [(cap_class, active_idx_subset), ...], ascending by class;
    a single-class ladder (storage cap <= CAP_CLASS_MIN) or disabled
    dense packing degenerates to one partition at the storage cap."""
    from .batch import _cap_ladder

    ladder = _cap_ladder(eng.config.cap)
    if len(ladder) == 1 or not eng.dense:
        return [(eng.config.cap, active_idx)]
    lad = np.asarray(ladder, np.int64)
    need = eng.count_ub()[a["lanes"][active_idx]]
    cls_i = np.minimum(np.searchsorted(lad, need), len(ladder) - 1)
    out = []
    for ci in np.unique(cls_i):
        out.append((ladder[int(ci)], active_idx[cls_i == ci]))
    return out


def pack_frame_grids(eng: BatchEngine, a: dict, on_device: bool = True,
                     small: bool = False) -> list[tuple]:
    """Stage 2: split the frame into per-cap-class grid trains (lanes
    deeper than a grid's time axis roll into the next grid — FIFO by
    construction), pack each grid's ops as columns, and DISPATCH the
    device-side scatter that rebuilds the padded grid on device. Returns
    [(ops, meta, lane_ids, cap_g), ...] with ops already device-resident;
    with on_device False nothing is dispatched and ops is the HostGrid
    (submit_frame: a small frame's scatter is part of its grid's one
    program).

    `small` is the one-phase rule's verdict on the frame (_compact_sizes,
    taken by submit_frame before the pack). A small frame whose lanes span
    more than one class packs ONE train at the deepest class present: any
    class at or above a lane's own is exact for it (_slice_books_cap; a
    lane's class is only the smallest that covers its count_ub), and at a
    few dozen orders a second grid's dispatch, gather, scatter-back and
    copy of the book stack cost far more than the shallow rows' share of
    a deeper kernel (MERGE_MAX_CELLS). Its floors are keyed apart from the
    per-class trains' (batch.merged_floor_key); a["merged"] is the class
    it ran at, 0 for a frame packed class by class.

    Each train's loop carries a SHRINKING active-op index set: each grid
    touches only the ops still alive at its time offset, so a G-grid
    train (a Zipf flow draining hot lanes) costs O(sum of survivors), not
    O(G * frame) — with 27 grids per frame the latter was the consumer's
    dominant host cost."""
    keep, t = a["keep"], a["t"]
    a["merged"] = 0
    grids: list[tuple] = []
    kept_idx = np.nonzero(keep)[0]
    if not len(kept_idx):
        return grids
    parts = _class_partitions(eng, a, kept_idx)
    if small and len(parts) > 1:
        deepest = parts[-1][0]
        lanes = len(np.unique(a["lanes"][kept_idx]))
        depth = int(t[kept_idx].max()) + 1
        if _next_pow2(lanes) * _next_pow2(depth) * deepest <= MERGE_MAX_CELLS:
            a["merged"] = deepest
            parts = [(deepest, kept_idx)]
    for cap_g, part_idx in parts:
        _pack_class_train(
            eng, a, part_idx, t[part_idx], cap_g, grids, on_device,
            merged_floor_key(cap_g) if a["merged"] else cap_g,
        )
    return grids


def _pack_class_train(eng: BatchEngine, a: dict, active_idx, t_sub,
                      cap_g: int, grids: list, on_device: bool,
                      floor_key: int) -> None:
    """Pack one cap class's grid train (the loop body of the original
    single-train pack_frame_grids, with geometry ratchets keyed by
    `floor_key`: the class, or a merged small frame's key for it). Each
    grid's geometry is two decisions made apart, both on BatchEngine:
    _grid_geometry picks the ROWS (a dense grid over the live lanes, or
    the full grid with row == lane once the row bucket reaches n_slots)
    and _grid_depth picks the DEPTH from the row count and the deepest
    lane still to carry, whichever kind the rows are. So a venue
    provisioned with exactly its live lanes runs a hot lane's frame as a
    couple of deep full grids, not as a train of max_t-deep ones; max_t
    is only the shallowest depth class."""
    lanes, t = a["lanes"], a["t"]
    t_off = 0
    while len(active_idx):
        live = np.unique(lanes[active_idx])
        first = t_off == 0
        use_dense, n_rows, lane_ids, row_of = eng._grid_geometry(
            live, first=first, cls=floor_key
        )
        if not use_dense:
            # Full grid: row == lane (identity map).
            row_of = np.arange(n_rows, dtype=np.int64)
        t_grid = eng._grid_depth(
            n_rows, int(t_sub.max()) - t_off + 1, floor_key, first,
            use_dense,
        )

        from . import nativehost

        in_window = t_sub < t_off + t_grid
        m = int(np.count_nonzero(in_window))
        m_pad = _next_pow4(max(m, 64))
        if nativehost.available():
            # Column pack + the 11 meta extractions in ONE native pass
            # (the numpy form below is ~15 separate mask passes).
            cols, flat, meta = nativehost.pack_grid(
                a, active_idx, row_of, t_off, t_grid, n_rows, m_pad,
                eng.config.dtype, MARKET, ACTION_ADD,
            )
        else:
            sel = active_idx[in_window]
            dt = np.dtype(eng.config.dtype)
            cols = np.empty((7, m_pad), dt)
            flat = np.full(m_pad, n_rows * t_grid, np.int32)
            pr, pt = row_of[lanes[sel]], t[sel] - t_off
            flat[:m] = (pr * t_grid + pt).astype(np.int32)
            # The kind word: the wire's number on an ADD, 0 on a cancel
            # (which ignores its kind). The rebased price is clamped as the
            # native packer clamps it (step.TAKER_PRICE_MAX32).
            op_kind = np.where(
                a["action"][sel] == ACTION_ADD, a["kind"][sel], 0
            )
            is_mkt = op_kind == MARKET
            op_price = np.where(
                is_mkt, 0, a["price"][sel] - a["bases"][sel]
            )
            if dt.itemsize <= 4:
                op_price = np.clip(
                    op_price, -TAKER_PRICE_MAX32, TAKER_PRICE_MAX32
                )
            for i, (_name, val) in enumerate(
                (
                    ("action", a["action"][sel]),
                    ("side", a["side"][sel]),
                    ("kind", op_kind),
                    ("price", op_price),
                    ("volume", a["volume"][sel]),
                    ("oid", a["oid_ids"][sel]),
                    ("uid", a["uid_ids"][sel]),
                )
            ):
                cols[i, :m] = val
            meta = {
                "lane": lanes[sel],
                "row": pr,
                "t": pt,
                "arrival": sel.astype(np.int64),
                "action": a["action"][sel],
                "side": a["side"][sel],
                "is_market": is_mkt.astype(np.int64),
                "price": a["price"][sel],
                "price_base": a["bases"][sel],
                "oid_id": a["oid_ids"][sel],
                "uid_id": a["uid_ids"][sel],
            }
        # The events' symbol_id: the one-chip lane, wherever a mesh
        # engine's placement stores the symbol (the same array without one).
        meta["lane"] = eng._symbol_ids(meta["lane"])
        ops = HostGrid(cols, flat, n_rows, t_grid)
        if on_device:
            ops = ops.on_device(eng.config.dtype)
        meta["_m_pad"] = m_pad  # host-only: shape-combo recording
        grids.append((ops, meta, lane_ids, cap_g))

        t_off += t_grid
        alive = t_sub >= t_off
        active_idx = active_idx[alive]
        t_sub = t_sub[alive]


def _tables(eng):
    return dict(
        symbols=eng.symbols.to_list(),
        oid_table=eng.oids.table,
        uid_table=eng.uids.table,
    )


def _assemble(eng, a, batches):
    from .events import EventBatch, empty_batch

    # Timeline flow counters (obs.timeline): _assemble runs exactly once
    # per applied frame on BOTH execution paths (apply_frame directly,
    # the fast path via resolve_frame), so it is the one spot where a
    # frame count cannot double on an exact-path fallback. Disabled
    # sampler = one attribute check, zero allocations.
    TIMELINE.note_frame(a["n"])
    st = eng.stats
    st.orders += a["n"]
    for k in np.flatnonzero(a["adds_by_kind"]).tolist():
        st.adds_by_kind[k] = st.adds_by_kind.get(k, 0) + int(
            a["adds_by_kind"][k]
        )
    for (_code, field, _label), n in zip(_EXPIRED, a["expired"].tolist()):
        setattr(st, field, getattr(st, field) + n)
    if not batches:
        eng.stats.cancels_missed += a["dels_total"]
        return empty_batch(**_tables(eng))
    out_cols = {
        name: np.concatenate([b[name] for b in batches])
        for name in batches[0]
    }
    order = np.argsort(out_cols["arrival"], kind="stable")
    out_cols = {name: v[order] for name, v in out_cols.items()}
    batch = EventBatch(columns=out_cols, **_tables(eng))
    cancels = int(batch.columns["is_cancel"].sum())
    eng.stats.cancels += cancels
    eng.stats.fills += len(batch) - cancels
    eng.stats.cancels_missed += a["dels_total"] - cancels
    return batch


def apply_frame(eng: BatchEngine, cols: dict):
    """Exact synchronous frame application (one _run_exact per grid);
    returns the frame's EventBatch. Caller guarantees admission was
    already applied."""
    from .events import decode_grid_columnar

    frame = cols.get("frame")  # the order-queue offset, where a consumer set it
    with span("frame_pack", frame=frame):
        a = _frame_arrays(eng, cols)
        grids = pack_frame_grids(eng, a)
    batches = []
    for ops, meta, lane_ids, cap_g in grids:
        contexts = {
            (int(r), int(tt)): None for r, tt in zip(meta["row"], meta["t"])
        }
        outs, overrides = eng._run_exact(ops, contexts, lane_ids, cap_g)
        expired = np.asarray(outs.expired)[meta["row"], meta["t"]]
        a["expired"] += [
            int(np.count_nonzero(expired == k))
            for k in expired_codes(eng.config)
        ]
        with span("frame_decode", frame=frame):
            batches.append(
                decode_grid_columnar(meta, splice_outs(outs, overrides))
            )
    # Synchronous path, nothing in flight: re-anchor count_ub exactly so
    # the grow-only ADD increments cannot drift classes upward forever.
    # Only when cap classes are live (a fetch per frame is wasted work
    # for single-class engines).
    from .batch import _cap_ladder

    if len(_cap_ladder(eng.config.cap)) > 1 and eng._ub_extra.any():
        counts = np.asarray(jax.device_get(eng.books.count))
        eng._note_exact_counts(counts.max(axis=1))
    return _assemble(eng, a, batches)


def process_frame(eng: BatchEngine, cols: dict):
    """apply_frame, transactional: a raised frame rolls the engine back
    to its pre-frame state."""
    cp = eng._checkpoint()
    try:
        return apply_frame(eng, cols)
    except Exception:
        eng._restore(cp)
        raise


# --- device-side event compaction (the fast path) -----------------------


#: Row order of the packed compaction matrices (fetch layout).
_FILL_FIELDS = (
    "src", "fill_price", "fill_qty", "maker_oid", "maker_uid",
    "maker_volume", "taker_after",
)
_CANCEL_FIELDS = ("src", "volume")


def _decode_compact(eng, meta, shape, fetched) -> dict:
    """Host-side decode of one grid's compacted events into raw event
    columns (decode_grid_columnar's output shape, same ordering rule)."""
    from .events import _COLUMNS

    t_len, k = shape
    totals, fills, cancels = fetched
    nf, nc = int(totals[0]), int(totals[1])

    from . import nativehost

    if nativehost.available():
        return nativehost.decode_compact(
            meta, t_len, k, nf, nc, fills, cancels
        )

    # (row, t) -> packed-op index join table.
    n_rows = int(meta["_n_rows"])
    op_index = np.full((n_rows, t_len), -1, np.int64)
    op_index[meta["row"], meta["t"]] = np.arange(len(meta["row"]))

    src = fills["src"][:nf].astype(np.int64)
    rr = src // (t_len * k)
    tt = (src // k) % t_len
    pos = op_index[rr, tt]  # every fill belongs to a packed ADD
    base = meta["price_base"][pos]
    fill_cols = {
        "arrival": meta["arrival"][pos],
        "is_cancel": np.zeros(nf, np.bool_),
        "symbol_id": meta["lane"][pos],
        "taker_uid": meta["uid_id"][pos],
        "taker_oid": meta["oid_id"][pos],
        "taker_side": meta["side"][pos].astype(np.int8),
        "taker_price": meta["price"][pos],
        "taker_volume": fills["taker_after"][:nf].astype(np.int64),
        "maker_uid": fills["maker_uid"][:nf].astype(np.int64),
        "maker_oid": fills["maker_oid"][:nf].astype(np.int64),
        "fill_price": fills["fill_price"][:nf].astype(np.int64) + base,
        "maker_volume": fills["maker_volume"][:nf].astype(np.int64),
        "match_volume": fills["fill_qty"][:nf].astype(np.int64),
        "is_market": meta["is_market"][pos].astype(np.bool_),
    }

    csrc = cancels["src"][:nc].astype(np.int64)
    cpos = op_index[csrc // t_len, csrc % t_len]
    cvol = cancels["volume"][:nc].astype(np.int64)
    cancel_cols = {
        "arrival": meta["arrival"][cpos],
        "is_cancel": np.ones(nc, np.bool_),
        "symbol_id": meta["lane"][cpos],
        "taker_uid": meta["uid_id"][cpos],
        "taker_oid": meta["oid_id"][cpos],
        "taker_side": meta["side"][cpos].astype(np.int8),
        "taker_price": meta["price"][cpos],
        "taker_volume": cvol,
        "maker_uid": meta["uid_id"][cpos],
        "maker_oid": meta["oid_id"][cpos],
        "fill_price": meta["price"][cpos],
        "maker_volume": cvol,
        "match_volume": np.zeros(nc, np.int64),
        "is_market": np.zeros(nc, np.bool_),
    }
    columns = {
        name: np.concatenate(
            [np.asarray(fill_cols[name], dt), np.asarray(cancel_cols[name], dt)]
        )
        for name, dt in _COLUMNS
    }
    # Global emission order: arrival, then record order within the op. The
    # fill src values are (r, t, k)-ascending, so records within an op are
    # already in order; a stable sort on arrival preserves that (cancels
    # have no records).
    order = np.argsort(columns["arrival"], kind="stable")
    return {name: v[order] for name, v in columns.items()}


def _compact_accum(config, outs, fills_acc, cancels_acc, totals_acc, g):
    """Append one grid's compacted events into the FRAME-level buffers
    (traceable; compact_accum is its own program, _grid_program holds it).

    Like compact_step_outputs, but events land at the frame's running
    offsets (the sums of earlier grids' counts in totals_acc) instead of
    per-grid buffers — the whole frame then resolves with ONE fetch of
    three arrays. Each fetched array pays a fixed cost, and a Zipf
    frame's grid TRAIN is dozens of grids: 3*G arrays -> 3. The
    accumulators are donated, so the train appends in place with no
    host sync; totals_acc[g] records this grid's TRUE
    fill/cancel counts (+ overflow flag + max n_fills + the adds that
    expired, per code of expired_codes(config)), which is also
    how the host later splits the flat buffers back into grids. The
    grid with g == 0 opens the frame: it is handed whatever buffers of
    the right shapes the engine holds and reads the totals as zero."""
    e_fills = fills_acc.shape[1]
    e_cancels = cancels_acc.shape[1]
    wide = fills_acc.dtype
    # A frame's first grid starts from zero totals whatever the buffers
    # held: they are an earlier frame's, handed back (_take_buffers). The
    # event matrices need no clearing, only the prefix the totals name is
    # ever read.
    totals_acc = jnp.where(g == 0, 0, totals_acc)
    off_f = jnp.sum(totals_acc[:, 0])
    off_c = jnp.sum(totals_acc[:, 1])
    fq = outs.fill_qty  # [R, T, K]
    r, t_len, k = fq.shape
    mask = (fq > 0).reshape(-1)
    idx = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask, off_f + idx, e_fills)
    maker_volume = jnp.where(
        outs.maker_remaining == 0, outs.maker_prefill, outs.maker_remaining
    )
    fill_src = dict(
        src=jnp.arange(r * t_len * k, dtype=jnp.int32),
        fill_price=outs.fill_price,
        fill_qty=fq,
        maker_oid=outs.maker_oid,
        maker_uid=outs.maker_uid,
        maker_volume=maker_volume,
        taker_after=outs.taker_after,
    )
    vals = jnp.stack(
        [fill_src[f].reshape(-1).astype(wide) for f in _FILL_FIELDS]
    )
    fills_acc = fills_acc.at[:, tgt].set(vals, mode="drop")

    cmask = (outs.cancel_found != 0).reshape(-1)  # [R*T]
    cidx = jnp.cumsum(cmask.astype(jnp.int32)) - 1
    ctgt = jnp.where(cmask, off_c + cidx, e_cancels)
    cancel_src = dict(
        src=jnp.arange(r * t_len, dtype=jnp.int32),
        volume=outs.cancel_volume,
    )
    cvals = jnp.stack(
        [cancel_src[f].reshape(-1).astype(wide) for f in _CANCEL_FIELDS]
    )
    cancels_acc = cancels_acc.at[:, ctgt].set(cvals, mode="drop")
    totals_acc = totals_acc.at[g].set(
        jnp.stack(
            [
                jnp.sum(mask.astype(jnp.int32)),
                jnp.sum(cmask.astype(jnp.int32)),
                jnp.sum(outs.book_overflow).astype(jnp.int32),
                jnp.max(outs.n_fills).astype(jnp.int32),
            ]
            + [
                jnp.sum((outs.expired == k).astype(jnp.int32))
                for k in expired_codes(config)
            ]
        ).astype(jnp.int32)  # x64 promotes int32 sums to int64
    )
    return fills_acc, cancels_acc, totals_acc


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def compact_accum(config, outs, fills_acc, cancels_acc, totals_acc, g):
    """_compact_accum as a program of its own, after a large frame's
    eng._step: the three buffers are donated."""
    return _compact_accum(
        config, outs, fills_acc, cancels_acc, totals_acc, g
    )


@functools.partial(
    jax.jit, static_argnums=(0, 1, 2), donate_argnums=(7, 8, 9)
)
def _grid_program(plan, n_rows, t_grid, books, cols, flat, ids,
                  fills_acc, cancels_acc, totals_acc, g):
    """One grid of a small frame (_one_phase, no mesh) as ONE program: the
    scatter of the packed host columns (_scatter_grid), the step the
    engine planned (batch.step_in_program), the compaction into the
    frame's buffers (_compact_accum, donated as in compact_accum) and the
    per-lane count reduction that re-anchors count_ub, in that order. The
    op grid and the step's outputs never leave it; the books are not
    donated (the checkpoint is the transaction). cols, flat and a dense
    grid's int32 lane ids (None on a full grid) come as host arrays. Keyed
    by the whole dispatch combo (COMBO_FIELDS): the statics and the
    arguments' shapes."""
    ops = _scatter_grid(jnp.dtype(plan.cfg.dtype), n_rows, t_grid, cols, flat)
    books, outs = step_in_program(plan, books, ops, ids)
    assert outs.fill_qty.shape[-1] == _k_rec(plan.cfg)
    buffers = _compact_accum(
        plan.cfg, outs, fills_acc, cancels_acc, totals_acc, g
    )
    return books, buffers, jnp.max(books.count, axis=-1)


def _k_rec(cfg) -> int:
    """The record axis K a step at cfg's cap class emits: with cap <
    max_fills the record slice clamps to cap (step.py `rec`)."""
    return min(cfg.max_fills, cfg.cap)


class PendingFrame:
    """A frame whose grids are dispatched (device side in flight) but not
    yet resolved: everything resolve_frame needs, plus the checkpoint that
    makes a tripped budget or failure transactionally recoverable."""

    __slots__ = ("cols", "arrays", "checkpoint", "items", "compact",
                 "n_kept", "one_phase")

    def __init__(self, cols, arrays, checkpoint, items, compact, n_kept,
                 one_phase):
        self.cols = cols
        self.arrays = arrays  # incl. add_counts for the count_ub handoff
        self.checkpoint = checkpoint
        self.items = items  # [(meta, (t_grid, K))]
        # (totals_acc, fills_acc, cancels_acc, counts_max)|None — counts_max
        # is the post-frame per-lane max-side resting count, riding the
        # frame's totals fetch to re-anchor count_ub (cap classes).
        self.compact = compact
        self.n_kept = n_kept
        # The event matrices' copy to the host started with the totals'
        # (ONE_PHASE_MAX_BYTES): resolve_frame takes all in one fetch.
        self.one_phase = one_phase


#: The one-phase rule: a frame whose two event matrices together hold at
#: most this many bytes has them copied to the host whole, with its
#: totals, and resolves with one wait; a larger one fetches the totals
#: first and then the used prefixes (resolve_frame). A frame of 60-80
#: orders has 2-8 KB of them, one of 4,096 orders 115 KB and up, of which
#: a seventh is used; an extra round trip costs the host what a transfer
#: far larger than either does, so the rule only has to keep the small
#: frames' fetch small.
ONE_PHASE_MAX_BYTES = 1 << 15


#: The most cells (rows x depth x cap class, rows and depth rounded up to
#: their powers of two) a small frame's merged grid may hold
#: (pack_frame_grids); over it the frame packs class by class. Merging
#: saves a grid's host dispatch and its gather, scatter-back and copy of
#: the book stack; it costs the shallow lanes' rows at the deepest class.
#: scripts/merge_cost.py on a TPU v5 lite (PR 44; submit to resolve of a
#: lone small frame, one deep lane and the rest shallow, merged / class by
#: class, ms): class 256, 64 rows x 8: 3.46 / 5.18; class 1024, 16, 64
#: and 128 rows: 3.45 / 5.22, 3.53 / 5.04, 4.12 / 5.92; class 4096, 16,
#: 64 and 128 rows (2**19, 2**21, 2**22 cells): 4.06 / 5.64, 4.71 / 5.91,
#: 5.52 / 6.98. The merge is 1.2-1.8 ms ahead at every point read, and its
#: own cost grows by 0.4 ns a cell at class 4096 (the kernel's 6.09 us a
#: step of 8 rows, PERF_LEDGER.jsonl PR 43, and the rows' copies): the
#: bound stands where the readings end, not where the merge was seen to
#: lose. spot10k.paced's merged grid (64 x 8 x 256) has 2**17 cells.
MERGE_MAX_CELLS = 1 << 22


def _one_phase(itemsize: int, e_fills: int, e_cancels: int) -> bool:
    """The one-phase rule on a frame's buffer widths."""
    return (
        len(_FILL_FIELDS) * e_fills + len(_CANCEL_FIELDS) * e_cancels
    ) * itemsize <= ONE_PHASE_MAX_BYTES


def export_metrics(eng: BatchEngine) -> None:
    """The fast path's frame counters on /metrics, read from eng.stats at
    scrape time (nothing on the frame's way; registering again rebinds to
    the newest engine's, as services are rebuilt across tests). Reused over
    frames and one-phase over frames are both near 1 where small frames
    flow steadily; a large-frame flow reads reuse near 1 and one-phase 0.
    Merged over frames is near 1 where small frames mix lanes of more than
    one cap class, 0 where every lane is of one class or frames are large."""
    from ..utils.metrics import REGISTRY

    stats = eng.stats  # the counters only: a gauge outlives its engine
    for name, help_, field in (
        ("gome_fast_frames_total",
         "frames dispatched on the fast path with device grids",
         "fast_frames"),
        ("gome_fast_frames_buffers_reused_total",
         "fast-path frames whose event buffers were an earlier frame's",
         "fast_frames_reused"),
        ("gome_fast_frames_one_phase_total",
         "fast-path frames whose events came back with their totals",
         "fast_frames_one_phase"),
        ("gome_fast_frames_classes_merged_total",
         "one-phase frames whose lanes spanned more than one cap class and "
         "were packed as one grid at the deepest",
         "fast_frames_merged"),
        ("gome_fast_grids_one_program_total",
         "grids of one-phase frames, dispatched as one program each",
         "fast_grids_one_program"),
        ("gome_device_calls_total",
         "grids dispatched to the device, on the fast or the exact path",
         "device_calls"),
    ):
        REGISTRY.callback_gauge(
            name, help_, lambda field=field: getattr(stats, field)
        )
    # The venue's lanes and the rows the device's lane axis was provisioned
    # to for them (BatchEngine.lane_rows: the compiled kernel's row floor).
    ref = weakref.ref(eng)
    for name, help_, field in (
        ("gome_engine_lanes",
         "lanes of the venue (engine.n_slots, doubled as symbols arrive)",
         "n_slots"),
        ("gome_engine_lane_rows",
         "rows of the book stack and of a full grid: the lanes padded to a "
         "row count the compiled kernel can block",
         "lane_rows"),
    ):
        REGISTRY.callback_gauge(
            name, help_,
            lambda field=field: getattr(ref(), field, 0),
        )
    # Adds applied and adds expired, by kind (types.OrderType's names).
    for kind in OrderType:
        REGISTRY.callback_gauge(
            "gome_orders_admitted_total",
            "adds applied by the engine, by order kind",
            lambda k=int(kind): stats.adds_by_kind.get(k, 0),
            labels={"kind": kind.name.lower()},
        )
    for _code, field, label in _EXPIRED:
        REGISTRY.callback_gauge(
            "gome_orders_expired_total",
            "adds that expired by their kind's rule (an IOC remainder "
            "dropped, a FOK add killed, a POST_ONLY add blocked) or, "
            "kind=\"stp\", by the venue's self-trade rule (an add of any "
            "kind stopped at its owner's resting order with volume left)",
            lambda field=field: getattr(stats, field),
            labels={"kind": label},
        )


def _wide(eng: BatchEngine) -> np.dtype:
    """The event buffers' dtype: wide enough for the books' and for int32
    source indices."""
    return np.promote_types(np.int32, np.dtype(eng.config.dtype))


def _zero_buffers(eng: BatchEngine, e_fills: int, e_cancels: int,
                  totals_len: int):
    """A fresh set of event buffers (fills, cancels, totals): three host
    arrays put on the device as compact_accum returns them (replicated
    over a mesh), so a fresh set and a handed-back one run one program. A
    transfer, not a device op; a steady flow makes none (_take_buffers)."""
    wide = _wide(eng)
    where = None
    if eng.mesh is not None:
        where = jax.sharding.NamedSharding(
            eng.mesh, jax.sharding.PartitionSpec()
        )
    return tuple(jax.device_put((
        np.zeros((len(_FILL_FIELDS), e_fills), wide),
        np.zeros((len(_CANCEL_FIELDS), e_cancels), wide),
        np.zeros((totals_len, n_totals(eng.config)), np.int32),
    ), where))


def _take_buffers(eng: BatchEngine, e_fills: int, e_cancels: int,
                  totals_len: int):
    """The frame's event buffers (fills, cancels, totals) and whether they
    are an earlier frame's: a resolved frame hands its set back
    (_give_buffers), keyed by its shapes, and the next frame of those
    shapes appends into it — compact_accum donates them and reads the
    totals as zero at g == 0, so no device op makes or clears them. A set
    is handed out once: the pop takes it from the engine, the donation
    kills its handles, and only a frame that resolved without a trip
    returns its own (a rewound frame's set goes with it). With none to
    hand out (the first frames of a shape: up to depth + 1 sets are alive
    at once) the frame starts on a fresh set."""
    sets = eng._event_buffers.get((e_fills, e_cancels, totals_len))
    if sets:
        return sets.pop(), True
    return _zero_buffers(eng, e_fills, e_cancels, totals_len), False


def _give_buffers(eng: BatchEngine, fills, cancels, totals) -> None:
    """Hand a resolved frame's event buffers back to the engine for the
    next frame of their shapes (_take_buffers)."""
    key = (fills.shape[1], cancels.shape[1], totals.shape[0])
    eng._event_buffers.setdefault(key, []).append((fills, cancels, totals))


# gomesurface: combo(build)
def submit_frame(eng: BatchEngine, cols: dict) -> PendingFrame:
    """Dispatch every grid of the frame + its device-side compaction
    back-to-back (no host sync) and start the async device->host copy of
    the frame-level event buffers. Advances eng.books — a later
    submit_frame builds on this frame's result, so frames pipeline while
    preserving sequential semantics. Raises (with rollback) only on
    host-side errors; device budget trips surface at resolve_frame."""
    cp = eng._checkpoint()
    frame = cols.get("frame")  # the order-queue offset, where a consumer set it
    try:
        with span("frame_pack", frame=frame,
                  orders=int(cols["n"])) as packed:
            a = _frame_arrays(eng, cols)
            books = eng.books
            items = []
            compact = None
            one_phase = False
            n_kept = int(np.count_nonzero(a["keep"]))
            if n_kept:  # the frame has grids
                e_fills, e_cancels, one_phase = _compact_sizes(
                    eng, n_kept, a["dels_total"]
                )
            # A one-phase frame's grids stay on the host: each runs as one
            # program, scatter included (_grid_program). Not under a mesh:
            # there a grid is placed by eng._step (shard_put), large or
            # small.
            one_program = one_phase and eng.mesh is None
            grids = pack_frame_grids(
                eng, a, on_device=not one_program, small=one_phase
            )
            if a["merged"]:
                eng.stats.fast_frames_merged += 1
                packed.note(merged=1, cap=a["merged"])
            if grids:
                (fills_acc, cancels_acc, totals_acc), reused = _take_buffers(
                    eng, e_fills, e_cancels, max(_next_pow2(len(grids)), 8)
                )
                eng.stats.fast_frames += 1
                eng.stats.fast_frames_reused += int(reused)
                eng.stats.fast_frames_one_phase += int(one_phase)
                packed.note(reused=int(reused))
            packed.note(grids=len(grids), takers=a["takers"])
        for g_i, (ops, meta, lane_ids, cap_g) in enumerate(grids):
            t_disp = TRACER.clock() if TRACER.enabled else 0.0
            t_disp_j = JOURNAL.clock() if JOURNAL.enabled else 0.0
            n_ops = len(meta["row"])
            dense = lane_ids is not None
            if one_program:
                n_rows, t_grid = ops.n_rows, ops.t_grid
            else:
                n_rows, t_grid = ops.action.shape
            with span(
                "grid_dispatch", frame=frame, rows=n_rows, t=t_grid,
                cap=int(cap_g), n_ops=n_ops, **eng.grid_note(dense),
                program=1 if one_program else 3,
            ):
                if one_program:
                    plan = eng._grid_plan(n_rows, dense, cap_g, n_ops)
                    books, buffers, counts_max = _grid_program(
                        plan, n_rows, t_grid, books, ops.cols, ops.flat,
                        lane_ids.astype(np.int32) if dense else None,
                        fills_acc, cancels_acc, totals_acc, np.int32(g_i),
                    )
                    fills_acc, cancels_acc, totals_acc = buffers
                    eng.stats.fast_grids_one_program += 1
                    k_rec = _k_rec(plan.cfg)  # the program asserts it
                else:
                    books, outs = eng._step(
                        books, ops, lane_ids, cap_g, n_ops=n_ops
                    )
                    fills_acc, cancels_acc, totals_acc = compact_accum(
                        eng.config, outs, fills_acc, cancels_acc,
                        totals_acc, np.int32(g_i),
                    )
                    k_rec = int(outs.fill_qty.shape[-1])
                eng.stats.device_calls += 1
            meta["_n_rows"] = n_rows
            # The record axis K comes from the ARRAY (or from the program
            # that asserted it), never from config.max_fills: with cap <
            # max_fills the step's record slice clamps to cap (step.py
            # `rec`), so the decode's flat src arithmetic — and the
            # truncation check in resolve_frame — must use the K the
            # records were actually emitted with (fuzz-found: seed 9087,
            # cap=4 K=8 mis-decoded fills and would have silently dropped
            # records of >K-fill ops).
            items.append((meta, (t_grid, k_rec)))
            # Record the full dispatch combo (grid geometry x frame
            # buffers) for shape_manifest/precompile_combos: this tuple
            # determines every jit trace the dispatch just performed.
            combo = (
                n_rows, t_grid, int(cap_g), lane_ids is not None,
                int(meta["_m_pad"]), k_rec,
                int(fills_acc.shape[1]), int(cancels_acc.shape[1]),
                int(totals_acc.shape[0]),
            )
            if TRACER.enabled:
                # Dispatch cost split by whether this shape combo had
                # been traced+compiled before: a first-seen combo pays
                # the synchronous jit trace + XLA compile right here
                # (dispatch itself is async), which is exactly the
                # invisible-latency-cliff the span taxonomy calls out.
                TRACER.observe_span(
                    "compile_hit" if eng.combo_seen(combo)
                    else "compile_miss",
                    t_disp, TRACER.clock(),
                )
            if JOURNAL.enabled and not eng.combo_seen(combo):
                # Compile journal: the SAME miss path, but recording the
                # combo itself (plus its analytic cost block) — the
                # histogram can only say a compile happened, the journal
                # says which shape and what it costs per dispatch. The
                # detail block runs only here, where a full trace+compile
                # was just paid.
                JOURNAL.record(
                    "frame_dispatch", combo,
                    JOURNAL.clock() - t_disp_j,
                    detail=frame_combo_detail(
                        np.dtype(eng.config.dtype).name, combo,
                        n_totals(eng.config),
                    ),
                )
            eng.record_combo(combo)
        eng.books = books
        if grids:
            from .batch import _cap_ladder

            compact = (totals_acc, fills_acc, cancels_acc)
            if len(_cap_ladder(eng.config.cap)) > 1:
                # The count_ub re-anchor rides the frame's totals fetch —
                # but only multi-class engines ever read it; single-class
                # ones skip the [S]-wide reduction and transfer. A small
                # frame's last program made it.
                compact += (
                    counts_max if one_program
                    else jnp.max(books.count, axis=-1),
                )
            # The fetch starts now. Totals (+counts_max) are tiny and
            # resolve needs them FIRST; small event matrices come whole
            # with them (ONE_PHASE_MAX_BYTES), large ones are fetched as
            # used-prefix slices sized from the totals (resolve_frame), so
            # that transfer scales with the frame's EVENTS, not with the
            # pow2-margined buffer capacity (7-8x the events on a margined
            # mixed flow).
            compact[0].copy_to_host_async()
            if len(compact) > 3:
                compact[3].copy_to_host_async()
            if one_phase:
                compact[1].copy_to_host_async()
                compact[2].copy_to_host_async()
        return PendingFrame(cols, a, cp, items, compact, n_kept, one_phase)
    except Exception:
        eng._restore(cp)
        raise


@functools.lru_cache(maxsize=256)
def _prefix_slice_fn(n_fields: int, length: int):
    """Jitted used-prefix slice [F, e] -> [F, length]: phase 2 of the
    two-phase frame fetch transfers only the events that exist, not the
    pow2-margined buffer capacity. length is pow2-bucketed by the caller
    so the compiled-shape set stays logarithmic."""

    @jax.jit
    def take(mat):
        return jax.lax.slice(mat, (0, 0), (n_fields, length))

    return take


def resolve_frame(eng: BatchEngine, pend: PendingFrame):
    """Fetch + decode a submitted frame. A small frame (pend.one_phase,
    ONE_PHASE_MAX_BYTES) resolves with ONE wait: totals, the count_ub
    re-anchor and both event matrices whole, all in flight since submit;
    the used prefix is sliced on the host. A large one keeps the TWO-phase
    device->host fetch:

      1. the [G, n_totals] totals (+ the [S] count_ub re-anchor), tiny and
         already in flight since submit;
      2. the USED PREFIX of the fill/cancel event matrices, pow2-bucketed
         from the totals — a margined mixed-flow buffer is 7-8x its
         actual events.

    Either way nothing is decoded before the trip check on the totals.
    Raises _NeedExact when a device budget tripped — the CALLER owns the
    recovery (rewind to pend.checkpoint, exact-run, resubmit anything
    submitted after); the single-frame wrapper apply_frame_fast and the
    pipelined executor (engine.pipeline.FramePipeline) both do. A frame
    that resolves hands its event buffers back to the engine for the next
    frame of their shapes (_take_buffers); a tripped or failed one does
    not."""
    global FETCH_SECONDS
    if pend.compact is None:
        return _assemble(eng, pend.arrays, [])
    # One span over both phases of the fetch and the trip check between
    # them. The totals fetch is the frame's completion barrier: blocking
    # there drains every dispatched grid, so this IS the device-execute
    # wait (an armed TRACER records it as that stage).
    frame = pend.cols.get("frame")
    with span("frame_fetch", frame=frame, grids=len(pend.items),
              phases=1 if pend.one_phase else 2) as fetched:
        t0 = time.perf_counter()
        totals_dev, fills_dev, cancels_dev = pend.compact[:3]
        if pend.one_phase:
            totals, fills_mat, cancels_mat, *rest = jax.device_get(
                pend.compact
            )
        else:
            totals, *rest = jax.device_get((totals_dev,) + pend.compact[3:])
        counts_max = rest[0] if rest else None
        FETCH_SECONDS += time.perf_counter() - t0
        g = len(pend.items)
        nf_g = totals[:g, 0].astype(np.int64)
        nc_g = totals[:g, 1].astype(np.int64)
        total_f = int(nf_g.sum())
        total_c = int(nc_g.sum())
        expired = totals[:g, _N_COUNTS:].sum(axis=0, dtype=np.int64)
        fetched.note(expired=int(expired.sum()))
        # A fills-buffer overflow ratchets the grow-only floor (keyed by
        # the FRAME's kept-op class) BEFORE the exact fallback, so the
        # next frame fits — one slow frame per ratchet step, not a
        # recurring tax. The totals are TRUE counts (appends past the
        # buffer drop but the mask sums fully), so one step reaches the
        # right size.
        tripped = False
        if total_f > fills_dev.shape[1]:
            cls = eng._buf_class(pend.n_kept)
            eng._fills_buf_floor[cls] = max(
                eng._fills_buf_floor.get(cls, 0), _next_pow2(total_f)
            )
            tripped = True
        if (
            tripped
            or int(totals[:g, 2].sum()) > 0  # book overflow: state is wrong
            # Records truncated: an op produced more fills than the K
            # its grid's record arrays were emitted with.
            or any(
                int(totals[i, 3]) > shape[1]
                for i, (_, shape) in enumerate(pend.items)
            )
            # Unreachable by construction (cancels <= the frame's DEL
            # count, which sizes the buffer) — defensive only.
            or total_c > cancels_dev.shape[1]
        ):
            raise _NeedExact()
        if not pend.one_phase:
            # Phase 2: fetch the used prefixes (pow2-bucketed, clamped to
            # the buffer) now the true counts are known.
            t0 = time.perf_counter()
            f_len = min(
                _next_pow2(max(total_f, 64)), int(fills_dev.shape[1])
            )
            c_len = min(
                _next_pow2(max(total_c, 64)), int(cancels_dev.shape[1])
            )
            fills_mat, cancels_mat = jax.device_get((
                _prefix_slice_fn(int(fills_dev.shape[0]), f_len)(fills_dev),
                _prefix_slice_fn(int(cancels_dev.shape[0]), c_len)(
                    cancels_dev
                ),
            ))
            FETCH_SECONDS += time.perf_counter() - t0
    # Re-anchor count_ub from this frame's true post-frame counts (the
    # pipeline resolves FIFO, so extra minus THIS frame's increments is
    # exactly the still-in-flight sum; a trip above skips this and the
    # rollback restores the checkpointed estimate instead). None for
    # single-class engines, which never read count_ub.
    if counts_max is not None:
        eng._note_exact_counts(counts_max, pend.arrays["add_counts"])
    pend.arrays["expired"] += expired
    off_f = np.concatenate(([0], np.cumsum(nf_g)))
    off_c = np.concatenate(([0], np.cumsum(nc_g)))
    batches = []
    with span("frame_decode", frame=frame, grids=g):
        for i, (meta, shape) in enumerate(pend.items):
            fills = {
                f: fills_mat[j, off_f[i] : off_f[i + 1]]
                for j, f in enumerate(_FILL_FIELDS)
            }
            cancels = {
                f: cancels_mat[j, off_c[i] : off_c[i + 1]]
                for j, f in enumerate(_CANCEL_FIELDS)
            }
            batches.append(
                _decode_compact(
                    eng, meta, shape, (totals[i], fills, cancels)
                )
            )
        batch = _assemble(eng, pend.arrays, batches)
    # Decoded: nothing reads the frame's buffers any more.
    _give_buffers(eng, fills_dev, cancels_dev, totals_dev)
    return batch


def apply_frame_fast(eng: BatchEngine, cols: dict):
    """Production hot path, single-frame form: submit + resolve with one
    overlapped fetch; falls back — transactionally — to the exact path
    when any device budget tripped. Semantics identical to apply_frame.
    Runs under a mesh too: the compaction is elementwise + one cumsum
    over the sharded record axis, and the fetch gathers per-chip blocks."""
    try:
        pend = submit_frame(eng, cols)
    except Exception:
        raise
    try:
        return resolve_frame(eng, pend)
    except _NeedExact:
        eng.stats.frame_fallbacks += 1
        eng._restore(pend.checkpoint)
        try:
            return apply_frame(eng, cols)
        except Exception:
            eng._restore(pend.checkpoint)
            raise
    except Exception:
        eng._restore(pend.checkpoint)
        raise


# gomesurface: quantizer
def _compact_sizes(eng, n_ops: int, n_dels: int) -> tuple[int, int, bool]:
    """Compaction buffer sizes for a frame of n_ops packed ops (n_dels of
    them DELs), and whether they put it under the one-phase rule
    (_one_phase). Sizes MUST be pow2-bucketed: every distinct size is a
    fresh kernel compile. But the buffers are also the frame's device->
    host transfer — so they start TIGHT and ratchet up instead of paying
    2x+ headroom forever:

      fills   — next_pow2(n_ops) (<=1 fill/op average) or the engine's
                grow-only floor, whichever is larger;
      cancels — next_pow2 of the grid's actual DEL count (the exact upper
                bound for its cancel events; a pure-ADD stream fetches a
                64-slot stub instead of an n_ops-sized buffer of zeros).

    Called once per FRAME (n_ops = the frame's kept ops; the whole
    frame's grids append into one buffer pair via compact_accum). Sizes
    are grow-only ratchets KEYED BY the pow2 op-count class
    (BatchEngine._fills_buf_floor): within a class, a frame that needs a
    larger buffer raises the floor so later frames reuse one compiled
    shape instead of oscillating (data-dependent sizes would recompile
    whenever a DEL count straddled a pow2 boundary); across classes,
    floors stay independent so small frames never fetch a big frame's
    buffer. A frame whose FILL count overflows its buffer transactionally
    re-runs on the exact path (resolve_frame) AND raises its class's
    floor, so that costs one slow frame per ratchet step, not a recurring
    tax; cancel events can never overflow (cancels <= n_dels by
    construction, step.py cancel_found). Deployments that know their flow
    pre-warm the floors (BatchEngine.prewarm_geometry).

    A frame under the one-phase rule takes a cancels buffer as wide as
    its op class (n_dels <= n_ops, so the DEL count can never move it):
    each of its grids is ONE program keyed by these widths too
    (_grid_program), and a DEL count that crossed a power of two would
    lower the kernel again where a large frame re-lowers a compaction. A
    few hundred bytes more to fetch; the rule is decided on the widened
    pair."""
    cls = eng._buf_class(n_ops)
    fills = max(cls, eng._fills_buf_floor.get(cls, 0))
    cancels = max(
        _next_pow2(max(n_dels, 64)), eng._cancels_buf_floor.get(cls, 0)
    )
    one_phase = _one_phase(_wide(eng).itemsize, fills, max(cancels, cls))
    if one_phase:
        cancels = max(cancels, cls)
    eng._fills_buf_floor[cls] = fills
    eng._cancels_buf_floor[cls] = cancels
    return fills, cancels, one_phase


# gomesurface: combo(replay), precompile
def precompile_combos(eng: BatchEngine, combos) -> int:
    """Replay recorded fast-path shape combos (BatchEngine.shape_manifest
    "combos") with ALL-PADDING inputs, forcing every jit trace+compile the
    live flow will need — scatter, step (dense or full, at the combo's cap
    class), and frame-level compaction: one program for a combo under the
    one-phase rule (_grid_program), the three calls for one over it, as
    submit_frame dispatches them — before real traffic arrives.

    All-padding means: scatter positions at the drop sentinel (R*T), so
    the DeviceOp grid is all NOPs; dense lane_ids at the lane_rows sentinel
    (gathered as zero books, scattered nowhere). Book state is read but
    results are DISCARDED — replay never mutates the engine (no program
    donates the books; the compaction donates only the dummy buffers
    built here). Floors should be prewarmed first
    (prewarm_geometry) so the live flow also CHOOSES these shapes.

    Returns the number of combos replayed. Cost: one compile each on a
    cold XLA cache (seconds each on the chip), milliseconds each warm —
    vs ~0.3-1s of un-hideable host TRACE time per shape if it first
    appears mid-traffic (the XLA persistent cache covers compiles
    only; traces are per-process)."""
    wide = _wide(eng)
    dt = np.dtype(eng.config.dtype)
    combos = sorted(set(map(tuple, combos)))
    replayed = 0
    failed = 0
    for combo in combos:
        # Per-combo isolation: one stale manifest combo (wrong tuple arity
        # from an older layout, a full-grid n_rows that no longer equals
        # n_slots after growth) must not abort every remaining replayable
        # combo — the documented best-effort contract holds at combo
        # granularity, not manifest granularity. A compile or device
        # error is not staleness: it propagates (is_device_fault).
        try:
            (
                n_rows, t_grid, cap_g, dense, m_pad, k_rec,
                e_fills, e_cancels, totals_len,
            ) = combo
            if cap_g > eng.config.cap:
                # Recorded after a storage-cap escalation this engine
                # hasn't done (caller can eng.ensure_cap() first —
                # load_geometry does). Unreplayable as-is; skip rather
                # than crash.
                continue
            grid = HostGrid(
                np.zeros((7, m_pad), dt),
                np.full(m_pad, n_rows * t_grid, np.int32), n_rows, t_grid,
            )
            lane_ids = (
                np.full(n_rows, eng.lane_rows, np.int64) if dense else None
            )
            buffers = _zero_buffers(eng, e_fills, e_cancels, totals_len)
            if eng.mesh is None and _one_phase(
                wide.itemsize, e_fills, e_cancels
            ):
                # The live frame of this combo runs one program a grid.
                out = _grid_program(
                    eng._grid_plan(n_rows, dense, cap_g, None),
                    n_rows, t_grid, eng.books, grid.cols, grid.flat,
                    lane_ids.astype(np.int32) if dense else None,
                    *buffers, np.int32(0),
                )
            else:
                _books, outs = eng._step(
                    eng.books, grid.on_device(dt), lane_ids, cap_g
                )
                out = compact_accum(
                    eng.config, outs, *buffers, np.int32(0)
                )
            # Serialize: each replay holds a transient books-sized output;
            # blocking frees it before the next combo allocates.
            jax.block_until_ready(out)
        except Exception as e:
            if is_device_fault(e):
                raise
            failed += 1
            continue
        eng.record_combo(combo)
        replayed += 1
    if failed:
        from ..utils.logging import get_logger

        get_logger("frames").warning(
            "precompile_combos: %d stale combo(s) skipped, %d replayed",
            failed, replayed,
        )
    from .batch import _cap_ladder

    if len(_cap_ladder(eng.config.cap)) > 1:
        # The count_ub re-anchor reduction that rides every frame fetch.
        jax.block_until_ready(jnp.max(eng.books.count, axis=-1))
    # Phase-2 prefix-slice kernels (resolve_frame): warm the plausible
    # pow2 lengths for every recorded buffer size that is fetched in two
    # phases, so a boundary-crossing event count never compiles
    # mid-traffic. Tiny graphs, but a compile is a compile.
    wide_zeros = {}
    for combo in combos:
        try:  # same per-combo isolation as the replay loop above
            if _one_phase(wide.itemsize, combo[6], combo[7]):
                continue
            for n_fields, e in (
                (len(_FILL_FIELDS), combo[6]),
                (len(_CANCEL_FIELDS), combo[7]),
            ):
                key = (n_fields, e)
                if key not in wide_zeros:
                    wide_zeros[key] = jnp.zeros((n_fields, e), wide)
                length = e
                while length >= 64:
                    jax.block_until_ready(
                        _prefix_slice_fn(n_fields, length)(wide_zeros[key])
                    )
                    length //= 2
        except Exception as e:
            if is_device_fault(e):
                raise
            continue
    return replayed


class _NeedExact(Exception):
    """Internal: a device budget tripped on the fast path — roll back and
    re-run the frame on the exact escalating path."""


def orders_from_frame(cols: dict):
    """Decode an ORDER frame into Order objects (the inverse of
    colwire.orders_to_cols) — for the consumer's poison bisect and the
    recovery scan, which handle orders one by one
    (bus.decode_message_orders); never on a hot path."""
    from ..types import Action, Order, OrderType, Side

    syms, uuids = cols["symbols"], cols["uuids"]
    sidx, uidx = cols["symbol_idx"].tolist(), cols["uuid_idx"].tolist()
    traces = cols.get("trace")  # GCO3 frames carry per-order contexts
    traces = traces.tolist() if traces is not None else None
    out = []
    for i, (a, s, k, p, v, o) in enumerate(
        zip(
            cols["action"].tolist(), cols["side"].tolist(),
            cols["kind"].tolist(), cols["price"].tolist(),
            cols["volume"].tolist(), cols["oids"].tolist(),
        )
    ):
        trace = None
        if traces is not None and traces[i]:
            trace = traces[i].decode()
        out.append(
            Order(
                uuid=uuids[uidx[i]], oid=o.decode(), symbol=syms[sidx[i]],
                side=Side(int(s)), price=int(p), volume=int(v),
                action=Action(int(a)), order_type=OrderType(int(k)),
                trace=trace,
            )
        )
    return out


def _prepare_bases_vec(eng, lanes, action, kind, price) -> np.ndarray:
    """Set / recenter per-lane price bases so every ADMITTED price of the
    batch is representable on device. Runs before packing; recentering
    shifts the lane's resting prices on device (rare — only when flow
    drifts more than REBASE_LIMIT ticks from the current base). Numpy
    segment min/max, and a Python loop only over the UNIQUE lanes that
    seed or recenter this batch (BatchEngine._admit_lane_range, which
    commits a lane's envelope only after every check passed).

    Returns a boolean drop mask aligned with the batch: True marks a DEL
    whose price is unrepresentable under the lane's (possibly just
    recentred) base. Only the prices of ADDs that can rest (LIMIT,
    POST_ONLY: types.may_rest) feed the grow-only envelope — MARKET prices
    are documented-ignored (encoded 0), the limit of an IOC or FOK add
    never rests (the packers clamp it, step.TAKER_PRICE_MAX32, so it
    widens nothing), and a DEL price is
    a lookup key, not an admission (a wrong-price cancel is in-contract
    and must miss, engine.go:92-98; the stock delorder client hardcodes
    price 0.5). Since every RESTING price always fits the window, an
    unrepresentable DEL provably matches nothing, so it is dropped
    host-side as a missed cancel instead of widening the envelope and
    poisoning the lane forever."""
    n = len(lanes)
    drop = np.zeros(n, bool)
    if not eng._rebase:
        return drop
    adm = (action == ACTION_ADD) & may_rest(kind)
    if adm.any():
        al = lanes[adm]
        ap = price[adm]
        # Steady-state fast path: prices already inside their lane's
        # admitted envelope AND within REBASE_LIMIT of its base need no
        # work at all — only the violating lanes run the (ufunc.at +
        # Python) admission below. The base-distance check matters: after
        # asymmetric growth a price can sit inside [env_lo, env_hi] yet
        # far enough from the base that _admit_lane_range would RECENTER
        # (batch.py REBASE_LIMIT); skipping that would leave price_base
        # stale and drop later DELs near the far envelope edge.
        inside = (
            eng._base_set[al]
            & (ap >= eng._env_lo[al])
            & (ap <= eng._env_hi[al])
            & (np.abs(ap - eng.price_base[al]) <= eng.REBASE_LIMIT)
        )
        if not inside.all():
            viol = ~inside
            al, ap = al[viol], ap[viol]
            uniq = np.unique(al)
            lo = np.full(eng.lane_span, np.iinfo(np.int64).max)
            hi = np.full(eng.lane_span, np.iinfo(np.int64).min)
            np.minimum.at(lo, al, ap)
            np.maximum.at(hi, al, ap)
            # Vectorized widen for lanes that only need their envelope
            # stretched (base already set, no recenter): the Python
            # _admit_lane_range loop is ~3 us/lane and steady flows admit
            # thousands of new per-lane extremes per frame while their
            # envelopes converge. Seeding and recentering stay on the
            # exact scalar path (rare).
            b = eng.price_base[uniq]
            easy = eng._base_set[uniq] & (
                np.maximum(np.abs(lo[uniq] - b), np.abs(hi[uniq] - b))
                <= eng.REBASE_LIMIT
            )
            ez = uniq[easy]
            eng._env_lo[ez] = np.minimum(eng._env_lo[ez], lo[ez])
            eng._env_hi[ez] = np.maximum(eng._env_hi[ez], hi[ez])
            for lane in uniq[~easy].tolist():
                eng._admit_lane_range(int(lane), int(lo[lane]), int(hi[lane]))
    dels = action == ACTION_DEL
    if dels.any():
        dl = lanes[dels]
        drop[dels] = (
            np.abs(price[dels] - eng.price_base[dl]) > eng._INT32_SAFE
        )
    return drop
