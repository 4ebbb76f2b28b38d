"""A small frame is one grid (ISSUE 44): a frame under the one-phase rule
whose lanes span more than one cap class packs ONE grid at the deepest class
present (frames.pack_frame_grids), where a large frame packs one train a
class as it always did.

Held here: the merged path against the class-partitioned path (the packer
called with the flag off, by the test) and the oracle, event for event and
book for book, over every order kind, the self-trade rule, a stale count_ub
that trips the merged grid, frames in flight and a four-device mesh; the
boundary of the rule; the floors, which a merged grid keeps apart from the
per-class trains', and their way through a saved geometry; the counter, on
/metrics and on the frame_pack span."""

import random
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.bus import colwire
from gome_tpu.engine import BatchEngine, BookConfig, frames
from gome_tpu.engine.batch import merged_floor_key
from gome_tpu.oracle import OracleEngine
from gome_tpu.types import Action, Order, OrderType, Side
from gome_tpu.utils.streams import multi_symbol_stream

from test_frames import _assert_same_books, _mesh, device_work  # noqa: F401
from test_stp import engine_book, oracle_book

LIMIT, MARKET, IOC, FOK, POST = (
    OrderType.LIMIT, OrderType.MARKET, OrderType.IOC, OrderType.FOK,
    OrderType.POST_ONLY,
)
BUY, SALE = Side.BUY, Side.SALE
INTERPRET = dict(kernel="pallas", pallas_interpret=True)
HOT = ("hot0", "hot1")
TAILS = tuple(f"t{i}" for i in range(20))


def mixed_flow(seed, n_frames, deep=90, users=("a", "b", "c")):
    """A listing and `n_frames` small frames for a venue of two classes in
    use: `hot0` and `hot1` rest `deep` orders a side (over the 64-slot
    class), twenty tail symbols a handful. Every small frame mixes both: on
    each hot symbol a limit add and its own cancel, a market order that
    walks three resting orders, an IOC add that leaves a remainder, a FOK
    add that is killed and one that fills, a post-only add that would take
    and one that rests, and fresh quotes that keep the side deep; on the
    tails a seeded flow of every kind with cancels of earlier adds. Three
    users, so under a self-trade rule some takers meet their own orders.
    Returns the frames, each a list of Orders."""
    rng = random.Random(seed)
    n = 0

    def order(sym, side, price, volume, kind=LIMIT, action=Action.ADD,
              oid=None, uuid=None):
        nonlocal n
        n += 1
        return Order(uuid=uuid or rng.choice(users), oid=oid or f"o{n}",
                     symbol=sym, side=side, price=price, volume=volume,
                     action=action, order_type=kind)

    listing = []
    for sym in HOT:
        for i in range(deep):
            listing.append(order(sym, BUY, 990 - i, 5))
            listing.append(order(sym, SALE, 1010 + i, 5))
    for sym in TAILS:
        for i in range(3):
            listing.append(order(sym, BUY, 998 - i, 4))
            listing.append(order(sym, SALE, 1002 + i, 4))
    out = [listing[i:i + 200] for i in range(0, len(listing), 200)]
    targets = []
    for _ in range(n_frames):
        frame = []
        for sym in HOT:
            mine = order(sym, BUY, 940, 5)
            frame += [
                mine,
                order(sym, BUY, 940, 0, action=Action.DEL, oid=mine.oid,
                      uuid=mine.uuid),
                order(sym, SALE, 0, 12, MARKET),
                order(sym, BUY, 1010, 12, IOC),
                order(sym, SALE, 985, 5000, FOK),
                order(sym, SALE, 990, 6, FOK),
                order(sym, BUY, 1012, 3, POST),
                order(sym, BUY, 985, 3, POST),
            ]
            for _k in range(4):
                frame.append(order(sym, BUY, 990 - rng.randrange(8), 5))
                frame.append(order(sym, SALE, 1010 + rng.randrange(8), 5))
        for _k in range(24):
            if targets and rng.random() < 0.25:
                t = targets.pop(rng.randrange(len(targets)))
                frame.append(order(t.symbol, t.side, t.price, 0,
                                   action=Action.DEL, oid=t.oid, uuid=t.uuid))
                continue
            kind = rng.choice([LIMIT, LIMIT, LIMIT, MARKET, IOC, FOK, POST])
            o = order(rng.choice(TAILS), Side(rng.randrange(2)),
                      1000 + rng.randint(-4, 4), rng.randint(1, 9), kind)
            frame.append(o)
            if kind in (LIMIT, POST):
                targets.append(o)
        rng.shuffle(frame)
        # The cancel of a frame's own add comes after it, whatever the shuffle.
        for sym in HOT:
            i_add = next(i for i, o in enumerate(frame)
                         if o.symbol == sym and o.price == 940
                         and o.action == Action.ADD)
            i_del = next(i for i, o in enumerate(frame)
                         if o.symbol == sym and o.price == 940
                         and o.action == Action.DEL)
            if i_del < i_add:
                frame[i_add], frame[i_del] = frame[i_del], frame[i_add]
        out.append(frame)
    return out


def partitioned(monkeypatch):
    """The packer as it was: from here on every frame packs one train a
    class, whatever its size (the flag off, nothing in the program read)."""
    real = frames.pack_frame_grids
    monkeypatch.setattr(
        frames, "pack_frame_grids",
        lambda eng, a, on_device=True, small=False: real(eng, a, on_device),
    )


def engine(cap=256, rule="none", mesh_devices=0, **kw):
    kw.setdefault("n_slots", 64)
    kw.setdefault("max_t", 16)
    return BatchEngine(
        BookConfig(cap=cap, max_fills=8, dtype=jnp.int32, self_trade=rule),
        mesh=_mesh(mesh_devices), **kw,
    )


def run(eng, chunks, depth=1, before=None):
    """Every frame through the fast path, `depth` of them in flight (a
    tripped frame rewinds to the exact path as apply_frame_fast does it);
    `before(k, eng)` runs ahead of frame k's submit."""
    in_flight, got = deque(), []
    for k, chunk in enumerate(list(chunks) + [None] * depth):
        if chunk is not None:
            if before is not None:
                before(k, eng)
            cols = colwire.orders_to_cols(chunk)
            if depth == 1:
                got.append(frames.apply_frame_fast(eng, cols))
                continue
            in_flight.append(frames.submit_frame(eng, cols))
        if in_flight and (len(in_flight) > depth - 1 or chunk is None):
            got.append(frames.resolve_frame(eng, in_flight.popleft()))
    return got


#: case -> (engine keywords, frames in flight, deep orders a hot side)
CASES = {
    "every_kind": (dict(**INTERPRET), 1, 90),
    "expire_taker": (dict(rule="expire_taker", **INTERPRET), 1, 90),
    "two_in_flight": (dict(**INTERPRET), 2, 90),
    "mesh4": (dict(mesh_devices=4, **INTERPRET), 1, 90),
    # The hot symbols rest 300 a side (the 1024 class of a cap-1024 venue)
    # and the host is told 100 before one frame: its merged grid runs at 256.
    "stale_count_ub": (dict(cap=1024, **INTERPRET), 1, 300),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_merged_path_equals_the_partitioned_path_and_the_oracle(
    case, monkeypatch
):
    """Small frames that mix deep and shallow lanes: packed as one grid at
    the deepest class present they give the events, event for event and in
    order, and the books, leaf for leaf, of the same frames packed one grid
    a class, and the oracle's. A lane whose count_ub is stale low trips the
    merged grid's guard (_guard_capped) and the frame's re-run is exact."""
    kw, depth, deep = CASES[case]
    kw = dict(kw)
    rule = kw.get("rule", "none")
    chunks = mixed_flow(seed=44, n_frames=10, deep=deep)
    listing = len(chunks) - 10
    stale_at = listing + 6 if case == "stale_count_ub" else None

    def lie(k, eng):
        if k == stale_at:
            for sym in HOT:
                lane = eng.symbol_lane(sym)
                eng._ub_base[lane], eng._ub_extra[lane] = 100, 0

    merged = engine(**kw)
    got = run(merged, chunks, depth, lie)
    partitioned(monkeypatch)
    parted = engine(**kw)
    want = run(parted, chunks, depth, lie)

    st = merged.stats
    tripped = int(stale_at is not None)
    # Every small frame is merged, and the listing's from the one that
    # takes a hot side past 64 on.
    assert 10 <= st.fast_frames_merged < len(chunks)
    assert st.frame_fallbacks == tripped
    assert parted.stats.fast_frames_merged == 0
    assert parted.stats.frame_fallbacks == tripped
    assert st.fast_frames == parted.stats.fast_frames == len(chunks)
    assert st.fast_frames_one_phase == len(chunks)
    # One grid a merged frame where the partitioned path has one a class:
    # two on a ladder of two (a tripped frame's exact re-run packs class by
    # class on both paths).
    if tripped:
        assert st.device_calls < parted.stats.device_calls
    else:
        assert (st.device_calls
                == parted.stats.device_calls - st.fast_frames_merged)
    if not kw.get("mesh_devices"):
        assert all(k.startswith("interpret_") for k in st.grids_by_kernel)
    assert len(got) == len(want) == len(chunks)
    for k, (g, w) in enumerate(zip(got, want)):
        assert g.columns.keys() == w.columns.keys()
        for name in w.columns:
            np.testing.assert_array_equal(
                g.columns[name], w.columns[name], err_msg=f"{k} {name}"
            )
    for name in ("orders", "fills", "cancels", "cancels_missed",
                 "adds_by_kind", "expired_ioc", "fok_killed",
                 "post_only_blocked", "stp_expired", "ops_by_kernel"):
        assert getattr(st, name) == getattr(parted.stats, name), name
    assert min(st.expired_ioc, st.fok_killed, st.post_only_blocked) > 0
    assert (st.stp_expired > 0) == (rule != "none")
    merged.verify_books()
    _assert_same_books(merged, parted)

    oracle = OracleEngine(self_trade=rule)
    events = [e for chunk in chunks for o in chunk for e in oracle.process(o)]
    assert [e for g in got for e in g.to_results()] == events
    for sym in HOT + TAILS[:4]:
        assert engine_book(merged, sym) == oracle_book(oracle, sym), sym


# -- the boundary of the rule --------------------------------------------------


def two_class_engine():
    """hot0 and hot1 rest 90 a side, the tails a handful: every later frame
    that touches both has lanes of the 64 and of the 256 class."""
    eng = engine()
    for chunk in mixed_flow(seed=7, n_frames=0):
        frames.apply_frame_fast(eng, colwire.orders_to_cols(chunk))
    return eng


def quotes(n, syms, tag):
    """`n` resting bids, far from the asks, dealt over `syms`."""
    return [Order(uuid="q", oid=f"{tag}{i}", symbol=syms[i % len(syms)],
                  side=BUY, price=900 - i % 7, volume=1)
            for i in range(n)]


@pytest.mark.parametrize("size,lanes,merged", [
    ("at_the_rule", HOT + TAILS, True),
    ("one_over", HOT + TAILS, False),
    ("at_the_rule_one_class", TAILS, False),
    ("at_the_rule_deep_class_alone", HOT, False),
])
def test_only_a_frame_under_the_rule_with_two_classes_is_merged(
    size, lanes, merged, monkeypatch
):
    """The rule set at 128 kept ops' bytes: a frame of 128 orders over both
    classes is one grid at 256; one of 129 packs one grid a class, as does
    every large frame; a frame whose lanes are all of one class packs as it
    always did and does not count as merged."""
    monkeypatch.setattr(frames, "ONE_PHASE_MAX_BYTES", (7 + 2) * 128 * 4)
    eng = two_class_engine()
    before = (eng.stats.fast_frames_merged, eng.stats.device_calls)
    n = 129 if size == "one_over" else 128
    cols = colwire.orders_to_cols(quotes(n, lanes, size))
    a = frames._frame_arrays(eng, cols)
    cp = eng._checkpoint()
    small = frames._compact_sizes(eng, n, 0)[2]
    caps = [g[3] for g in frames.pack_frame_grids(
        eng, a, on_device=False, small=small)]
    eng._restore(cp)
    assert small == (size != "one_over")
    classes = {"one_over": [64, 256], "at_the_rule": [256],
               "at_the_rule_one_class": [64],
               "at_the_rule_deep_class_alone": [256]}[size]
    assert caps == classes and a["merged"] == (256 if merged else 0)
    pend = frames.submit_frame(eng, cols)
    frames.resolve_frame(eng, pend)
    assert pend.one_phase == small
    assert eng.stats.fast_frames_merged - before[0] == int(merged)
    assert eng.stats.device_calls - before[1] == len(classes)
    eng.verify_books()


def test_a_merged_grid_past_the_cell_bound_packs_class_by_class(monkeypatch):
    """The merge is bounded by the merged grid's cells (MERGE_MAX_CELLS):
    with the bound under a frame's rows x depth x class it packs one grid a
    class; at it, one."""
    eng = two_class_engine()
    orders = quotes(60, HOT + TAILS, "c")  # 22 lanes, 3 deep: 32 x 4 x 256
    cols = colwire.orders_to_cols(orders)
    for bound, caps in ((32 * 4 * 256 - 1, [64, 256]), (32 * 4 * 256, [256])):
        monkeypatch.setattr(frames, "MERGE_MAX_CELLS", bound)
        cp = eng._checkpoint()
        a = frames._frame_arrays(eng, cols)
        got = [g[3] for g in frames.pack_frame_grids(eng, a, False, True)]
        eng._restore(cp)
        assert got == caps and bool(a["merged"]) == (len(caps) == 1)


# -- the ratchets stay apart ---------------------------------------------------


def large_frame(seed):
    """1,200 orders over both hot symbols and the tails, far over the rule:
    600 bids and then their cancels, so no book is deeper after it."""
    rng = random.Random(seed)
    adds = [Order(uuid="L", oid=f"L{seed}.{i}", symbol=rng.choice(HOT + TAILS),
                  side=BUY, price=900 - rng.randrange(9), volume=1)
            for i in range(600)]
    return adds + [
        Order(uuid="L", oid=o.oid, symbol=o.symbol, side=BUY, price=o.price,
              volume=0, action=Action.DEL) for o in adds
    ]


def test_merged_small_frames_leave_a_large_frames_geometry_alone():
    """After twenty merged small frames a large frame's grids have the rows,
    depth and class they get on an engine that saw none: the merged grids'
    floors lie under keys of their own (batch.merged_floor_key)."""
    chunks = mixed_flow(seed=9, n_frames=20)
    listing = chunks[:-20]

    def shapes_of_the_large_frame(small_frames):
        eng = engine()
        for chunk in listing + small_frames:
            frames.apply_frame_fast(eng, colwire.orders_to_cols(chunk))
        pend = frames.submit_frame(
            eng, colwire.orders_to_cols(large_frame(1)))
        frames.resolve_frame(eng, pend)
        assert not pend.one_phase
        return eng, sorted(c[:4] for c in eng.combos() if c[6] >= 1024)

    fresh, want = shapes_of_the_large_frame([])
    seasoned, got = shapes_of_the_large_frame(chunks[-20:])
    listed = fresh.stats.fast_frames_merged  # the listing's own frames
    assert seasoned.stats.fast_frames_merged == listed + 20
    assert got == want and {c[2] for c in got} == {64, 256}
    floors = seasoned.geometry_floors()
    key = merged_floor_key(256)
    # The merged grids' rows (two dozen lanes) and the deep train's (two).
    assert floors["rows_floor"][key] == 32 and floors["rows_floor"][256] == 8
    assert key in floors["t_floor"]
    for name in ("rows_floor", "t_floor"):
        for cls in (64, 256):
            assert floors[name][cls] == fresh.geometry_floors()[name][cls]


def test_a_saved_geometry_replays_the_merged_grids(
    tmp_path, device_work  # noqa: F811
):
    """shape_manifest -> JSON -> prewarm_geometry -> precompile_combos
    carries the merged grids' floors under their key and their combos: a
    fresh engine that loaded the file and listed the venue runs the same
    flow, small frames and a large one among them, and lowers nothing."""
    from gome_tpu.engine.orchestrator import MatchEngine

    chunks = mixed_flow(seed=11, n_frames=6)
    listing, flow = chunks[:-6], chunks[-6:-2] + [large_frame(2)] + chunks[-2:]

    def mk():
        return MatchEngine(
            config=BookConfig(cap=256, max_fills=8, dtype=jnp.int32),
            n_slots=64, max_t=16,
        )

    def run_on(eng, some):
        for chunk in some:
            for o in chunk:
                eng.mark(o)
        return [
            eng.process_frame(colwire.orders_to_cols(c), fast=True)
            .to_results() for c in some
        ]

    first = mk()
    run_on(first, listing)
    listed = first.stats.fast_frames_merged
    want = run_on(first, flow)
    assert first.stats.fast_frames_merged == listed + 6
    path = str(tmp_path / "geometry.json")
    first.save_geometry(path)
    key = merged_floor_key(256)
    saved = first.batch.geometry_floors()
    assert key in saved["rows_floor"] and key in saved["t_floor"]

    second = mk()
    assert second.load_geometry(path) == first.batch.combo_count()
    loaded = second.batch.geometry_floors()
    assert loaded["rows_floor"] == saved["rows_floor"]
    assert loaded["t_floor"] == saved["t_floor"]
    run_on(second, listing)  # (its frames meet floors the first's did not)
    _eager, lowered = device_work
    lowered.clear()
    assert run_on(second, flow) == want
    assert lowered == []
    assert second.stats.fast_frames_merged == listed + 6
    assert second.batch.geometry_floors() == saved


# -- the counter ----------------------------------------------------------------


def test_the_merged_counter_is_on_metrics_and_on_the_pack_span(monkeypatch):
    """fast_frames_merged rises by one for a merged frame and by none for a
    frame of one class or a large one; gome_fast_frames_classes_merged_total
    is on /metrics beside gome_fast_frames_one_phase_total; the frame_pack
    span of a merged frame notes merged=1 and the class it ran at."""
    from gome_tpu.utils import tracing
    from gome_tpu.utils.metrics import REGISTRY

    notes = []
    real_note = tracing.span.note

    def noting(self, **meta):
        if self.name == "frame_pack":
            notes.append(meta)
        return real_note(self, **meta)

    monkeypatch.setattr(tracing.span, "note", noting)
    eng = two_class_engine()
    frames.export_metrics(eng)
    base = eng.stats.fast_frames_merged
    seen = []
    for orders in (quotes(40, HOT + TAILS, "m"), quotes(40, TAILS, "n"),
                   large_frame(3), quotes(40, HOT + TAILS, "p")):
        notes.clear()
        frames.apply_frame_fast(eng, colwire.orders_to_cols(orders))
        seen.append((eng.stats.fast_frames_merged - base,
                     [m for m in notes if "merged" in m]))
    assert [n for n, _ in seen] == [1, 1, 1, 2]
    assert seen[0][1] == seen[3][1] == [dict(merged=1, cap=256)]
    assert seen[1][1] == seen[2][1] == []
    text = REGISTRY.render()
    assert f"gome_fast_frames_classes_merged_total {base + 2}" in text
    assert "gome_fast_frames_one_phase_total" in text
    assert f"gome_fast_frames_total {eng.stats.fast_frames}" in text


def test_a_stream_of_one_class_never_merges():
    """Every lane under 64 deep: small frames or large, nothing is merged
    and every frame packs as it did."""
    eng = engine(cap=256)
    orders = multi_symbol_stream(n=1400, n_symbols=12, seed=4,
                                 cancel_prob=0.3)
    for chunk in (orders[:60], orders[60:120], orders[120:1320],
                  orders[1320:]):
        frames.apply_frame_fast(eng, colwire.orders_to_cols(chunk))
    assert eng.stats.fast_frames == 4 and eng.stats.fast_frames_merged == 0
    assert all(k > 0 for k in eng.geometry_floors()["rows_floor"])
    eng.verify_books()
