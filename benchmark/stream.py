"""The one general order-stream generator: numpy and the plain reference only,
no JAX, no program code.

The work a stream asks of the engine is the same in every seed:

  * **Stratified.** Which symbol rank each order goes to comes from a
    low-discrepancy sequence over the Zipf distribution, and which kind the
    j-th order of a lane is (limit add, market add, cancel, cancel aimed at an
    add of the same request) from another: request k has the same count per
    rank and per kind whatever the seed. The seed draws prices, volumes, sides,
    users, which symbol holds which rank, and the order inside each request.
  * **The reference runs in the loop**, one Book per symbol: the `Book` of the
    configuration's own reference file where it defines one
    (configs/<name>_reference.py, found by path so that worker processes can
    load it), else benchmark/reference.py's. So cancels aim at what is really
    resting under the venue's own rules, and the expected events come with
    the stream.
  * **The flow names its add kinds** (flow.add_kinds, optional): each entry
    {name, kind, share_of_adds, pricing} is a kind of add that is not a market
    order: the byte that goes into the `kind` column and onto the wire, its
    share of those adds (the shares sum to 1) and how it is priced: `passive`,
    `marketable`, or `steered` by the side's resting count. Without the key
    there is one, the limit add (kind 0, steered).
  * **Depth is steered.** Every lane has a band [lo, hi] for each side's
    resting count (flow.bands, by rank; chosen from the engine's cap ladder in
    the configuration's file). Inside it the generator picks passive or
    marketable prices and hit-or-miss cancel targets with probabilities that
    follow the depth, so the count settles about a quarter of the way up the
    band; at the guard strips next to the edges the choice is forced, so the
    count never leaves the band once it is in.
  * **The opening** (flow.opening.orders) is drawn from flow.opening.seed
    whatever `--seed` is: the books fill the same way in every run, so every
    run's set-up walks the engine's grow-only geometry through the same shapes.

Books are independent per symbol: `generate` splits the lanes over worker
processes and merges by stream index, so any number of workers gives the same
bytes.
"""

from __future__ import annotations

import math
import random

import numpy as np

from . import reference, spec

BUY, SALE = 0, 1
LIMIT, MARKET = 0, 1
PRICINGS = ("steered", "passive", "marketable")
#: The add kinds of a flow that names none: the limit add alone.
LIMIT_ADD = dict(name="limit", kind=LIMIT, share_of_adds=1.0,
                 pricing="steered")
PHI = (math.sqrt(5.0) - 1.0) / 2.0
PHI2 = math.sqrt(2.0) - 1.0
COLUMNS = ("sym", "uid", "oid", "side", "kind", "cancel", "price", "volume")
N_EVENT_FIELDS = len(reference.EVENT_FIELDS)


def popularity(flow: dict) -> np.ndarray:
    n = int(flow["symbols"])
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(flow["zipf_a"])
    return p / p.sum()


def add_kinds(flow: dict) -> list[dict]:
    """The flow's kinds of add other than the market order, checked."""
    kinds = flow.get("add_kinds") or [LIMIT_ADD]
    for entry in kinds:
        if entry["pricing"] not in PRICINGS:
            raise ValueError(f"add kind {entry['name']!r}: pricing "
                             f"{entry['pricing']!r} is not one of {PRICINGS}")
        if not 0 <= int(entry["kind"]) <= 127 or int(entry["kind"]) == MARKET:
            raise ValueError(f"add kind {entry['name']!r}: kind "
                             f"{entry['kind']!r} is not a byte of its own")
    total = sum(float(entry["share_of_adds"]) for entry in kinds)
    if abs(total - 1.0) > 1e-9:
        raise ValueError(f"flow.add_kinds: share_of_adds sum to {total}, not 1")
    return kinds


def kind_shares(flow: dict) -> np.ndarray:
    """[each add kind..., market, cancel, cancel_same] shares of all orders."""
    c = float(flow["cancel_share"])
    same = c * float(flow["cancel_same_request_share"])
    adds = 1.0 - c
    market = adds * float(flow["market_share_of_adds"])
    return np.array(
        [(adds - market) * float(entry["share_of_adds"])
         for entry in add_kinds(flow)] + [market, c - same, same])


def book_class(reference_path: str | None):
    """The Book the generator's loop keeps per symbol: the one the
    configuration's reference file defines, else reference.Book. It has
    reference.Book's interface: add and cancel (True when the order rested,
    when the cancel hit; events through `emit` in EVENT_FIELDS order), count
    (resting orders per side) and prices (occupied prices per side,
    ascending)."""
    if reference_path is None:
        return reference.Book
    module = spec.load_module("benchmark_stream_reference", reference_path)
    return getattr(module, "Book", reference.Book)


def _guard(lo: int, hi: int) -> int:
    """Width of the strips next to a band's edges where the choice is forced."""
    return max(8, (hi - lo) // 8) if lo > 0 else max(2, (hi - lo) // 4)


def listing_plan(flow: dict) -> np.ndarray:
    """Ranks of the listing's stream slots: the venue's session opens with
    quotes resting in every book, before the Zipf flow starts. Every symbol
    gets flow.listing.orders_per_side passive orders a side, a lane whose band
    starts above zero enough to stand inside its band (its lower guard strip
    plus 8); dealt round after round, symbol after symbol. So every lane is in
    its band, and the tail stationary, from the first order after it."""
    listing = flow.get("listing")
    if listing is None:
        return np.zeros(0, np.int32)
    per_side = np.full(int(flow["symbols"]), int(listing["orders_per_side"]))
    for row in flow["bands"]:
        lo, hi = row["band"]
        if lo > 0:
            a, b = row["ranks"][0] - 1, min(row["ranks"][1], len(per_side))
            per_side[a:b] = np.maximum(per_side[a:b], lo + _guard(lo, hi) + 8)
    rounds = [np.flatnonzero(2 * per_side > r)
              for r in range(int(2 * per_side.max()))]
    return np.concatenate(rounds).astype(np.int32) if rounds else \
        np.zeros(0, np.int32)


def opening_requests(flow: dict, request_orders: int) -> int:
    """Whole requests that hold the opening's flow.opening.orders orders."""
    orders = int((flow.get("opening") or {}).get("orders", 0))
    return -(-orders // request_orders)


def rank_plan(flow: dict, n: int) -> np.ndarray:
    """Rank (0 = hottest) of each of n stream slots, before the seed's
    shuffle inside requests: the same in every seed."""
    cdf = np.cumsum(popularity(flow))
    cdf[-1] = 1.0
    listed = listing_plan(flow)[:n]
    u = ((np.arange(n - len(listed), dtype=np.float64) + 0.5) * PHI) % 1.0
    return np.r_[listed, np.searchsorted(cdf, u, side="right")].astype(
        np.int32)


def band_of(flow: dict, rank: int) -> tuple[int, int]:
    """[lo, hi] of a lane by its rank (1-based in the file)."""
    for row in flow["bands"]:
        if row["ranks"][0] <= rank + 1 <= row["ranks"][1]:
            return int(row["band"][0]), int(row["band"][1])
    raise ValueError(f"no band for rank {rank + 1}")


def _lane_rng(seed: int, rank: int) -> random.Random:
    return random.Random(seed * 1_000_003 + rank)


def run_lane(flow: dict, seed: int, rank: int, sym: int, pos, request_orders,
             n_list: int, out, events, trace, Book=reference.Book):
    """One lane's orders at stream indices `pos` (ascending), written into the
    column arrays `out` at those indices; its events appended to `events`.
    n_list is the listing's length (listing_plan). `trace`, when not None, is
    an int32 array [requests, 6] that receives
    per request (min, max, end) of each side's resting count. `Book` is the
    venue's book (book_class)."""
    lo, hi = band_of(flow, rank)
    guard = _guard(lo, hi)
    lo_t, hi_t = lo + guard, hi - guard
    if lo == 0:
        lo_t = 0
    span = max(hi_t - lo_t, 1)
    mid, tick = int(flow["mid"]), int(flow["tick"])
    band = int(flow["band"]) // tick  # price levels a side
    v_lo, v_hi = flow["lots"]
    n_users = int(flow["users"])
    opening = flow.get("opening") or {}
    n_open = opening_requests(flow, request_orders) * request_orders
    rng_open = _lane_rng(int(opening.get("seed", 0)), rank)
    rng_seed = _lane_rng(seed, rank)
    adds = add_kinds(flow)
    k_market = len(adds)  # then cancel, then cancel aimed at the same request
    k_cancel, k_cancel_same = k_market + 1, k_market + 2
    cum = np.cumsum(kind_shares(flow))
    cum[-1] = 1.0
    phase = (rank * 0.3819660112501051) % 1.0
    kinds = np.searchsorted(
        cum, ((np.arange(len(pos)) + 0.5) * PHI2 + phase) % 1.0, side="right"
    ).tolist()

    book = Book()
    d = book.count
    alive: dict[int, tuple] = {}   # oid -> (side, price, uid)
    lists = ([], [])               # resting oids per side, oldest first
    grave: list[tuple] = []        # (oid, side, price, uid) of orders gone
    req_adds: list[int] = []
    gone: list[int] = []
    emit = events.append
    o_sym, o_uid, o_oid, o_side = out["sym"], out["uid"], out["oid"], out["side"]
    o_kind, o_cancel, o_price, o_vol = (out["kind"], out["cancel"],
                                        out["price"], out["volume"])
    cur_req = -1
    t_row = None

    def bury(oid):
        side, price, uid = alive.pop(oid)
        grave.append((oid, side, price, uid))
        if len(grave) > 512:
            del grave[:256]

    def pick(side, rng):
        """A resting order of `side`, newest quarter preferred."""
        lst = lists[side]
        while lst:
            back = rng.randrange(max(len(lst) // 4, 1))
            oid = lst[-1 - back]
            info = alive.get(oid)
            if info is not None and info[0] == side:
                return oid, info
            del lst[-1 - back]
        return None

    for j, g in enumerate(pos.tolist()):
        k = g // request_orders
        if k != cur_req:
            cur_req = k
            req_adds = []
            if trace is not None:
                t_row = trace[k]
                t_row[0] = t_row[1] = d[0]
                t_row[3] = t_row[4] = d[1]
        rng = rng_open if g < n_open else rng_seed
        kind = -1 if g < n_list else kinds[j]  # a listing slot: no plan kind
        uid = rng.randrange(n_users)
        side = rng.randrange(2)
        vol = rng.randint(v_lo, v_hi)
        x0 = min(max((d[0] - lo_t) / span, 0.0), 1.0)
        x1 = min(max((d[1] - lo_t) / span, 0.0), 1.0)
        x = (x0, x1)
        cancel, okind, price, oid = False, LIMIT, 0, g
        if kind >= k_cancel:
            cancel, vol = True, 1
            t = side if d[side] > lo_t else 1 - side
            target = None
            if d[t] > lo_t and rng.random() < x[t]:
                if kind == k_cancel_same and req_adds:
                    cand = req_adds[rng.randrange(len(req_adds))]
                    info = alive.get(cand)
                    if info is not None and d[info[0]] > lo_t:
                        target = (cand, info)
                if target is None:
                    target = pick(t, rng)
            if target is not None:
                oid, (side, price, uid) = target
            elif grave:
                oid, side, price, uid = grave[rng.randrange(len(grave))]
            else:
                price = mid  # its own index as oid: an order that never was
        elif g < n_list:  # the listing: one passive quote, sides in turn
            side = j % 2
            off = tick * rng.randrange(band)
            price = mid - 1 - off if side == BUY else mid + 1 + off
        elif kind == k_market:
            okind = MARKET
            if d[1 - side] <= lo_t:
                if d[side] > lo_t:
                    side = 1 - side
                else:
                    vol = 1
        else:
            okind, pricing = adds[kind]["kind"], adds[kind]["pricing"]
            if pricing == "steered":
                want_pass = rng.random() < 1.0 - 0.5 * x[side]
            else:
                want_pass = pricing == "passive"
            if want_pass and d[side] < hi_t:
                passive = True
            elif d[1 - side] > lo_t:
                passive = False
            elif d[side] < hi_t:
                passive = True
            else:
                side, passive = 1 - side, True
            off = tick * rng.randrange(band)
            if passive:
                price = mid - 1 - off if side == BUY else mid + 1 + off
            elif side == BUY:
                price = max(mid + 1 + off, book.prices[SALE][0])
            else:
                price = min(mid - 1 - off, book.prices[BUY][-1])
        o_sym[g], o_uid[g], o_oid[g], o_side[g] = sym, uid, oid, side
        o_kind[g], o_cancel[g], o_price[g], o_vol[g] = okind, cancel, price, vol
        if cancel:
            if book.cancel(g, sym, uid, oid, side, price, emit):
                bury(oid)
        else:
            rested = book.add(g, sym, uid, oid, side, okind, price, vol, emit,
                              gone)
            if gone:
                for dead in gone:
                    bury(dead)
                del gone[:]
            if rested:
                alive[oid] = (side, price, uid)
                lists[side].append(oid)
                req_adds.append(oid)
        if t_row is not None:
            a, b = d
            if a < t_row[0]:
                t_row[0] = a
            if a > t_row[1]:
                t_row[1] = a
            if b < t_row[3]:
                t_row[3] = b
            if b > t_row[4]:
                t_row[4] = b
            t_row[2], t_row[5] = a, b


def _empty_columns(n: int) -> dict:
    return dict(
        sym=np.zeros(n, np.int32), uid=np.zeros(n, np.int32),
        oid=np.zeros(n, np.int64), side=np.zeros(n, np.int8),
        kind=np.zeros(n, np.int8), cancel=np.zeros(n, np.bool_),
        price=np.zeros(n, np.int64), volume=np.zeros(n, np.int64),
    )


def layout(flow: dict, seed: int, n_requests: int, request_orders: int):
    """(rank of every stream index, symbol of every rank): the seed's shuffle
    of the plan inside each request (the opening's requests: its own seed's)."""
    n = n_requests * request_orders
    plan = rank_plan(flow, n).reshape(n_requests, request_orders)
    opening = flow.get("opening") or {}
    n_open = min(opening_requests(flow, request_orders), n_requests)
    keys = np.empty((n_requests, request_orders))
    keys[:n_open] = np.random.default_rng(
        [int(opening.get("seed", 0)), 1]).random((n_open, request_orders))
    keys[n_open:] = np.random.default_rng([int(seed), 1]).random(
        (n_requests - n_open, request_orders))
    # the listing keeps its place: a slot below its length is a listing slot
    keys.reshape(n)[:len(listing_plan(flow))] = -1.0
    order = np.argsort(keys, axis=1, kind="stable")
    ranks = np.take_along_axis(plan, order, axis=1).reshape(n)
    sym_of_rank = np.random.default_rng([int(seed), 2]).permutation(
        int(flow["symbols"])).astype(np.int32)
    return ranks, sym_of_rank


def traced_ranks(flow: dict) -> list[int]:
    """Ranks (0-based) whose depth is recorded: the lanes held off zero."""
    out = []
    for row in flow["bands"]:
        if row["band"][0] > 0:
            out.extend(range(row["ranks"][0] - 1, row["ranks"][1]))
    return [r for r in out if r < int(flow["symbols"])]


def work(args) -> dict:
    """One worker's share: the lanes in `groups` (rank, symbol, positions)."""
    flow, seed, n_requests, request_orders, groups, reference_path = args
    Book = book_class(reference_path)
    n = n_requests * request_orders
    traced = set(traced_ranks(flow))
    n_list = len(listing_plan(flow))
    out = _empty_columns(n)
    events: list = []
    traces = {}
    for rank, sym, pos in groups:
        trace = None
        if rank in traced:
            trace = traces[rank] = np.zeros((n_requests, 6), np.int32)
        run_lane(flow, seed, rank, sym, pos, request_orders, n_list, out,
                 events, trace, Book)
        if trace is not None:  # requests without an order of the lane
            seen = np.zeros(n_requests, bool)
            seen[np.unique(pos // request_orders)] = True
            last = np.zeros(6, np.int32)
            for k in range(n_requests):
                if seen[k]:
                    last = trace[k]
                else:
                    trace[k] = last[[2, 2, 2, 5, 5, 5]]
    idx = np.concatenate([g[2] for g in groups]) if groups else \
        np.zeros(0, np.int64)
    ev = np.array(events, np.int64).reshape(-1, N_EVENT_FIELDS)
    return dict(idx=idx, cols={k: v[idx] for k, v in out.items()}, events=ev,
                traces=traces)


def split(ranks: np.ndarray, sym_of_rank: np.ndarray, workers: int):
    """Lanes dealt to `workers` groups, heaviest first to the lightest group."""
    order = np.argsort(ranks, kind="stable")
    counts = np.bincount(ranks, minlength=len(sym_of_rank))
    bounds = np.r_[0, np.cumsum(counts)]
    groups = [[] for _ in range(workers)]
    load = [0] * workers
    for rank in np.argsort(-counts, kind="stable").tolist():
        if counts[rank] == 0:
            break
        w = load.index(min(load))
        load[w] += int(counts[rank])
        groups[w].append((rank, int(sym_of_rank[rank]),
                          order[bounds[rank]:bounds[rank + 1]]))
    return groups


def merge(parts: list, n: int) -> dict:
    """Workers' shares into one stream: columns by stream index, events in
    stream order (one order's events are contiguous and keep their order)."""
    cols = _empty_columns(n)
    for p in parts:
        for k in COLUMNS:
            cols[k][p["idx"]] = p["cols"][k]
    ev = np.concatenate([p["events"] for p in parts])
    ev = ev[np.argsort(ev[:, 0], kind="stable")]
    traces = {}
    for p in parts:
        traces.update(p["traces"])
    return dict(cols=cols, events=ev, traces=traces)


def generate(flow: dict, seed: int, n_requests: int, request_orders: int,
             workers: int = 1, pool=None, reference_path=None) -> dict:
    """The stream of n_requests x request_orders orders: {"cols": columns by
    stream index, "events": int64 [n_events, 13] in reference.EVENT_FIELDS
    order, "traces": {rank: [requests, 6] depth (min, max, end per side)},
    "sym_of_rank"}. `pool` is a multiprocessing pool of at least `workers`
    processes; without one the lanes run here. `reference_path` is the
    configuration's reference file, whose Book the loop keeps (book_class)."""
    ranks, sym_of_rank = layout(flow, seed, n_requests, request_orders)
    jobs = [(flow, seed, n_requests, request_orders, g, reference_path)
            for g in split(ranks, sym_of_rank, workers)]
    parts = pool.map(work, jobs, chunksize=1) if pool is not None else \
        [work(j) for j in jobs]
    out = merge(parts, n_requests * request_orders)
    out["sym_of_rank"] = sym_of_rank
    out["ranks"] = ranks
    return out


def facts(stream: dict, request_orders: int, flow: dict | None = None) -> dict:
    """What a stream is made of, for the earlier-line report and the tests.
    With a flow that names its add kinds, the share of each among all
    orders."""
    named = {}
    if flow is not None and flow.get("add_kinds"):
        adds = ~stream["cols"]["cancel"]
        named = dict(add_kind_shares={
            entry["name"]: float(
                (adds & (stream["cols"]["kind"] == entry["kind"])).mean())
            for entry in add_kinds(flow)})
    cols = stream["cols"]
    n = len(cols["sym"])
    cancel = cols["cancel"]
    market = (cols["kind"] == MARKET) & ~cancel
    counts = np.bincount(stream["ranks"])
    ev = stream["events"]
    return dict(
        orders=n, requests=n // request_orders,
        limit_share=float((~cancel & ~market).mean()),
        cancel_share=float(cancel.mean()),
        market_share=float(market.mean()),
        top_rank_share=float(counts.max() / n),
        symbols_touched=int((counts > 0).sum()),
        events=int(len(ev)), events_per_order=float(len(ev) / max(n, 1)),
        cancel_events=int((ev[:, 12] == 0).sum()), **named,
    )
