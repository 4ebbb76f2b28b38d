"""Benchmark: sustained device matching throughput, 10K-symbol exchange-scale
load on one TPU chip (BASELINE.json config 4 shape; north star >= 1M
orders/sec across 10K symbols on one v5e).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "orders/sec", "vs_baseline": N}

vs_baseline: the reference publishes no numbers (BASELINE.json "published":
{}), so the denominator is the north-star target itself — vs_baseline =
value / 1e6, i.e. the fraction of the 1M orders/sec goal achieved.

Method: S symbol lanes x T time slots of real limit orders (tight price
band around mid so flows cross and match constantly), packed host-side with
numpy, executed as G chained batch_step calls (scan over T x vmap over S)
with donated book state. Synchronization discipline: the device runs the
G-grid chain without ANY host round trip; each grid's StepOutput is folded
into a device-side scalar accumulator (total fills + total overflows), and
ONE data-dependent scalar fetch closes the timed region. It is also the
production shape: the consumer keeps the device fed and decodes event
batches asynchronously, off the critical path. Orders/sec counts every op
applied to a book.

Every mode's JSON line names the device it ran on (platform, device_kind,
device_count). A mode measures the chip: without `--check` it exits
non-zero when JAX finds no TPU. `python bench.py --check` is a tiny CPU
self-check of the control flow, and its metric name says so.

Dtype note: the default is BENCH_DTYPE=int32 + the VMEM-resident Pallas
kernel — the high-throughput configuration, valid for workloads whose
tick/lot ranges keep per-side depth prefix sums under 2^31 (the bench's
int32 grids use coarser lot units accordingly). BENCH_DTYPE=int64 selects
the exact-integer envelope of the reference's accuracy=8 fixed-point
scaling (SURVEY §2.2) — prefix sums over a full (default 256-slot) side
can exceed 2^31 at 1e8-scaled lots — and runs on the scan path (Mosaic has
no 64-bit lowering).
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

import numpy as np


def build_grids(s, t, g, seed=0, dtype=np.int64):
    """G full [S, T] grids of crossing limit-order flow around mid=1.00
    (1e8 ticks at accuracy 8): uniform prices in ±0.5% of mid, volumes
    1..100 lots-of-1e6, random sides. Every slot is a live order."""
    rng = np.random.default_rng(seed)
    grids = []
    oid_base = 1
    for _ in range(g):
        price = rng.integers(99_500_000, 100_500_000, size=(s, t), dtype=dtype)
        volume = rng.integers(1, 101, size=(s, t), dtype=dtype) * 1_000_000
        side = rng.integers(0, 2, size=(s, t), dtype=np.int32)
        action = np.ones((s, t), np.int32)
        oid = (np.arange(s * t, dtype=dtype) + oid_base).reshape(s, t)
        oid_base += s * t
        uid = np.ones((s, t), dtype=dtype)
        grids.append(
            dict(
                action=action, side=side,
                kind=np.zeros((s, t), np.int32),
                price=price, volume=volume, oid=oid, uid=uid,
            )
        )
    return grids


def build_config_grids(cfg, s, t, g, seed=0, dtype=np.int64):
    """BASELINE.json config shapes 1-5 (BENCH_CONFIG); grids are NOP-padded
    where a shape leaves slots idle (the caller counts action != 0 for its
    throughput denominator). The default bench path is build_grids: uniform
    full grids, the exchange-scale config-4 shape at peak device utilization.

      1  single-symbol limit cross (BUY sweeps resting asks; S=1 lane live)
      2  single-symbol mixed stream with partial fills + cancels
      3  100-symbol Poisson flow (only lanes 0..99 live, Poisson thinning)
      4  Zipf-skewed per-symbol arrival rates across all S lanes
      5  market + limit mix with multi-level depth-walk fills
    """
    rng = np.random.default_rng(seed)
    grids = []
    oid_base = 1
    for _ in range(g):
        d = dict(
            action=np.zeros((s, t), np.int32),
            side=np.zeros((s, t), np.int32),
            kind=np.zeros((s, t), np.int32),
            price=np.zeros((s, t), dtype),
            volume=np.zeros((s, t), dtype),
            oid=np.zeros((s, t), dtype),
            uid=np.ones((s, t), dtype),
        )
        if cfg in (1, 2):
            mask = np.zeros((s, t), bool)
            mask[0, :] = True
        elif cfg == 3:
            lanes = min(100, s)
            mask = np.zeros((s, t), bool)
            mask[:lanes] = rng.random((lanes, t)) < 0.7  # Poisson thinning
        elif cfg == 4:
            ranks = np.arange(1, s + 1, dtype=np.float64)
            p_live = np.minimum(1.0, (1.0 / ranks) * 8)  # Zipf(1) rates
            mask = rng.random((s, t)) < p_live[:, None]
        else:  # 5
            mask = np.ones((s, t), bool)
        n = int(mask.sum())
        d["action"][mask] = 1
        d["side"][mask] = rng.integers(0, 2, n)
        if cfg == 1:
            # alternate resting asks and sweeping bids on the one lane
            tt = np.arange(t)
            d["side"][0] = (tt % 2 == 0).astype(np.int32)  # even: SALE rests
            d["price"][0] = np.where(
                tt % 2 == 0, 100_000_000 + (tt % 8) * 1000, 101_000_000
            )
            # Balanced flow: each sweeping bid consumes exactly the two
            # asks rested since the last one (5+5 lots) — the book hovers
            # at steady depth instead of accumulating a side without bound.
            d["volume"][0] = np.where(tt % 2 == 0, 5_000_000, 10_000_000)
        else:
            d["price"][mask] = rng.integers(99_500_000, 100_500_000, n)
            d["volume"][mask] = rng.integers(1, 101, n) * 1_000_000
        if cfg in (2, 5):
            # ~15% cancels of random earlier oids (misses allowed — the
            # reference's DeleteOrder on a filled order returns false)
            cm = mask & (rng.random((s, t)) < 0.15)
            d["action"][cm] = 2
            d["oid"][cm] = rng.integers(1, max(oid_base, 2), int(cm.sum()))
        if cfg == 5:
            mm = mask & (rng.random((s, t)) < 0.25) & (d["action"] == 1)
            d["kind"][mm] = 1
        fresh = d["action"] == 1
        d["oid"][fresh] = oid_base + np.arange(int(fresh.sum()))
        oid_base += int(fresh.sum())
        grids.append(d)
    return grids


def _device_block(check: bool) -> dict:
    """The device every JSON line of this run names. A mode that was not
    given --check measures the chip and refuses to run without one: a
    number from the CPU backend or the Pallas interpreter is never
    printed under a device metric's name."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" and not check:
        sys.exit(
            f"bench: JAX found no TPU (platform {d.platform!r}); device "
            "metrics come from the chip only — `--check` runs the CPU "
            "self-check"
        )
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": len(devices),
    }


def _metric(name: str, dev: dict) -> str:
    """A metric name that says so when it was not taken on the chip."""
    if dev["platform"] == "tpu":
        return name
    return f"CPU SELF-CHECK ({dev['platform']}), not a device metric: {name}"


def _next_pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _analytic_block(dtype_name):
    """Analytic XLA cost metrics (gome_tpu.obs.costmodel) folded into
    every BENCH payload: the BENCH_*.json snapshots then carry flops/order,
    bytes/order, arithmetic intensity, and peak HBM per hot-path entry —
    plus the donation savings — next to wall-clock orders/sec, so the
    analytic trajectory rides the same files as the measured one.
    BENCH_ANALYTIC=0 skips (e.g. repeated sweeps)."""
    if os.environ.get("BENCH_ANALYTIC", "1") == "0":
        return None
    from gome_tpu.obs import costmodel

    return costmodel.bench_analytics(dtype_name)


def _measured_block(dtype_name):
    """MEASURED roofline metrics (gome_tpu.obs.profiler) folded next to
    the analytic block in every BENCH payload: per-entry device time
    from a bounded jax.profiler capture, achieved GFLOP/s / GB/s
    (analytic work / measured time), and efficiency vs the machine
    ceiling — so BENCH_*.json carries what the hardware DID next to
    what XLA said it should do. BENCH_MEASURED=0 skips (captures cost
    seconds)."""
    if os.environ.get("BENCH_MEASURED", "1") == "0":
        return None
    from gome_tpu.obs import profiler

    return profiler.bench_measured(dtype_name)


def _host_block():
    """Host-CPU admit metrics (gome_tpu.obs.hostprof) folded into the
    mixed-stream SERVICE payload next to the analytic/measured blocks:
    measured gateway admit ns/order + achievable orders/sec/core and
    the per-stage split from the sampling profiler's deterministic
    drill — so BENCH_SERVICE_*.json carries the host trajectory (the
    front-door bottleneck, ROADMAP open item 1) from r06 onward.
    BENCH_HOST=0 skips; failures degrade to a stderr note, never a
    broken bench."""
    if os.environ.get("BENCH_HOST", "1") == "0":
        return None
    try:
        from gome_tpu.obs import hostprof

        return hostprof.bench_host()
    except Exception as e:
        print(f"# host admit drill unavailable: {e}", file=sys.stderr)
        return None


def _admit_block():
    """Scalar-vs-columnar admit comparison (gome_tpu.obs.hostprof.
    bench_admit) folded into the mixed-stream SERVICE payload next to
    the host block: the IDENTICAL seeded flow through the single-order
    DoOrder path and the round-11 columnar DoOrderBatch core, side by
    side with the speedup ratio — so BENCH_SERVICE_*.json records the
    front-door rework's headline win. BENCH_ADMIT=0 skips; failures
    degrade to a stderr note, never a broken bench."""
    if os.environ.get("BENCH_ADMIT", "1") == "0":
        return None
    try:
        from gome_tpu.obs import hostprof

        return hostprof.bench_admit()
    except Exception as e:
        print(f"# admit bench unavailable: {e}", file=sys.stderr)
        return None


def admit_main():
    """--admit: the scalar-vs-columnar admit comparison standalone —
    host-only (no jax import, no engine), prints the bench_admit JSON
    payload. The fastest way to see the round-11 front-door numbers on
    any machine."""
    from gome_tpu.obs import hostprof

    doc = hostprof.bench_admit()
    # Host-only mode: no jax, no device behind any number in it.
    doc.update(platform="host", device_kind=None, device_count=0)
    print(json.dumps(doc, indent=1))
    s, c = doc["scalar"], doc["columnar"]
    print(
        f"# admit: scalar {s['admit_ns_per_order']} ns/order "
        f"({s['admit_orders_per_sec_per_core'] / 1e3:.0f}K/sec/core) vs "
        f"columnar {c['admit_ns_per_order']} ns/order "
        f"({c['admit_orders_per_sec_per_core'] / 1e3:.0f}K/sec/core) — "
        f"{doc['speedup_x']}x",
        file=sys.stderr,
    )
    return 0


def _jit_cache_sizes(**fns):
    """{name: compiled-variant count} for the bench's own jits — the
    payload's compile count (how many distinct shapes the timed chain
    minted). Best-effort: the probe is a jax-internal accessor."""
    out = {}
    for name, fn in fns.items():
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            try:
                out[name] = size()
            except Exception:
                pass
    return out


FIELDS = ("action", "side", "kind", "price", "volume", "oid", "uid")


def pack_dense_rounds(grids, t_dense, s_total, cap=None, depth_bound=None):
    """Convert NOP-padded [S, T] grids into dense rounds over LIVE lanes
    (the host-side packing the engine's dense path does —
    gome_tpu.engine.batch.dense_batch_step): per lane, concatenate its live
    ops across the whole timeline (FIFO preserved), then emit rounds of up
    to t_dense ops per still-live lane until every stream drains. Rows and
    time depth bucket to powers of two (bounded compile shapes); padding
    rows carry the out-of-range sentinel lane id = s_total.

    cap: the storage cap — when given, each round also gets a CAP CLASS
    (engine.batch._cap_ladder; VERDICT r4 #2): the smallest class covering
    every round lane's depth bound. A book can never hold more resting
    orders than the ops ever sent to it, so bounding by per-lane op totals
    is provably overflow-free for the tail of a Zipf flow while hot-lane
    rounds keep the full cap — the device stops paying one hot lane's
    depth on 10K shallow rows. depth_bound ([s_total] per-lane totals)
    lets the caller count ops across the WHOLE run (warmup + timed): a
    chain replaying from post-warmup books carries the warmup's resting
    depth, which this packer's own timeline cannot see. Defaults to this
    pack's totals. The engine-side guard (batch._guard_capped) still folds
    any violation into the overflow count the bench refuses to hide.

    Returns (rounds, caps): rounds = [(lane_ids[R]|None, ops dict of
    [R, T_d])], caps aligned per round (cap... repeated when cap=None).
    """
    from gome_tpu.engine.batch import _cap_ladder

    streams: dict[int, list] = {}
    for d in grids:
        live = d["action"] != 0
        for lane in np.nonzero(live.any(axis=1))[0]:
            m = live[lane]
            streams.setdefault(int(lane), []).append(
                {f: d[f][lane][m] for f in FIELDS}
            )
    merged = {
        lane: {f: np.concatenate([c[f] for c in chunks]) for f in FIELDS}
        for lane, chunks in streams.items()
    }
    total_len = {lane: len(m["action"]) for lane, m in merged.items()}
    ladder = _cap_ladder(cap) if cap else None
    use_classes = (
        ladder is not None
        and len(ladder) > 1
        and os.environ.get("BENCH_CAP_CLASSES", "1") != "0"
    )
    offsets = {lane: 0 for lane in merged}
    rounds = []
    caps = []

    def round_cap(lanes):
        if not use_classes:
            return cap
        if depth_bound is not None:
            bound = max(int(depth_bound[lane]) for lane in lanes)
        else:
            bound = max(total_len[lane] for lane in lanes)
        return next((c for c in ladder if c >= bound), ladder[-1])

    def emit(lanes, depth):
        # A round touching most lanes goes out as a FULL grid (lane_ids
        # None): a gather/scatter of nearly every row costs one DMA per row
        # on TPU — at 8K rows that dwarfs the matching work itself.
        if len(lanes) > s_total // 2:
            ops = {
                f: np.zeros(
                    (s_total, depth),
                    np.int32 if f in ("action", "side", "kind")
                    else merged[lanes[0]][f].dtype,
                )
                for f in FIELDS
            }
            for lane in sorted(lanes):
                s0 = offsets[lane]
                chunk = {
                    f: merged[lane][f][s0 : s0 + depth] for f in FIELDS
                }
                n = len(chunk["action"])
                for f in FIELDS:
                    ops[f][lane, :n] = chunk[f]
                offsets[lane] += n
                if offsets[lane] >= len(merged[lane]["action"]):
                    del merged[lane], offsets[lane]
            caps.append(round_cap(lanes))
            rounds.append((None, ops))
            return
        # Min 8 rows: the Pallas kernel's sublane-alignment floor; sentinel
        # padding rows are free.
        rows = max(8, _next_pow2(len(lanes)))
        ops = {
            f: np.zeros(
                (rows, depth),
                np.int32 if f in ("action", "side", "kind")
                else merged[lanes[0]][f].dtype,
            )
            for f in FIELDS
        }
        lane_ids = np.full(rows, s_total, np.int32)
        for r, lane in enumerate(sorted(lanes)):
            lane_ids[r] = lane
            s0 = offsets[lane]
            chunk = {f: merged[lane][f][s0 : s0 + depth] for f in FIELDS}
            n = len(chunk["action"])
            for f in FIELDS:
                ops[f][r, :n] = chunk[f]
            offsets[lane] += n
            if offsets[lane] >= len(merged[lane]["action"]):
                del merged[lane], offsets[lane]
        caps.append(round_cap(lanes))
        rounds.append((lane_ids, ops))

    while merged:
        # Few fat rounds beat many tight ones (every round is a dispatch).
        # Each sweep emits at most two rounds:
        # every short-stream lane in one shallow depth-8 round (padding is
        # bounded 8x, and the whole round is one dispatch), and the deep
        # lanes in one round as deep as the kernel's VMEM budget allows for
        # their block size (record outputs are [T, K, block]) — a lane
        # appears at most once per sweep, so its chunks stay FIFO.
        shallow, deep, max_deep = [], [], 0
        for lane in merged:
            rem = len(merged[lane]["action"]) - offsets[lane]
            if rem <= 8:
                shallow.append(lane)
            else:
                deep.append(lane)
                max_deep = max(max_deep, rem)
        if shallow:
            emit(shallow, 8)
        if deep:
            block = min(max(8, _next_pow2(len(deep))), 128)
            t_vmem = (64 * 128) // block  # ~6MB of [T, K, block] records
            emit(deep, min(t_dense, t_vmem, _next_pow2(max_deep)))
    return rounds, caps


def _svc_columns(rng, n, n_symbols, oid0):
    """Raw order columns — what the gRPC handlers would have accumulated.
    Data GENERATION is the load client's job and stays off the clock; all
    gateway work on these columns (frame encode, pre-pool marking,
    publish) is timed. This is the CLEAN stream: 100% limit ADDs, uniform
    symbols, one uuid — the upper-bound measurement. The headline uses
    _svc_columns_mixed (the reference-driver-shaped flow)."""
    return dict(
        n=n,
        action=np.ones(n, np.uint8),
        side=rng.integers(0, 2, n).astype(np.uint8),
        kind=np.zeros(n, np.uint8),
        price=rng.integers(99_500_000, 100_500_000, n).astype(np.int64),
        volume=rng.integers(1, 101, n).astype(np.int64),
        symbol_idx=rng.integers(0, n_symbols, n).astype(np.uint32),
        uuid_idx=np.zeros(n, np.uint32),
        oids=np.char.add("o", np.arange(oid0, oid0 + n).astype("U12")).astype(
            "S"
        ),
    )


class _MixedFlow:
    """Config-5-shaped service load (the reference driver randomizes both
    sides and the new framework's config 5 adds markets + depth walks,
    doorder.go:38-47): ~45% cancels (a fifth of them targeting ADDs from
    the SAME frame, some ordered before their ADD — the
    cancel-before-consume race the pre-pool exists for, SURVEY §2.3.3),
    ~25% market orders among ADDs, 256 distinct uuids, Zipf(1) symbol
    popularity. Stateful: cancels target really-issued (symbol, oid,
    price) triples from a rolling pool of resting limit orders, biased
    to RECENT entries (most real cancels reprice fresh quotes).

    The cancel rate is chosen for depth-STATIONARITY: with this flow's
    rest rate (~55% x 75% limits x ~60% non-crossing), ~45% cancels is the
    equilibrium point where a hot Zipf lane's resting depth stays bounded
    (~300) instead of growing linearly and escalating book capacity
    forever — real exchange message mixes are majority-cancel (10:1+
    cancel-to-trade is common), so this is still conservative."""

    CANCEL_P = 0.45
    MARKET_P = 0.25
    SAME_FRAME_P = 0.2  # fraction of cancels aimed at this frame's ADDs
    RECENT_BIAS = 4  # pool cancels target the newest 1/4 of live entries
    N_UUIDS = 256
    POOL_MAX = 1 << 20

    def __init__(self, rng, n_symbols):
        self.rng = rng
        ranks = np.arange(1, n_symbols + 1, dtype=np.float64)
        w = 1.0 / ranks
        self.sym_p = w / w.sum()
        self.n_symbols = n_symbols
        self.oid0 = 1
        # Rolling pool of cancellable resting orders (ring buffer).
        self.pool_sym = np.zeros(self.POOL_MAX, np.uint32)
        self.pool_price = np.zeros(self.POOL_MAX, np.int64)
        self.pool_oid = np.zeros(self.POOL_MAX, np.int64)
        self.pool_uuid = np.zeros(self.POOL_MAX, np.uint32)
        self.pool_n = 0
        self.pool_head = 0

    def _pool_push(self, sym, price, oid, uuid):
        k = len(sym)
        idx = (self.pool_head + np.arange(k)) % self.POOL_MAX
        self.pool_sym[idx] = sym
        self.pool_price[idx] = price
        self.pool_oid[idx] = oid
        self.pool_uuid[idx] = uuid
        self.pool_head = (self.pool_head + k) % self.POOL_MAX
        self.pool_n = min(self.pool_n + k, self.POOL_MAX)

    def frame(self, n):
        rng = self.rng
        action = np.ones(n, np.uint8)
        dels = rng.random(n) < self.CANCEL_P
        if self.pool_n == 0:
            dels[:] = False
        action[dels] = 2
        adds = ~dels
        n_add = int(adds.sum())

        sym = rng.choice(
            self.n_symbols, size=n, p=self.sym_p
        ).astype(np.uint32)
        price = rng.integers(99_500_000, 100_500_000, n).astype(np.int64)
        volume = rng.integers(1, 101, n).astype(np.int64)
        kind = np.zeros(n, np.uint8)
        mkt = adds & (rng.random(n) < self.MARKET_P)
        kind[mkt] = 1
        oid_nums = np.zeros(n, np.int64)
        oid_nums[adds] = self.oid0 + np.arange(n_add)
        self.oid0 += n_add
        uuid_idx = rng.integers(0, self.N_UUIDS, n).astype(np.uint32)

        # Cancels carry the original order's (symbol, uuid, oid, price) —
        # the pre-pool key is S:U:O (ordernode.go:89-92) and the book
        # lookup needs the exact resting price (engine.go:92-93). Mostly
        # resting orders from earlier frames; some from THIS frame's
        # limit ADDs (the cancel-before-consume race when the DEL
        # precedes its ADD in the stream).
        di = np.nonzero(dels)[0]
        if len(di):
            same = rng.random(len(di)) < self.SAME_FRAME_P
            ai = np.nonzero(adds & (kind == 0))[0]
            if len(ai) == 0:
                same[:] = False
            n_pool = int((~same).sum())
            if n_pool:
                # Newest-quarter bias (ring indices count back from head).
                depth = max(self.pool_n // self.RECENT_BIAS, 1)
                back = rng.integers(1, depth + 1, n_pool)
                pi = (self.pool_head - back) % self.POOL_MAX
                tgt = di[~same]
                sym[tgt] = self.pool_sym[pi]
                price[tgt] = self.pool_price[pi]
                oid_nums[tgt] = self.pool_oid[pi]
                uuid_idx[tgt] = self.pool_uuid[pi]
            if same.any():
                ti = rng.integers(0, len(ai), int(same.sum()))
                src = ai[ti]
                tgt = di[same]
                sym[tgt] = sym[src]
                price[tgt] = price[src]
                oid_nums[tgt] = oid_nums[src]
                uuid_idx[tgt] = uuid_idx[src]

        rest = adds & (kind == 0)
        self._pool_push(
            sym[rest], price[rest], oid_nums[rest], uuid_idx[rest]
        )
        return dict(
            n=n,
            action=action,
            side=rng.integers(0, 2, n).astype(np.uint8),
            kind=kind,
            price=np.where(mkt, 0, price),
            volume=volume,
            symbol_idx=sym,
            uuid_idx=uuid_idx,
            oids=np.char.add(
                "o", oid_nums.astype("U12")
            ).astype("S"),
        )


class _SimFlow:
    """gome_tpu.sim traffic source (--flow sim / BENCH_FLOW=sim): the
    on-device Hawkes/Zipf generator drives the service bench instead of
    the hand-rolled _MixedFlow — clustered (self-exciting) arrivals,
    Zipf(a) symbol popularity, book-coupled limit placement, and cancels
    that target really-resting (symbol, uuid, oid, price) quadruples.
    Generation is the load client's job and stays off the clock: each
    pump runs one device gen step, applies the grid to a sim-side book
    stack (so later grids quote against the evolved state), then
    converts to the service column contract via sim.replay's
    grid_to_columns (deliberate-miss cancels dropped — the pre-pool
    tracks oid liveness). `.frame(n)` buffers pumps until it can hand
    out exactly n orders; the surplus carries into the next frame."""

    T_BINS = 1024  # one thinned event max per bin -> <= 1024 orders/pump

    def __init__(self, seed, n_symbols):
        import jax
        import jax.numpy as jnp

        from gome_tpu.engine.batch import batch_step
        from gome_tpu.engine.book import BookConfig, init_books
        from gome_tpu.sim.flow import FlowConfig, flow_init, gen_ops_jit
        from gome_tpu.sim.replay import grid_to_columns

        self._apply = batch_step
        self._gen = gen_ops_jit
        self._to_cols = grid_to_columns
        self._get = jax.device_get
        self.seed = seed
        self.config = FlowConfig(
            n_lanes=n_symbols,
            t_bins=self.T_BINS,
            ref_price=100_000_000,  # match the service price magnitude
            ref_spread=50,
        )
        # Generation-side books are independent of the engine under test
        # (the load client does not see the matcher's state); cap 64 is
        # deep enough that cancel targets come from a faithful book.
        self.book_config = BookConfig(cap=64, max_fills=8, dtype=jnp.int32)
        self.books = init_books(self.book_config, n_symbols)
        self.state = flow_init(self.config, jax.random.PRNGKey(seed))
        self._buf = []
        self._buffered = 0

    def _pump(self):
        self.state, ops = self._gen(self.config, self.state, self.books)
        self.books, _ = self._apply(self.book_config, self.books, ops)
        cols = self._to_cols(
            self._get(ops)._asdict(), drop_misses=True
        )
        if cols["n"]:
            self._buf.append(cols)
            self._buffered += cols["n"]

    def frame(self, n):
        while self._buffered < n:
            self._pump()
        cat = {
            k: np.concatenate([b[k] for b in self._buf])
            for k in self._buf[0]
            if k != "n"
        }
        out = {k: v[:n] for k, v in cat.items()}
        out["n"] = n
        rest = {k: v[n:] for k, v in cat.items()}
        m = len(rest["action"])
        self._buf = [dict(rest, n=m)] if m else []
        self._buffered = m
        return out

    def describe(self):
        """Flow provenance for the bench JSON payload (enough to rebuild
        the FlowConfig and regenerate the stream bit-exactly)."""
        c = self.config
        return {
            "kind": "sim",
            "seed": self.seed,
            "n_lanes": c.n_lanes,
            "t_bins": c.t_bins,
            "dt": c.dt,
            "rates": {
                "submit": c.submit_rate,
                "cancel": c.cancel_rate,
                "market": c.market_rate,
            },
            "hawkes": {
                "excite_self": c.excite_self,
                "excite_cross": c.excite_cross,
                "excite_kind": c.excite_kind,
                "decay": c.decay,
                "branching_ratio": round(c.branching_ratio(), 6),
            },
            "zipf_a": c.zipf_a,
            "offset_p": c.offset_p,
            "ref_price": c.ref_price,
        }


_SVC_UUIDS = [f"u{i}" for i in range(256)]  # shared uuid dictionary


def _svc_gateway_step(cols, symbols, pool, queue, uuids=_SVC_UUIDS):
    """The gateway's per-frame work, all ON the clock: wire-encode the
    frame (the batching DoOrder handler's output), mark the pre-pool
    (main.go:44-45 for every ADD), publish to doOrder."""
    from gome_tpu.bus.colwire import encode_order_frame

    cols = dict(cols, symbols=symbols, uuids=uuids)
    payload = encode_order_frame(
        cols["n"], cols["action"], cols["side"], cols["kind"],
        cols["price"], cols["volume"], symbols, cols["symbol_idx"],
        uuids, cols["uuid_idx"], cols["oids"],
    )
    mark_frame = getattr(pool, "mark_frame", None)
    if mark_frame is not None:
        mark_frame(cols)
    else:
        ADD = 1
        for a, k, u, o in zip(
            cols["action"].tolist(), cols["symbol_idx"].tolist(),
            cols["uuid_idx"].tolist(), cols["oids"].tolist(),
        ):
            if a == ADD:
                pool.add((symbols[k], uuids[u], o.decode()))
    queue.publish(payload)


def _svc_warmup(engine, consumer, bus, make_frame, symbols, margin=True):
    """Warm the service pipeline until its compiled shapes are pinned.

    Frame geometry (grid-2 packed rows/depth ratchets, compaction buffer
    classes) evolves as the books reach steady state, and every distinct
    shape is a trace+compile (seconds cold, ~1s of host CPU re-trace
    even cache-hit) — none of it belongs inside the
    timed region, exactly as a production deployment pre-warms its known
    geometry (BatchEngine.prewarm_geometry). Two phases:

      1. drain warm frames until the geometry ratchets hold still for two
         consecutive frames (min 2, max 8);
      2. the stochastic tails (live-lane count, per-lane depth, DEL count)
         can still cross a pow2 bucket mid-run, so pin the row/depth/
         cancel ratchets at 2x the observed steady state — far beyond any
         per-frame fluctuation — and run one more frame so the margined
         shapes compile too.

    make_frame() produces one frame's columns (a stateful generator —
    clean or mixed flow). Returns the number of warm frames consumed.

    margin=False (a run that loaded a persisted geometry manifest) skips
    phase 2: the loaded floors already carry a previous run's margin, and
    re-margining on every run would COMPOUND — 2x per run until the row
    floor exceeds n_slots and every tail class degenerates to a full
    grid (the r5 regression: floors hit 65536 on a 10240-lane book and
    each run minted fresh shapes forever instead of converging)."""
    n_warm = 0
    stable = 0
    # Minimum 8 warm frames regardless of ratchet stability: the BOOKS
    # also need to reach flow steady state (a crossing flow fills depth
    # over its first ~8 frames), and a manifest-loaded run whose floors
    # hold still from frame 1 must not start timing inside that book
    # transient — it would measure a different window of the flow than a
    # fresh run does.
    while n_warm < 8 or stable < 2:
        if n_warm >= 12:
            break
        cols = make_frame()
        geo = engine.batch.geometry_floors()
        _svc_gateway_step(cols, symbols, engine.pre_pool, bus.order_queue)
        consumer.drain()
        stable = stable + 1 if engine.batch.geometry_floors() == geo else 0
        n_warm += 1
    if not margin:
        return n_warm
    # The stability loop's ratchets include WARMUP TRANSIENTS (count_ub
    # overestimates while books fill send hundreds of lanes into a deep
    # cap class exactly once, latching e.g. a 1024-row x 1024-deep grid
    # floor that steady state never needs — seconds of device time per
    # frame, forever). Reset, let two steady-state frames re-ratchet
    # honest geometry, then pin the margin on THAT. The recorded shape
    # COMBOS from the transient frames are forgotten with the floors:
    # save_geometry would otherwise persist them and every later boot
    # would precompile deep-grid shapes the steady-state flow never uses.
    engine.batch.reset_geometry_floors(combos=True)
    for _ in range(2):
        _svc_gateway_step(
            make_frame(), symbols, engine.pre_pool, bus.order_queue
        )
        consumer.drain()
        n_warm += 1
    g = engine.batch.geometry_floors()
    engine.batch.prewarm_geometry(
        rows_floor={c: 2 * v for c, v in g["rows_floor"].items()},
        t_floor={c: 2 * v for c, v in g["t_floor"].items()},
        cancels_buf={b: 2 * v for b, v in g["cancels_buf"].items()},
        # fills_buf is dominated by pow2(grid n_ops) within each class —
        # no margin needed.
    )
    _svc_gateway_step(make_frame(), symbols, engine.pre_pool, bus.order_queue)
    consumer.drain()
    return n_warm + 1


def service_main():
    """End-to-end SERVICE bench: the full post-gRPC-arrival pipeline in
    one process — gateway side (frame encode + pre-pool mark + publish,
    timed) then consumer side (frame decode -> admission -> vectorized
    pack -> device matching -> device-side event compaction -> overlapped
    fetch (cross-frame pipelined) -> columnar decode -> EVENT-frame
    publish -> offset commit, timed). Only load GENERATION and compile
    warmup are off the clock.

    Prints ONE JSON line with the measured gateway->matchOrder number
    (gateway + consumer time combined — everything after gRPC arrival).
    The stderr breakdown also reports the rate excluding time blocked on
    the device->host fetch, plus the gateway/consumer split (separate
    processes in the reference topology; serialized here on one host)."""
    check = "--check" in sys.argv
    import jax

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    if check:
        jax.config.update("jax_platforms", "cpu")
    dev = _device_block(check)
    import jax.numpy as jnp

    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine import frames as engine_frames
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer

    N = int(os.environ.get("SVC_ORDERS", 8_192 if check else 1_048_576))
    FRAME = int(os.environ.get("SVC_FRAME", 2_048 if check else 262_144))
    S = int(os.environ.get("SVC_SYMBOLS", 64 if check else 10_240))
    CAP = int(os.environ.get("SVC_CAP", 32 if check else 256))
    PIPE = int(os.environ.get("SVC_PIPELINE", 2))  # cross-frame pipelining
    engine = MatchEngine(
        config=BookConfig(cap=CAP, max_fills=16, dtype=jnp.int32),
        n_slots=S,
        max_t=32,
        kernel="pallas",
        # A Zipf frame's hottest lane runs ~30K ops deep; the kernel's
        # time-paged blocks make depth nearly free, so a deep ceiling
        # collapses the dense grid train (27 grids -> ~5) and with it the
        # per-grid dispatch + host cost.
        dense_t_max=int(os.environ.get("SVC_DENSE_T", 8192)),
    )
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
        pipeline_depth=PIPE,
    )

    # Clamp BEFORE the manifest key is built: a small-N run records
    # different frame-shape combos than a full-size run, so they must not
    # share one manifest file (keying on the pre-clamp FRAME did).
    FRAME = min(FRAME, N)

    # Persisted geometry (shape manifest), only at a path given
    # explicitly (SVC_GEOMETRY): like a production deployment, the service
    # then loads the flow's recorded floors + shape combos from the
    # previous run and precompiles them off-clock — the timed region
    # contains zero first-seen traces (the XLA persistent cache already
    # made the compiles one-time; this closes the per-process TRACE gap).
    # Unset, no manifest is read or written: the manifest changes what the
    # run does, so it never appears from what an earlier run left on disk.
    geom_path = os.environ.get("SVC_GEOMETRY")
    t0 = time.perf_counter()
    # The margin/reset warmup pass runs only when NO manifest exists:
    # keyed on file presence, not replay count — a manifest whose combos
    # are all above the boot cap replays 0 but its floors still loaded
    # and must not be reset + re-margined (compounding).
    have_manifest = bool(geom_path) and os.path.exists(geom_path)
    # presize_cap=False: this one process runs BOTH streams, and the
    # shallow clean phase must not pay the mixed flow's stationary cap
    # from boot — the mixed warmup escalates off-clock (persistent-cache
    # reads) exactly like production would on first escalation.
    n_pre = (
        engine.load_geometry(geom_path, presize_cap=False)
        if geom_path else 0
    )
    if n_pre:
        print(
            f"# geometry manifest: {n_pre} shape combos precompiled in "
            f"{time.perf_counter() - t0:.1f}s ({geom_path})",
            file=sys.stderr,
        )

    rng = np.random.default_rng(7)
    symbols = [f"sym{i}" for i in range(S)]

    from gome_tpu.bus.colwire import decode_event_frame

    def run_stream(label, make_frame, repeats=1):
        """Warm (off clock) then time one stream REPEATS times: gateway
        phase + consumer drain per repeat. Returns the MEDIAN repeat's
        measurement dict (by throughput) extended with the per-run list,
        per-run getrusage deltas, and a per-frame consumer CPU-time
        histogram — VERDICT r5 #1/#2: a headline must be a median with
        contention telemetry attached, not a best-of-N outlier with no
        record of what the host was doing. process_time tracks the CPU
        this process actually spent (excludes time blocked on the device
        fetch and CPU taken by other processes — the stable cost measure
        on a contended host)."""
        n_warm = _svc_warmup(
            engine, consumer, bus, make_frame, symbols,
            margin=not have_manifest,
        )
        runs = []
        cpu_frame: list[float] = []  # consumer CPU seconds per frame step
        for _rep in range(max(1, repeats)):
            frames_cols = [make_frame() for _ in range(-(-N // FRAME))]
            n_total = sum(int(c["n"]) for c in frames_cols)
            engine_frames.FETCH_SECONDS = 0.0
            ev_skip = bus.match_queue.end_offset()  # prior frames' events
            st0 = (
                engine.stats.device_calls,
                engine.stats.cap_escalations,
                engine.stats.frame_fallbacks,
            )
            ru0 = resource.getrusage(resource.RUSAGE_SELF)

            # Gateway phase (timed): encode + mark + publish every frame.
            t0 = time.perf_counter()
            for cols in frames_cols:
                _svc_gateway_step(
                    cols, symbols, engine.pre_pool, bus.order_queue
                )
            t_gateway = time.perf_counter() - t0

            # Consumer phase (timed), step by step: batch_n=1 means one
            # run_once ≈ one frame, so the per-step process_time delta IS
            # the per-frame CPU cost — the distribution the median
            # headline needs next to it (a flat median with a fat p99
            # tail is a contention story, not a throughput story).
            t0 = time.perf_counter()
            c0 = time.process_time()
            n_done = 0
            while (
                bus.order_queue.committed() < bus.order_queue.end_offset()
            ):
                s0 = time.process_time()
                n_step = consumer.run_once()
                dt = time.process_time() - s0
                if n_step:
                    cpu_frame.append(dt)
                n_done += n_step
            t_consumer = time.perf_counter() - t0
            cpu_consumer = time.process_time() - c0
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            fetch_s = engine_frames.FETCH_SECONDS
            elapsed = t_gateway + t_consumer
            assert n_done == n_total, (n_done, n_total)

            n_events = 0
            ev_bytes = 0
            for m in bus.match_queue.read_from(ev_skip, 1 << 30):
                ev_bytes += len(m.body)
                n_events += len(decode_event_frame(m.body))
            host_s = max(elapsed - fetch_s, 1e-9)
            runs.append(dict(
                label=label,
                orders=n_done,
                events=n_events,
                throughput=n_done / elapsed,
                ex_fetch=n_done / host_s,
                consumer_cpu_orders_per_sec_per_core=(
                    n_done / max(cpu_consumer, 1e-9)
                ),
                gateway_s=t_gateway,
                consumer_s=t_consumer,
                consumer_cpu_s=cpu_consumer,
                fetch_blocked_s=fetch_s,
                rusage=dict(
                    utime_s=round(ru1.ru_utime - ru0.ru_utime, 6),
                    stime_s=round(ru1.ru_stime - ru0.ru_stime, 6),
                    nvcsw=ru1.ru_nvcsw - ru0.ru_nvcsw,
                    nivcsw=ru1.ru_nivcsw - ru0.ru_nivcsw,
                    majflt=ru1.ru_majflt - ru0.ru_majflt,
                ),
            ))
            print(
                f"# [{label} {_rep + 1}/{max(1, repeats)}] "
                f"orders={n_done} events={n_events} "
                f"warm_frames={n_warm} gateway={t_gateway:.3f}s "
                f"consumer={t_consumer:.3f}s fetch_blocked={fetch_s:.3f}s "
                f"| ex-fetch "
                f"{n_done / host_s / 1e6:.2f}M orders/sec | "
                f"consumer-only ex-fetch "
                f"{n_done / max(t_consumer - fetch_s, 1e-9) / 1e6:.2f}M | "
                f"event-frame bytes/order={ev_bytes / max(n_done, 1):.1f} "
                f"| device_calls={engine.stats.device_calls - st0[0]} "
                f"escalations={engine.stats.cap_escalations - st0[1]} "
                f"fallbacks={engine.stats.frame_fallbacks - st0[2]} "
                f"cap={engine.config.cap} | "
                f"consumer_cpu={cpu_consumer:.3f}s -> "
                f"{n_done / max(cpu_consumer, 1e-9) / 1e6:.2f}M "
                f"orders/sec/core | nivcsw={runs[-1]['rusage']['nivcsw']}",
                file=sys.stderr,
            )
        ordered = sorted(runs, key=lambda r: r["throughput"])
        meas = dict(ordered[len(ordered) // 2])  # the median run
        meas["runs"] = runs
        meas["median_throughput"] = meas["throughput"]
        meas["best_throughput"] = ordered[-1]["throughput"]
        cf = np.asarray(cpu_frame, np.float64)
        if len(cf):
            p50, p90, p99 = np.percentile(cf, [50, 90, 99])
            meas["cpu_per_frame_s"] = dict(
                count=len(cf), mean=round(float(cf.mean()), 6),
                p50=round(float(p50), 6), p90=round(float(p90), 6),
                p99=round(float(p99), 6), max=round(float(cf.max()), 6),
            )
        return meas

    # Clean stream first (pure limit ADDs, uniform symbols — the upper
    # bound), then the HEADLINE mixed stream (reference-driver shape:
    # Zipf symbols, ~45% cancels incl. same-frame races, ~25% markets,
    # 256 uuids). Clean-first also means the mixed phase's extra compiled
    # shapes (deep dense grids for hot Zipf lanes, cancel buffers) are
    # charged to the mixed warmup, not the clean timed region.
    oid_box = [1]

    def clean_frame():
        cols = _svc_columns(rng, FRAME, S, oid_box[0])
        oid_box[0] += FRAME
        return cols

    clean = run_stream("clean", clean_frame)
    # Headline traffic source: the hand-rolled reference-driver-shaped
    # _MixedFlow (default), or the gome_tpu.sim Hawkes/Zipf generator
    # (--flow sim / BENCH_FLOW=sim) — same column contract, but with
    # clustered arrivals and book-coupled placement, and with its full
    # provenance (seed + model params) recorded in the payload.
    flow_kind = os.environ.get("BENCH_FLOW", "mixed")
    if "--flow" in sys.argv:
        flow_kind = sys.argv[sys.argv.index("--flow") + 1]
    if flow_kind == "sim":
        head_flow = _SimFlow(int(os.environ.get("SVC_SIM_SEED", 11)), S)
        flow_info = head_flow.describe()
        flow_label = "SIM Hawkes/Zipf"
    elif flow_kind == "mixed":
        head_flow = _MixedFlow(np.random.default_rng(11), S)
        flow_info = {
            "kind": "mixed",
            "seed": 11,
            "cancel_p": _MixedFlow.CANCEL_P,
            "market_p": _MixedFlow.MARKET_P,
            "same_frame_p": _MixedFlow.SAME_FRAME_P,
            "zipf_a": 1.0,
        }
        flow_label = "MIXED"
    else:
        raise SystemExit(f"unknown --flow {flow_kind!r} (mixed|sim)")
    # The HEADLINE is the MEDIAN of SVC_REPEATS timed repeats (VERDICT r5
    # #1/#2): one repeat is a sample, not a claim — the best repeat stays
    # in the payload as a secondary field, next to the per-run rusage
    # deltas (nivcsw = the contention record) and the per-frame CPU
    # histogram that say WHY the spread is what it is.
    REPEATS = int(os.environ.get("SVC_REPEATS", 5))
    mixed = run_stream(
        flow_kind, lambda: head_flow.frame(FRAME), repeats=REPEATS
    )
    if geom_path:
        engine.save_geometry(geom_path)

    throughput = mixed["median_throughput"]
    result = {
        "metric": _metric(
            f"service throughput gateway->matchOrder, {flow_label} "
            "stream "
            f"(Zipf symbols, cancels + market orders, 256 uuids; "
            f"everything after gRPC arrival), "
            f"{S} symbols, {FRAME}-order frames, int32 pallas, pipeline "
            f"depth {PIPE}; MEDIAN of {REPEATS} timed repeats",
            dev,
        ),
        **dev,
        "kernel_grids": dict(engine.stats.grids_by_kernel),
        "scan_giveways": dict(engine.stats.scan_giveways),
        "flow": flow_info,
        "value": round(throughput),
        "unit": "orders/sec",
        "vs_baseline": round(throughput / 1_000_000, 3),
        "best_of_runs": round(mixed["best_throughput"]),
        "runs": [
            {
                "throughput": round(r["throughput"]),
                "consumer_cpu_orders_per_sec_per_core": round(
                    r["consumer_cpu_orders_per_sec_per_core"]
                ),
                "gateway_s": round(r["gateway_s"], 3),
                "consumer_s": round(r["consumer_s"], 3),
                "fetch_blocked_s": round(r["fetch_blocked_s"], 3),
                "rusage": r["rusage"],
            }
            for r in mixed["runs"]
        ],
        "cpu_per_frame_s": mixed.get("cpu_per_frame_s"),
    }
    analytic = _analytic_block("int32")
    if analytic is not None:
        # The drill's own compile trajectory: how many distinct dispatch
        # shape combos this flow minted (the perf ratchet gates the
        # scripted-drill equivalent).
        analytic["compiled_frame_combos"] = engine.batch.combo_count()
        result["analytic"] = analytic
    measured = _measured_block("int32")
    if measured is not None:
        result["measured"] = measured
    host = _host_block()
    if host is not None:
        result["host"] = host
    admit = _admit_block()
    if admit is not None:
        result["admit"] = admit
    print(json.dumps(result))
    print(
        f"# mixed vs clean: measured {mixed['throughput'] / 1e3:.0f}K vs "
        f"{clean['throughput'] / 1e3:.0f}K orders/sec | consumer CPU "
        f"{mixed['consumer_cpu_orders_per_sec_per_core'] / 1e6:.2f}M vs "
        f"{clean['consumer_cpu_orders_per_sec_per_core'] / 1e6:.2f}M "
        f"orders/sec/core",
        file=sys.stderr,
    )


def latency_main():
    """--latency: order->publish latency vs frame size, pipeline depth
    held constant (the throughput/latency trade-off curve; the reference
    is fully async and publishes no latency numbers — main.go:49 — so
    this sets the bar).

    Method: a closed-loop steady-state run per frame size — the gateway
    publishes a frame, then the consumer takes one step (with cross-frame
    pipelining, up to `depth` frames stay in flight), so frames complete
    while later ones are being produced, exactly like production.
    Completion times attribute FIFO (frames resolve in order). An order's
    latency = its frame's publish-completion time minus its synthetic
    arrival time: arrivals are spread uniformly over the frame's
    accumulation window at the run's own sustained rate (an order that
    arrives just after a frame closes waits a full accumulation window —
    the batching bridge's cost, which this measurement deliberately
    includes; SURVEY L4: who batches and at what latency cost).

    Prints one JSON line per frame size with throughput and
    p50/p99/p99.9 order->publish latency."""
    check = "--check" in sys.argv
    import jax

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    if check:
        jax.config.update("jax_platforms", "cpu")
    dev = _device_block(check)
    import jax.numpy as jnp

    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer

    from gome_tpu.utils.metrics import Registry
    from gome_tpu.utils.trace import TRACER, FlightRecorder

    N = int(os.environ.get("SVC_ORDERS", 8_192 if check else 1_048_576))
    S = int(os.environ.get("SVC_SYMBOLS", 64 if check else 10_240))
    CAP = int(os.environ.get("SVC_CAP", 32 if check else 256))
    PIPE = int(os.environ.get("SVC_PIPELINE", 2))
    sizes = (
        (512, 2048)
        if check
        else tuple(
            int(x)
            for x in os.environ.get(
                "SVC_LATENCY_FRAMES", "4096,32768,262144"
            ).split(",")
        )
    )
    symbols = [f"sym{i}" for i in range(S)]

    for frame_n in sizes:
        engine = MatchEngine(
            config=BookConfig(cap=CAP, max_fills=16, dtype=jnp.int32),
            n_slots=S,
            max_t=32,
            kernel="pallas",
        )
        bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
        consumer = OrderConsumer(
            engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
            pipeline_depth=PIPE,
        )
        flow = _MixedFlow(np.random.default_rng(11), S)
        make_frame = lambda: flow.frame(frame_n)
        _svc_warmup(engine, consumer, bus, make_frame, symbols)

        # Per-stage breakdown (ISSUE 2): arm the order-lifecycle tracer
        # for the TIMED region only (warmup excluded), with a private
        # registry so frame sizes don't pollute each other. The drive
        # publishes raw frames (no per-order ids), so what lands here are
        # the batch-scoped engine/consumer stages — pad_pack,
        # compile_hit/miss, device_execute, decode, publish — i.e. WHERE
        # the end-to-end latency goes.
        TRACER.install(FlightRecorder(keep_n=8), registry=Registry())

        n_frames = max(PIPE + 2, N // frame_n)
        frames = [make_frame() for _ in range(n_frames)]
        pub_t: list = []  # publish time per frame, FIFO
        done_t: list = []
        t0 = time.perf_counter()
        for cols in frames:
            pub_t.append(time.perf_counter())
            _svc_gateway_step(cols, symbols, engine.pre_pool, bus.order_queue)
            n = consumer.run_once()
            now = time.perf_counter()
            for _ in range(n // frame_n):
                done_t.append(now)
        while len(done_t) < n_frames:
            n = consumer.run_once()
            now = time.perf_counter()
            for _ in range(n // frame_n):
                done_t.append(now)
        elapsed = time.perf_counter() - t0
        total = n_frames * frame_n
        rate = total / elapsed

        # Per-order latency: arrivals uniform over each frame's
        # accumulation window (ending at its publish) at the sustained
        # rate; completion = the frame's resolve+publish time.
        offs = (np.arange(frame_n, dtype=np.float64)[::-1] + 1) / rate
        lat = np.concatenate(
            [d - (p - offs) for p, d in zip(pub_t, done_t)]
        )
        p50, p99, p999 = np.percentile(lat, [50, 99, 99.9])

        # Corrected (intended-start) percentiles, ISSUE 17: the legacy
        # numbers above anchor each order to its frame's ACTUAL publish —
        # if the pipeline stalls, publishes slip with it and the queueing
        # delay never reaches the percentiles (coordinated omission). The
        # corrected recorder charges every order from a FIXED open-loop
        # schedule at the run's sustained rate anchored at run start.
        from gome_tpu.obs.capacity import LogHistogram, OpenLoopSchedule

        sched = OpenLoopSchedule(rate, t0=t0)
        chist = LogHistogram(rel_err=0.01, min_value=1e-7, max_value=600.0)
        for f, d in enumerate(done_t):
            base = f * frame_n
            for v in (
                d - (t0 + (np.arange(frame_n) + base + 1) * sched.interval)
            ).tolist():
                chist.record(v if v > 0 else 0.0)
        cp50, cp99, cp999 = chist.percentiles((0.5, 0.99, 0.999))
        # Per-stage latency breakdown from the tracer's stage histograms:
        # the BENCH payload then records WHERE the end-to-end time goes
        # (batch-wait vs pack vs compile vs device vs decode vs publish),
        # not just that it went.
        stages = {
            stage: {
                "count": v["count"],
                "p50_us": round(v["p50"] * 1e6, 1),
                "p99_us": round(v["p99"] * 1e6, 1),
                "mean_us": round(v["mean"] * 1e6, 1),
            }
            for stage, v in sorted(TRACER.stage_summary().items())
        }
        TRACER.disable()
        print(
            json.dumps(
                {
                    "metric": _metric(
                        f"order->publish latency, {frame_n}-order frames, "
                        f"mixed stream, pipeline depth {PIPE}, {S} symbols",
                        dev,
                    ),
                    **dev,
                    "value": round(p99 * 1e3, 1),
                    "unit": "ms p99",
                    "throughput_orders_per_sec": round(rate),
                    "p50_ms": round(p50 * 1e3, 1),
                    "p99_ms": round(p99 * 1e3, 1),
                    "p999_ms": round(p999 * 1e3, 1),
                    "closed_loop": {
                        "p50_ms": round(p50 * 1e3, 1),
                        "p99_ms": round(p99 * 1e3, 1),
                        "p999_ms": round(p999 * 1e3, 1),
                        "method": "arrivals anchored to actual publishes",
                    },
                    "corrected": {
                        "p50_ms": round(cp50 * 1e3, 1),
                        "p99_ms": round(cp99 * 1e3, 1),
                        "p999_ms": round(cp999 * 1e3, 1),
                        "method": (
                            "open-loop intended schedule at sustained "
                            "rate (coordinated-omission-safe)"
                        ),
                        "histogram_rel_err": 0.01,
                    },
                    "stages": stages,
                }
            )
        )


def grpc_main():
    """--grpc: the measured gRPC front door — the real OrderGateway served
    over a real channel, driven by the pipelined doorder client (a
    separate process), with the FrameBatcher bridging requests into
    ORDER frames for the pipelined frame consumer (the production
    single-binary topology: client process | gateway+consumer process).

    This process holds the chip; the client child imports no JAX. The
    client, the gRPC server threads and the consumer share the host's
    cores. The reference's only ingest is this path (main.go:22-64); it
    publishes no numbers to compare against."""
    check = "--check" in sys.argv
    import subprocess

    import jax

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    if check:
        jax.config.update("jax_platforms", "cpu")
    dev = _device_block(check)
    import jax.numpy as jnp

    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.batcher import FrameBatcher
    from gome_tpu.service.consumer import OrderConsumer
    from gome_tpu.service.gateway import OrderGateway

    # MODE unary: one DoOrder RPC per order (the reference's only ingest
    # shape, main.go:39-52). MODE batch: the amortized DoOrderBatch RPC
    # with CLIENT_BATCH orders per request — the production front door.
    MODE = os.environ.get("SVC_GRPC_MODE", "batch")
    CLIENT_BATCH = int(os.environ.get("SVC_GRPC_CLIENT_BATCH", 1_024))
    default_n = 4_096 if check else (131_072 if MODE == "unary" else 1_048_576)
    N = int(os.environ.get("SVC_GRPC_ORDERS", default_n))
    S = int(os.environ.get("SVC_SYMBOLS", 64 if check else 1_024))
    CAP = int(os.environ.get("SVC_CAP", 64 if check else 256))
    PIPE = int(os.environ.get("SVC_PIPELINE", 2))
    BATCH = int(os.environ.get("SVC_GRPC_BATCH", 4_096))
    CONC = int(
        os.environ.get(
            "SVC_GRPC_CONCURRENCY", 128 if MODE == "unary" else 8
        )
    )

    engine = MatchEngine(
        config=BookConfig(cap=CAP, max_fills=16, dtype=jnp.int32),
        n_slots=S,
        max_t=32,
        kernel="pallas",
        # Full grids only: the batcher's deadline flushes emit arbitrary
        # partial-frame sizes, and letting each pick its own dense-grid
        # geometry compiles a fresh kernel per size class. At 1024 uniform
        # lanes the full [S, max_t] grid is one compiled family.
        dense=False,
    )
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=64, batch_wait_s=0.001, match_wire="frame",
        pipeline_depth=PIPE,
    )
    batcher = FrameBatcher(bus.order_queue, max_n=BATCH, max_wait_s=0.05)
    gateway = OrderGateway(
        bus, accuracy=8, mark=engine.mark, batcher=batcher
    )

    from concurrent import futures

    import grpc as _grpc

    from gome_tpu.api.service import add_order_servicer

    server = _grpc.server(futures.ThreadPoolExecutor(max_workers=8))
    add_order_servicer(server, gateway)
    port = server.add_insecure_port("127.0.0.1:0")
    assert port != 0
    server.start()

    def run_client(n, seed):
        out = subprocess.run(
            [
                sys.executable, "-m", "gome_tpu.clients.doorder",
                f"127.0.0.1:{port}", str(n), str(CONC), str(S),
                "0.995", "1.005", "4", str(seed),
                str(CLIENT_BATCH if MODE == "batch" else 0),
            ],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    # Warmup: compile every shape off the clock.
    consumer.start()
    run_client(min(N, 4 * BATCH) + 1, seed=1)
    deadline = time.monotonic() + 300
    while bus.order_queue.committed() < bus.order_queue.end_offset() or len(
        consumer._pipe or ()
    ):
        batcher.flush()
        time.sleep(0.02)
        assert time.monotonic() < deadline, "warmup drain stalled"

    # Timed: client start -> every order matched and published.
    ev_skip = bus.match_queue.end_offset()
    c0 = time.process_time()
    t0 = time.perf_counter()
    stats = run_client(N + 1, seed=2)
    batcher.flush()
    deadline = time.monotonic() + 600
    while bus.order_queue.committed() < bus.order_queue.end_offset() or len(
        consumer._pipe or ()
    ):
        batcher.flush()
        time.sleep(0.005)
        assert time.monotonic() < deadline, "timed drain stalled"
    elapsed = time.perf_counter() - t0
    server_cpu = time.process_time() - c0
    consumer.stop()
    server.stop(0)

    from gome_tpu.bus.colwire import decode_event_frame

    n_events = sum(
        len(decode_event_frame(m.body))
        for m in bus.match_queue.read_from(ev_skip, 1 << 30)
    )
    rate = N / elapsed
    client_mode = (
        "DoOrderBatch x" + str(CLIENT_BATCH) if MODE == "batch"
        else "unary DoOrder"
    )
    print(
        json.dumps(
            {
                "metric": _metric(
                    "gRPC-inclusive throughput: doorder client "
                    f"({client_mode}, "
                    f"concurrency {CONC}, separate process) -> real "
                    f"OrderGateway -> FrameBatcher({BATCH}) -> frame "
                    f"consumer -> matchOrder; {S} symbols "
                    "(client+server+consumer share the host)",
                    dev,
                ),
                **dev,
                "value": round(rate),
                "unit": "orders/sec",
                "vs_baseline": round(rate / 1_000_000, 3),
            }
        )
    )
    print(
        f"# client-side rate {stats['orders_per_s']:.0f}/s "
        f"(ok={stats['ok']} rejected={stats['rejected']}) | end-to-end "
        f"{rate:.0f}/s over {elapsed:.2f}s | events={n_events} | server "
        f"process CPU {server_cpu:.2f}s -> "
        f"{N / max(server_cpu, 1e-9) / 1e3:.0f}K orders/sec/core "
        "(gateway handlers + batcher + consumer combined)",
        file=sys.stderr,
    )


def _gateway_proc_main():
    """One gateway process for --grpc-scale: real gRPC server +
    OrderGateway + FrameBatcher publishing ORDER frames to its own file
    bus queue; pre-pool markers in the shared RESP server (the reference's
    gateway shape, main.go:22-52, horizontally replicated). Prints READY
    <port>, then waits for one stdin line and reports its process CPU."""
    busdir, resp_port, batch = sys.argv[2:5]
    from concurrent import futures

    import grpc as _grpc

    from gome_tpu.api.service import add_order_servicer
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.service.batcher import FrameBatcher
    from gome_tpu.service.gateway import OrderGateway

    bus = make_bus(BusConfig(backend="file", dir=busdir))
    pool = RespPrePool(RespClient(port=int(resp_port)))

    def mark(order):
        pool.add((order.symbol, order.uuid, order.oid))

    def unmark(order):
        pool.discard((order.symbol, order.uuid, order.oid))

    batcher = FrameBatcher(
        bus.order_queue, max_n=int(batch), max_wait_s=0.05
    )
    gateway = OrderGateway(
        bus, accuracy=8, mark=mark, unmark=unmark, batcher=batcher
    )
    server = _grpc.server(futures.ThreadPoolExecutor(max_workers=8))
    add_order_servicer(server, gateway)
    port = server.add_insecure_port("127.0.0.1:0")
    assert port != 0
    server.start()
    c0 = time.process_time()
    print(f"READY {port}", flush=True)
    sys.stdin.readline()  # parent signals: clients done
    batcher.flush()
    print(json.dumps({"cpu": time.process_time() - c0}), flush=True)
    batcher.close()
    server.stop(0)


def grpc_scale_main():
    """--grpc-scale: N gateway processes feeding ONE consumer (VERDICT r4
    #3's scaling table). Each gateway owns a gRPC port, a FrameBatcher,
    and a file-bus doOrder queue; a shared RESP server holds the pre-pool
    markers; each gateway gets its own batch-mode doorder client with a
    DISJOINT symbol namespace (per-symbol FIFO is then per-queue by
    construction). The consumer drains all N queues through one engine
    PINNED TO THE CPU BACKEND: ingest, not matching, is under test, and
    the output says so (platform "cpu", engine_pinned_to "cpu"). Which
    process owns the chip in this topology is A1/B4's to settle.

    The table reports per-gateway-CORE rates (process CPU) — the
    multiplicative claim — and the measured aggregate wall rate."""
    import shutil
    import subprocess
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")  # explicit pin, see above
    dev = dict(_device_block(check=True), engine_pinned_to="cpu")
    import jax.numpy as jnp

    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient

    check = "--check" in sys.argv
    N_PER_GW = int(os.environ.get("SVC_GRPC_ORDERS", 4_096 if check else 262_144))
    S = int(os.environ.get("SVC_SYMBOLS", 64 if check else 256))
    CLIENT_BATCH = int(os.environ.get("SVC_GRPC_CLIENT_BATCH", 1_024))
    BATCH = int(os.environ.get("SVC_GRPC_BATCH", 4_096))
    CONC = int(os.environ.get("SVC_GRPC_CONCURRENCY", 8))
    sizes = [
        int(x)
        for x in os.environ.get(
            "SVC_GRPC_GATEWAYS", "1,2" if check else "1,2,4"
        ).split(",")
    ]
    here = os.path.dirname(os.path.abspath(__file__))
    rows = []
    for n_gw in sizes:
        root = tempfile.mkdtemp(prefix="gome_gwscale_")
        srv = subprocess.Popen(
            [sys.executable, "-m", "gome_tpu.persist.respserver",
             "--port", "0"],
            stdout=subprocess.PIPE, text=True,
        )
        gws: list = []
        clients: list = []
        try:
            ready = srv.stdout.readline().split()
            assert ready and ready[0] == "READY", ready
            resp_port = int(ready[1])
            busdirs = [os.path.join(root, f"gw{i}", "bus") for i in range(n_gw)]
            gws[:] = [
                subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--gateway-proc", busdirs[i], str(resp_port),
                     str(BATCH)],
                    stdout=subprocess.PIPE, stdin=subprocess.PIPE,
                    text=True, cwd=here,
                )
                for i in range(n_gw)
            ]
            ports = []
            for p in gws:
                line = p.stdout.readline().split()
                assert line and line[0] == "READY", line
                ports.append(int(line[1]))

            # One pipelined batch client per gateway, disjoint symbols.
            t0 = time.perf_counter()
            clients[:] = [
                subprocess.Popen(
                    [sys.executable, "-m", "gome_tpu.clients.doorder",
                     f"127.0.0.1:{ports[i]}", str(N_PER_GW + 1),
                     str(CONC), str(S), "0.995", "1.005", "4", str(3 + i),
                     str(CLIENT_BATCH), f"g{i}."],
                    stdout=subprocess.PIPE, text=True, cwd=here,
                )
                for i in range(n_gw)
            ]
            stats = []
            for c in clients:
                out, _ = c.communicate(timeout=1200)
                assert c.returncode == 0
                stats.append(json.loads(out.strip().splitlines()[-1]))
            for s in stats:  # fail at the point of failure, not downstream
                assert s.get("aborted", 0) == 0, s
            t_clients = time.perf_counter() - t0
            cpus = []
            for p in gws:
                p.stdin.write("done\n")
                p.stdin.flush()
                cpus.append(json.loads(p.stdout.readline())["cpu"])
                p.wait(timeout=60)

            # Consumer: one engine drains every gateway's queue (frames
            # interleave across queues; symbols are disjoint per queue so
            # per-symbol FIFO holds).
            engine = MatchEngine(
                config=BookConfig(cap=64, max_fills=16, dtype=jnp.int32),
                n_slots=max(1024, S * n_gw), max_t=32, kernel="scan",
            )
            engine.pre_pool = RespPrePool(RespClient(port=resp_port))
            from gome_tpu.bus.colwire import decode_order_frame

            buses = [
                make_bus(BusConfig(backend="file", dir=d)) for d in busdirs
            ]
            c0 = time.process_time()
            t0 = time.perf_counter()
            n_done = 0
            for bus in buses:
                q = bus.order_queue
                off = q.committed()
                while True:
                    msgs = q.read_from(off, 64)
                    if not msgs:
                        break
                    for m in msgs:
                        cols = decode_order_frame(m.body)
                        engine.process_frame(cols, fast=True)
                        n_done += int(cols["n"])
                    off = msgs[-1].offset + 1
                    q.commit(off)
            t_consume = time.perf_counter() - t0
            consumer_cpu = time.process_time() - c0
            total = sum(s["sent"] for s in stats)
            assert n_done == total, (n_done, total)
            rows.append(
                dict(
                    gateways=n_gw,
                    orders=total,
                    aggregate_wall_orders_per_sec=total / t_clients,
                    per_gateway_core_orders_per_sec=[
                        round(s["sent"] / max(c, 1e-9))
                        for s, c in zip(stats, cpus)
                    ],
                    client_rates=[round(s["orders_per_s"]) for s in stats],
                    consumer_drain_orders_per_sec=round(
                        n_done / max(t_consume, 1e-9)
                    ),
                    consumer_cpu_orders_per_sec_per_core=round(
                        n_done / max(consumer_cpu, 1e-9)
                    ),
                )
            )
            print(f"# gateways={n_gw}: {json.dumps(rows[-1])}",
                  file=sys.stderr)
        finally:
            # Reap EVERYTHING: a client timeout or a failed assert must
            # not orphan gateway/client processes onto the bench core.
            for p in clients + gws:
                if p.poll() is None:
                    p.terminate()
            for p in clients + gws:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            srv.terminate()
            srv.wait(timeout=10)
            shutil.rmtree(root, ignore_errors=True)
    best = max(rows, key=lambda r: sum(r["per_gateway_core_orders_per_sec"]))
    print(
        json.dumps(
            {
                "metric": (
                    "HOST ingest scaling (consumer engine pinned to the "
                    "CPU backend, no device metric): N gateway processes "
                    f"(DoOrderBatch x{CLIENT_BATCH}, FrameBatcher "
                    f"{BATCH}) -> one consumer; "
                    "per-gateway-core rates are process-CPU based"
                ),
                **dev,
                "value": round(
                    sum(best["per_gateway_core_orders_per_sec"])
                ),
                "unit": "orders/sec (sum of per-gateway-core rates)",
                "rows": rows,
            }
        )
    )


def _shard_consumer_main():
    """One sharded consumer process (spawned by --service --shards N):
    drains its shard's doOrder file queue through a full MatchEngine with
    the pre-pool in the shared RESP marker server — the reference's
    consumer process shape. Self-times the post-warmup drain and reports
    one JSON line on stdout. The engine is PINNED TO THE CPU BACKEND: N
    processes cannot share one chip, and the report carries the platform
    it ran on."""
    import jax

    jax.config.update("jax_platforms", "cpu")  # explicit pin, see above
    import jax.numpy as jnp

    busdir, resp_port, warm_orders, cap, n_slots, pipe = sys.argv[2:8]
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine import frames as engine_frames
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.service.consumer import OrderConsumer

    bus = make_bus(BusConfig(backend="file", dir=busdir))
    engine = MatchEngine(
        config=BookConfig(cap=int(cap), max_fills=16, dtype=jnp.int32),
        n_slots=int(n_slots),
        max_t=32,
        kernel="scan",
    )
    engine.pre_pool = RespPrePool(RespClient(port=int(resp_port)))
    consumer = OrderConsumer(
        engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
        pipeline_depth=int(pipe),
    )
    # Warmup (compiles) off the clock — synchronously (depth 0), so no
    # timed frame can be pipelined in flight before the clock starts.
    consumer.pipeline_depth = 0
    done = 0
    while done < int(warm_orders):
        done += consumer.run_once()
    consumer.pipeline_depth = int(pipe)
    events0 = engine.stats.fills + engine.stats.cancels
    print("READY", flush=True)
    go = os.path.join(busdir, "..", "..", "go")
    deadline = time.monotonic() + 300
    while not os.path.exists(go):
        if time.monotonic() > deadline:
            print(json.dumps({"error": "go-file timeout"}), flush=True)
            sys.exit(1)
        time.sleep(0.005)
    engine_frames.FETCH_SECONDS = 0.0
    t0 = time.perf_counter()
    c0 = time.process_time()
    n = consumer.drain()
    t_consumer = time.perf_counter() - t0
    cpu = time.process_time() - c0
    print(
        json.dumps(
            dict(
                orders=n,
                t_consumer=t_consumer,
                cpu=cpu,
                fetch_s=engine_frames.FETCH_SECONDS,
                events=engine.stats.fills + engine.stats.cancels - events0,
                platform=jax.devices()[0].platform,
            )
        ),
        flush=True,
    )


def service_sharded_main(n_shards: int):
    """--service --shards N: the reference's full multi-process topology
    at scale — a shared RESP marker-server process, THIS process as the
    gateway (symbol-hash routing orders to per-shard doOrder file queues,
    marking the shared pre-pool, all timed), and N consumer processes
    each draining its shard through its own engine PINNED TO THE CPU
    BACKEND (one chip belongs to one process; which process owns which
    chip in this topology is A1/B4's to settle). Aggregate
    gateway->matchOrder throughput = N_orders / (gateway time + consumer
    wall time): a host number about the topology's correctness and
    per-shard cost, and the output says so."""
    import shutil
    import subprocess
    import tempfile

    check = "--check" in sys.argv
    from gome_tpu.engine.prepool import RespPrePool
    from gome_tpu.parallel.router import ShardRouter
    from gome_tpu.persist.resp import RespClient
    from gome_tpu.bus import make_bus
    from gome_tpu.config import BusConfig

    # Sharded defaults are smaller than the single-process bench: the N
    # consumers run CPU-backend engines, and CPU matching at the full
    # 10K-lane geometry would measure XLA:CPU, not the topology.
    N = int(os.environ.get("SVC_ORDERS", 8_192 if check else 262_144))
    FRAME = int(os.environ.get("SVC_FRAME", 2_048 if check else 32_768))
    S = int(os.environ.get("SVC_SYMBOLS", 64 if check else 2_048))
    CAP = int(os.environ.get("SVC_CAP", 32 if check else 64))
    PIPE = int(os.environ.get("SVC_PIPELINE", 2))
    FRAME = min(FRAME, N)
    N_WARM = 2

    root = tempfile.mkdtemp(prefix="gome_shard_bench_")
    procs: list = []
    srv = subprocess.Popen(
        [sys.executable, "-m", "gome_tpu.persist.respserver", "--port", "0"],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        ready = srv.stdout.readline().split()
        assert ready and ready[0] == "READY", ready
        resp_port = int(ready[1])

        router = ShardRouter(n_shards)
        symbols = [f"sym{i}" for i in range(S)]
        shard_of_sym = np.array(
            [router.route(s) for s in symbols], np.int64
        )
        busdirs = [os.path.join(root, f"shard{i}", "bus") for i in range(n_shards)]
        buses = [
            make_bus(BusConfig(backend="file", dir=d)) for d in busdirs
        ]
        pool = RespPrePool(RespClient(port=resp_port))

        rng = np.random.default_rng(7)
        oid0 = 1
        frames_cols = []
        for start in range(0, (N_WARM * n_shards) * FRAME + N, FRAME):
            n = min(FRAME, (N_WARM * n_shards) * FRAME + N - start)
            frames_cols.append(_svc_columns(rng, n, S, oid0))
            oid0 += n

        def gateway_step(cols):
            shards = shard_of_sym[cols["symbol_idx"]]
            for sh in range(n_shards):
                mask = shards == sh
                n_sh = int(mask.sum())
                if n_sh == 0:
                    continue
                sub = dict(
                    cols,
                    n=n_sh,
                    **{
                        k: np.ascontiguousarray(cols[k][mask])
                        for k in (
                            "action", "side", "kind", "price", "volume",
                            "symbol_idx", "uuid_idx", "oids",
                        )
                    },
                )
                _svc_gateway_step(
                    sub, symbols, pool, buses[sh].order_queue
                )

        n_warm_frames = N_WARM * n_shards
        warm_counts = [0] * n_shards
        for cols in frames_cols[:n_warm_frames]:
            shards = shard_of_sym[cols["symbol_idx"]]
            for sh in range(n_shards):
                warm_counts[sh] += int((shards == sh).sum())
            gateway_step(cols)

        # Publish the timed frames BEFORE starting consumers, timing the
        # gateway work by itself (on one core, concurrent phases would
        # just interleave; the reference runs these as separate hosts).
        t0 = time.perf_counter()
        for cols in frames_cols[n_warm_frames:]:
            gateway_step(cols)
        t_gateway = time.perf_counter() - t0

        procs[:] = [
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--service-consumer", busdirs[i], str(resp_port),
                    str(warm_counts[i]), str(CAP), str(S), str(PIPE),
                ],
                stdout=subprocess.PIPE, text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            for i in range(n_shards)
        ]
        for p in procs:
            line = p.stdout.readline().strip()
            assert line == "READY", line
        t0 = time.perf_counter()
        with open(os.path.join(root, "go"), "w"):
            pass
        reports = []
        for p in procs:
            reports.append(json.loads(p.stdout.readline()))
            p.wait(timeout=600)
        t_wall = time.perf_counter() - t0

        n_done = sum(r["orders"] for r in reports)
        fetch_s = sum(r["fetch_s"] for r in reports)
        elapsed = t_gateway + t_wall
        throughput = n_done / elapsed
        result = {
            "metric": (
                "HOST topology run (consumer engines pinned to the CPU "
                "backend, no device metric): sharded service throughput "
                f"gateway->matchOrder, {n_shards} consumer processes + "
                f"RESP marker server + gateway (symbol-hash routed file "
                f"buses), {S} symbols, {FRAME}-order frames"
            ),
            "platform": reports[0]["platform"],
            "device_kind": None,
            "engine_pinned_to": "cpu",
            "value": round(throughput),
            "unit": "orders/sec",
            "vs_baseline": round(throughput / 1_000_000, 3),
        }
        print(json.dumps(result))
        per_shard = ", ".join(
            f"s{i}: {r['orders']}"
            f"@{r['orders'] / max(r['t_consumer'], 1e-9) / 1e3:.0f}K/s"
            f" (cpu {r['orders'] / max(r.get('cpu', 0), 1e-9) / 1e3:.0f}K/s/core)"
            for i, r in enumerate(reports)
        )
        # What M dedicated cores would deliver: each shard's measured CPU
        # cost, summed — the scaling claim grounded in this run's numbers.
        agg_cpu = sum(
            r["orders"] / max(r.get("cpu", 0), 1e-9) for r in reports
        )
        print(
            f"# orders={n_done} gateway={t_gateway:.3f}s consumers_wall="
            f"{t_wall:.3f}s fetch_blocked_sum={fetch_s:.3f}s | "
            f"aggregate-ex-fetch "
            f"{n_done / max(elapsed - fetch_s, 1e-9) / 1e6:.2f}M | "
            f"aggregate-at-{n_shards}-dedicated-cores "
            f"{agg_cpu / 1e6:.2f}M orders/sec | {per_shard}",
            file=sys.stderr,
        )
    finally:
        # Never orphan a consumer: a failure before the `go` file exists
        # would leave the others busy-polling forever.
        for p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=10)
                except Exception:
                    pass
        srv.terminate()
        srv.wait(timeout=10)
        shutil.rmtree(root, ignore_errors=True)


def main():
    if "--service-consumer" in sys.argv:
        return _shard_consumer_main()
    if "--gateway-proc" in sys.argv:
        return _gateway_proc_main()
    if "--admit" in sys.argv:
        return admit_main()
    if "--latency" in sys.argv:
        return latency_main()
    if "--grpc-scale" in sys.argv:
        return grpc_scale_main()
    if "--grpc" in sys.argv:
        return grpc_main()
    if "--service" in sys.argv:
        if "--shards" in sys.argv:
            n = int(sys.argv[sys.argv.index("--shards") + 1])
            return service_sharded_main(n)
        return service_main()
    check = "--check" in sys.argv
    DTYPE = os.environ.get("BENCH_DTYPE", "int32")  # int64 | int32
    import jax

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()

    # x64 only when the book dtype needs it: with x64 on, every jnp.arange /
    # Python-int literal inside the kernel promotes to int64, which Mosaic
    # (Pallas TPU) rejects and which doubles index-array traffic.
    if DTYPE == "int64":
        jax.config.update("jax_enable_x64", True)
    if check:
        jax.config.update("jax_platforms", "cpu")
    dev = _device_block(check)

    import jax.numpy as jnp

    from gome_tpu.engine import BookConfig, batch_step, init_books
    from gome_tpu.engine.book import DeviceOp

    CFG = os.environ.get("BENCH_CONFIG", "")  # "", or "1".."5"
    # Each BASELINE config has a natural symbol count: sizing the lane axis
    # to the live symbols keeps the measurement about the flow shape, not
    # about dispatching a mostly-NOP grid (overridable via BENCH_SYMBOLS).
    cfg_symbols = {"1": 8, "2": 8, "3": 128}
    default_s = 64 if check else cfg_symbols.get(CFG, 10240)
    S = int(os.environ.get("BENCH_SYMBOLS", default_s))
    T = int(os.environ.get("BENCH_T", 4 if check else 16))
    # Single-symbol configs need a longer timeline for a meaningful
    # measurement: their dense rounds re-pack the one live lane 1024 deep,
    # so 48 grids would collapse into a single dispatch.
    cfg_grids = {"1": 1280, "2": 1280, "3": 480}
    default_g = 2 if check else int(cfg_grids.get(CFG, 48))
    G = int(os.environ.get("BENCH_GRIDS", default_g))
    # Per-op cost on the scan path is O(cap); a single-symbol book in the
    # config-1 crossing flow is a few levels deep, so the 256-slot default
    # (sized for 10K-symbol exchange load) would pay 4x the vector work for
    # nothing on the latency configs.
    # Config 3's Poisson flow random-walks ~350 levels deep over its 480-
    # grid timeline: cap=512 runs it overflow-free (256 drops ~130K rests).
    cfg_cap = {"1": 64, "2": 256, "3": 512}
    default_cap = 32 if check else int(cfg_cap.get(CFG, 256))
    CAP = int(os.environ.get("BENCH_CAP", default_cap))
    # Default = the high-throughput configuration: VMEM-resident Pallas
    # kernel on int32 ticks. BENCH_DTYPE=int64 selects the exact-envelope
    # configuration (accuracy=8 with unbounded depth sums), which runs on
    # the scan path (Mosaic has no 64-bit lowering).
    default_kernel = "pallas" if DTYPE == "int32" else "scan"
    KERNEL = os.environ.get("BENCH_KERNEL", default_kernel)  # scan | pallas
    config = BookConfig(
        cap=CAP,
        max_fills=16,
        dtype=jnp.int32 if DTYPE == "int32" else jnp.int64,
    )

    if KERNEL == "pallas":
        from gome_tpu.ops import (
            default_block_s,
            pallas_available,
            pallas_batch_step,
        )

        interp = not pallas_available(config.dtype)
        if interp:  # interpret mode (CPU check) has no blocking constraint
            default_block = next(b for b in (128, 8, 1) if S % b == 0)
        else:
            default_block = default_block_s(S, CAP)
            if default_block is None:
                print(
                    f"# NOTE: S={S} has no valid compiled-kernel blocking; "
                    "falling back to the scan kernel",
                    file=sys.stderr,
                )
        block_s = (
            int(os.environ["BENCH_BLOCK_S"])
            if "BENCH_BLOCK_S" in os.environ
            else default_block
        )
    if KERNEL == "pallas" and block_s is None:
        KERNEL = "scan"
    if KERNEL == "pallas":
        stepper = jax.jit(
            lambda books, ops: pallas_batch_step(
                config, books, ops, block_s=block_s, interpret=interp
            ),
            donate_argnums=(0,),
        )
    else:
        stepper = jax.jit(
            lambda books, ops: batch_step(config, books, ops),
            donate_argnums=(0,),
        )

    # Per-grid device-side reduction of the outputs the host actually
    # watches during a bench: fills and overflow count. Per-grid sums fit
    # int32 comfortably (S*T*K < 2^31); the cross-grid total is accumulated
    # host-side in Python ints after ONE stacked fetch, so no wrap is
    # possible at any run length even with x64 off.
    fold = jax.jit(
        lambda o: jnp.stack([jnp.sum(o.n_fills), jnp.sum(o.book_overflow)])
    )
    add = jax.jit(lambda a, b: a + b)
    # Device accumulators are int32 when x64 is off; flush to host Python
    # ints often enough that the on-device partial stays under 2^31 for ANY
    # grid geometry (per-grid fills <= S*T*max_fills).
    per_grid_max = S * T * config.max_fills
    FLUSH_EVERY = max(1, min(256, (2**31 - 1) // max(per_grid_max, 1)))

    books = init_books(config, S)
    np_dtype = np.int32 if DTYPE == "int32" else np.int64
    if CFG:
        raw = build_config_grids(int(CFG), S, T, G + 2, dtype=np_dtype)
        # warmup consumes 2 grids; count only the timed ones
        timed_orders = sum(int((d["action"] != 0).sum()) for d in raw[2:])
    else:
        raw = build_grids(S, T, G + 2, dtype=np_dtype)
        timed_orders = S * T * G
    if DTYPE == "int32":
        # int32 mode uses coarser lot units so per-side depth totals stay
        # far from 2^31 (the documented int32-mode operating contract).
        for d in raw:
            d["volume"] = (d["volume"] // 1_000_000).astype(np_dtype)
    # Compiled-kernel parity gate: three compiled-lowering crashes were
    # already found by fuzzing (the lowering is the risk surface), so every
    # TPU pallas bench certifies compiled == scan BEFORE timing and refuses
    # to report on mismatch. BENCH_PARITY=0 skips (e.g. repeated runs in
    # one session). CPU/interpret runs skip automatically.
    if (
        KERNEL == "pallas"
        and not check
        and os.environ.get("BENCH_PARITY", "1") != "0"
        and jax.default_backend() == "tpu"
        and pallas_available(config.dtype)  # the compiled kernel IS timed
    ):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
        from tpu_parity_check import run_suite

        rc = run_suite(
            S=128, T=8, CAP=CAP, K=config.max_fills, G=2,
            log=lambda m: print(f"# parity: {m}", file=sys.stderr),
        )
        if rc != 0:
            print(
                "# FATAL: compiled pallas kernel diverges from the scan "
                "path — refusing to report bench numbers",
                file=sys.stderr,
            )
            sys.exit(1)

    # Dense-round path for the sparse/latency-bound config shapes: 1-2
    # (single live lane — deep time axis amortizes dispatch), 3 (100-lane
    # Poisson — merging each lane's timeline into depth-64 rounds cuts the
    # dispatch count ~6x vs 70%-occupied [128, 16] full grids, which were
    # dispatch-bound), and 4 (Zipf — device work must track APPLIED ops,
    # not the 10K provisioned lanes). Same packing strategy as the
    # engine's dense path; BENCH_DENSE=0 forces the historical full-grid
    # measurement.
    if CFG in ("1", "2", "3", "4") and os.environ.get("BENCH_DENSE", "1") != "0":
        from gome_tpu.engine.batch import dense_batch_step, dense_kernel_step
        from gome_tpu.ops import default_block_s, pallas_available

        # Global depth ceiling; the packer additionally scales each round's
        # depth to the kernel's VMEM budget for its block size.
        t_dense = int(os.environ.get("BENCH_DENSE_T", 1024))
        # Cap-class depth bound over warmup AND timed ops: the timed chain
        # replays from post-warmup books, so a lane's resting depth is
        # bounded by its op total across both phases, not the timed phase
        # alone.
        full_bound = sum((d["action"] != 0).sum(axis=1) for d in raw)
        warm_rounds, warm_caps = pack_dense_rounds(
            raw[:2], t_dense, S, CAP, depth_bound=full_bound
        )
        timed_rounds, timed_caps = pack_dense_rounds(
            raw[2:], t_dense, S, CAP, depth_bound=full_bound
        )
        use_kernel = KERNEL == "pallas" and pallas_available(config.dtype)

        def chain_fn(rounds, round_caps):
            """One jitted program running a whole round chain: the entire
            timeline is ONE device dispatch — the unrolled trace chains every
            round's gather -> kernel -> scatter (or full-grid step)
            back-to-back on device. Each round runs at ITS cap class (the
            dense steps slice the shared storage; engine.batch)."""
            import dataclasses

            from gome_tpu.engine.batch import full_kernel_step

            cfgs = [
                config if c == CAP else dataclasses.replace(config, cap=c)
                for c in round_caps
            ]
            blocks = [
                default_block_s(S if ids is None else len(ids), cfg.cap)
                if use_kernel
                else None
                for (ids, _), cfg in zip(rounds, cfgs)
            ]

            def chain(books, rounds):
                acc = None
                for (ids, ops), bs, cfg in zip(rounds, blocks, cfgs):
                    if ids is None:  # full-grid round (no gather/scatter)
                        if bs is not None:
                            books, outs = full_kernel_step(
                                cfg, books, DeviceOp(**ops), bs
                            )
                        else:
                            books, outs = batch_step(
                                cfg, books, DeviceOp(**ops)
                            )
                    elif bs is not None:
                        books, outs = dense_kernel_step(
                            cfg, books, jnp.asarray(ids),
                            DeviceOp(**ops), bs,
                        )
                    else:
                        books, outs = dense_batch_step(
                            cfg, books, jnp.asarray(ids), DeviceOp(**ops)
                        )
                    f = jnp.stack(
                        [jnp.sum(outs.n_fills), jnp.sum(outs.book_overflow)]
                    )
                    acc = f if acc is None else acc + f
                return books, acc

            # NOT donated: every rep replays the identical timeline from
            # the same post-warmup books0, so the input stack must survive
            # the call. XLA inserts exactly one protective copy inside the
            # compiled chain — cheaper than the 7 per-leaf host
            # dispatches of an eager reset.
            return jax.jit(chain)

        warm_chain = chain_fn(warm_rounds, warm_caps)
        timed_chain = chain_fn(timed_rounds, timed_caps)
        stage = os.environ.get("BENCH_STAGED", "1") != "0"
        if stage:
            warm_rounds = jax.device_put(warm_rounds)
            timed_rounds = jax.device_put(timed_rounds)
            jax.block_until_ready(timed_rounds)

        books = init_books(config, S)
        books0, acc = warm_chain(books, warm_rounds)  # steady-state books
        int(acc[0])
        # Untimed pass: compile the timed chain.
        _, acc = timed_chain(books0, timed_rounds)
        int(acc[0])

        # The timed region ends with ONE scalar fetch, a fixed cost beside
        # the device work of a single chain at these config sizes. Chain
        # the whole timeline CHAIN_REPS times back-to-back (async
        # dispatches pipeline) so the fetch amortizes. Each rep REPLAYS
        # the identical timeline from the same post-warmup books (an
        # async device-side copy, no host sync):
        # carrying books across reps deepened the Zipf hot lanes without
        # bound — ~108K silently dropped rests per r4-style run at
        # cap=256 — so the replay is both the honest measurement and the
        # overflow-free one.
        chain_reps = int(
            os.environ.get(
                "BENCH_CHAIN_REPS", max(1, 1_000_000 // max(timed_orders, 1))
            )
        )
        REPEATS = int(os.environ.get("BENCH_REPEATS", 3))
        elapsed = float("inf")
        overflows = 0
        for _ in range(max(1, REPEATS)):
            int(jnp.sum(books0.count))  # barrier: state settled off-clock
            acc = None
            t0 = time.perf_counter()
            for _ in range(chain_reps):
                _, a = timed_chain(books0, timed_rounds)
                acc = a if acc is None else add(acc, a)
            totals = np.asarray(jax.device_get(acc), np.int64)
            pass_elapsed = time.perf_counter() - t0
            if pass_elapsed < elapsed:
                elapsed = pass_elapsed
                overflows = int(totals[1])
        if overflows:
            print(
                f"# WARNING: {overflows} book overflows at cap={CAP} — "
                "raise BENCH_CAP for an honest run",
                file=sys.stderr,
            )
        throughput = timed_orders * chain_reps / elapsed
        result = {
            "metric": _metric(
                f"device matching throughput, config {CFG}, dense "
                f"rounds over live lanes (t_dense={t_dense}), "
                f"cap={CAP}, {DTYPE} ticks",
                dev,
            ),
            **dev,
            "kernel": "pallas" if use_kernel else "scan",
            "value": round(throughput),
            "unit": "orders/sec",
            "vs_baseline": round(throughput / 1_000_000, 3),
        }
        analytic = _analytic_block(DTYPE)
        if analytic is not None:
            analytic["compile_count"] = _jit_cache_sizes(
                chain=timed_chain
            ).get("chain")
            result["analytic"] = analytic
        measured = _measured_block(DTYPE)
        if measured is not None:
            result["measured"] = measured
        print(json.dumps(result))
        if os.environ.get("BENCH_VERBOSE"):
            shapes = [
                tuple(ops["action"].shape) for _, ops in timed_rounds
            ]
            print(
                f"# elapsed={elapsed:.3f}s applied={timed_orders} "
                f"x{chain_reps} reps, rounds={len(timed_rounds)} "
                f"shapes={shapes[:8]}... caps={timed_caps[:8]}... "
                f"platform={jax.devices()[0].platform}",
                file=sys.stderr,
            )
        return

    grids = [DeviceOp(**g) for g in raw]

    # Stage all grids on device before timing (BENCH_STAGED=0 to include
    # host->device transfer in the loop).
    if os.environ.get("BENCH_STAGED", "1") != "0":
        grids = [jax.device_put(g) for g in grids]
        jax.block_until_ready(grids)

    # Warmup: compile + 2 grids (also fills books to steady state, and warms
    # every graph the timed loop uses — nothing compiles inside the timing).
    # The scalar int() fetch is a data-dependent completion barrier.
    books, outs = stepper(books, grids[0])
    acc = fold(outs)
    books, outs = stepper(books, grids[1])
    acc = add(acc, fold(outs))
    int(acc[0])

    # Repeat the timed chain and report the best pass: a single pass can
    # absorb external noise on a shared host. Each repeat
    # restarts from the same post-warmup book state (the donated chain
    # would otherwise keep deepening the books across repeats).
    REPEATS = int(os.environ.get("BENCH_REPEATS", 3))
    books0 = jax.tree.map(jnp.copy, books)
    int(jnp.sum(books0.count))  # materialize the pristine copy off the clock
    elapsed = float("inf")
    total_fills = overflows = 0
    for _ in range(max(1, REPEATS)):
        books = jax.tree.map(jnp.copy, books0)
        int(jnp.sum(books.count))  # barrier: copy completes off the clock
        totals = np.zeros(2, np.int64)
        acc = None
        t0 = time.perf_counter()
        for i, grid in enumerate(grids[2:]):
            books, outs = stepper(books, grid)
            acc = fold(outs) if acc is None else add(acc, fold(outs))
            if (i + 1) % FLUSH_EVERY == 0:
                totals += np.asarray(jax.device_get(acc), np.int64)
                acc = None
        if acc is not None:
            # Final data-dependent fetch = the completion barrier.
            totals += np.asarray(jax.device_get(acc), np.int64)
        pass_elapsed = time.perf_counter() - t0
        if pass_elapsed < elapsed:
            elapsed = pass_elapsed
            total_fills = int(totals[0])
            # Passes replay identical grids from identical state; report
            # one pass's overflow count, not the sum over repeats.
            overflows = int(totals[1])

    if overflows:
        # A production engine escalates cap and replays (BatchEngine);
        # the bench must instead be configured so the budget never trips.
        print(
            f"# WARNING: {overflows} book overflows at cap={CAP} — raise "
            "BENCH_CAP for an honest run",
            file=sys.stderr,
        )
    orders = timed_orders
    throughput = orders / elapsed
    cfg_tag = f", config {CFG}" if CFG else ""
    result = {
        "metric": _metric(
            f"device matching throughput, {S} symbols x {T}-deep "
            f"grids, cap={CAP}, {DTYPE} ticks, {KERNEL} kernel{cfg_tag}",
            dev,
        ),
        **dev,
        "kernel": KERNEL + ("-interpret" if KERNEL == "pallas" and interp else ""),
        "value": round(throughput),
        "unit": "orders/sec",
        "vs_baseline": round(throughput / 1_000_000, 3),
    }
    analytic = _analytic_block(DTYPE)
    if analytic is not None:
        analytic["compile_count"] = _jit_cache_sizes(
            stepper=stepper
        ).get("stepper")
        result["analytic"] = analytic
    measured = _measured_block(DTYPE)
    if measured is not None:
        result["measured"] = measured
    print(json.dumps(result))
    if os.environ.get("BENCH_VERBOSE"):
        print(
            f"# elapsed={elapsed:.3f}s orders={orders} "
            f"fills={total_fills} platform="
            f"{jax.devices()[0].platform}",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
