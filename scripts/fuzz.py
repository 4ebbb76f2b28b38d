"""Differential fuzz: device engine vs the pure-Python oracle on randomized
streams under adversarial engine geometries (tiny caps -> constant cap
escalation, tiny max_fills -> record escalations, max_t=1 -> per-op grids,
lane growth, int32 rebasing at extreme price bases, and the three ways to
run a batch through the one frame packer: the list form (exact), the
encoded frame on the compacted fast path, and ORDER frames through
MatchEngine admission + the cross-frame device pipeline).

    python scripts/fuzz.py [n_cases] [seed0] [--tpu] [--stp]

--stp runs every case under the venue rule engine.self_trade
"expire_taker", engine and oracle alike (the flow has 3 users, so it
self-crosses without steering).

Prints one line per case; exits nonzero on the first divergence with a
reproducer description. Runs on CPU by default — the fuzz target is
SEMANTICS, and every randomized geometry is a fresh compile on the chip;
pass --tpu to fuzz the compiled lowering anyway.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def configure(tpu: bool = False) -> None:
    """Set the jax global config the fuzz cases need. Called from main() —
    NOT at import time, so importing this module (the CI slice in
    tests/test_fuzz.py does) never mutates process-global jax state; the
    test harness's conftest owns that configuration there.

    Enable x64 up front in BOTH modes: int64 cases would flip it mid-process
    (engine.book.ensure_dtype_usable), and flipping jax_enable_x64 between
    traced cases can send jax's dtype-promotion cache into infinite
    recursion on a later pallas retrace (observed on TPU). Caveat: int32
    SCAN-path cases therefore fuzz under x64-on promotion, whereas the
    production bench runs x64 off — the compiled-kernel trace is x64-immune
    (pallas_match pins the flag off), and bench.py itself covers the x64-off
    scan configuration."""
    import jax

    if not tpu:
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)


def _expired(stats) -> tuple:
    return (
        stats.expired_ioc, stats.fok_killed, stats.post_only_blocked,
        stats.stp_expired,
    )


def run_case(seed: int, self_trade: str = "none") -> str:
    """One case. `self_trade` is the venue's rule (types.SELF_TRADE_RULES)
    for the engine and the oracle alike: the flow draws its owners from 3
    users, so it self-crosses without steering. It is an argument and not
    a draw, so that a seed's flow is the same under both rules."""
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine, BookConfig
    from gome_tpu.oracle import OracleEngine
    from gome_tpu.types import Action, Order, OrderType, Side

    rng = np.random.default_rng(seed)
    cap = int(rng.choice([4, 8, 16, 64]))
    max_fills = int(rng.choice([1, 2, 4, 8]))
    max_t = int(rng.choice([1, 3, 16]))
    n_slots = int(rng.choice([1, 2, 8, 16]))
    dtype = jnp.int32 if rng.random() < 0.5 else jnp.int64
    # list: Order lists through the exact path (process); fast: the
    # encoded frame through apply_frame_fast (device-side compaction,
    # exact fallback); frame: ORDER frames through MatchEngine admission +
    # the cross-frame device pipeline (random depth) — the native host
    # ops' differential target.
    mode = str(rng.choice(["list", "fast", "frame"]))
    n_symbols = int(rng.choice([1, 3, 7]))
    base_price = int(
        rng.choice(
            [100, 10_000_000,
             10_000_000_000_000 if dtype == jnp.int32 else 100_000]
        )
    )
    band = int(rng.choice([3, 50, 5_000]))
    n_orders = int(rng.choice([50, 200]))
    market_p = float(rng.choice([0.0, 0.15]))
    # Share of the other adds that carry a time in force (IOC, FOK and
    # POST_ONLY in equal parts); the rest are limit adds.
    tif_p = float(rng.choice([0.0, 0.45]))
    cancel_p = float(rng.choice([0.0, 0.3]))
    chunk = int(rng.choice([1, 17, 64]))

    orders = []
    # (symbol, oid, side, price) of prior limit ADDs: cancels need the exact
    # resting side+price to hit (SURVEY §2.3.2); most cancels target those,
    # a minority deliberately miss (wrong price) to cover the not-found path.
    live: list[tuple[str, str, Side, int]] = []
    for i in range(n_orders):
        sym = f"s{int(rng.integers(n_symbols))}"
        if live and rng.random() < cancel_p:
            sym_o, oid, side_o, price_o = live[int(rng.integers(len(live)))]
            if rng.random() < 0.25:  # deliberate miss
                price_o = price_o + int(rng.integers(1, band + 2))
            orders.append(
                Order(uuid="u", oid=oid, symbol=sym_o, side=side_o,
                      price=price_o, volume=0, action=Action.DEL)
            )
            continue
        kind = OrderType.MARKET if rng.random() < market_p else OrderType.LIMIT
        if kind is OrderType.LIMIT and rng.random() < tif_p:
            kind = (OrderType.IOC, OrderType.FOK, OrderType.POST_ONLY)[
                int(rng.integers(3))
            ]
        side = Side(int(rng.integers(2)))
        price = (
            0 if (kind is OrderType.MARKET and rng.random() < 0.5)
            else base_price + int(rng.integers(-band, band + 1))
        )
        if kind in (OrderType.IOC, OrderType.FOK) and rng.random() < 0.1:
            # A taker's limit far outside the lane's price envelope (it
            # feeds none): beyond what a rebased int32 price can hold.
            price = max(base_price + int(rng.choice([-1, 1])) * (1 << 33), 1)
        orders.append(
            Order(uuid=f"u{int(rng.integers(3))}", oid=str(i), symbol=sym,
                  side=side, price=price, volume=int(rng.integers(1, 30)),
                  order_type=kind)
        )
        if kind is not OrderType.MARKET:
            # cancel targets; one aimed at an IOC or FOK add always misses
            live.append((sym, str(i), side, price))

    oracle = OracleEngine(self_trade=self_trade)
    expected = []
    for o in orders:
        expected.extend(oracle.process(o))

    # GOME_FUZZ_KERNEL=pallas (with --tpu) fuzzes the COMPILED kernel inside
    # the full engine: escalation replays, rebasing, growth — each geometry
    # is a fresh Mosaic compile, so keep case counts small on TPU. The
    # engine falls back to scan when the compiled kernel cannot run (int64,
    # unblockable lane counts); the effective path is printed per case so a
    # green run cannot masquerade as compiled-kernel coverage.
    kernel = os.environ.get("GOME_FUZZ_KERNEL", "scan")
    if kernel not in ("scan", "pallas"):
        raise ValueError(f"GOME_FUZZ_KERNEL must be scan|pallas, got {kernel!r}")
    depth = 0
    if mode == "frame":
        from gome_tpu.bus.colwire import decode_order_frame, encode_orders
        from gome_tpu.engine.orchestrator import MatchEngine
        from gome_tpu.engine.pipeline import FramePipeline

        depth = int(rng.choice([1, 2, 3]))
        meng = MatchEngine(
            config=BookConfig(cap=cap, max_fills=max_fills, dtype=dtype,
                              self_trade=self_trade),
            n_slots=n_slots, max_t=max_t, kernel=kernel,
        )
        engine = meng.batch
        for o in orders:
            meng.mark(o)
        pipe = FramePipeline(meng, depth=depth)
        got = []
        for i in range(0, len(orders), chunk):
            cols = decode_order_frame(encode_orders(orders[i : i + chunk]))
            for _tok, batch in pipe.feed(cols):
                got.extend(batch.to_results())
        for _tok, batch in pipe.flush():
            got.extend(batch.to_results())
    else:
        engine = BatchEngine(
            BookConfig(cap=cap, max_fills=max_fills, dtype=dtype,
                       self_trade=self_trade),
            n_slots=n_slots, max_t=max_t, kernel=kernel,
        )
        got = []
        for i in range(0, len(orders), chunk):
            part = orders[i : i + chunk]
            got.extend(_run_part(engine, part, mode))
    from gome_tpu.ops import default_block_s, pallas_available

    effective = (
        "pallas"
        if kernel == "pallas"
        and pallas_available(dtype)
        and default_block_s(engine.n_slots) is not None
        else "scan"
    )
    desc = (
        f"seed={seed} cap={cap} K={max_fills} max_t={max_t} slots={n_slots} "
        f"dtype={np.dtype(dtype).name} mode={mode}"
        f"{f'(depth={depth})' if depth else ''} "
        f"kernel={effective} base={base_price} band={band} n={n_orders} "
        f"chunk={chunk} tif={tif_p} self_trade={self_trade}"
    )
    if got != expected:
        first = next(
            (j for j, (a, b) in enumerate(zip(got, expected)) if a != b),
            min(len(got), len(expected)),
        )
        raise AssertionError(
            f"DIVERGENCE [{desc}] events {len(got)} vs {len(expected)}, "
            f"first mismatch at {first}:\n got: "
            f"{got[first] if first < len(got) else '<none>'}\n exp: "
            f"{expected[first] if first < len(expected) else '<none>'}"
        )
    engine.verify_books()
    expired = _expired(engine.stats)
    want = _expired(oracle.stats)
    if expired != want:
        raise AssertionError(
            f"DIVERGENCE [{desc}] expired (IOC, FOK, POST_ONLY, STP) "
            f"{expired} vs the oracle's {want}"
        )
    return (
        f"OK [{desc}] events={len(got)} esc="
        f"{engine.stats.cap_escalations}"
        f"/{engine.stats.fill_record_escalations}"
        f" expired={'/'.join(map(str, expired))}"
    )


def _run_part(engine, part, mode: str) -> list:
    """One chunk through a BatchEngine: "list" is the exact list form,
    "fast" the same orders as an encoded frame on the compacted path —
    two executions of one packer, both held to the oracle."""
    if mode == "list":
        return engine.process(part)
    from gome_tpu.bus.colwire import decode_order_frame, encode_orders
    from gome_tpu.engine.frames import apply_frame_fast

    cols = decode_order_frame(encode_orders(part))
    return apply_frame_fast(engine, cols).to_results()


def run_sim_case(seed: int) -> str:
    """Oracle parity on SIM-generated flow (gome_tpu.sim): a seeded
    Hawkes/Zipf stream — clustered arrivals, Zipf-hot lanes, book-coupled
    placement, and cancels targeting really-resting (oid, price) pairs —
    exercises resting-queue depths and cancel patterns the uniform
    stream above never reaches. The grid is linearized in (t, lane)
    order (per-lane order preserved; lanes are independent) and fed to
    both the oracle and a randomized adversarial engine geometry."""
    import jax
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine, BookConfig
    from gome_tpu.oracle import OracleEngine
    from gome_tpu.sim.env import EnvConfig, env_reset
    from gome_tpu.sim.flow import FlowConfig
    from gome_tpu.sim.replay import _record_step, orders_from_grid

    rng = np.random.default_rng(seed)
    flow = FlowConfig(
        n_lanes=int(rng.choice([2, 4, 7])),
        t_bins=int(rng.choice([32, 64])),
        # Hotter-than-default excitation drives deeper bursts.
        excite_self=float(rng.choice([0.25, 0.45])),
        cancel_rate=float(rng.choice([0.8, 1.4, 2.0])),
        market_rate=float(rng.choice([0.2, 0.8])),
        offset_p=float(rng.choice([0.2, 0.5])),
        vol_max=int(rng.choice([5, 60])),
    )
    # Generation-side geometry is generous (cap 64) so the stream's
    # cancel targets come from a faithfully evolved book; the engine
    # under test gets an ADVERSARIAL geometry below.
    gen_cfg = EnvConfig(
        flow=flow, book=BookConfig(cap=64, max_fills=8, dtype=jnp.int32)
    )
    n_grids = int(rng.choice([8, 20]))
    state, _ = env_reset(gen_cfg, jax.random.PRNGKey(seed))
    orders = []
    for _ in range(n_grids):
        state, bg_ops, _info = _record_step(gen_cfg, state)
        orders.extend(orders_from_grid(jax.device_get(bg_ops)._asdict()))

    oracle = OracleEngine()
    expected = []
    for o in orders:
        expected.extend(oracle.process(o))

    cap = int(rng.choice([4, 8, 16]))
    max_fills = int(rng.choice([1, 2, 4]))
    max_t = int(rng.choice([1, 3, 16]))
    n_slots = int(rng.choice([1, 2, flow.n_lanes]))
    dtype = jnp.int32 if rng.random() < 0.5 else jnp.int64
    mode = str(rng.choice(["list", "fast"]))
    chunk = int(rng.choice([1, 17, 64]))
    engine = BatchEngine(
        BookConfig(cap=cap, max_fills=max_fills, dtype=dtype),
        n_slots=n_slots, max_t=max_t,
    )
    got = []
    for i in range(0, len(orders), chunk):
        part = orders[i : i + chunk]
        got.extend(_run_part(engine, part, mode))
    desc = (
        f"seed={seed} SIM lanes={flow.n_lanes} t_bins={flow.t_bins} "
        f"grids={n_grids} n={len(orders)} cap={cap} K={max_fills} "
        f"max_t={max_t} slots={n_slots} dtype={np.dtype(dtype).name} "
        f"mode={mode} chunk={chunk}"
    )
    if got != expected:
        first = next(
            (j for j, (a, b) in enumerate(zip(got, expected)) if a != b),
            min(len(got), len(expected)),
        )
        raise AssertionError(
            f"DIVERGENCE [{desc}] events {len(got)} vs {len(expected)}, "
            f"first mismatch at {first}:\n got: "
            f"{got[first] if first < len(got) else '<none>'}\n exp: "
            f"{expected[first] if first < len(expected) else '<none>'}"
        )
    engine.verify_books()
    expired = _expired(engine.stats)
    want = _expired(oracle.stats)
    if expired != want:
        raise AssertionError(
            f"DIVERGENCE [{desc}] expired (IOC, FOK, POST_ONLY, STP) "
            f"{expired} vs the oracle's {want}"
        )
    return (
        f"OK [{desc}] events={len(got)} esc="
        f"{engine.stats.cap_escalations}"
        f"/{engine.stats.fill_record_escalations}"
        f" expired={'/'.join(map(str, expired))}"
    )


def main():
    configure(tpu="--tpu" in sys.argv)
    sim = "--sim" in sys.argv
    stp = "--stp" in sys.argv  # the venue's rule expire_taker (run_case)
    args = [a for a in sys.argv[1:] if a not in ("--tpu", "--sim", "--stp")]
    n = int(args[0]) if len(args) > 0 else 30
    seed0 = int(args[1]) if len(args) > 1 else 1000
    case = run_sim_case if sim else run_case
    if stp and not sim:
        case = lambda s: run_case(s, self_trade="expire_taker")
    for s in range(seed0, seed0 + n):
        print(case(s), flush=True)
    print(f"ALL {n} CASES PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
