"""Full-output parity of the COMPILED Pallas kernel vs the scan path on a
real TPU (the pytest suite runs the kernel in interpreter mode on CPU; this
script closes the compiled-lowering gap). Run on a TPU host:

    python scripts/tpu_parity_check.py [S T CAP K G]    # one full-grid check
    python scripts/tpu_parity_check.py --suite [S T CAP K G]

Exit 0 on exact equality of every book leaf and every StepOutput leaf
across chained grids of crossing flow (with cancels and every order kind);
1 on a mismatch or when JAX finds no TPU; 2 on a geometry the compiled
kernel cannot block. `--suite` defaults to the served deployment's own
geometry (DEPLOYMENT below); chip_smoke.py runs it before it starts the
service.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


#: The served deployment (README, chip_smoke.py): the full grid, and the
#: dense (R, T, CAP) shapes its flow reaches — the hot lane's deep 8-row
#: grids at the storage cap and at the escalated classes (512, 1024), the
#: service bench's dense depth (8192), and a 128-lane-blocked dense grid.
DEPLOYMENT = dict(S=10240, T=32, CAP=256, K=16, G=2)
DEPLOYMENT_DENSE = (
    (8, 1024, 256), (8, 1024, 512), (8, 1024, 1024), (8, 8192, 256),
    (256, 32, 64),
)


#: Order kinds of the random grids (types.OrderType's numbers: LIMIT,
#: MARKET, IOC, FOK, POST_ONLY) and their shares.
_KINDS = (0, 1, 3, 4, 6)
_KIND_P = (0.5, 0.1, 0.15, 0.1, 0.15)


def _block_or_fail(rows, cap, what, log):
    """(block_s, 0) for a check, or (None, exit code): 1 when JAX finds no
    TPU (a compiled-kernel check that did not run is a failure, not a
    skip), 2 when the compiled kernel cannot block the geometry."""
    import jax.numpy as jnp

    from gome_tpu.ops import kernel_plan

    block_s, _interpret, reason = kernel_plan(rows, cap, jnp.int32)
    if reason == "no_tpu_backend":
        log(f"FAIL {what}: no TPU backend (compiled-kernel parity needs one)")
        return None, 1
    if block_s is None:
        log(f"FAIL {what}: rows={rows} cap={cap} gives way to scan "
            f"({reason}; see gome_tpu.ops.plan_block_s)")
        return None, 2
    return block_s, 0


def run_parity(S=512, T=16, CAP=128, K=16, G=4, log=print) -> int:
    """Compiled-kernel vs scan parity on the current (TPU) backend.
    Returns 0 on exact equality of every leaf, 1 on mismatch or no TPU, 2
    on an unblockable S. Importable — bench.py gates every TPU pallas
    bench on this before reporting numbers."""
    import jax
    import jax.numpy as jnp

    from gome_tpu.engine import BookConfig, batch_step, init_books
    from gome_tpu.engine.book import DeviceOp
    from gome_tpu.ops import pallas_batch_step

    block_s, rc = _block_or_fail(S, CAP, "full", log)
    if rc:
        return rc
    config = BookConfig(cap=CAP, max_fills=K, dtype=jnp.int32)

    def grid(seed):
        r = np.random.default_rng(seed)
        action = r.choice([1, 1, 1, 2], size=(S, T)).astype(np.int32)
        return DeviceOp(
            action=action,
            side=r.integers(0, 2, (S, T)).astype(np.int32),
            kind=r.choice(_KINDS, size=(S, T), p=_KIND_P).astype(np.int32),
            price=r.integers(995_000, 1_005_000, (S, T)).astype(np.int32),
            volume=r.integers(1, 100, (S, T)).astype(np.int32),
            oid=(np.arange(S * T).reshape(S, T) % 97 + 1).astype(np.int32),
            uid=np.ones((S, T), np.int32),
        )

    b_scan = b_pall = init_books(config, S)
    for g in range(G):
        ops = grid(g)
        b_scan, o_scan = batch_step(config, b_scan, ops)
        b_pall, o_pall = pallas_batch_step(
            config, b_pall, ops, block_s=block_s, interpret=False
        )
        if not _leaves_equal(o_scan, o_pall, f"grid {g} StepOutput", log):
            return 1
        if not _leaves_equal(b_scan, b_pall, f"grid {g} BookState", log):
            return 1
        fills = int(np.asarray(jax.device_get(o_scan.n_fills)).sum())
        log(f"grid {g}: OK ({fills} fills)")
    log(f"PARITY OK: pallas == scan on {G} grids ({S}x{T} ops each at "
        f"cap {CAP}, block_s {block_s}, cancels and all five order kinds included)")
    return 0


def _leaves_equal(pair_a, pair_b, what, log) -> bool:
    import jax

    for name in pair_a._fields:
        a = np.asarray(jax.device_get(getattr(pair_a, name)))
        b = np.asarray(jax.device_get(getattr(pair_b, name)))
        if not np.array_equal(a, b):
            bad = np.argwhere(a != b)[:5]
            log(f"MISMATCH {what}.{name} at {bad}")
            return False
    return True


def run_dense_parity(R=8, T=128, CAP=32, K=8, S=64, log=print) -> int:
    """Compiled dense gather/scatter kernel (dense_kernel_step) vs the scan
    dense path on deep time axes — the time-blocked VMEM kernel's block_t
    loop is only exercised with T >> block_t."""
    import jax.numpy as jnp

    from gome_tpu.engine import BookConfig, init_books
    from gome_tpu.engine.batch import dense_batch_step, dense_kernel_step
    from gome_tpu.engine.book import DeviceOp

    bs, rc = _block_or_fail(R, CAP, "dense", log)
    if rc:
        return rc
    config = BookConfig(cap=CAP, max_fills=K, dtype=jnp.int32)
    r = np.random.default_rng(11)
    lane_ids = np.sort(r.choice(S, R, replace=False)).astype(np.int64)

    def ops(seed):
        q = np.random.default_rng(seed)
        return DeviceOp(
            action=q.choice([1, 1, 1, 2], size=(R, T)).astype(np.int32),
            side=q.integers(0, 2, (R, T)).astype(np.int32),
            kind=q.choice(_KINDS, size=(R, T), p=_KIND_P).astype(np.int32),
            price=q.integers(995_000, 1_005_000, (R, T)).astype(np.int32),
            volume=q.integers(1, 100, (R, T)).astype(np.int32),
            oid=(np.arange(R * T).reshape(R, T) % 211 + 1).astype(np.int32),
            uid=np.ones((R, T), np.int32),
        )

    b_scan = b_pall = init_books(config, S)
    ids = jnp.asarray(lane_ids)
    for g in range(2):
        o = ops(100 + g)
        b_scan, o_scan = dense_batch_step(config, b_scan, ids, o)
        b_pall, o_pall = dense_kernel_step(config, b_pall, ids, o, bs)
        if not _leaves_equal(o_scan, o_pall, f"dense grid {g} StepOutput", log):
            return 1
        if not _leaves_equal(b_scan, b_pall, f"dense grid {g} BookState", log):
            return 1
    log(f"dense PARITY OK: dense kernel == scan dense path ({R}x{T} deep "
        f"rounds at cap {CAP} over {S} lanes, block_s {bs})")
    return 0


def run_edge_price_parity(S=128, T=8, CAP=32, K=8, log=print) -> int:
    """Rebased int32 prices near the +/-2^30 envelope edges (what lane
    rebasing feeds the kernel for BTC-magnitude symbols)."""
    import jax.numpy as jnp

    from gome_tpu.engine import BookConfig, batch_step, init_books
    from gome_tpu.engine.book import DeviceOp
    from gome_tpu.ops import pallas_batch_step

    bs, rc = _block_or_fail(S, CAP, "edge", log)
    if rc:
        return rc
    config = BookConfig(cap=CAP, max_fills=K, dtype=jnp.int32)
    half = (1 << 30) - 1000

    def ops(seed, base):
        q = np.random.default_rng(seed)
        return DeviceOp(
            action=q.choice([1, 1, 1, 2], size=(S, T)).astype(np.int32),
            side=q.integers(0, 2, (S, T)).astype(np.int32),
            kind=np.zeros((S, T), np.int32),
            price=(base + q.integers(-900, 900, (S, T))).astype(np.int32),
            volume=q.integers(1, 50, (S, T)).astype(np.int32),
            oid=(np.arange(S * T).reshape(S, T) % 97 + 1).astype(np.int32),
            uid=np.ones((S, T), np.int32),
        )

    b_scan = b_pall = init_books(config, S)
    for g, base in enumerate((half, -half)):
        o = ops(50 + g, base)
        b_scan, o_scan = batch_step(config, b_scan, o)
        b_pall, o_pall = pallas_batch_step(
            config, b_pall, o, block_s=bs, interpret=False
        )
        if not _leaves_equal(o_scan, o_pall, f"edge grid {g} StepOutput", log):
            return 1
        if not _leaves_equal(b_scan, b_pall, f"edge grid {g} BookState", log):
            return 1
    log("edge PARITY OK: rebased prices at +/-2^30 envelope edges")
    return 0


def run_engine_escalation_parity(log=print) -> int:
    """ENGINE-level differential on TPU with the compiled kernel: a
    sweep-heavy stream that trips cap + fill-record budgets, so the
    certified surface includes the escalation replay geometries
    (cap/max_fills doublings) and the frame fast path's rollback — not
    just the steady-state grid shape."""
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine, BookConfig
    from gome_tpu.oracle import OracleEngine
    from gome_tpu.types import Order, Side

    _, rc = _block_or_fail(8, 8, "escalation", log)
    if rc:
        return rc

    orders = [
        Order(uuid="u", oid=str(i), symbol=f"s{i % 3}", side=Side.SALE,
              price=100 + (i % 37), volume=1 + (i % 5))
        for i in range(120)
    ]
    orders.append(
        Order(uuid="u", oid="sweep", symbol="s0", side=Side.BUY, price=300,
              volume=10_000)  # >> max_fills resting orders: escalates
    )
    eng = BatchEngine(
        BookConfig(cap=8, max_fills=4, dtype=jnp.int32),
        n_slots=8, max_t=8, kernel="pallas",
    )
    got = []
    for i in range(0, len(orders), 40):
        got.extend(
            eng.process_columnar(orders[i : i + 40]).to_results()
        )
    oracle = OracleEngine()
    want = [r for o in orders for r in oracle.process(o)]
    if got != want:
        log(f"MISMATCH escalation stream: {len(got)} vs {len(want)} events")
        return 1
    if eng.stats.cap_escalations < 1:
        log("escalation: WARNING — stream did not escalate (geometry drift)")
    if eng.stats.scan_giveways:
        log(f"FAIL escalation: grids gave way to scan "
            f"{eng.stats.scan_giveways}")
        return 2
    eng.verify_books()
    log(f"escalation PARITY OK: compiled kernel through cap/record "
        f"escalations == oracle ({len(got)} events, "
        f"{eng.stats.cap_escalations} escalations)")
    return 0


def run_fuzz_slice(cases=2, log=print) -> int:
    """A small compiled-mode slice of the differential fuzzer's geometry
    space (the three round-1 Mosaic crashes were all found by randomized
    geometries; CI only runs interpret mode). Cases alternate between the
    two ways to run a frame's grids: the list form (exact path) and the
    encoded frame on the compacted fast path."""
    import jax.numpy as jnp

    _, rc = _block_or_fail(8, 16, "fuzz", log)
    if rc:
        return rc

    from gome_tpu.bus.colwire import decode_order_frame, encode_orders
    from gome_tpu.engine import BatchEngine, BookConfig
    from gome_tpu.engine.frames import apply_frame_fast
    from gome_tpu.oracle import OracleEngine
    from gome_tpu.utils.streams import multi_symbol_stream

    rng = np.random.default_rng(int(os.environ.get("BENCH_FUZZ_SEED", "5")))
    for c in range(cases):
        cap = int(rng.choice([8, 16]))
        k = int(rng.choice([2, 4, 8]))
        n_sym = int(rng.integers(2, 6))
        orders = multi_symbol_stream(
            n=150, n_symbols=n_sym, seed=int(rng.integers(1, 1 << 30)),
            cancel_prob=0.2,
        )
        eng = BatchEngine(
            BookConfig(cap=cap, max_fills=k, dtype=jnp.int32),
            n_slots=8, max_t=8, kernel="pallas",
        )
        run = "fast" if c % 2 else "list"
        got = []
        for i in range(0, len(orders), 50):
            part = orders[i : i + 50]
            if run == "list":
                got.extend(eng.process(part))
            else:
                cols = decode_order_frame(encode_orders(part))
                got.extend(apply_frame_fast(eng, cols).to_results())
        oracle = OracleEngine()
        want = [r for o in orders for r in oracle.process(o)]
        if got != want:
            log(f"MISMATCH fuzz case {c} ({run}, cap={cap} K={k} "
                f"syms={n_sym})")
            return 1
        eng.verify_books()
        log(f"fuzz case {c} OK ({run}, cap={cap} K={k} syms={n_sym}, "
            f"{len(got)} events)")
    log(f"fuzz PARITY OK: {cases} compiled-mode randomized geometries")
    return 0


def run_suite(S=128, T=8, CAP=256, K=16, G=2, dense=((8, 128, 32),),
              log=print) -> int:
    """The full certification the bench and chip_smoke.py gate on: every
    code path _step can select on TPU — the full grid (incl. cancels +
    markets), dense deep rounds at each (R, T, CAP) of `dense` gathered
    from an S-lane stack (block_t covered), envelope-edge prices,
    escalation replays, and a compiled-mode fuzz slice. Returns the first
    non-zero code: a check that could not run (no TPU: 1, unblockable
    geometry: 2) fails the suite exactly like a mismatch."""
    checks = [
        lambda: run_parity(S=S, T=T, CAP=CAP, K=K, G=G, log=log),
        *(
            lambda r=r, t=t, cap=cap: run_dense_parity(
                R=r, T=t, CAP=cap, K=K, S=max(S, r), log=log
            )
            for r, t, cap in dense
        ),
        lambda: run_edge_price_parity(CAP=min(CAP, 32), log=log),
        lambda: run_engine_escalation_parity(log=log),
        lambda: run_fuzz_slice(log=log),
    ]
    for fn in checks:
        rc = fn()
        if rc != 0:
            return rc
    return 0


def main():
    args = [int(a) for a in sys.argv[1:] if not a.startswith("--")][:5]
    if "--suite" in sys.argv or not args:
        geo = dict(zip(("S", "T", "CAP", "K", "G"), args))
        return run_suite(**{**DEPLOYMENT, **geo}, dense=DEPLOYMENT_DENSE)
    S, T, CAP, K, G = args + [512, 16, 128, 16, 4][len(args):]
    return run_parity(S, T, CAP, K, G)


if __name__ == "__main__":
    sys.exit(main())
