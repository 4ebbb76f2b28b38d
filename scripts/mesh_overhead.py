"""Measure what the mesh costs (VERDICT r4 #5) — two parts:

Part A (runs wherever JAX runs; meaningful on the REAL chip): mesh=1
shard_map dispatch overhead. The same full grid at the headline service
shape (10240 lanes x 32 t, int32, pallas) steps through (a) the unsharded
engine path and (b) `sharded_batch_step` over a 1-device mesh — same
kernel, same bytes, the delta is what shard_map + sharding constraints
add per dispatch. Dense variant included (the Zipf hot path).

Part B (host-side analysis, no device needed): per-shard row-padding
overhead under Zipf skew. The dense packer buckets each shard's row block
to the MAX per-shard live count (engine/batch.py _grid_geometry), so skew
concentrates rows on one shard and every other shard pads to match. For
D in {1,2,4,8} over the service bench's own Zipf flow: dispatched-rows /
live-lanes ratio (p50/p95) — the true multi-chip tax of the dense win.

Part C (`--curve`): the MEASURED D=1/2/4/8 throughput + per-shard skew
curve (ISSUE 9 / ROADMAP open item 2), written to MULTICHIP_r06.json
with the measured-roofline profiler block embedded. Runs over 8 virtual
CPU devices on the dev container (curve shape + skew structure are
real; absolute rates are a CPU floor) and over real devices on a pod.

Usage:
    python scripts/mesh_overhead.py            # Part A on default backend
    python scripts/mesh_overhead.py --skew     # Part B (host only)
    python scripts/mesh_overhead.py --curve [out.json]   # Part C
Output: one JSON line per part (stored in ARCHITECTURE.md's table).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def part_a():
    import jax
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine, BookConfig, init_books
    from gome_tpu.engine.book import DeviceOp
    from gome_tpu.parallel import make_mesh, shard_batch, sharded_batch_step
    from gome_tpu.parallel.mesh import sharded_dense_step

    S = int(os.environ.get("MESH_SYMBOLS", 10_240))
    T = int(os.environ.get("MESH_T", 32))
    CAP = int(os.environ.get("MESH_CAP", 256))
    REPS = int(os.environ.get("MESH_REPS", 100))
    config = BookConfig(cap=CAP, max_fills=16, dtype=jnp.int32)

    rng = np.random.default_rng(3)
    n_ops = S * T

    def mk_grid(rows):
        f = {}
        shape = (rows, T)
        f["action"] = rng.integers(1, 2, shape)  # all ADDs
        f["side"] = rng.integers(0, 2, shape)
        f["kind"] = np.zeros(shape, np.int64)
        f["price"] = rng.integers(90, 110, shape)
        f["volume"] = rng.integers(1, 50, shape)
        f["oid"] = np.arange(rows * T).reshape(shape) + 1
        f["uid"] = np.ones(shape, np.int64)
        from gome_tpu.engine.book import GRID_I32_FIELDS

        return DeviceOp(**{
            k: np.asarray(
                v, np.int32 if k in GRID_I32_FIELDS else config.dtype
            )
            for k, v in f.items()
        })

    ops = mk_grid(S)

    def sync(tree):
        """Force completion with a value fetch: the probe syncs by
        materializing a scalar that depends on the result."""
        leaf = jax.tree.leaves(tree)[0]
        np.asarray(jax.device_get(leaf.sum()))

    def time_step(fn, books0, *args):
        """Thread the books output back in each iteration: steps must
        form a true serial chain (independent calls let the device/link
        pipeline them and the per-step time reads fictitiously low). The
        closing sync's own round trip is measured separately and subtracted so it does not smear a
        constant into every per-step time."""
        books, out = fn(books0, *args)  # compile
        sync(out)
        t0 = time.perf_counter()
        sync(books0)
        t_sync = time.perf_counter() - t0
        books = books0
        t0 = time.perf_counter()
        for _ in range(REPS):
            books, out = fn(books, *args)
        sync(books)
        return max(time.perf_counter() - t0 - t_sync, 1e-9) / REPS

    results = {}

    # Unsharded full-grid pallas step (the single-chip headline path).
    # device_put the grids up front for BOTH paths: numpy inputs would
    # re-upload ~10MB per call and measure the host->device link.
    eng = BatchEngine(config, n_slots=S, max_t=T, kernel="pallas")
    ops = jax.device_put(ops)
    t_unsharded = time_step(lambda b, o: eng._step(b, o), eng.books, ops)
    results["full_unsharded_ms"] = round(t_unsharded * 1e3, 3)

    # mesh=1: the same step through shard_map + pinned shardings.
    mesh = make_mesh(1)
    stepper = sharded_batch_step(config, mesh, kernel="pallas")
    books_m = shard_batch(mesh, init_books(config, S))
    ops_m = shard_batch(mesh, ops)
    t_mesh1 = time_step(lambda b, o: stepper(b, o), books_m, ops_m)
    results["full_mesh1_ms"] = round(t_mesh1 * 1e3, 3)
    results["full_mesh1_overhead_pct"] = round(
        (t_mesh1 / t_unsharded - 1) * 100, 1
    )

    # Dense variant: 1024 live lanes of the 10240 (Zipf-ish live set).
    # Keep the host-born lane ids around as numpy: both the unsharded
    # device_put and the mesh placement shard from the host ORIGINAL —
    # round-tripping the device copy back through np.asarray would pay
    # device->host->device on the timed setup path (GL805).
    R = 1024
    dense_ops = jax.device_put(mk_grid(R))
    ids_np = np.arange(R, dtype=np.int32)
    lane_ids = jax.device_put(ids_np)
    eng2 = BatchEngine(config, n_slots=S, max_t=T, kernel="pallas")
    t_dense = time_step(
        lambda b, o: eng2._step(b, o, lane_ids), eng2.books, dense_ops
    )
    results["dense_unsharded_ms"] = round(t_dense * 1e3, 3)
    dstepper = sharded_dense_step(config, mesh, kernel="pallas")
    books2 = shard_batch(mesh, init_books(config, S))
    ids_m = shard_batch(mesh, ids_np)
    dops_m = shard_batch(mesh, dense_ops)
    t_dense_m = time_step(
        lambda b, i, o: dstepper(b, i, o), books2, ids_m, dops_m
    )
    results["dense_mesh1_ms"] = round(t_dense_m * 1e3, 3)
    results["dense_mesh1_overhead_pct"] = round(
        (t_dense_m / t_dense - 1) * 100, 1
    )
    results["orders_per_step"] = n_ops
    results["platform"] = jax.devices()[0].platform
    print(json.dumps({"mesh_overhead_mesh1": results}))


def part_b():
    """Row-padding overhead of per-shard max bucketing under Zipf skew —
    pure host analysis of the packer's own math (_grid_geometry)."""
    from gome_tpu.engine.batch import _next_pow2

    S = int(os.environ.get("MESH_SYMBOLS", 10_240))
    FRAMES = 64
    rng = np.random.default_rng(11)
    # The service bench's Zipf shape: symbol ~ Zipf(1.2) capped to S.
    rows = {}
    for d in (1, 2, 4, 8):
        ratios = []
        local = S // d
        for _ in range(FRAMES):
            syms = rng.zipf(1.2, size=8192) % S
            live = np.unique(syms)
            shard = live // local
            counts = np.bincount(shard, minlength=d)
            r_s = max(8, _next_pow2(int(counts.max())))
            dispatched = r_s * d
            ratios.append(dispatched / len(live))
        ratios = np.asarray(ratios)
        rows[f"D{d}"] = dict(
            p50_rows_per_live_lane=round(float(np.median(ratios)), 2),
            p95_rows_per_live_lane=round(
                float(np.percentile(ratios, 95)), 2
            ),
        )
    print(json.dumps({"mesh_dense_row_padding_zipf": rows}))


def _force_virtual_devices(n: int = 8) -> None:
    """Give this process `n` devices on the CPU backend (the conftest
    mechanism): the XLA flag and the platform must both land before
    jax's FIRST backend initialization — importing jax is fine, using a
    device is not. On a real pod slice set MESH_CURVE_PLATFORM= (empty)
    to keep the native device set instead."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag
        ).strip()
    import jax

    platform = os.environ.get("MESH_CURVE_PLATFORM", "cpu")
    if platform:
        jax.config.update("jax_platforms", platform)
        try:
            jax.config.update("jax_num_cpu_devices", n)
        except AttributeError:
            pass  # pre-0.5 JAX: the XLA_FLAGS spelling applies instead


def curve(out_path: str = "MULTICHIP_r06.json"):
    """The first MEASURED D=1/2/4/8 curve (ISSUE 9 / ROADMAP open item
    2): one fixed Zipf live set dispatched through the engine's real
    dense mesh path (`_grid_geometry` layout -> `sharded_dense_step`)
    at each mesh width, timing a serial dispatch chain AND replaying
    each shard's block independently on its own device
    (parallel.mesh.shard_execution_report) — so the JSON carries
    throughput, per-shard dispatched rows, per-shard live lanes, and
    per-shard execution time: the skew tax as measured numbers. The
    measured-roofline profiler block (gome_tpu.obs.profiler) is
    embedded alongside.

    On the dev/CI container the mesh is 8 VIRTUAL CPU devices sharing
    the host's cores: per-shard structure, skew ratios, and the curve's
    SHAPE are real measurements; absolute orders/sec are a CPU floor,
    not a chip claim. On a pod slice the same entry measures the real
    thing (MESH_CURVE_PLATFORM= to keep native devices)."""
    _force_virtual_devices(8)
    import jax
    import jax.numpy as jnp

    from gome_tpu.engine import BatchEngine, BookConfig
    from gome_tpu.engine.book import GRID_I32_FIELDS, DeviceOp
    from gome_tpu.obs import profiler
    from gome_tpu.parallel import make_mesh, shard_execution_report

    S = int(os.environ.get("MESH_CURVE_SYMBOLS", 4096))
    T = int(os.environ.get("MESH_CURVE_T", 16))
    CAP = int(os.environ.get("MESH_CURVE_CAP", 64))
    REPS = int(os.environ.get("MESH_CURVE_REPS", 20))
    config = BookConfig(cap=CAP, max_fills=8, dtype=jnp.int32)
    rng = np.random.default_rng(17)

    # ONE Zipf live set shared by every mesh width: the curve then
    # varies only in shard geometry, never in flow. S/4 draws keeps the
    # live set sparse enough that the dense packer engages at every D
    # (per-shard MAX bucketing must stay under the full grid) while the
    # hot-shard concentration still shows the real skew tax.
    live = np.unique(rng.zipf(1.2, size=S // 4) % S)

    def mk_grid(rows):
        shape = (rows, T)
        f = dict(
            action=np.ones(shape, np.int64),
            side=rng.integers(0, 2, shape),
            kind=np.zeros(shape, np.int64),
            price=rng.integers(90, 110, shape),
            volume=rng.integers(1, 50, shape),
            oid=np.arange(rows * T).reshape(shape) + 1,
            uid=np.ones(shape, np.int64),
        )
        return DeviceOp(**{
            k: np.asarray(
                v, np.int32 if k in GRID_I32_FIELDS else config.dtype
            )
            for k, v in f.items()
        })

    points = []
    for d in (1, 2, 4, 8):
        mesh = make_mesh(d)
        eng = BatchEngine(config, n_slots=S, max_t=T, kernel="scan",
                          mesh=mesh)
        use_dense, n_rows, lane_ids, _ = eng._grid_geometry(live)
        assert use_dense, f"dense packer declined at D={d}"
        ops = mk_grid(n_rows)
        books, outs = eng._step(eng.books, ops, lane_ids)  # compile+warm
        jax.block_until_ready(outs)
        books = eng.books
        t0 = time.perf_counter()
        for _ in range(REPS):
            books, outs = eng._step(books, ops, lane_ids)
        jax.block_until_ready(books)
        per_step = (time.perf_counter() - t0) / REPS
        live_orders = len(live) * T
        shard_counts = np.bincount(live // (S // d), minlength=d)
        point = dict(
            devices=d,
            dispatched_rows=int(n_rows),
            live_lanes=int(len(live)),
            rows_per_live_lane=round(n_rows / len(live), 4),
            live_per_shard=[int(c) for c in shard_counts],
            shard_skew=round(int(shard_counts.max()) * d / len(live), 4),
            step_ms=round(per_step * 1e3, 3),
            live_orders_per_sec=round(live_orders / per_step),
            dispatched_orders_per_sec=round(n_rows * T / per_step),
        )
        if d > 1:
            point["per_shard"] = shard_execution_report(
                config, mesh, eng.books, lane_ids, ops
            )
        points.append(point)
        print(json.dumps({"multichip_point": point}), flush=True)

    doc = dict(
        artifact="MULTICHIP_r06",
        method=(
            "measured D=1/2/4/8 dense mesh dispatch over one fixed "
            "Zipf(1.2) live set; engine _grid_geometry layout through "
            "sharded_dense_step, serial chain best-effort mean of "
            f"{REPS} reps; per-shard blocks replayed independently per "
            "device (shard_execution_report). Virtual-CPU meshes share "
            "host cores: curve shape and skew are measurements, "
            "absolute rates are a CPU floor."
        ),
        platform=jax.devices()[0].platform,
        n_devices_available=jax.device_count(),
        jax=jax.__version__,
        geometry=dict(symbols=S, t=T, cap=CAP, reps=REPS),
        curve=points,
        profile=profiler.bench_measured("int32", repeats=4),
    )
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    if "--skew" in sys.argv:
        part_b()
    elif "--curve" in sys.argv:
        curve(
            sys.argv[sys.argv.index("--curve") + 1]
            if len(sys.argv) > sys.argv.index("--curve") + 1
            and not sys.argv[sys.argv.index("--curve") + 1].startswith("-")
            else "MULTICHIP_r06.json"
        )
    else:
        part_a()
