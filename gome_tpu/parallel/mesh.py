"""Multi-chip scaling: symbol-sharded books over a device mesh.

The reference's only parallelism axis is per-symbol independence — every
Redis key is symbol-prefixed and symbols share nothing (SURVEY §2.1). The
TPU equivalent: the [S] symbol-lane axis of the stacked BookState/op grids is
partitioned across a 1-D `jax.sharding.Mesh` ("sym" axis). Matching needs
ZERO collectives — XLA partitions the batched scan x vmap step into S/D
independent lanes per chip; cross-chip traffic exists only at the dispatch
layer (host routes orders to the chip owning the symbol's lane — the
EP-style symbol-hash routing of SURVEY §2.1) and for global metrics
reductions (psum over "sym").

Multi-host: the same mesh spans hosts; lane routing keys on
lane // lanes_per_shard so each host's bridge feeds only its local shard and
order traffic rides DCN at the dispatch layer, never inside the step
(SURVEY §5.8).
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.book import BookConfig, BookState, DeviceOp
from ..engine.batch import batch_step

SYM_AXIS = "sym"


def _shard_map_fn(mesh: Mesh):
    """jax.shard_map bound to `mesh` with the varying-mesh-axis check off:
    the checker has no rule for pallas_call, whose ShapeDtypeStruct outputs
    carry no varying-mesh-axis annotation, and the bodies here are
    embarrassingly parallel so the check proves nothing."""
    return functools.partial(jax.shard_map, mesh=mesh, check_vma=False)


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the symbol axis. n_devices must divide the lane count
    used with it. Raises when fewer than n_devices devices exist — a
    silently smaller mesh would pass every downstream divisibility check
    against the WRONG size and ship a topology the operator didn't ask
    for."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"mesh wants {n_devices} devices but only "
                    f"{len(devices)} are available"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SYM_AXIS,))


def symbol_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for any array whose leading axis is the symbol-lane axis
    (every BookState leaf and every DeviceOp grid leaf)."""
    return NamedSharding(mesh, P(SYM_AXIS))


# gomelint: hotpath — per-dispatch mesh placement of the ops grid
def shard_batch(mesh: Mesh, tree):
    """Place a [S, ...]-leaved pytree (BookState stack or DeviceOp grid)
    with the leading axis split across the mesh."""
    return jax.device_put(tree, symbol_sharding(mesh))


def sharded_batch_step(
    config: BookConfig,
    mesh: Mesh,
    kernel: str = "scan",
    pallas_interpret: bool = False,
):
    """The batched step with explicit symbol-axis shardings pinned on inputs
    and outputs — the full multi-chip matching step. Compiles to per-chip
    independent lane work with no communication.

    kernel="scan": XLA scan x vmap, partitioned by GSPMD. kernel="pallas":
    the VMEM-resident kernel runs PER CHIP inside a shard_map over the
    symbol mesh — each chip sees its local [S/D, ...] block and launches
    the same compiled kernel a single-chip engine would, so multi-chip
    keeps the kernel's ~3x win over the scan path. Gives way to the scan
    step where ops.kernel_plan finds no blocking for the local lane count
    (BatchEngine._step counts the same decision in EngineStats).
    """
    sharding = symbol_sharding(mesh)

    if kernel == "pallas":
        shard_map = _shard_map_fn(mesh)
        from ..engine.batch import full_kernel_step
        from ..ops import kernel_plan

        def stepper(books: BookState, ops: DeviceOp):
            # The same per-chip decision BatchEngine._step counted.
            block, interpret, _reason = kernel_plan(
                ops.action.shape[0] // mesh.size,
                config.cap,
                config.dtype,
                pallas_interpret,
            )
            if block is None:
                return batch_step(config, books, ops)
            # full_kernel_step carries the cap-class slice/guard/write-back
            # (engine.batch): local book blocks may be stored wider than
            # this grid's cap class.
            per_chip = lambda b, o: full_kernel_step(
                config, b, o, block, interpret
            )
            spec = P(SYM_AXIS)
            return shard_map(
                per_chip,
                in_specs=(spec, spec),
                out_specs=(spec, spec),
            )(books, ops)

    else:

        def stepper(books: BookState, ops: DeviceOp):
            return batch_step(config, books, ops)

    return jax.jit(
        stepper,
        in_shardings=(sharding, sharding),
        out_shardings=(sharding, sharding),
    )


def sharded_dense_step(
    config: BookConfig,
    mesh: Mesh,
    kernel: str = "scan",
    pallas_interpret: bool = False,
):
    """Per-shard dense gather/scatter step under the mesh — the multi-chip
    form of engine.batch.dense_batch_step/dense_kernel_step.

    Skewed (Zipf) flow is exactly where dense packing matters, and per-
    symbol key isolation makes it embarrassingly partitionable
    (ordernode.go:89-117): each shard gathers only its LOCAL live lanes,
    so the whole step needs zero collectives. The packer
    (BatchEngine._grid_geometry) lays the compact row axis out as
    [D * R_s] — shard d's rows occupy the contiguous block
    [d*R_s, (d+1)*R_s) and name only lanes that shard owns — so the
    standard symbol-axis sharding hands every chip its own [R_s] block of
    rows, its own [S/D] block of books, and the step inside shard_map is
    the SAME gather -> scan/kernel -> scatter a single-chip dense grid
    runs.

    Returns a jitted fn(books, local_ids, ops) with shardings pinned;
    local_ids are shard-local lane indices (sentinel >= S/D on padding
    rows — gathered as zero books, dropped by the scatter)."""
    sharding = symbol_sharding(mesh)
    shard_map = _shard_map_fn(mesh)
    from ..engine.batch import (
        _guard_capped,
        _lane_scan_impl,
        _scatter_books_cap,
        _slice_books_cap,
    )

    def per_chip(books, ids, ops):
        import jax.numpy as jnp

        # Cap-class slice/guard/scatter, as in engine.batch.dense_*_step:
        # the stored block may be wider than this grid's cap class.
        cap = config.cap
        base = _slice_books_cap(books, cap)
        sub = jax.tree.map(
            lambda a: jnp.take(a, ids, axis=0, mode="fill", fill_value=0),
            base,
        )
        pre_counts = sub.count
        block, interpret = None, False
        if kernel == "pallas":
            from ..ops import kernel_plan

            # The same per-chip decision BatchEngine._step counted.
            block, interpret, _reason = kernel_plan(
                ids.shape[0], config.cap, config.dtype, pallas_interpret
            )
        if block is not None:
            from ..ops import pallas_batch_step

            sub, outs = pallas_batch_step(
                config, sub, ops, block_s=block, interpret=interpret,
                grid_kind="dense",
            )
        else:
            sub, outs = jax.vmap(
                lambda b, o: _lane_scan_impl(config, b, o)
            )(sub, ops)
        outs = _guard_capped(outs, pre_counts, cap, ops)
        new_books = _scatter_books_cap(books, ids, sub, cap)
        return new_books, outs

    spec = P(SYM_AXIS)

    def stepper(books: BookState, ids, ops: DeviceOp):
        return shard_map(
            per_chip,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec),
        )(books, ids, ops)

    return jax.jit(
        stepper,
        in_shardings=(sharding, sharding, sharding),
        out_shardings=(sharding, sharding),
    )


def shard_execution_report(
    config: BookConfig,
    mesh: Mesh,
    books: BookState,
    lane_ids,
    ops: DeviceOp,
    repeats: int = 3,
) -> dict:
    """MEASURED per-shard execution time for one dense mesh dispatch
    (ISSUE 9): the skew tax as device seconds, not a host histogram.

    ``shard_map`` executes every shard inside ONE dispatch, so the host
    never sees per-shard time. This probe exploits the dense layout's
    shard-locality (each row block [d*R_s, (d+1)*R_s) names only shard
    d's lanes, zero collectives) to replay each shard's block as an
    INDEPENDENT single-device call — same gather -> scan -> scatter
    graph (engine.batch.dense_batch_step), same shapes, pinned to that
    shard's own device — and times it best-of-``repeats``. Because the
    per-shard row height R_s is the bucketed MAX of the live counts,
    every shard pays the hottest shard's row count; ``exec_ms`` vs
    ``live_lanes`` is that tax, measured.

    Args mirror the dispatch: ``books`` the full [S] stack, ``lane_ids``
    the [D*R_s] GLOBAL ids with sentinel ``S`` on padding rows (exactly
    what ``BatchEngine._grid_geometry`` returns), ``ops`` the [D*R_s, T]
    grid. An offline/ops-surface probe — never the dispatch path.
    """
    import time

    import jax.numpy as jnp

    from ..engine.batch import dense_batch_step

    d = mesh.size
    s = int(books.count.shape[0])
    local = s // d
    r_s = len(lane_ids) // d
    devices = list(np.asarray(mesh.devices).flat)

    ids_np = np.asarray(lane_ids)
    shards = []
    for j in range(d):
        dev = devices[j]
        blk = jax.tree.map(
            lambda a, j=j: jax.device_put(a[j * local:(j + 1) * local], dev),
            books,
        )
        ids_j = ids_np[j * r_s:(j + 1) * r_s]
        # Localize exactly as the dispatch does (engine.batch._step):
        # global lane % local IS the local index; sentinel -> `local`
        # (out of range: gathered as zeros, dropped by the scatter).
        ids_local = jax.device_put(
            jnp.asarray(
                np.where(ids_j >= s, local, ids_j % local), jnp.int32
            ),
            dev,
        )
        ops_j = jax.tree.map(
            lambda a, j=j: jax.device_put(a[j * r_s:(j + 1) * r_s], dev), ops
        )
        jax.block_until_ready(dense_batch_step(config, blk, ids_local, ops_j))
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(
                dense_batch_step(config, blk, ids_local, ops_j)
            )
            best = min(best, time.perf_counter() - t0)
        live_j = int((ids_j < s).sum())
        shards.append({
            "shard": j,
            "device": str(dev),
            "rows": r_s,
            "live_lanes": live_j,
            "rows_per_live_lane": round(r_s / live_j, 4) if live_j else None,
            "exec_ms": round(best * 1e3, 4),
        })
    times = [sh["exec_ms"] for sh in shards]
    lives = [sh["live_lanes"] for sh in shards]
    total_live = sum(lives) or 1
    return {
        "n_shards": d,
        "rows_per_shard": r_s,
        "dispatched_rows": d * r_s,
        "live_lanes": sum(lives),
        "shards": shards,
        "exec_ms_max": max(times),
        "exec_ms_mean": round(sum(times) / len(times), 4),
        "live_skew": round(max(lives) * d / total_live, 4),
        "rows_per_live_lane": round(d * r_s / total_live, 4),
    }


def global_fill_rate(outs) -> jax.Array:
    """Example cross-chip reduction: total fills in a batch (a psum over the
    sharded lane axis, handled by XLA from the jnp.sum)."""
    import jax.numpy as jnp

    return jnp.sum(outs.n_fills)
