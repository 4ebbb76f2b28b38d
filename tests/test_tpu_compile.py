"""The served path's two kernel geometries compiled for a described v5e chip
(no chip attached, nothing runs): the TPU's own compiler accepts them, and
each kernel's device event carries its geometry's name (ISSUE 25), which is
what a profile's `device_ops` then tells apart. All such compiles stay in
this one file: only the worker that is given it loads the TPU's library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.engine import batch as B
from gome_tpu.engine.book import BookConfig, DeviceOp, init_book
from gome_tpu.ops.pallas_match import plan_block_s


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, n_slots, rows, t, cap, dense,
                   self_trade="none"):
    cfg = BookConfig(cap=cap, max_fills=16, dtype=jnp.int32,
                     self_trade=self_trade)
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    books = jax.tree.map(place, jax.eval_shape(
        lambda: jax.vmap(lambda _: init_book(cfg))(jnp.arange(n_slots))
    ))
    cell = jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one_chip)
    ops = DeviceOp(**{f: cell for f in DeviceOp._fields})
    block, reason = plan_block_s(rows, cap)
    assert block is not None, reason
    with jax.enable_x64(False):  # the deployment's int32 process
        if dense:
            ids = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
            lowered = B.dense_kernel_step.lower(
                cfg, books, ids, ops, block, False
            )
        else:
            lowered = B.full_kernel_step.lower(cfg, books, ops, block, False)
        return lowered.compile().as_text()


@pytest.mark.parametrize("n_slots, rows, t, cap, dense, name", [
    # hotpair8, full grids of the 1024-slot class at its three depth
    # classes (BatchEngine._grid_depth): a 4,096-order frame runs as two
    # of the deepest; max_t is the shallowest
    (8, 8, 32, 1024, False, "match_full_r8_t32_c1024"),
    (8, 8, 256, 1024, False, "match_full_r8_t256_c1024"),
    (8, 8, 1024, 1024, False, "match_full_r8_t1024_c1024"),
    # spot10k: the wide class-64 dense grid over the live lanes
    (10240, 2048, 256, 64, True, "match_dense_r2048_t256_c64"),
])
def test_the_kernel_compiles_for_v5e_under_its_geometrys_name(
        one_chip, n_slots, rows, t, cap, dense, name):
    calls = [ln.strip() for ln in
             _compiled_text(one_chip, n_slots, rows, t, cap, dense).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].startswith(f"%{name}."), calls[0][:120]


@pytest.mark.parametrize("n_slots, rows, t, cap, dense, name", [
    # spot10k_stp: the wide class-64 dense grid, the deep band's class-256
    # grid, and the widest class a lane can grow into, as a full grid
    (10240, 2048, 256, 64, True, "match_dense_r2048_t256_c64"),
    (10240, 32, 512, 256, True, "match_dense_r32_t512_c256"),
    (8, 8, 256, 1024, False, "match_full_r8_t256_c1024"),
])
def test_the_kernel_compiles_for_v5e_under_self_trade_prevention(
        one_chip, n_slots, rows, t, cap, dense, name):
    """The venue rule expire_taker (step._match: one compare against the
    op's uid and one masked integer minimum over [cap]) lowers in Mosaic at
    every slot width in use, in the one kernel, under the same name."""
    text = _compiled_text(one_chip, n_slots, rows, t, cap, dense,
                          self_trade="expire_taker")
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].startswith(f"%{name}."), calls[0][:120]


@pytest.mark.parametrize("rows, t, cap, name", [
    # spot10k_mesh4: the wide class-64 grid and the deep band's class-256
    # grid, the lane axis split over four chips; the kernel's name holds a
    # chip's rows (a quarter of the grid's)
    (2048, 16, 64, "match_dense_r512_t16_c64"),
    (32, 512, 256, "match_dense_r8_t512_c256"),
])
def test_the_sharded_dense_step_compiles_for_four_v5e_chips_without_collectives(
        topo, monkeypatch, rows, t, cap, name):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from gome_tpu.ops import pallas_match
    from gome_tpu.parallel.mesh import SYM_AXIS, sharded_dense_step

    # kernel_plan asks the default backend, which is the CPU here; the step
    # is compiled for the described chips, so the test answers for them.
    monkeypatch.setattr(pallas_match, "pallas_available",
                        lambda dtype=jnp.int32: True)
    mesh = Mesh(np.asarray(topo.devices), (SYM_AXIS,))
    split = NamedSharding(mesh, PartitionSpec(SYM_AXIS))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=split)
    store = BookConfig(cap=256, max_fills=16, dtype=jnp.int32)
    books = jax.tree.map(place, jax.eval_shape(
        lambda: jax.vmap(lambda _: init_book(store))(jnp.arange(10240))
    ))
    cell = jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=split)
    ops = DeviceOp(**{f: cell for f in DeviceOp._fields})
    ids = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=split)
    cfg = BookConfig(cap=cap, max_fills=16, dtype=jnp.int32)
    with jax.enable_x64(False):
        text = sharded_dense_step(cfg, mesh, kernel="pallas").lower(
            books, ids, ops).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and calls[0].startswith(f"%{name}."), calls
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective


@pytest.mark.parametrize(
    "n_slots, store_cap, rows, t, cap, dense, m_pad, e_fills, name", [
        # hotpair8.paced: 81 orders a frame, one full grid of the 1024-slot
        # class at the shallowest depth
        (8, 1024, 8, 32, 1024, False, 256, 128, "match_full_r8_t32_c1024"),
        # spot10k.paced: 62 orders a frame; until ISSUE 44 (and for a frame
        # of one class since) two dense grids: the tail's
        # lanes at class 64 and the deep band's at class 256 (the cell's
        # floors in a chip run: rows 64 and 32, depth 8)
        (10240, 256, 64, 8, 64, True, 64, 64, "match_dense_r64_t8_c64"),
        (10240, 256, 32, 8, 256, True, 64, 64, "match_dense_r32_t8_c256"),
        # ... and since ISSUE 44 ONE: every lane of the frame, up to 63, at
        # the deepest class present, at the depths its floor can settle on
        (10240, 256, 64, 8, 256, True, 64, 64, "match_dense_r64_t8_c256"),
        (10240, 256, 64, 16, 256, True, 64, 64, "match_dense_r64_t16_c256"),
        (10240, 256, 64, 32, 256, True, 64, 64, "match_dense_r64_t32_c256"),
    ])
def test_a_small_frames_grid_compiles_for_v5e_as_one_program(
        one_chip, n_slots, store_cap, rows, t, cap, dense, m_pad, e_fills,
        name):
    """frames._grid_program at the paced cells' geometries: scatter, the
    kernel's step, compaction and the count reduction lower and compile
    for the chip as ONE module, with the kernel in it under its geometry's
    name and the three event buffers aliased to their outputs."""
    import numpy as np

    from gome_tpu.engine import frames

    store = BookConfig(cap=store_cap, max_fills=16, dtype=jnp.int32)
    shape = lambda s, dt=jnp.int32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    books = jax.tree.map(lambda a: shape(a.shape, a.dtype), jax.eval_shape(
        lambda: jax.vmap(lambda _: init_book(store))(jnp.arange(n_slots))
    ))
    block, reason = plan_block_s(rows, cap)
    assert block is not None, reason
    plan = B.StepPlan(
        BookConfig(cap=cap, max_fills=16, dtype=jnp.int32), dense, block,
        False,
    )
    with jax.enable_x64(False):  # the deployment's int32 process
        text = frames._grid_program.lower(
            plan, rows, t, books, shape((7, m_pad)), shape((m_pad,)),
            shape((rows,)) if dense else None,
            shape((len(frames._FILL_FIELDS), e_fills)),
            shape((len(frames._CANCEL_FIELDS), e_fills)),  # the op class
            shape((8, frames.n_totals(plan.cfg))), np.int32(0),
        ).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and calls[0].startswith(f"%{name}."), calls
    assert text.count("may-alias") + text.count("must-alias") >= 3


def _engine_on_the_chip(monkeypatch, n_slots, cap):
    """A BatchEngine as a configuration file builds it (kernel pallas, the
    lanes the venue states), planning its grids as on the chip: kernel_plan
    asks the default backend, which is the CPU here, so the test answers
    for the described chip."""
    from gome_tpu.ops import pallas_match

    monkeypatch.setattr(pallas_match, "pallas_available",
                        lambda dtype=jnp.int32: True)
    return B.BatchEngine(
        BookConfig(cap=cap, max_fills=16, dtype=jnp.int32), n_slots=n_slots,
        kernel="pallas")


def _lowered_full_grid(one_chip, eng, t, cap_g):
    """The full grid the engine dispatches at depth t and cap class cap_g,
    lowered for the chip from the engine's own stack and plan."""
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    use_dense, rows, _ids, _row_of = eng._grid_geometry(
        np.arange(eng.n_slots))
    assert not use_dense
    plan = eng._grid_plan(rows, False, cap_g, None)
    assert plan.block_s is not None and not plan.interpret
    cell = jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one_chip)
    ops = DeviceOp(**{f: cell for f in DeviceOp._fields})
    with jax.enable_x64(False):  # the deployment's int32 process
        return B.full_kernel_step.lower(
            plan.cfg, jax.tree.map(place, eng.books), ops, plan.block_s,
            plan.interpret)


# The two tests below loop over their cases inside one test each, and do not
# parametrise: pytest-xdist hands out files largest first (loadscopereorder),
# and this file has to stay smaller than tests/test_hostprof.py (15 tests), so
# that no worker runs that file's signal sampler after this one has loaded the
# TPU's library into the process: the sampler then kills the worker (the
# parent's tree too; ROADMAP C).


def test_a_venue_of_one_lane_compiles_for_v5e_at_the_kernels_row_floor(
        one_chip, monkeypatch):
    """n_slots 1 as hotpair1's file says it: the engine's own stack and
    plan give grids Mosaic takes, at the deepest class an 8-row block's
    VMEM budget admits: one lane provisioned to the kernel's 8-row floor,
    full grids of the 4096-slot class at the three depth classes (a
    4,096-order frame runs as four of the deepest)."""
    eng = _engine_on_the_chip(monkeypatch, 1, 4096)
    assert (eng.n_slots, eng.lane_rows) == (1, 8)
    for t in (32, 256, 1024):
        text = _lowered_full_grid(one_chip, eng, t, 4096).compile().as_text()
        calls = [ln.strip() for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert len(calls) == 1
        assert calls[0].startswith(f"%match_full_r8_t{t}_c4096."), (
            calls[0][:120])


def test_where_the_lanes_are_blockable_the_program_is_the_parents(
        one_chip, monkeypatch):
    """The floor is the identity at the accepted venues' lane counts: what
    the engine lowers from its own stack and plan is, letter for letter,
    what the parent lowered from a stack of n_slots rows."""
    for case in (
        (8, 1024, 1024, 1024),   # hotpair8's deepest full grid
        (10240, 256, 32, 64),    # spot10k's full grid at the class-64 slice
    ):
        _the_program_is_the_parents(one_chip, monkeypatch, *case)


def _the_program_is_the_parents(one_chip, monkeypatch, n_slots, store_cap, t,
                                cap_g):
    eng = _engine_on_the_chip(monkeypatch, n_slots, store_cap)
    assert eng.lane_rows == eng.lane_span == n_slots
    mine = _lowered_full_grid(one_chip, eng, t, cap_g).as_text()
    # the parent: books of n_slots rows, a grid of n_slots rows
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    store = BookConfig(cap=store_cap, max_fills=16, dtype=jnp.int32)
    books = jax.tree.map(place, jax.eval_shape(
        lambda: jax.vmap(lambda _: init_book(store))(jnp.arange(n_slots))
    ))
    cell = jax.ShapeDtypeStruct((n_slots, t), jnp.int32, sharding=one_chip)
    ops = DeviceOp(**{f: cell for f in DeviceOp._fields})
    block, reason = plan_block_s(n_slots, cap_g)
    assert block is not None, reason
    with jax.enable_x64(False):
        parents = B.full_kernel_step.lower(
            BookConfig(cap=cap_g, max_fills=16, dtype=jnp.int32), books, ops,
            block, False).as_text()
    assert mine == parents
    assert f"match_full_r{n_slots}_t{t}_c{cap_g}" in mine
