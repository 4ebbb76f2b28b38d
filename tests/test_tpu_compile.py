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
import pytest

from gome_tpu.engine import batch as B
from gome_tpu.engine.book import BookConfig, DeviceOp, init_book
from gome_tpu.ops.pallas_match import plan_block_s


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, n_slots, rows, t, cap, dense):
    cfg = BookConfig(cap=cap, max_fills=16, dtype=jnp.int32)
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
