"""Bring-up guarantees (ISSUE 21): nothing on the served path hides the chip.

  * a compile / lowering / device-runtime error is not a poison order: it
    stops the consumer and turns health red, dead-lettering nothing;
  * geometry replay stays best-effort for stale manifests and re-raises
    compile errors;
  * EngineStats counts every dispatched grid by the kernel that ran it,
    and every kernel="pallas" grid that gave way to scan by its reason;
  * chip_smoke.py refuses to run off the chip without --rehearsal, and
    its rehearsal drives the whole command on the CPU.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gome_tpu.engine import BatchEngine, BookConfig
from gome_tpu.types import Action, Order, Side

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _add(oid, price, side=Side.BUY, volume=5, symbol="eth2usdt"):
    return Order(
        uuid="u", oid=oid, symbol=symbol, side=side, price=price,
        volume=volume, action=Action.ADD,
    )


def _refusing_step(*_a, **_k):
    raise jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: scoped vmem "
        "allocation exceeds the limit"
    )


# --- the dispatch decision ---------------------------------------------------


def test_plan_block_s_names_why_a_grid_gives_way():
    from gome_tpu.ops.pallas_match import kernel_plan, plan_block_s

    assert plan_block_s(10240, 256) == (128, None)
    assert plan_block_s(64, 1024) == (64, None)  # whole-axis block fits
    assert plan_block_s(100, 256) == (None, "unblockable_rows")
    assert plan_block_s(264, 64) == (None, "unblockable_rows")
    assert plan_block_s(128, 1024) == (None, "tile_over_budget")
    assert plan_block_s(512, 1024) == (None, "tile_over_budget")
    # Off the chip the compiled kernel cannot run at all ...
    assert kernel_plan(128, 256, jnp.int32) == (None, False, "no_tpu_backend")
    assert kernel_plan(128, 256, jnp.int64) == (None, False, "int64_books")
    # ... and the interpreter stands in: no layout rule, same VMEM budget.
    assert kernel_plan(128, 256, jnp.int32, interpret=True) == (128, True, None)
    assert kernel_plan(100, 256, jnp.int32, interpret=True) == (4, True, None)
    assert kernel_plan(128, 1024, jnp.int32, interpret=True) == (
        None, False, "tile_over_budget"
    )


def test_engine_stats_count_every_grid_by_the_kernel_that_ran_it():
    """Interpret path: the per-kernel counters add up to the grids
    dispatched, and the one grid whose book tile is over the VMEM budget
    gives way to scan with its reason counted."""
    eng = BatchEngine(
        BookConfig(cap=1024, max_fills=4, dtype=jnp.int32),
        n_slots=128, max_t=4, kernel="pallas", pallas_interpret=True,
    )
    calls = []
    real_step = eng._step

    def counting_step(*a, **k):
        calls.append(k.get("n_ops"))
        return real_step(*a, **k)

    eng._step = counting_step
    # 3 symbols -> an 8-row dense grid: the whole-axis block fits at any cap.
    few = [_add(f"a{i}", 100 + i, symbol=f"s{i % 3}") for i in range(9)]
    eng.process_columnar(few)
    # 100 symbols -> rows bucket to n_slots: a full [128, 4] grid. The
    # packer takes a grid's cap class from its lanes' resting-count bound;
    # with the bound past the 256 class (as after 300 ADDs a lane) the grid
    # runs at the storage cap 1024, whose 128-lane book tile is over the
    # budget.
    eng.note_packed_adds(np.full(eng.n_slots, 300))
    wide = [_add(f"b{i}", 100, symbol=f"w{i}") for i in range(100)]
    eng.process_columnar(wide)

    st = eng.stats
    assert st.grids_by_kernel.get("interpret_dense", 0) >= 1
    assert st.grids_by_kernel.get("scan_full", 0) >= 1
    assert st.scan_giveways == {"tile_over_budget": st.grids_by_kernel["scan_full"]}
    assert sum(st.grids_by_kernel.values()) == len(calls) == st.device_calls
    assert sum(st.ops_by_kernel.values()) == sum(calls) == len(few) + len(wide)
    assert set(st.ops_by_kernel) == set(st.grids_by_kernel)


def test_scan_engine_counts_scan_grids_and_no_giveways():
    eng = BatchEngine(BookConfig(cap=8, max_fills=4), n_slots=8, max_t=4)
    eng.process_columnar([_add("a", 100), _add("b", 100, side=Side.SALE)])
    assert sum(eng.stats.grids_by_kernel.values()) == eng.stats.device_calls
    assert all(k.startswith("scan_") for k in eng.stats.grids_by_kernel)
    assert eng.stats.scan_giveways == {}  # nobody asked for the kernel


# --- the two error splits ----------------------------------------------------


def test_device_fault_stops_consumer_and_health_without_dead_lettering():
    from gome_tpu.bus import encode_order
    from gome_tpu.config import Config, GrpcConfig
    from gome_tpu.service.app import EngineService
    from gome_tpu.service.consumer import _poisoned
    from gome_tpu.service.health import HealthMonitor, Watchdog

    svc = EngineService(Config(grpc=GrpcConfig(port=0)))
    svc.engine.batch._step = _refusing_step
    orders = [_add(f"o{i}", 100 + i) for i in range(3)]
    for o in orders:
        svc.engine.mark(o)
        svc.bus.order_queue.publish(encode_order(o))
    poisoned_before = _poisoned.value()

    svc.consumer.start()
    svc.feed.start()
    try:
        deadline = time.monotonic() + 20
        while svc.consumer._thread.is_alive():
            assert time.monotonic() < deadline, "consumer did not stop"
            time.sleep(0.01)
        assert "Mosaic failed to compile" in svc.consumer.device_fault
        # Nothing dead-lettered, nothing committed, marks restored: the
        # orders wait behind the committed offset for a working kernel.
        assert _poisoned.value() == poisoned_before
        assert svc.bus.order_queue.committed() == 0
        assert svc.bus.match_queue.end_offset() == 0
        for o in orders:
            assert svc.engine._prekey(o) in svc.engine.pre_pool
        health = HealthMonitor(svc).check()
        assert not health.healthy and not health.consumer_alive
        assert "Mosaic" in health.detail["device_fault"]
        # The watchdog does not flap a consumer the chip refuses.
        dog = Watchdog(svc)
        dog.check_once()
        assert not svc.consumer._thread.is_alive()
        assert dog._restart_times == []
    finally:
        svc.consumer.stop()
        svc.feed.stop()


def test_device_fault_is_never_bisected_by_the_poison_policy():
    """Even with the policy's threshold at 1 (quarantine on the first
    failure) a device fault dead-letters nothing — the poison-ORDER
    behaviour itself is pinned by test_advice_fixes / test_frames /
    test_rebasing."""
    from gome_tpu.bus import MemoryQueue, QueueBus, encode_order
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer, _poisoned

    engine = MatchEngine(
        config=BookConfig(cap=8, max_fills=4), n_slots=8, max_t=4
    )
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=16, batch_wait_s=0, poison_threshold=1
    )
    engine.batch._step = _refusing_step
    o = _add("only", 100)
    engine.mark(o)
    bus.order_queue.publish(encode_order(o))
    before = _poisoned.value()
    assert consumer.step_with_policy() == 0
    assert consumer.device_fault is not None and consumer._stop.is_set()
    assert _poisoned.value() == before
    assert bus.order_queue.committed() == 0
    # Called directly, the quarantine pass re-raises it too.
    with pytest.raises(jax.errors.JaxRuntimeError):
        consumer.quarantine_once()
    assert _poisoned.value() == before


def test_precompile_reraises_compile_error_and_skips_stale_combo(
        tmp_path, monkeypatch):
    from gome_tpu.engine import frames
    from gome_tpu.engine.orchestrator import MatchEngine

    engine = MatchEngine(
        config=BookConfig(cap=8, max_fills=4, dtype=jnp.int32),
        n_slots=8, max_t=4,
    )
    eng = engine.batch
    good = (8, 4, 8, False, 64, 4, 64, 64, 8)  # frames.COMBO_FIELDS order
    stale = (8, 4, 8)  # an older layout's arity
    assert frames.precompile_combos(eng, [stale, good]) == 1

    manifest = tmp_path / "geometry.json"
    manifest.write_text(json.dumps(
        {"floors": eng.geometry_floors(), "combos": [list(good)]}
    ))
    assert engine.load_geometry(str(manifest)) == 1

    # (the combo's buffers are under the one-phase rule: its replay is the
    # one program of a small frame's grid, not eng._step)
    monkeypatch.setattr(frames, "_grid_program", _refusing_step)
    with pytest.raises(jax.errors.JaxRuntimeError):
        frames.precompile_combos(eng, [stale, good])
    with pytest.raises(jax.errors.JaxRuntimeError):
        engine.load_geometry(str(manifest))
    # Staleness alone is still best-effort, compile errors or not.
    assert frames.precompile_combos(eng, [stale]) == 0


# --- chip_smoke.py -----------------------------------------------------------


def test_chip_smoke_refuses_the_cpu_and_rehearses_with_the_flag(tmp_path):
    """One subprocess each, run side by side: without the flag on CPU it
    exits non-zero before any work and prints no result; alone in a
    directory it exits non-zero too; the rehearsal passes, labels every
    line, its last line is the verdict (ok + device, no other key but the
    rehearsal label) and the line before it, the report, carries the
    oracle comparison."""
    smoke = os.path.join(REPO_ROOT, "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    run = lambda argv, cwd: subprocess.Popen(
        [sys.executable, *argv], cwd=cwd, env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    alone = tmp_path / "alone"
    alone.mkdir()
    (alone / "chip_smoke.py").write_text(open(smoke).read())
    procs = {
        "rehearsal": run(
            # 50-order requests on 64 lanes: the listing's first takes
            # the full grid, the later ones (live on under half the
            # lanes) dense ones
            [smoke, "--rehearsal", "--symbols", "64", "--cap", "8",
             "--max-fills", "4", "--max-t", "4", "--orders", "400",
             "--batches", "8"], REPO_ROOT,
        ),
        "no_flag": run([smoke], REPO_ROOT),
        "sized_without_flag": run([smoke, "--orders", "10"], REPO_ROOT),
        "alone": run(["chip_smoke.py"], str(alone)),
    }
    out = {k: (*p.communicate(timeout=300), p.returncode)
           for k, p in procs.items()}

    stdout, stderr, rc = out["no_flag"]
    assert rc == 3 and stdout == "" and "no TPU" in stderr
    stdout, stderr, rc = out["sized_without_flag"]
    assert rc == 2 and stdout == "" and "--rehearsal" in stderr
    stdout, stderr, rc = out["alone"]
    assert rc == 2 and stdout == "" and "not around this file" in stderr

    stdout, stderr, rc = out["rehearsal"]
    assert rc == 0, stderr[-3000:]
    lines = stdout.strip().splitlines()
    assert all("CPU REHEARSAL" in ln for ln in lines)
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"cpu_rehearsal", "ok", "device"}
    assert verdict["ok"] is True
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    doc = json.loads(lines[-2])
    assert doc["ok"] is True and doc["failures"] == []
    assert doc["device"] == verdict["device"]
    for key in (
        "platform", "device_kind", "device_count", "versions", "deployment",
        "native", "parity", "stream", "client", "orders", "events",
        "kernels", "engine", "compile", "phase_seconds",
    ):
        assert key in doc, key
    assert set(doc["versions"]) >= {"jax", "jaxlib", "libtpu"}
    assert doc["client"]["jax_imported"] is False
    assert doc["orders"]["sent"] == doc["orders"]["acknowledged"] == 400
    assert doc["orders"]["matched"] == 400
    ev = doc["events"]
    assert ev["equal"] and ev["oracle"] == ev["match_queue"] == ev["compared"] > 0
    assert doc["engine"]["step_failures"] == doc["engine"]["poison_orders"] == 0
    assert doc["engine"]["cap_escalations"] >= 1
    grids = doc["kernels"]["grids"]
    assert grids["interpret_full"] > 0 and grids["interpret_dense"] > 0
    assert sum(grids.values()) <= doc["kernels"]["device_calls"]
    assert doc["native"]["loaded"] is True
    assert {"count", "seconds", "cache_dir", "cache_cold"} <= set(doc["compile"])
    assert set(doc["phase_seconds"]) >= {"native", "stream", "boot", "serve", "compare"}
