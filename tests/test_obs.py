"""Device-level observability (gome_tpu.obs): cost model attribution,
compile journal, /cost endpoint, live-buffer accounting, and the perf
ratchet CLI — the ISSUE 5 surface."""

import json
import sys
import urllib.request

import numpy as np

import jax.numpy as jnp
import pytest

from gome_tpu.engine import frames
from gome_tpu.engine.batch import BatchEngine
from gome_tpu.engine.book import BookConfig
from gome_tpu.obs import JOURNAL, CompileJournal, costmodel, live
from gome_tpu.obs.compile_journal import frame_combo_detail
from gome_tpu.utils.metrics import Registry


@pytest.fixture(autouse=True)
def _journal_disabled():
    """Every test leaves the process-global journal disabled (the
    hot-path default other tests assume)."""
    yield
    JOURNAL.disable()


def _frame(n, n_symbols=4, seed=0, oid0=0, cancels=0.0):
    rng = np.random.default_rng(seed)
    action = np.ones(n, np.int64)
    if cancels:
        action[rng.random(n) < cancels] = 2
    return dict(
        n=n,
        action=action,
        side=rng.integers(0, 2, n).astype(np.int64),
        kind=np.zeros(n, np.int64),
        price=rng.integers(99_000, 101_000, n).astype(np.int64),
        volume=rng.integers(1, 10, n).astype(np.int64),
        symbols=[f"s{i}" for i in range(n_symbols)],
        symbol_idx=rng.integers(0, n_symbols, n).astype(np.int64),
        uuids=["u0"],
        uuid_idx=np.zeros(n, np.int64),
        oids=np.char.add(
            "o", np.arange(oid0, oid0 + n).astype("U8")
        ).astype("S"),
    )


def _engine(cap=16, n_slots=8, max_t=8):
    return BatchEngine(
        BookConfig(cap=cap, max_fills=4, dtype=jnp.int32),
        n_slots=n_slots, max_t=max_t,
    )


# --- cost model -----------------------------------------------------------


def test_cost_model_keys_present_per_entry():
    """Every hot-path entry reports the attribution keys on the CPU
    backend; fields a backend declines are None (skip-safe), never
    absent."""
    rows = costmodel.entry_report("int32")
    entries = {r["entry"] for r in rows if "error" not in r}
    for want in costmodel.RATCHET_ENTRIES:
        assert want in entries, f"missing cost-model entry {want}"
    for r in rows:
        if "error" in r:
            continue
        for key in (
            "flops", "bytes_accessed", "arithmetic_intensity",
            "argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
            "peak_hbm_bytes", "jaxpr_eqns", "context",
        ):
            assert key in r, (r["entry"], key)
        if r["flops"] is None:
            pytest.skip("backend returned no cost_analysis")
        assert r["flops"] >= 0
        assert r["bytes_accessed"] > 0
        assert r["jaxpr_eqns"] > 1  # unwrapped past the pjit wrapper
        if r.get("n_ops"):
            assert r["flops_per_order"] == pytest.approx(
                r["flops"] / r["n_ops"]
            )


def test_cost_model_reports_are_memoized():
    assert costmodel.entry_report("int32") is costmodel.entry_report("int32")
    assert (
        costmodel.donation_report("int32")
        is costmodel.donation_report("int32")
    )


def test_donation_report_twin_peak_le_public():
    """The donation-effectiveness report: each _donating twin's peak HBM
    must be <= its public entry's (the footprint win PR 4 claimed; a
    backend without donation support reports equality, never worse)."""
    report = costmodel.donation_report("int32")
    assert {d["entry"] for d in report if "error" not in d} >= {
        "batch_step", "dense_batch_step", "lane_scan"
    }
    for d in report:
        if "error" in d or d["peak_hbm_saved_bytes"] is None:
            continue
        assert (
            d["donating_peak_hbm_bytes"] <= d["public_peak_hbm_bytes"]
        ), d
        # CPU XLA implements donation for these graphs: the twin really
        # aliases buffers (the report is measuring something).
        assert d["donating_alias_bytes"] >= 0


def test_ratchet_metrics_flat_and_deterministic():
    m1 = costmodel.ratchet_metrics("int32")
    assert m1, "no gated metrics produced"
    for name, v in m1.items():
        assert isinstance(v, (int, float)) and v >= 0, (name, v)
    # memoized source => identical on re-read (the determinism the CI
    # gate relies on)
    assert costmodel.ratchet_metrics("int32") == m1


def test_bench_analytics_shape():
    block = costmodel.bench_analytics("int32")
    assert block["dtype"] == "int32"
    assert "batch_step" in block["entries"]
    assert "donation" in block
    json.dumps(block)  # bench payload must be JSON-serializable


# --- compile journal ------------------------------------------------------


def test_journal_records_miss_not_hit():
    """First dispatch of a shape combo lands in the journal; replaying
    the identical frame shape (all hits) records nothing new."""
    reg = Registry()
    j = CompileJournal().install(keep_n=16, registry=reg)
    # swap the global for the engine hook's benefit
    old = frames.JOURNAL
    frames.JOURNAL = j
    try:
        eng = _engine()
        frames.apply_frame_fast(eng, _frame(32, seed=1))
        first = j.entries()
        assert first, "no journal entries after first frame"
        assert all(e["entry"] == "frame_dispatch" for e in first)
        for e in first:
            assert e["seconds"] >= 0
            assert eng.combo_seen(e["key"])
            d = e["detail"]
            for key in (
                "grid_cells", "upload_bytes", "ops_grid_bytes",
                "record_bytes", "fetch_buffer_bytes", "scatter_jaxpr_eqns",
            ):
                assert key in d and d[key] != 0, (key, d)
        frames.apply_frame_fast(eng, _frame(32, seed=2, oid0=32))
        assert len(j.entries()) == len(first), "hit recorded as miss"
        # totals agree with the ring
        assert j.summary()["frame_dispatch"]["count"] == len(first)
        assert "gome_compile_seconds" in reg.render()
    finally:
        frames.JOURNAL = old


def test_journal_ring_is_bounded_but_totals_are_not():
    j = CompileJournal().install(keep_n=4, registry=Registry())
    for i in range(10):
        j.record("e", (i,), 0.01)
    assert len(j.entries()) == 4
    assert [e["key"] for e in j.entries()] == [(6,), (7,), (8,), (9,)]
    assert j.summary()["e"]["count"] == 10
    assert j.summary()["e"]["seconds"] == pytest.approx(0.1)


def test_journal_install_validates_and_disable_clears():
    j = CompileJournal()
    with pytest.raises(ValueError):
        j.install(keep_n=0)
    j.install(keep_n=2, registry=Registry())
    j.record("e", (1,), 0.5)
    assert j.enabled and j.entries()
    j.disable()
    assert not j.enabled and j.entries() == [] and j.summary() == {}
    j.record("e", (1,), 0.5)  # no-op, no crash
    assert j.entries() == []


def test_disabled_journal_allocates_nothing():
    """The no-op-singleton guard (same pattern as tests/test_trace.py):
    a disabled journal on the frame hot path is one attribute check and
    zero allocations."""
    j = CompileJournal()  # never installed
    assert not j.enabled

    def drill(n):
        i = 0
        while i < n:
            if j.enabled:
                raise AssertionError("unreachable")
            j.record("frame_dispatch", (1, 2, 3), 0.0)
            i += 1

    drill(64)  # warm any lazy caches
    before = sys.getallocatedblocks()
    drill(200)
    after = sys.getallocatedblocks()
    assert after - before <= 2, f"hot-path hooks allocated {after - before}"


def test_frame_combo_detail_arithmetic():
    combo = (8, 16, 64, True, 256, 4, 512, 64, 8)
    d = frame_combo_detail("int32", combo, 7)
    assert d["grid_cells"] == 128
    assert d["upload_bytes"] == 256 * (7 * 4 + 4)
    assert d["ops_grid_bytes"] == 128 * (3 * 4 + 4 * 4)
    assert d["record_bytes"] == 128 * 4 * 5 * 4
    assert d["fetch_buffer_bytes"] == (7 * 512 + 2 * 64) * 4 + 8 * 7 * 4
    assert d["dense"] is True


# --- /cost endpoint -------------------------------------------------------


def test_cost_endpoint_http_validity():
    from gome_tpu.config import Config, EngineConfig, OpsConfig
    from gome_tpu.service.app import EngineService

    cfg = Config(
        engine=EngineConfig(cap=16, max_fills=4, n_slots=4, max_t=4,
                            dtype="int32"),
        ops=OpsConfig(port=0, enabled=True),
    )
    svc = EngineService(cfg)
    assert JOURNAL.enabled  # ops.cost armed the journal at boot
    # one fast-path frame so the journal carries a real combo
    frames.apply_frame_fast(svc.engine.batch, _frame(16, seed=3))
    svc.ops.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{svc.ops.port}/cost", timeout=30
        ) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read().decode())
        assert doc["compile_journal"]["enabled"] is True
        assert doc["compile_journal"]["entries"], "journal empty over HTTP"
        assert doc["live_buffers"]["total"]["count"] > 0
        assert doc["live_buffers"]["subsystems"]["engine_books"]["bytes"] > 0
        entries = {
            e["entry"] for e in doc["cost_model"]["entries"]
            if "error" not in e
        }
        assert "batch_step" in entries
        donation = {d["entry"]: d for d in doc["cost_model"]["donation"]}
        assert "batch_step" in donation
        # /metrics carries the new families too
        with urllib.request.urlopen(
            f"http://127.0.0.1:{svc.ops.port}/metrics", timeout=10
        ) as r:
            metrics = r.read().decode()
        assert "gome_compile_seconds" in metrics
        assert 'gome_hbm_resident_bytes{subsystem="engine_books"}' in metrics
        assert "gome_live_arrays" in metrics
    finally:
        svc.ops.stop()


# --- live-buffer accounting ----------------------------------------------


def test_live_array_stats_sees_allocations():
    import jax

    base = live.live_array_stats()
    held = [jnp.zeros((128,), jnp.int32) for _ in range(4)]
    jax.block_until_ready(held)
    now = live.live_array_stats()
    assert now["count"] >= base["count"] + 4
    assert now["bytes"] >= base["bytes"] + 4 * 128 * 4
    del held
    after = live.live_array_stats()
    assert after["count"] <= base["count"] + 1


def test_pytree_stats_counts_leaves():
    eng = _engine()
    s = live.pytree_stats(eng.books)
    assert s["count"] == 7  # BookState leaves
    assert s["bytes"] > 0


def test_leak_detector_on_scripted_loops():
    """A loop that retains a buffer per step is flagged; a loop whose
    allocations die each step is flat."""
    leak: list = []

    def leaking():
        leak.append(jnp.zeros((64,), jnp.int32) + 1)

    report = live.leak_report(leaking, steps=4, settle=2)
    assert report["leaked"] >= 4, report
    with pytest.raises(AssertionError):
        live.assert_steady_state(leaking, steps=3, settle=1)
    leak.clear()

    def steady():
        x = jnp.zeros((64,), jnp.int32) + 1
        x.block_until_ready()

    report = live.assert_steady_state(steady, steps=4, settle=2)
    assert report["leaked"] <= 0


def test_live_monitor_gauges():
    eng = _engine()
    reg = Registry()
    mon = live.LiveBufferMonitor().register("books", lambda: eng.books)
    mon.export(reg)
    text = reg.render()
    assert 'gome_hbm_resident_bytes{subsystem="books"}' in text
    snap = mon.snapshot()
    assert snap["subsystems"]["books"]["bytes"] == live.pytree_stats(
        eng.books
    )["bytes"]


# --- perf ratchet CLI -----------------------------------------------------


@pytest.fixture(scope="module")
def ratchet():
    sys.path.insert(
        0, str(__import__("pathlib").Path(__file__).parent.parent / "scripts")
    )
    import perf_ratchet

    return perf_ratchet


def test_perf_ratchet_end_to_end(ratchet, tmp_path, capsys):
    base = tmp_path / "PERF_BASELINE.json"
    report = tmp_path / "report.json"

    # no baseline -> explicit failure telling the operator what to do
    assert ratchet.main(["--baseline", str(base)]) == 1

    # --update-baseline writes it; the gate then passes
    assert ratchet.main(
        ["--baseline", str(base), "--update-baseline"]
    ) == 0
    doc = json.loads(base.read_text())
    assert doc["metrics"] and doc["jax"]
    assert "frame_drill.compile_count" in doc["metrics"]
    assert ratchet.main(
        ["--baseline", str(base), "--report", str(report)]
    ) == 0
    gated = json.loads(report.read_text())["gated"]
    # Same metric set; the analytic rows are bit-identical run to run,
    # the wall-clock admit rows (gated with 3x headroom) are not.
    assert set(gated) == set(doc["metrics"])
    wallclock = set(ratchet.WALLCLOCK_GATED)
    for name, v in gated.items():
        if name not in wallclock:
            assert v == doc["metrics"][name], name
    # The wall-clock rows carry their wide per-metric tolerance in the
    # committed baseline document.
    for name in wallclock & set(doc["metrics"]):
        assert doc["tolerance"][name] == ratchet.WALLCLOCK_TOLERANCE

    # deliberate fixture regression: shrink a baseline value -> the
    # current (unchanged) code now reads as regressed and the gate fails
    doc["metrics"]["batch_step.flops_per_order"] *= 0.5
    doc["metrics"]["frame_drill.compile_count"] -= 1
    base.write_text(json.dumps(doc))
    assert ratchet.main(["--baseline", str(base)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION batch_step.flops_per_order" in out
    assert "REGRESSION frame_drill.compile_count" in out


def test_perf_ratchet_jax_version_mismatch_downgrades_xla_gates(
    ratchet, tmp_path
):
    base = tmp_path / "PERF_BASELINE.json"
    assert ratchet.main(
        ["--baseline", str(base), "--update-baseline"]
    ) == 0
    doc = json.loads(base.read_text())
    doc["jax"] = "0.0.0-other"
    # an XLA metric "regression" under a DIFFERENT toolchain is advisory…
    doc["metrics"]["batch_step.flops_per_order"] *= 0.5
    base.write_text(json.dumps(doc))
    assert ratchet.main(["--baseline", str(base)]) == 0
    # …but the version-independent compile count still gates hard
    doc["metrics"]["frame_drill.compile_count"] -= 1
    base.write_text(json.dumps(doc))
    assert ratchet.main(["--baseline", str(base)]) == 1


def test_committed_baseline_gates_green():
    """The repo's committed PERF_BASELINE.json must pass against the
    current code on this toolchain — CI runs exactly this gate."""
    import pathlib
    import subprocess

    root = pathlib.Path(__file__).parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "scripts" / "perf_ratchet.py")],
        capture_output=True, text=True, cwd=root,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
