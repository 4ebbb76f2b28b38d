"""Host-path observability (gome_tpu.obs.hostprof): the in-process
sampling profiler, the stage-join arithmetic, the gateway admit drill,
the /hostprof endpoint, the disabled hot-path contract, and the
committed HOSTPROF_r01 artifact — the ISSUE 10 surface."""

import json
import os
import signal
import sys
import time
import urllib.request

import pytest

from gome_tpu.obs import hostprof
from gome_tpu.obs.hostprof import (
    ADMIT_STAGES,
    HOST_STAGES,
    HOSTPROF,
    HostSampler,
    classify_node,
    classify_stack,
    stage_join,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _hostprof_disabled():
    """Every test leaves the process-global host profiler disabled (the
    hot-path default other tests assume)."""
    yield
    HOSTPROF.disable()


def _busy(seconds: float) -> int:
    """Pure-Python spin so both sampler modes (CPU- and wall-paced)
    accumulate samples."""
    deadline = time.perf_counter() + seconds
    acc = 0
    while time.perf_counter() < deadline:
        acc += sum(range(256))
    return acc


# --- the sampler ----------------------------------------------------------


def test_thread_sampler_bounds_and_ring_limits():
    """Thread mode samples this thread at wall pace; the ring honors
    ``keep`` and the distinct-stack counter honors ``max_stacks`` (the
    overflow bucket absorbs the rest, so sample totals never lie)."""
    s = HostSampler(hz=500.0, keep=8, max_stacks=4, mode="thread")
    s.start()
    try:
        _busy(0.25)
    finally:
        s.stop()
    assert s.mode_used == "thread"
    assert s.samples > 0, "wall-paced sampler captured nothing in 250ms"
    assert len(s.ring()) <= 8
    # max_stacks distinct keys + at most the overflow bucket
    counts = s.counts()
    assert len(counts) <= 5
    assert sum(counts.values()) == s.samples
    # stopped sampler is quiescent: totals stay put
    n = s.samples
    time.sleep(0.05)
    assert s.samples == n
    collapsed = s.collapsed()
    assert collapsed and all(
        line.rsplit(" ", 1)[1].isdigit()
        for line in collapsed.splitlines()
    )
    s.reset()
    assert s.samples == 0 and not s.counts() and not s.ring()


def test_walk_caps_depth_keeping_deepest_frames():
    s = HostSampler(mode="thread", max_depth=4)

    def recurse(n):
        if n:
            return recurse(n - 1)
        return s._walk(sys._getframe())

    stack = recurse(20)
    assert len(stack) == 4
    # deepest frames survive the cap: the leaf is _walk's caller (nodes
    # are module:qualname, so the nested function ends "<locals>.recurse")
    assert all(node.endswith(".recurse") for node in stack)


@pytest.mark.skipif(
    not hasattr(signal, "setitimer"), reason="no setitimer on platform"
)
def test_signal_sampler_smoke():
    """SIGPROF mode arms from the main thread and samples CPU-paced.
    The kernel tick bounds delivery (~CONFIG_HZ), so only a handful of
    samples is asserted, not the nominal hz."""
    s = HostSampler(hz=997.0, mode="signal")
    s.start()
    try:
        deadline = time.perf_counter() + 2.0
        while s.samples < 5 and time.perf_counter() < deadline:
            _busy(0.05)
    finally:
        s.stop()
    assert s.mode_used == "signal"
    assert s.samples >= 5, "SIGPROF delivered almost nothing in 2s of CPU"


def test_sampler_rejects_bad_args():
    with pytest.raises(ValueError):
        HostSampler(hz=0)
    with pytest.raises(ValueError):
        HostSampler(mode="perf")


# --- stage join: golden arithmetic on a scripted sample stream ------------


def test_classify_node_matches_qualname_leaf():
    # 3.11+ qualnames carry the class prefix; the rule function name
    # matches the LAST dotted component so both spellings classify.
    assert classify_node(
        "gome_tpu.service.gateway:OrderGateway._validate_add"
    ) == "validate"
    assert classify_node(
        "gome_tpu.service.gateway:_validate_add"
    ) == "validate"
    assert classify_node("gome_tpu.fixed:scale") == "order_build"
    assert classify_node("json:dumps") is None


def test_classify_stack_deepest_mapped_frame_wins():
    # json.dumps under encode_order rolls UP to codec_encode...
    assert classify_stack((
        "x:main",
        "gome_tpu.service.gateway:DoOrder",
        "gome_tpu.bus.codec:encode_order",
        "json:dumps",
    )) == "codec_encode"
    # ...while a deeper mapped frame beats the shallower ingress match
    assert classify_stack((
        "gome_tpu.service.gateway:DoOrder",
        "gome_tpu.service.gateway:_validate_add",
    )) == "validate"
    assert classify_stack(("x:main", "other:loop")) is None


def test_stage_join_golden_fixture():
    """Exact arithmetic over a hand-written sample stream: measured wall
    distributes by sampled share, stage rows + unattributed sum to the
    window, coverage is the attributed fraction."""
    counts = {
        ("x:main", "gome_tpu.service.gateway:DoOrder",
         "gome_tpu.service.gateway:_validate_add"): 10,
        ("x:main", "gome_tpu.service.gateway:DoOrder",
         "gome_tpu.service.gateway:order_from_request",
         "gome_tpu.types:__init__"): 20,
        ("x:main", "gome_tpu.service.gateway:DoOrder",
         "gome_tpu.service.gateway:order_from_request",
         "gome_tpu.fixed:scale"): 5,
        ("x:main", "gome_tpu.service.gateway:DoOrder",
         "gome_tpu.service.gateway:_traced_emit",
         "gome_tpu.bus.codec:encode_order", "json:dumps"): 25,
        ("x:main", "gome_tpu.service.gateway:DoOrder"): 30,
        ("x:main", "other:loop"): 10,
    }
    join = stage_join(counts, n_orders=1000, window_ns=1e9)
    assert join["total_samples"] == 100
    assert join["attributed_samples"] == 90
    assert join["coverage_pct"] == 90.0
    # 1e9 ns window / 1000 orders = 1e6 ns/order, split by sample share
    assert join["stages"] == {
        "ingress": {"samples": 30, "pct": 30.0, "ns_per_order": 300_000.0},
        "validate": {"samples": 10, "pct": 10.0, "ns_per_order": 100_000.0},
        "order_build": {"samples": 25, "pct": 25.0,
                        "ns_per_order": 250_000.0},
        "codec_encode": {"samples": 25, "pct": 25.0,
                         "ns_per_order": 250_000.0},
    }
    assert join["unattributed"] == {
        "samples": 10, "ns_per_order": 100_000.0,
    }
    # rows render in HOST_STAGES order (the taxonomy's pipeline order)
    order = [st for st in HOST_STAGES if st in join["stages"]]
    assert list(join["stages"]) == order
    # window identity: stage ns + unattributed ns == window / orders
    total_ns = sum(
        row["ns_per_order"] for row in join["stages"].values()
    ) + join["unattributed"]["ns_per_order"]
    assert total_ns == pytest.approx(1e6)


def test_stage_join_empty_counts():
    join = stage_join({}, n_orders=10, window_ns=1e6)
    assert join["total_samples"] == 0
    assert join["coverage_pct"] == 0.0
    assert join["stages"] == {}


# --- the gateway admit drill ----------------------------------------------


def test_gateway_drill_produces_admit_path_stages():
    """The drill splits the admit wall function-by-function. Thread mode
    (wall-paced, ~hz true cadence) keeps the sample count deterministic
    enough that every major admit stage shows up."""
    drill = hostprof.gateway_drill(
        n_orders=4000, mode="thread", hz=997.0,
        min_samples=200, max_rounds=8, seed=7,
    )
    assert drill["kind"] == "gateway_admit_drill"
    assert drill["orders"] >= 4000
    assert drill["admit_ns_per_order"] > 0
    assert drill["admit_orders_per_sec_per_core"] > 0
    assert drill["sampler"]["mode"] == "thread"
    assert drill["sampler"]["samples"] >= 200 or drill["rounds"] == 8
    for st in ("order_build", "codec_encode", "enqueue"):
        assert st in drill["stages"], (st, drill["stages"])
    assert set(drill["stages"]) <= set(HOST_STAGES)
    assert set(drill["stages"]) <= set(ADMIT_STAGES)
    # the window identity holds on real data too (0.1-rounding per row)
    rows = list(drill["stages"].values())
    total_ns = sum(r["ns_per_order"] for r in rows) + (
        drill["unattributed"]["ns_per_order"]
    )
    tol = 0.1 * (len(rows) + 1) + 0.2
    assert abs(total_ns - drill["admit_ns_per_order"]) <= tol
    assert ";" in drill["collapsed"]


def test_drill_requests_deterministic():
    a = hostprof._drill_requests(64, seed=7)
    b = hostprof._drill_requests(64, seed=7)
    assert [(r.SerializeToString(), d) for r, d in a] == [
        (r.SerializeToString(), d) for r, d in b
    ]
    assert any(is_del for _, is_del in a), "no cancels in the mix"


# --- the singleton: install / payload / gauges ----------------------------


def test_hostprof_install_drill_payload_and_gauges():
    from gome_tpu.utils.metrics import REGISTRY

    HOSTPROF.install(hz=101.0, keep_n=64)
    assert HOSTPROF.enabled
    HOSTPROF.note_admit(3)
    rep = HOSTPROF.drill(
        n_orders=1024, min_samples=16, max_rounds=2, seed=7
    )
    assert rep["stages"], "singleton drill attributed nothing"
    doc = HOSTPROF.payload()
    assert doc["enabled"] is True
    assert doc["hz"] == 101.0 and doc["keep"] == 64
    # the drill's own admits flow through note_admit too (>= the manual 3)
    assert doc["admits"] >= 3
    assert doc["drill"] is rep or doc["drill"] == rep
    assert doc["live"]["enabled"] is True
    metrics = REGISTRY.render()
    assert "gome_hostprof_samples_total" in metrics
    assert "gome_hostprof_admit_orders_per_sec_per_core" in metrics
    assert 'gome_hostprof_stage_ns_per_order{stage="validate"}' in metrics
    assert ";" in HOSTPROF.collapsed()  # drill fallback when live idle


def test_hostprof_endpoint_http_validity():
    from gome_tpu.config import Config, EngineConfig, OpsConfig
    from gome_tpu.obs.compile_journal import JOURNAL
    from gome_tpu.obs.profiler import PROFILER
    from gome_tpu.obs.timeline import TIMELINE
    from gome_tpu.service.app import EngineService

    cfg = Config(
        engine=EngineConfig(cap=16, max_fills=4, n_slots=4, max_t=4,
                            dtype="int32"),
        ops=OpsConfig(port=0, enabled=True),
    )
    svc = EngineService(cfg)
    assert HOSTPROF.enabled  # ops.hostprof armed the profiler at boot
    svc.ops.start()
    try:
        base = f"http://127.0.0.1:{svc.ops.port}"
        with urllib.request.urlopen(
            f"{base}/hostprof?drill=1", timeout=120
        ) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read().decode())
        assert doc["enabled"] is True
        drill = doc["drill"]
        assert drill and drill["sampler"]["samples"] > 0
        assert drill["stages"]
        with urllib.request.urlopen(
            f"{base}/hostprof?format=collapsed", timeout=30
        ) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert ";" in body, f"no collapsed stacks over HTTP: {body[:120]}"
    finally:
        svc.ops.stop()
        JOURNAL.disable()
        TIMELINE.disable()
        PROFILER.disable()


# --- disabled contract: no-op + zero hot-path allocations -----------------


def test_disabled_hostprof_is_inert():
    HOSTPROF.disable()
    assert not HOSTPROF.enabled
    assert HOSTPROF.payload() == {
        "enabled": False, "live": None, "drill": None,
    }
    assert HOSTPROF.collapsed() == "# hostprof disabled\n"
    HOSTPROF.start()  # all lifecycle hooks are no-ops while disabled
    HOSTPROF.stop()
    assert HOSTPROF.last_drill() is None


def test_disabled_admit_hook_allocates_nothing():
    """Same contract as TRACER/JOURNAL/TIMELINE/PROFILER: the gateway's
    per-order hook costs one attribute check and ZERO allocations when
    disabled."""
    HOSTPROF.disable()

    def drill(n):
        i = 0
        while i < n:
            HOSTPROF.note_admit()
            i += 1

    drill(64)  # warm any lazy caches
    before = sys.getallocatedblocks()
    drill(200)
    after = sys.getallocatedblocks()
    assert after - before <= 2, f"hot-path hook allocated {after - before}"


# --- the committed HOSTPROF_r01 artifact ----------------------------------


def test_hostprof_r01_artifact_pin():
    """Schema pin for the committed host roofline: the per-stage admit
    breakdown covers >= 80% of the measured admit wall, and the
    host-vs-device table carries the front-door mismatch."""
    path = os.path.join(REPO_ROOT, "HOSTPROF_r01.json")
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["artifact"] == "HOSTPROF_r01"
    drill = doc["drill"]
    assert drill["kind"] == "gateway_admit_drill"
    assert drill["orders"] > 0
    assert drill["admit_ns_per_order"] > 0
    assert drill["sampler"]["samples"] > 0
    assert drill["coverage_pct"] >= 80.0, (
        "stage map no longer explains the admit wall — re-run "
        "scripts/profile_consumer.py --gateway --out HOSTPROF_r01.json "
        "after extending STAGE_RULES"
    )
    # acceptance: stage ns/order rows sum to >= 80% of the admit wall
    stage_sum = sum(
        row["ns_per_order"] for row in drill["stages"].values()
    )
    assert stage_sum >= 0.8 * drill["admit_ns_per_order"]
    for st, row in drill["stages"].items():
        assert st in HOST_STAGES
        assert row["samples"] > 0 and row["ns_per_order"] >= 0
    # the function-by-function split actually split: validation and the
    # pre-pool mark are distinguishable from the handler shell
    assert "validate" in drill["stages"]
    assert "mark" in drill["stages"]
    roof = doc["roofline"]
    assert roof["host_gateway_admit"]["orders_per_sec_per_core"] > 0
    assert roof["front_door_mismatch_device_vs_gateway"] > 1
    assert roof["front_door_mismatch_consumer_vs_gateway"] > 1
