"""gomelint golden fixtures: every rule family fires on seeded-bad input,
stays silent on the idiomatic good twin, honors suppressions — and the
whole tree comes back clean (the same gate CI's analysis job enforces)."""

from __future__ import annotations

import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from gome_tpu.analysis import run_source
from gome_tpu.analysis.core import rule_catalogue, run_paths
from gome_tpu.analysis.envelope import check_engine_envelope, check_jaxpr
from gome_tpu.analysis.runtime import (
    LockDisciplineError,
    OwnedLock,
    instrument,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# --- GL1xx trace-safety ---------------------------------------------------


BAD_TRACE = '''
import jax
import numpy as np

@jax.jit
def f(x, y):
    if x > 0:
        y = y + 1
    v = float(x)
    w = x.item()
    z = np.asarray(y)
    for row in x:
        z = z + 1
    return v + w
'''


def test_trace_safety_flags_bad_fixture():
    findings = run_source(BAD_TRACE)
    assert rules_of(findings) == ["GL101", "GL102", "GL103", "GL104"]
    # the `if` and the `for` are two distinct GL103 sites
    assert sum(f.rule == "GL103" for f in findings) == 2


def test_trace_safety_propagates_through_call_graph():
    src = '''
import jax

def helper(a):
    return int(a)

@jax.jit
def g(x):
    return helper(x)
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL101"]
    assert "helper" in findings[0].message


def test_trace_safety_scan_body_is_traced():
    src = '''
import jax

@jax.jit
def g(xs):
    def body(carry, x):
        return carry, float(x)
    return jax.lax.scan(body, 0, xs)
'''
    assert rules_of(run_source(src)) == ["GL101"]


GOOD_TRACE = '''
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnums=0)
def f(config, x):
    n = x.shape[-1]
    if config.cap > 4:          # static arg: host branching is fine
        x = x + 1
    k = 1
    while k < n:                # shape-derived bound: static under trace
        x = x + jnp.pad(x[..., :-k], [(0, 0)] * (x.ndim - 1) + [(k, 0)])
        k *= 2
    if jnp.dtype(x.dtype).itemsize <= 4:
        x = jnp.minimum(x, 7)
    return x

def host_only(a):
    return float(a.sum())       # not reachable from any jit entry
'''


def test_trace_safety_good_twin_is_clean():
    assert run_source(GOOD_TRACE) == []


def test_trace_safety_identity_test_is_static():
    # `x is None` never concretizes a tracer — branching on it is host-
    # static (the bench's mixed full/dense round-chain relies on this)
    src = '''
import jax

@jax.jit
def f(x, ids):
    if ids is None:
        return x
    return x + 1
'''
    assert run_source(src) == []


def test_trace_safety_namedtuple_unroll_idiom_is_clean():
    # the engine/step.py idiom: iterate a host container of tracers
    src = '''
import jax

@jax.jit
def f(own, entry):
    out = list(own)
    for a in out:
        a = a + 1
    pairs = [a + v for a, v in zip(own, entry)]
    return pairs
'''
    assert run_source(src) == []


def test_trace_safety_line_suppression():
    src = '''
import jax

@jax.jit
def f(x):
    return float(x)  # gomelint: disable=GL101 — fixture-sanctioned
'''
    assert run_source(src) == []
    assert rules_of(run_source(src, keep_suppressed=True)) == ["GL101"]


def test_file_suppression():
    src = '''
# gomelint: disable-file=GL101
import jax

@jax.jit
def f(x):
    return float(x)
'''
    assert run_source(src) == []


# --- GL2xx int32-envelope (jaxpr) ----------------------------------------


def test_envelope_flags_float_and_width_creep():
    x = jnp.zeros((4,), jnp.int32)
    f32 = jax.make_jaxpr(lambda v: v.astype(jnp.float32) * 2.5)(x)
    assert rules_of(check_jaxpr(f32, "int32", "fixture")) == ["GL202"]

    with jax.enable_x64(True):
        i64 = jax.make_jaxpr(
            lambda v: v.astype(jnp.int64) + 1
        )(jnp.zeros((4,), jnp.int32))
        f64 = jax.make_jaxpr(lambda v: v * 2.5)(jnp.zeros((4,), jnp.float64))
    assert rules_of(check_jaxpr(i64, "int32", "fixture")) == ["GL203"]
    assert "GL201" in rules_of(check_jaxpr(f64, "int32", "fixture"))


def test_envelope_recurses_into_nested_jaxprs():
    # the creep hides inside a scan body — the walk must find it
    def body(c, x):
        return c, x.astype(jnp.float32) * 0.5

    closed = jax.make_jaxpr(
        lambda xs: jax.lax.scan(body, jnp.int32(0), xs)
    )(jnp.zeros((4,), jnp.int32))
    assert "GL202" in rules_of(check_jaxpr(closed, "int32", "nested"))


def test_envelope_int64_engine_allows_int64():
    with jax.enable_x64(True):
        i64 = jax.make_jaxpr(
            lambda v: v + 1
        )(jnp.zeros((4,), jnp.int64))
    assert check_jaxpr(i64, "int64", "fixture") == []


@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_engine_envelope_clean(dtype):
    """The real engine graphs — step, batch, dense, compaction, scatter,
    pallas-interpret — audited in the dtype's native x64 mode."""
    assert check_engine_envelope(dtype) == []


@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_envelope_audits_sim_generator(dtype):
    """The sim flow generator rides the same envelope audit: its entry
    must be traced (allow_floats — the Hawkes intensities are f32 by
    design) and `test_engine_envelope_clean` above proves it clean."""
    from gome_tpu.analysis.envelope import traced_entries

    contexts = [rec["context"] for rec in traced_entries(dtype)]
    assert "sim/flow.py:gen_ops" in contexts


def test_envelope_allow_floats_still_flags_strong_f64():
    """The weak-f64 scalar exemption (jax library python literals, e.g.
    inside jax.random under x64) must not exempt STRONG float64 values
    under allow_floats."""
    with jax.enable_x64(True):
        strong = jax.make_jaxpr(
            lambda v: v * 2.0
        )(jnp.zeros((4,), jnp.float64))
        weak_scalar = jax.make_jaxpr(
            lambda k: jax.random.uniform(k, (), jnp.float32)
        )(jax.random.PRNGKey(0))
    assert "GL201" in rules_of(
        check_jaxpr(strong, "int64", "fixture", allow_floats=True)
    )
    assert check_jaxpr(
        weak_scalar, "int64", "fixture", allow_floats=True
    ) == []


# --- GL3xx recompile-hazard ----------------------------------------------


BAD_RECOMPILE = '''
import functools
import jax

def make(n):
    @jax.jit
    def f(x):
        return x * n
    return f

def run(x):
    return jax.jit(lambda v: v + 1)(x)

class Engine:
    @jax.jit
    def step(self, x):
        return x

y = jax.jit(lambda x: x, static_argnums=(0,))([1, 2])
'''


def test_recompile_flags_bad_fixture():
    assert rules_of(run_source(BAD_RECOMPILE)) == [
        "GL301", "GL302", "GL303", "GL304",
    ]


GOOD_RECOMPILE = '''
import functools
import jax

@functools.lru_cache(maxsize=256)
def make(n):                     # the engine/frames.py factory idiom
    @jax.jit
    def f(x):
        return x * n
    return f

@jax.jit
def top(x):
    return x

step = functools.partial(jax.jit, static_argnums=0)(top)
'''


def test_recompile_good_twin_is_clean():
    assert run_source(GOOD_RECOMPILE) == []


# --- GL4xx lock-discipline -----------------------------------------------


BAD_LOCKS = '''
import threading

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._buf = []          # guarded by self._lock
        self.total = 0          # guarded by self._lock
        self._ghost = 0         # guarded by self._missing

    def submit(self, o):
        with self._lock:
            self._buf.append(o)
        self.total += 1

    def peek(self):
        return len(self._buf)

    def escape(self):
        with self._lock:
            return lambda: self._buf.pop()
'''


def test_locks_flags_bad_fixture():
    findings = run_source(BAD_LOCKS)
    assert rules_of(findings) == ["GL401", "GL402", "GL403"]
    lines = {f.rule: f.line for f in findings}
    assert lines["GL401"] == 14  # self.total += 1 off-lock
    # the closure escaping the with-block is an off-lock read
    assert any(f.rule == "GL402" and f.line == 21 for f in findings)


GOOD_LOCKS = '''
import threading

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._buf = []          # guarded by self._lock
        self.total = 0          # guarded by self._lock

    def submit(self, o):
        with self._lock:
            self._buf.append(o)
            self.total += 1

    def _flush_locked(self):
        batch, self._buf = self._buf, []
        return batch

    # holds: self._lock
    def annotated(self):
        return list(self._buf)

    def flush(self):
        with self._lock:
            return self._flush_locked()
'''


def test_locks_good_twin_is_clean():
    assert run_source(GOOD_LOCKS) == []


def test_locks_condition_counts_as_lock():
    src = '''
import threading

class Q:
    def __init__(self):
        self._cond = threading.Condition()
        self._n = 0  # guarded by self._cond

    def bump(self):
        with self._cond:
            self._n += 1
            self._cond.notify_all()
'''
    assert run_source(src) == []


# --- GL4xx runtime assertion mode ----------------------------------------


class _Thing:
    def __init__(self):
        self._lock = threading.Lock()
        self.counter = 0

    def bump(self):
        with self._lock:
            self.counter += 1

    def racy_bump(self):
        self.counter += 1


def test_runtime_instrument_catches_off_lock_write():
    t = _Thing()
    lock = instrument(t, ("counter",))
    t.bump()  # disciplined write: fine
    assert t.counter == 1
    with pytest.raises(LockDisciplineError):
        t.racy_bump()
    # the violating write did not land
    assert t.counter == 1
    assert isinstance(lock, OwnedLock)


def test_runtime_owned_lock_tracks_owner():
    lock = OwnedLock()
    assert not lock.held_by_me()
    with lock:
        assert lock.held_by_me()
        seen = []
        th = threading.Thread(target=lambda: seen.append(lock.held_by_me()))
        th.start()
        th.join()
        assert seen == [False]
    assert not lock.held_by_me()


def test_runtime_instrument_on_real_batcher():
    """The production FrameBatcher under runtime assertions: a full
    submit/flush cycle never writes its guarded state off-lock."""
    from gome_tpu.bus.memory import MemoryQueue
    from gome_tpu.service.batcher import FrameBatcher
    from gome_tpu.types import Action, Order, OrderType, Side

    b = FrameBatcher(MemoryQueue("doOrder"), max_n=2, max_wait_s=60)
    try:
        instrument(b, ("_buf", "_spill", "_oldest", "_degraded_since"))
        for i in range(4):
            b.submit(Order(
                uuid="u", oid=f"o{i}", symbol="S", side=Side.BUY,
                price=100, volume=1, action=Action.ADD,
                order_type=OrderType.LIMIT,
            ))
        b.flush()
    finally:
        b.close()


# --- GL7xx thread-escape analysis -----------------------------------------


BAD_THREADS = '''
import threading

class Feed:
    def __init__(self):
        self._lock = threading.Lock()
        self.events = 0
        self.state = "idle"
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        self.events += 1

    def set_state(self, s):
        with self._lock:
            self.state = s
'''


def test_threads_flags_bad_fixture():
    findings = run_source(BAD_THREADS)
    assert rules_of(findings) == ["GL701", "GL702"]
    lines = {f.rule: f.line for f in findings}
    assert lines["GL701"] == 12  # self.events += 1, no lock, no contract
    assert lines["GL702"] == 16  # self.state under an undeclared lock
    assert "owns a thread" in findings[0].message


GOOD_THREADS = '''
import threading

class Feed:
    def __init__(self):
        self._lock = threading.Lock()
        self.events = 0  # guarded by self._lock
        self.state = "idle"  # single-writer: the fan-out loop
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        with self._lock:
            self.events += 1
        self.state = "running"
'''


def test_threads_good_twin_is_clean():
    assert run_source(GOOD_THREADS) == []


def test_threads_singleton_escape_root():
    """A module-level ALL-CAPS singleton escapes its class even with no
    thread of its own — every importing thread can reach it."""
    src = '''
class Registry:
    def __init__(self):
        self.installed = False

    def install(self):
        self.installed = True

REGISTRY = Registry()
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL701"]
    assert "module-level singleton REGISTRY" in findings[0].message
    # lowercase module assignment is NOT an escape root
    assert run_source(src.replace("REGISTRY", "_registry")) == []


def test_threads_transitive_construction_escapes():
    """`self.seq = SeqTracker()` inside an escaped class escapes
    SeqTracker too (its instance rides the shared object)."""
    src = '''
import threading

class Tracker:
    def __init__(self):
        self.seen = 0

    def observe(self):
        self.seen += 1

class Feed:
    def __init__(self):
        self.seq = Tracker()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        self.seq.observe()
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL701"]
    assert "constructed into escaped Feed" in findings[0].message


def test_threads_gl703_contradictory_contracts():
    src = '''
import threading

class Both:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0  # guarded by self._lock; also  # single-writer: loop
'''
    findings = run_source(src)
    # flagged even though Both never escapes: the annotation is
    # self-contradictory wherever it lives
    assert rules_of(findings) == ["GL703"]


def test_threads_gl704_second_writer_outside_thread():
    src = '''
import threading

class Sampler:
    def __init__(self):
        self.count = 0  # single-writer: the tick thread
        self._thread = threading.Thread(target=self._tick, daemon=True)

    def _tick(self):
        self.count += 1

    def reset(self):
        self.count = 0
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL704"]
    assert findings[0].line == 13  # reported at the OUTSIDE site
    assert "line 10" in findings[0].message  # with the thread-side witness


def test_threads_gl704_suppression_with_justification():
    src = '''
import threading

class Sampler:
    def __init__(self):
        self.count = 0  # single-writer: the tick thread
        self._thread = threading.Thread(target=self._tick, daemon=True)

    def _tick(self):
        self.count += 1

    def reset(self):
        self.count = 0  # gomelint: disable=GL704 — called before start()
'''
    assert run_source(src) == []


def test_threads_class_level_single_writer_claim():
    """A `# single-writer` on the class line covers every attribute —
    the whole-object claim (SeqTracker, HostSampler idiom)."""
    src = '''
class Tracker:  # single-writer: the observe() caller
    def __init__(self):
        self.seen = 0

    def observe(self):
        self.seen += 1

TRACKER = Tracker()
'''
    assert run_source(src) == []


def test_threads_guarded_contract_hands_off_to_gl4():
    """A declared guard makes GL7xx stand down — and GL4xx take over:
    the same off-lock mutation now fires GL401 instead of GL70x."""
    src = '''
import threading

class Feed:
    def __init__(self):
        self._lock = threading.Lock()
        self.events = 0  # guarded by self._lock
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        self.events += 1
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL401"]


# --- whole-tree clean runs (the CI gate) ---------------------------------


def test_whole_tree_is_clean():
    findings = run_paths([os.path.join(ROOT, "gome_tpu")])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_exits_zero_on_tree_and_lists_rules():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "gomelint.py"),
         os.path.join(ROOT, "gome_tpu"), "--report",
         os.path.join(ROOT, ".gomelint-test-report.json")],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout
    import json
    with open(os.path.join(ROOT, ".gomelint-test-report.json")) as fh:
        report = json.load(fh)
    assert report["count"] == 0
    os.unlink(os.path.join(ROOT, ".gomelint-test-report.json"))

    rules = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "gomelint.py"),
         "--list-rules"],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    assert rules.returncode == 0
    for rule in ("GL101", "GL201", "GL301", "GL401"):
        assert rule in rules.stdout


def test_rule_catalogue_covers_all_families():
    from gome_tpu.analysis import envelope  # noqa: F401 — registers GL2xx
    cat = rule_catalogue()
    for family in ("GL1", "GL2", "GL3", "GL4", "GL5", "GL6", "GL7",
                   "GL8", "GL9"):
        assert any(r.startswith(family) for r in cat), family


# --- GL5xx transfer-hygiene (hot-path engine) ----------------------------


HOT_PREAMBLE = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def device_step(x):
    return x * 2
'''

BAD_TRANSFERS = HOT_PREAMBLE + '''
def hot(engine, orders):  # gomelint: hotpath
    outs = device_step(orders)
    total = outs[0].item()                      # GL501
    host = np.asarray(outs)                     # GL502
    if outs.sum() > 0:                          # GL503
        total += 1
    for i in range(4):
        jax.block_until_ready(outs)             # GL504
        up = jnp.asarray(np.zeros(8))           # GL505
    return total, host, up
'''


def test_transfers_flags_every_rule():
    findings = run_source(BAD_TRANSFERS)
    assert rules_of(findings) == [
        "GL501", "GL502", "GL503", "GL504", "GL505",
    ]


GOOD_TRANSFERS = HOT_PREAMBLE + '''
def hot(engine, orders):  # gomelint: hotpath
    grid = jnp.asarray(np.zeros(8))             # transfer OUTSIDE the loop
    outs = device_step(grid)
    host = np.asarray(jax.device_get(outs))     # the sanctioned fetch
    jax.block_until_ready(outs)                 # drain once, not per-item
    if host.sum() > 0:                          # host-side branch
        return float(host[0])                   # host scalar: no sync
    return 0.0
'''


def test_transfers_good_twin_is_clean():
    assert run_source(GOOD_TRANSFERS) == []


def test_transfers_silent_off_hot_path():
    # identical body, no hotpath annotation: cold code may sync freely
    cold = BAD_TRANSFERS.replace("  # gomelint: hotpath", "")
    assert run_source(cold) == []


def test_transfers_silent_inside_jit():
    # inside traced code the same idioms are GL1xx's domain, not GL5xx's
    src = HOT_PREAMBLE + '''
def hot(x):  # gomelint: hotpath
    return traced(x)

@jax.jit
def traced(x):
    return x.item()
'''
    findings = run_source(src)
    assert not any(f.rule.startswith("GL5") for f in findings)
    assert any(f.rule == "GL102" for f in findings)  # GL1xx still covers it


def test_transfers_suppression():
    src = HOT_PREAMBLE + '''
def hot(x):  # gomelint: hotpath
    outs = device_step(x)
    return outs.item()  # gomelint: disable=GL501 — single drain point
'''
    assert run_source(src) == []


# --- hot-path reachability (analysis.callgraph) --------------------------


def test_hotpath_seed_on_preceding_line():
    src = HOT_PREAMBLE + '''
# gomelint: hotpath
def loop(x):
    outs = device_step(x)
    return float(outs)
'''
    assert rules_of(run_source(src)) == ["GL501"]


def test_hotpath_propagates_through_calls():
    src = HOT_PREAMBLE + '''
def loop(x):  # gomelint: hotpath
    return helper(x)

def helper(x):
    outs = device_step(x)
    return outs.tolist()
'''
    findings = run_source(src)
    assert rules_of(findings) == ["GL501"]
    assert "helper" in findings[0].message


def test_hotpath_callback_edge():
    # a function REFERENCED (not called) from hot code is conservatively hot
    src = HOT_PREAMBLE + '''
import threading

class Consumer:
    def start(self):  # gomelint: hotpath
        t = threading.Thread(target=self._loop)
        t.start()

    def _loop(self):
        outs = device_step(1)
        while outs.any():                        # GL503 via callback edge
            pass
'''
    assert rules_of(run_source(src)) == ["GL503"]


def test_hotpath_closure_edge():
    src = HOT_PREAMBLE + '''
def loop(x):  # gomelint: hotpath
    def inner():
        outs = device_step(x)
        return int(outs)
    return inner()
'''
    assert rules_of(run_source(src)) == ["GL501"]


def test_hotpath_cross_module():
    from gome_tpu.analysis import run_sources

    mods = {
        "svc/consumer.py": HOT_PREAMBLE + '''
from engine import apply

def run_once(x):  # gomelint: hotpath
    return apply(x)
''',
        "engine/impl.py": HOT_PREAMBLE + '''
def apply(x):
    outs = device_step(x)
    return float(outs)                           # GL501, hot via consumer
''',
    }
    findings = run_sources(mods)
    assert [f.rule for f in findings] == ["GL501"]
    assert findings[0].path == "engine/impl.py"


# --- GL6xx buffer-donation ------------------------------------------------


def _avals(*specs):
    return [tuple(s) for s in specs]


def test_donation_gl601_fires_and_donating_twin_is_silent():
    from gome_tpu.analysis.donation import audit_donation

    out = _avals(((8, 128), "int32"), ((8,), "int32"))
    args = [None, _avals(((8, 128), "int32"), ((8,), "int32"))]
    bad = audit_donation("m.py:step", args, static_argnums=(0,),
                         donate_argnums=(), out_avals=out)
    assert [f.rule for f in bad] == ["GL601"]
    good = audit_donation("m.py:step", args, static_argnums=(0,),
                          donate_argnums=(1,), out_avals=out)
    assert good == []


def test_donation_gl601_ignores_immaterial_args():
    from gome_tpu.analysis.donation import audit_donation

    out = _avals(((1024, 64), "int32"), ((8,), "int32"))
    args = [_avals(((8,), "int32"))]  # a lane-id sliver: matching but tiny
    assert audit_donation("m.py:f", args, (), (), out) == []


def test_donation_gl602_fires_on_useless_donation():
    from gome_tpu.analysis.donation import audit_donation

    out = _avals(((8, 128), "int32"))
    bad = audit_donation(
        "m.py:f", [_avals(((4, 4), "float32"))], static_argnums=(),
        donate_argnums=(0,), out_avals=out,
    )
    assert [f.rule for f in bad] == ["GL602"]
    good = audit_donation(
        "m.py:f", [_avals(((8, 128), "int32"))], static_argnums=(),
        donate_argnums=(0,), out_avals=out,
    )
    assert good == []


DONATING_DEF = '''
import functools, jax

@functools.partial(jax.jit, donate_argnums=(0,))
def stepd(state, ops):
    return state + ops, ops
'''


def test_donation_gl603_fires_on_use_after_donation():
    src = DONATING_DEF + '''
def bad_caller(state, ops):
    new, _ = stepd(state, ops)
    return state.sum() + new          # state was donated: deleted
'''
    findings = run_source(src)
    assert [f.rule for f in findings] == ["GL603"]


def test_donation_gl603_rebind_and_return_are_clean():
    src = DONATING_DEF + '''
def rebinding(state, ops):
    state, _ = stepd(state, ops)      # the rebind IS the death
    return state

def tail(state, ops):
    if ops is None:
        return stepd(state, ops)      # returns: nothing after reads state
    return state.sum()
'''
    assert run_source(src) == []


def test_engine_donation_audit_is_clean():
    """The committed donation policy (twins donated, books retained with
    justified suppressions) audits clean — the acceptance gate."""
    from gome_tpu.analysis.core import apply_file_suppressions
    from gome_tpu.analysis.donation import check_engine_donation

    findings = apply_file_suppressions(check_engine_donation("int32"), ROOT)
    assert findings == [], "\n".join(f.format() for f in findings)


# --- baseline fingerprints + ratchet -------------------------------------


def test_fingerprint_survives_line_drift_and_file_moves(tmp_path):
    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding

    a = tmp_path / "a.py"
    a.write_text("x = 1\nbad_line = sync()\n")
    f1 = Finding("GL501", str(a), 2, 0, "sync on hot path [hot path: f]")
    [(_, fp1)] = fingerprint_findings([f1])

    # line drift: same content three lines lower
    a.write_text("# pad\n# pad\nx = 1\nbad_line = sync()\n")
    f2 = Finding("GL501", str(a), 4, 0, "sync on hot path [hot path: f]")
    [(_, fp2)] = fingerprint_findings([f2])
    assert fp1 == fp2

    # file move: same content under a new path
    b = tmp_path / "moved" ; b.mkdir()
    bb = b / "renamed.py"
    bb.write_text("bad_line = sync()\n")
    f3 = Finding("GL501", str(bb), 1, 0, "sync on hot path [hot path: f]")
    [(_, fp3)] = fingerprint_findings([f3])
    assert fp1 == fp3

    # changed code on the flagged line => new fingerprint
    bb.write_text("bad_line = other_sync()\n")
    [(_, fp4)] = fingerprint_findings([f3])
    assert fp4 != fp1


def test_fingerprint_disambiguates_identical_findings(tmp_path):
    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding

    a = tmp_path / "a.py"
    a.write_text("v = s()\nv = s()\n")
    fs = [Finding("GL501", str(a), 1, 0, "m"),
          Finding("GL501", str(a), 2, 0, "m")]
    fps = [fp for _, fp in fingerprint_findings(fs)]
    assert len(set(fps)) == 2


def test_fingerprint_stable_under_duplicate_line_reorder(tmp_path):
    """Property: permuting identical-text duplicate lines within a file
    (moving whole statement blocks around) leaves the fingerprint
    multiset untouched — the occurrence index is an ordinal among
    interchangeable duplicates, never a position hash."""
    import itertools

    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding

    blocks = ["v = s()", "w = t()", "v = s()", "u = r()", "v = s()"]
    a = tmp_path / "a.py"

    def fps_for(order):
        lines = [blocks[i] for i in order]
        a.write_text("\n".join(lines) + "\n")
        fs = [Finding("GL501", str(a), ln + 1, 0, "m")
              for ln, text in enumerate(lines) if text == "v = s()"]
        return sorted(fp for _, fp in fingerprint_findings(fs))

    base = fps_for(range(5))
    assert len(set(base)) == 3  # three duplicates, three distinct indices
    for order in itertools.permutations(range(5)):
        assert fps_for(order) == base, order


def test_fingerprint_occurrence_index_is_file_scoped(tmp_path):
    """Renaming one module must not renumber another module's duplicate-
    key findings ('moving a module keeps its findings baselined'). The
    pre-2.1.0 counter spanned files in path-sort order, so a rename
    upstream churned fingerprints in untouched files."""
    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding

    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("v = s()\n")
    b.write_text("v = s()\n")
    fb = Finding("GL501", str(b), 1, 0, "m")
    [_, (_, fp_b)] = fingerprint_findings(
        [Finding("GL501", str(a), 1, 0, "m"), fb])

    # rename a.py so it sorts AFTER b.py: b's fingerprint must not move
    z = tmp_path / "z.py"
    a.rename(z)
    [(_, fp_b2), (_, fp_z)] = fingerprint_findings(
        [fb, Finding("GL501", str(z), 1, 0, "m")])
    assert fp_b2 == fp_b
    # identical cross-file keys share one baseline entry by design:
    # either instance matches it, and neither can churn the other
    assert fp_z == fp_b2


def test_baseline_roundtrip_and_partition(tmp_path):
    from gome_tpu.analysis.baseline import (
        fingerprint_findings, load_baseline, partition, save_baseline,
    )
    from gome_tpu.analysis.core import Finding

    a = tmp_path / "a.py"
    a.write_text("old = sync()\n")
    old = Finding("GL501", str(a), 1, 0, "old debt")
    fps = fingerprint_findings([old])
    path = tmp_path / "baseline.json"
    save_baseline(str(path), fps)
    base = load_baseline(str(path))
    assert len(base) == 1

    a.write_text("old = sync()\nnew = sync2()\n")
    new = Finding("GL502", str(a), 2, 0, "new debt")
    both = fingerprint_findings([old, new])
    fresh, known = partition(both, base)
    assert [f.rule for f, _ in known] == ["GL501"]
    assert [f.rule for f, _ in fresh] == ["GL502"]


# --- SARIF 2.1.0 ----------------------------------------------------------


def test_sarif_output_validates():
    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding
    from gome_tpu.analysis.sarif import to_sarif, validate_sarif

    fs = [
        Finding("GL501", "gome_tpu/x.py", 10, 4, "a sync"),
        Finding("GL601", "gome_tpu/y.py", 1, 0, "a double-buffer"),
    ]
    fps = fingerprint_findings(fs)
    doc = to_sarif(fps, baselined={fps[1][1]})
    assert validate_sarif(doc) == []
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "gomelint"
    res = run["results"]
    assert res[0]["level"] == "error" and res[0]["baselineState"] == "new"
    assert res[1]["level"] == "warning"
    assert res[1]["suppressions"][0]["kind"] == "external"
    assert res[0]["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 10
    # the SARIF fingerprint IS the baseline fingerprint
    assert res[0]["partialFingerprints"]["gomelint/v1"] == fps[0][1]


def test_sarif_validator_rejects_malformed():
    from gome_tpu.analysis.sarif import validate_sarif

    assert validate_sarif({"version": "2.0.0", "runs": []})
    bad_run = {
        "version": "2.1.0",
        "runs": [{"tool": {"driver": {"name": ""}},
                  "results": [{"message": {}, "level": "fatal",
                               "locations": [{"physicalLocation": {
                                   "region": {"startLine": 0}}}]}]}],
    }
    errs = validate_sarif(bad_run)
    assert any("level" in e for e in errs)
    assert any("startLine" in e for e in errs)
    assert any("message" in e for e in errs)


def test_sarif_matches_jsonschema_expectations():
    jsonschema = pytest.importorskip("jsonschema")
    # a hand-reduced slice of the official 2.1.0 schema: the properties
    # gomelint emits, with the spec's required/enum constraints
    schema = {
        "type": "object",
        "required": ["version", "runs"],
        "properties": {
            "version": {"enum": ["2.1.0"]},
            "runs": {"type": "array", "items": {
                "type": "object", "required": ["tool"],
                "properties": {
                    "tool": {"type": "object", "required": ["driver"],
                             "properties": {"driver": {
                                 "type": "object", "required": ["name"]}}},
                    "results": {"type": "array", "items": {
                        "type": "object", "required": ["message"],
                        "properties": {
                            "level": {"enum": ["none", "note", "warning",
                                               "error"]},
                            "message": {"type": "object",
                                        "required": ["text"]},
                        }}},
                }}},
        },
    }
    from gome_tpu.analysis.baseline import fingerprint_findings
    from gome_tpu.analysis.core import Finding
    from gome_tpu.analysis.sarif import to_sarif

    doc = to_sarif(fingerprint_findings(
        [Finding("GL000", "x.py", 1, 0, "m")]))
    jsonschema.validate(doc, schema)


# --- whole-tree assertions for the new families ---------------------------


def test_whole_tree_clean_for_transfer_and_donation_families():
    """Satellite guarantee: the annotated hot paths (consumer, batcher,
    engine driver, pipeline) carry no GL5xx host-sync and no GL603
    use-after-donation today — regressions fail here with the exact
    file:line."""
    findings = [
        f for f in run_paths([os.path.join(ROOT, "gome_tpu"),
                              os.path.join(ROOT, "scripts"),
                              os.path.join(ROOT, "bench.py")])
        if f.rule.startswith(("GL5", "GL6"))
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_hot_path_seeds_reach_the_engine():
    """The hotpath annotations must actually cover the order path: if a
    refactor renames a seed or breaks an edge, the GL5xx family would go
    silently blind — this pins the reachability of the core driver."""
    import glob

    from gome_tpu.analysis import callgraph
    from gome_tpu.analysis.core import Project, SourceModule

    mods = []
    for p in sorted(glob.glob(os.path.join(ROOT, "gome_tpu", "**", "*.py"),
                              recursive=True)):
        with open(p, encoding="utf-8") as fh:
            mods.append(SourceModule(p, fh.read()))
    graph = callgraph.build(Project(mods))
    hot = {fn.name for fn in graph.hot_functions()}
    for must in ("run_once", "_run_exact", "submit_frame", "resolve_frame",
                 "pack_frame_grids", "feed"):
        assert must in hot, f"{must} fell off the hot path"


# --- CLI v2: baseline ratchet, SARIF, --version ---------------------------


def _cli(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "gomelint.py"),
         *args],
        capture_output=True, text=True, env=env, cwd=cwd,
    )


def test_cli_version():
    out = _cli(["--version"])
    assert out.returncode == 0
    assert "gomelint 2." in out.stdout


def test_cli_baseline_ratchet_flow(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(HOT_PREAMBLE + '''
def hot(x):  # gomelint: hotpath
    outs = device_step(x)
    return float(outs)
''')
    base = tmp_path / "baseline.json"

    # 1. new finding, no baseline: fail
    r = _cli([str(bad), "--baseline", str(base)])
    assert r.returncode == 1 and "GL501" in r.stdout

    # 2. accept the debt: --update-baseline exits 0 and writes the file
    r = _cli([str(bad), "--baseline", str(base), "--update-baseline"])
    assert r.returncode == 0 and base.exists()

    # 3. ratchet: the same finding is baselined, exit 0
    r = _cli([str(bad), "--baseline", str(base)])
    assert r.returncode == 0 and "baselined" in r.stdout

    # 4. line drift above the finding: fingerprint stable, still 0
    bad.write_text("# moved\n# down\n" + bad.read_text())
    r = _cli([str(bad), "--baseline", str(base)])
    assert r.returncode == 0

    # 5. NEW debt fails even with the old one baselined
    bad.write_text(bad.read_text() + '''
def hot2(x):  # gomelint: hotpath
    outs = device_step(x)
    return outs.item()
''')
    r = _cli([str(bad), "--baseline", str(base)])
    assert r.returncode == 1 and "1 new" in r.stdout

    # 6. --no-baseline: everything fails again
    r = _cli([str(bad), "--no-baseline"])
    assert r.returncode == 1


def test_cli_sarif_format(tmp_path):
    import json as _json

    from gome_tpu.analysis.sarif import validate_sarif

    bad = tmp_path / "bad.py"
    bad.write_text(HOT_PREAMBLE + '''
def hot(x):  # gomelint: hotpath
    return float(device_step(x))
''')
    sarif_path = tmp_path / "out.sarif"
    r = _cli([str(bad), "--no-baseline", "--format", "sarif",
              "--sarif", str(sarif_path)])
    assert r.returncode == 1
    doc = _json.loads(r.stdout)
    assert validate_sarif(doc) == []
    on_disk = _json.loads(sarif_path.read_text())
    assert on_disk["runs"][0]["results"][0]["ruleId"] == "GL501"


def test_committed_baseline_matches_tree():
    """The acceptance command: the full run (AST families) against the
    COMMITTED baseline exits 0 — new debt anywhere fails this test before
    it fails CI."""
    r = _cli(["gome_tpu", "scripts", "bench.py"])
    assert r.returncode == 0, r.stdout + r.stderr


# --- GL8xx sharding & partition consistency -------------------------------


GL801_BAD = '''
import jax
from jax.sharding import PartitionSpec as P

step_a = jax.jit(impl_a, in_shardings=(P('sym'),), out_shardings=(P('sym'),))
step_b = jax.jit(impl_b, in_shardings=(P(None),), out_shardings=(P(None),))

def frame(x):
    y = step_a(x)
    return step_b(y)                            # GL801: P('sym') -> P(None)
'''

GL801_GOOD = GL801_BAD.replace("P(None)", "P('sym')")


def test_spec_mismatch_between_chained_entries():
    findings = run_source(GL801_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL801"]
    assert "P('sym')" in findings[0].message
    assert "P(None)" in findings[0].message
    assert run_source(GL801_GOOD, select={"GL8"}) == []


GL801_FACTORY_BAD = '''
import jax
from jax.sharding import PartitionSpec as P

def make_step(impl, mesh):
    sharding = P('sym')
    return jax.jit(impl, in_shardings=(sharding, sharding),
                   out_shardings=(sharding, P(None)))

def frame(impl, mesh, books, ops):
    stepper = make_step(impl, mesh)
    books, outs = stepper(books, ops)
    books2, outs2 = stepper(books, outs)        # GL801 on arg #1
    return books2, outs2
'''

GL801_FACTORY_GOOD = GL801_FACTORY_BAD.replace(
    "(sharding, P(None))", "(sharding, sharding)")


def test_spec_mismatch_through_factory_alias():
    """The parallel/mesh.py idiom: a factory RETURNS the jitted entry,
    callers alias it (`stepper = sharded_dense_step(...)`). Spec flow
    must follow the alias and the tuple unpack; the alias-substituted
    canonical form makes `sharding` and `P('sym')` compare equal."""
    findings = run_source(GL801_FACTORY_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL801"]
    assert "argument #1" in findings[0].message
    assert run_source(GL801_FACTORY_GOOD, select={"GL8"}) == []


def test_factory_call_itself_is_not_a_dispatch():
    """Calling the factory only CONSTRUCTS the entry — the construction
    call must not be treated as a sharded dispatch of its arguments."""
    src = '''
import jax
from jax.sharding import PartitionSpec as P

def make_step(impl):
    return jax.jit(impl, in_shardings=(P('sym'),), out_shardings=(P('sym'),))

def setup(impl_host):
    return make_step(impl_host)
'''
    assert run_source(src, select={"GL8"}) == []


GL802_BAD = '''
import numpy as np

class Eng:
    def geometry(self, live):
        d = self.mesh.size
        local = self.n_slots // d
        counts = np.bincount(live // local, minlength=d)
        r_s = max(8, int(counts.max()))         # GL802 anchors here
        if r_s * d >= self.n_slots:
            return self.n_slots
        n_rows = r_s * d
        return n_rows
'''

GL802_GOOD = '''
import numpy as np

class Eng:
    def geometry(self, live, shard_id):
        d = self.mesh.size
        local = self.n_slots // d
        counts = np.bincount(live // local, minlength=d)
        r_s = max(8, int(counts[shard_id]))     # per-shard, no reduction
        return r_s * d
'''


def test_global_max_padding_flagged_once_at_derivation():
    """One finding per derived block var, anchored at the derivation (the
    line a fix rewrites), even when the product appears on several
    lines; the telemetry-style inline `counts.max() * d` expression that
    never lands in a variable is not the padding decision and must not
    flag."""
    findings = run_source(GL802_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL802"]
    assert len(findings) == 1
    assert findings[0].line == 9  # r_s = max(8, int(counts.max()))
    assert "MULTICHIP_r06" in findings[0].message
    assert run_source(GL802_GOOD, select={"GL8"}) == []


def test_global_max_telemetry_expression_not_flagged():
    src = '''
import numpy as np

def observe(skew, live, mesh):
    d = mesh.size
    counts = np.bincount(live, minlength=d)
    skew.observe(int(counts.max()) * d / len(live))
'''
    assert run_source(src, select={"GL8"}) == []


GL803_BAD = '''
from zlib import crc32

def route(symbol, n):
    return crc32(symbol.encode()) % n           # GL803
'''

GL803_GOOD = '''
from gome_tpu.fleet.router import partition_of

def route(symbol, n):
    return partition_of(symbol, n)
'''


def test_ad_hoc_partition_hash_flagged():
    findings = run_source(GL803_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL803"]
    assert "partition_of" in findings[0].message
    assert run_source(GL803_GOOD, select={"GL8"}) == []


def test_blessed_router_modules_may_hash():
    """The one-policy rule needs an implementation somewhere: the blessed
    placement helpers themselves are exempt, everything else routes
    through them."""
    for blessed in ("gome_tpu/fleet/router.py", "gome_tpu/parallel/router.py"):
        assert run_source(GL803_BAD, path=blessed, select={"GL8"}) == []
    assert rules_of(run_source(GL803_BAD, path="gome_tpu/fleet/drill.py",
                               select={"GL8"})) == ["GL803"]


GL804_BAD = '''
import jax
from jax.sharding import PartitionSpec as P

step = jax.jit(impl, donate_argnums=(0,),
               in_shardings=(P('sym'), P(None)), out_shardings=(P(None),))
'''

GL804_GOOD = GL804_BAD.replace("out_shardings=(P(None),)",
                               "out_shardings=(P('sym'),)")


def test_donation_across_sharding_boundary():
    findings = run_source(GL804_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL804"]
    assert "donated argument #0" in findings[0].message
    assert run_source(GL804_GOOD, select={"GL8"}) == []


def test_donation_without_shardings_is_gl6_territory():
    """Plain donation with no spec surface stays GL6xx's audit — GL804
    only speaks when both donation AND shardings are declared."""
    src = '''
import jax

step = jax.jit(impl, donate_argnums=(0,))
'''
    assert run_source(src, select={"GL8"}) == []


GL805_BAD = '''
import jax
import numpy as np

def frame(mesh, books):
    books = jax.device_put(books)
    host = np.asarray(jax.device_get(books))
    return shard_batch(mesh, host)              # GL805
'''

GL805_GOOD = '''
import jax
import numpy as np

def frame(mesh, books):
    books = jax.device_put(books)
    return shard_batch(mesh, books)             # on-device reshard: fine
'''


def test_host_roundtrip_into_mesh_flagged():
    findings = run_source(GL805_BAD, select={"GL8"})
    assert rules_of(findings) == ["GL805"]
    assert "round trip" in findings[0].message
    assert run_source(GL805_GOOD, select={"GL8"}) == []


def test_host_source_upload_is_clean():
    """Placing genuinely host-born data (params, numpy construction) on
    the mesh is the sanctioned upload path, not a round trip."""
    src = '''
import numpy as np

def place(mesh, lane_ids):
    ids_np = np.asarray(lane_ids)               # param: host-born
    return shard_batch(mesh, ids_np)
'''
    assert run_source(src, select={"GL8"}) == []


def test_host_roundtrip_through_factory_entry():
    src = '''
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

def make_step(impl):
    return jax.jit(impl, in_shardings=(P('sym'),), out_shardings=(P('sym'),))

def frame(impl, books):
    stepper = make_step(impl)
    books = stepper(books)
    host = np.asarray(books)
    return stepper(host)                        # GL805
'''
    assert rules_of(run_source(src, select={"GL8"})) == ["GL805"]


def test_gl8_suppression_and_select_compose():
    suppressed = GL803_BAD.replace(
        "% n           # GL803",
        "% n  # gomelint: disable=GL803 — fixture")
    assert run_source(suppressed, select={"GL8"}) == []
    # family select keeps GL8 out of a GL5-only run and vice versa
    assert run_source(GL803_BAD, select={"GL5"}) == []


# --- GL806 sharding manifest ----------------------------------------------


def test_manifest_extract_is_deterministic_and_complete():
    from gome_tpu.analysis.sharding import extract_manifest

    m = extract_manifest("int32")
    assert m["dtype"] == "int32"
    e = m["entries"]
    batch = e["engine/batch.py:batch_step"]
    assert batch["kind"] == "engine_entry"
    assert batch["classification"] == "sym_sharded"
    assert batch["donation"]["batch_step_donating"] == [2]
    assert all(a.endswith(":int32") for a in batch["in_avals"])
    dense = e["parallel/mesh.py:sharded_dense_step"]
    assert dense["kind"] == "mesh_entry"
    assert dense["mesh_axes"] == ["sym"]
    assert dense["in_shardings"] == ["symbol_sharding(mesh)"] * 3
    assert dense["shard_map_in_specs"] == ["P('sym')"] * 3
    assert dense["shard_map_out_specs"] == ["P('sym')"] * 2
    assert dense["classification"] == "shard_local"
    # the best-effort pallas record must stay OUT: its presence varies
    # by environment and the manifest must diff clean across machines
    assert not any("pallas" in ctx for ctx in e)
    assert extract_manifest("int32") == m


def test_committed_manifest_matches_tree():
    """The GL806 acceptance pin: the committed shard_manifest.json equals
    the extracted spec surface — spec drift fails here (and in CI) until
    --update-manifest is run and the diff reviewed."""
    from gome_tpu.analysis.sharding import check_sharding_manifest

    findings = check_sharding_manifest("int32")
    assert findings == [], "\n".join(f.format() for f in findings)


def test_manifest_missing_drift_and_dtype_gate(tmp_path):
    from gome_tpu.analysis.sharding import (
        check_sharding_manifest,
        extract_manifest,
        load_manifest,
        save_manifest,
    )

    path = str(tmp_path / "manifest.json")
    missing = check_sharding_manifest("int32", path)
    assert rules_of(missing) == ["GL806"]
    assert "no committed sharding manifest" in missing[0].message

    save_manifest(path, extract_manifest("int32"))
    assert check_sharding_manifest("int32", path) == []

    doc = load_manifest(path)
    doc["entries"]["parallel/mesh.py:sharded_dense_step"][
        "shard_map_out_specs"] = ["P(None)", "P(None)"]
    save_manifest(path, doc)
    drift = check_sharding_manifest("int32", path)
    assert rules_of(drift) == ["GL806"]
    assert "sharded_dense_step" in drift[0].message
    assert "shard_map_out_specs" in drift[0].message

    doc["entries"].pop("engine/batch.py:batch_step")
    doc["entries"]["engine/batch.py:imaginary"] = {"kind": "engine_entry"}
    save_manifest(path, doc)
    msgs = [f.message for f in check_sharding_manifest("int32", path)]
    assert any("batch_step: entry is new" in m for m in msgs)
    assert any("imaginary: entry vanished" in m for m in msgs)

    # the manifest pins the CI dtype: audits of the OTHER dtype skip it
    assert check_sharding_manifest("int64", path) == []


def test_cli_update_manifest_requires_jaxpr():
    r = _cli(["gome_tpu", "--update-manifest"])
    assert r.returncode == 2
    assert "--jaxpr" in r.stderr


def test_cli_manifest_flow(tmp_path):
    """CLI end-to-end: a missing manifest fails the GL8 gate with GL806;
    --update-manifest writes the spec surface and exits 0 (the ratchet's
    create/repair action, symmetric with --update-baseline)."""
    path = str(tmp_path / "manifest.json")
    r = _cli(["gome_tpu/parallel", "--jaxpr", "--select", "GL8",
              "--manifest", path, "--no-baseline"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "GL806" in r.stdout

    r = _cli(["gome_tpu/parallel", "--jaxpr", "--select", "GL8",
              "--manifest", path, "--update-manifest"])
    assert r.returncode == 0, r.stdout + r.stderr
    import json as _json
    doc = _json.loads(open(path).read())
    assert "parallel/mesh.py:sharded_dense_step" in doc["entries"]
    assert doc["tool"].startswith("gomelint 2.")


def test_whole_tree_clean_for_sharding_family():
    """Satellite guarantee for GL8xx: the mesh tier, the engine geometry,
    and every script dispatch either satisfy the sharding rules or carry
    a cited suppression (the GL802 global-max block in _grid_geometry is
    owned by ROADMAP item 2) — regressions fail here with file:line."""
    findings = [
        f for f in run_paths([os.path.join(ROOT, "gome_tpu"),
                              os.path.join(ROOT, "scripts"),
                              os.path.join(ROOT, "bench.py")])
        if f.rule.startswith("GL8")
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_mesh_overhead_keeps_lane_ids_resident():
    """Regression for the GL805 the tree sweep found: part_a built its
    mesh lane ids by np.asarray(device_array) — a device->host->device
    round trip on the setup path. The fix shards the host-born numpy
    original; the file must stay GL805-clean."""
    path = os.path.join(ROOT, "scripts", "mesh_overhead.py")
    findings = [f for f in run_paths([path]) if f.rule == "GL805"]
    assert findings == [], "\n".join(f.format() for f in findings)
    # and the scan is not blind there: the old shape still fires
    bad = '''
import jax
import numpy as np

def part(mesh, R):
    lane_ids = jax.device_put(np.arange(R, dtype=np.int32))
    return shard_batch(mesh, np.asarray(lane_ids, np.int32))
'''
    assert rules_of(run_source(bad, select={"GL8"})) == ["GL805"]


# --- GL9xx compile surface -------------------------------------------------


SURFACE_OK = '''
import jax
from functools import lru_cache

# gomesurface: quantizer
def _pow2(n):
    return 1 << max(n - 1, 0).bit_length()

# gomesurface: quantizer
def _pow4(n):
    v = 1
    while v < n:
        v *= 4
    return v

COMBO_FIELDS = ("n_rows", "cap_g")

@lru_cache(maxsize=None)
def make_step(n_rows, cap_g):
    @jax.jit
    def step(x):
        return x[:n_rows, :cap_g]
    return step

# gomesurface: combo(build)
def submit(eng, ops, counts):  # gomelint: hotpath
    rows = _pow2(len(ops))
    cap = _pow2(counts.max())
    combo = (rows, cap)
    eng.record_combo(combo)
    return make_step(rows, cap)(ops)

# gomesurface: combo(replay), precompile
def boot_replay(eng):
    for combo in eng.combos():
        (n_rows, cap_g) = combo
        make_step(n_rows, cap_g)

# gomesurface: combo(persist)
def manifest(eng):
    return {"combos": sorted(eng.combos())}
'''


def _gl9(src, **kw):
    return run_source(src, select={"GL9"}, **kw)


def test_surface_complete_fixture_is_clean():
    """The whole contract composed: quantized build, agreeing replay
    unpack, persist through combos(), precompile covering the factory —
    every GL901-GL904 check stays silent at once."""
    assert _gl9(SURFACE_OK) == []


def test_gl901_raw_reduction_to_combo_and_factory():
    bad = SURFACE_OK.replace("_pow2(len(ops))", "len(ops)")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL901"]
    msgs = "\n".join(f.message for f in findings)
    assert "combo dimension 'n_rows'" in msgs
    assert "shape argument #0 of jit factory make_step()" in msgs


def test_gl901_attribute_reduction_is_a_source():
    bad = SURFACE_OK.replace("_pow2(counts.max())", "counts.max()")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL901"]
    assert any("combo dimension 'cap_g'" in f.message for f in findings)


def test_gl901_quantizer_alias_launders():
    """`bucket = _pow2 if first else _pow4; bucket(len(ops))` — an alias
    of a quantizer is a quantizer (the batch.py first-grow idiom)."""
    src = SURFACE_OK + '''
def resize(eng, ops, first):  # gomelint: hotpath
    bucket = _pow2 if first else _pow4
    m = bucket(len(ops))
    return make_step(m, 8)(ops)
'''
    assert _gl9(src) == []
    # and the scan is not blind: drop the laundering call, it fires
    raw = src.replace("bucket(len(ops))", "len(ops)")
    assert rules_of(_gl9(raw)) == ["GL901"]


def test_gl902_build_arity_drift():
    bad = SURFACE_OK.replace("combo = (rows, cap)", "combo = (rows, cap, 7)")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL902"]
    assert "3 element(s)" in findings[0].message
    assert "COMBO_FIELDS declares 2" in findings[0].message


def test_gl902_build_order_drift_via_provenance():
    bad = SURFACE_OK.replace("combo = (rows, cap)", "combo = (cap, rows)")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL902"]
    assert all("drifted" in f.message for f in findings)


def test_gl902_replay_unpack_drift_and_oob_subscript():
    bad = SURFACE_OK.replace("(n_rows, cap_g) = combo",
                             "(cap_g, n_rows) = combo")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL902"]
    assert "replay unpack binds (cap_g, n_rows)" in findings[0].message

    oob = SURFACE_OK.replace("        make_step(n_rows, cap_g)",
                             "        make_step(n_rows, combo[5])")
    findings = _gl9(oob)
    assert rules_of(findings) == ["GL902"]
    assert "combo[5] is outside the 2-field combo layout" \
        in findings[0].message


def test_gl902_persist_must_read_the_combo_set():
    bad = SURFACE_OK.replace('{"combos": sorted(eng.combos())}', "{}")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL902"]
    assert "never reads the recorded combo set" in findings[0].message


def test_gl902_missing_role_annotation():
    bad = SURFACE_OK.replace("# gomesurface: combo(persist)\n", "")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL902"]
    assert "combo(persist)" in findings[0].message


def test_gl902_seen_combos_reach_through_regression():
    """Regression pin for the sweep's chokepoint refactor: the
    obs/timeline.py rollup used to read `len(eng._seen_combos)` directly;
    it now goes through combo_count(). The OLD shape must keep firing
    anywhere outside the chokepoint's home module..."""
    reach = '''
def rollup(eng):
    return {"combos": len(eng._seen_combos)}
'''
    findings = _gl9(reach, path="obs/timeline.py")
    assert rules_of(findings) == ["GL902"]
    assert "record_combo" in findings[0].message
    # ...while engine/batch.py, the set's single owner, is exempt.
    assert _gl9(reach, path="engine/batch.py") == []


def test_gl903_uncovered_hot_entry():
    bad = SURFACE_OK.replace("# gomesurface: combo(replay), precompile",
                             "# gomesurface: combo(replay)")
    findings = _gl9(bad)
    assert rules_of(findings) == ["GL903"]
    # both the factory and its jitted inner are now unreachable at boot
    msgs = "\n".join(f.message for f in findings)
    assert "make_step" in msgs
    assert "precompile" in msgs


def test_gl903_silent_without_a_replay_system():
    """A project with no precompile annotation AND no COMBO_FIELDS has
    no replay system to register into — GL903 would be unactionable."""
    src = '''
import jax

@jax.jit
def step(x):
    return x

def hot(x):  # gomelint: hotpath
    return step(x)
'''
    assert _gl9(src) == []


def test_gl904_hot_path_resets():
    bad = '''
def drain(eng):  # gomelint: hotpath
    reap(eng)

def reap(eng):
    eng.reset_geometry_floors()
    eng._seen_combos.clear()
'''
    # path inside the chokepoint module isolates GL904 from the GL902
    # reach-through rule
    findings = _gl9(bad, path="engine/batch.py")
    assert rules_of(findings) == ["GL904"]
    msgs = "\n".join(f.message for f in findings)
    assert "reset_geometry_floors()" in msgs
    assert "_seen_combos.clear()" in msgs
    # the same resets in maintenance code nothing hot reaches are fine
    good = bad.replace("  # gomelint: hotpath", "")
    assert _gl9(good, path="engine/batch.py") == []


def test_gl9_suppression_composes():
    src = '''
def drain(eng):  # gomelint: hotpath
    eng.reset_geometry_floors()  # gomelint: disable=GL904 — boot drain
'''
    assert _gl9(src, path="engine/batch.py") == []


def test_whole_tree_clean_for_surface_family():
    """Satellite guarantee for GL9xx: every engine quantizer is
    annotated, the combo sites agree with COMBO_FIELDS, all hot jit
    entries replay from precompile_combos, and no reset is hot-reachable
    (the sim/replay.py record tool carries the one cited suppression)."""
    findings = [
        f for f in run_paths([os.path.join(ROOT, "gome_tpu"),
                              os.path.join(ROOT, "scripts"),
                              os.path.join(ROOT, "bench.py")])
        if f.rule.startswith("GL9")
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


# --- GL905 combo universe --------------------------------------------------


def test_universe_extract_is_deterministic_and_total():
    from gome_tpu.analysis.surface import extract_universe
    from gome_tpu.engine.frames import COMBO_FIELDS

    u = extract_universe()
    assert u["fields"] == list(COMBO_FIELDS)
    assert list(u["dimensions"]) == list(COMBO_FIELDS)
    for name, dim in u["dimensions"].items():
        # no unbounded holes: every dimension has a real generator
        assert dim["cardinality"] >= 1, name
        assert "UNKNOWN" not in dim["generator"], name
    assert u["cardinality_log2_bound"] > 0
    assert u["bounds"]["max_frame_ops"] == 1 << 20
    assert extract_universe() == u


def test_committed_universe_matches_tree():
    """The GL905 acceptance pin: the committed combo_universe.json equals
    the extracted bound — a config-bound or quantizer change fails here
    (and in CI) until --update-universe is run and the diff reviewed."""
    from gome_tpu.analysis.surface import check_universe

    findings = check_universe()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_universe_missing_drift_and_dimension_churn(tmp_path):
    from gome_tpu.analysis.surface import (
        check_universe,
        extract_universe,
        load_universe,
        save_universe,
    )

    path = str(tmp_path / "universe.json")
    missing = check_universe(path)
    assert rules_of(missing) == ["GL905"]
    assert "no committed combo universe" in missing[0].message

    save_universe(path, extract_universe())
    assert check_universe(path) == []

    doc = load_universe(path)
    doc["dimensions"]["t_grid"]["max"] = 2048
    save_universe(path, doc)
    drift = check_universe(path)
    assert rules_of(drift) == ["GL905"]
    assert "t_grid" in drift[0].message and "max" in drift[0].message

    doc["dimensions"]["t_grid"]["max"] = 1024
    doc["bounds"]["max_t"] = 64
    doc["dimensions"].pop("m_pad")
    doc["dimensions"]["imaginary"] = {"kind": "enum", "values": [1]}
    save_universe(path, doc)
    msgs = [f.message for f in check_universe(path)]
    assert any("bounds changed" in m for m in msgs)
    assert any("m_pad: dimension is new" in m for m in msgs)
    assert any("imaginary: dimension vanished" in m for m in msgs)


def test_cli_update_universe_requires_jaxpr():
    r = _cli(["gome_tpu", "--update-universe"])
    assert r.returncode == 2
    assert "--jaxpr" in r.stderr


def test_cli_universe_flow(tmp_path):
    """CLI end-to-end: a missing universe fails the GL9 gate with GL905;
    --update-universe writes the per-dimension bound and exits 0 (the
    ratchet's create/repair action, symmetric with --update-manifest)."""
    path = str(tmp_path / "universe.json")
    r = _cli(["gome_tpu/analysis", "--jaxpr", "--select", "GL9",
              "--universe", path, "--no-baseline"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "GL905" in r.stdout

    r = _cli(["gome_tpu/analysis", "--jaxpr", "--select", "GL9",
              "--universe", path, "--update-universe"])
    assert r.returncode == 0, r.stdout + r.stderr
    import json as _json
    doc = _json.loads(open(path).read())
    assert len(doc["dimensions"]) == 9
    assert doc["tool"].startswith("gomelint 2.")

    r = _cli(["gome_tpu/analysis", "--jaxpr", "--select", "GL9",
              "--universe", path, "--no-baseline"])
    assert r.returncode == 0, r.stdout + r.stderr


# --- GL906 runtime escape --------------------------------------------------


#: A dispatch combo from the committed universe's interior (engine
#: defaults: 8 rows, full 8-step grid, cap class 64, dense, the floors).
_COMBO_IN = (8, 8, 64, True, 64, 4, 64, 64, 8)


def test_combo_escapes_against_committed_universe():
    from gome_tpu.analysis.surface import combo_escapes, load_universe

    u = load_universe(os.path.join(ROOT, "gome_tpu", "analysis",
                                   "combo_universe.json"))
    assert u is not None
    assert combo_escapes(_COMBO_IN, u) == []

    off_lattice = (8, 48) + _COMBO_IN[2:]
    [why] = combo_escapes(off_lattice, u)
    assert "t_grid=48" in why and "pow2" in why

    # m_pad is pow4: a pow2 value off the pow4 lattice escapes
    not_pow4 = _COMBO_IN[:4] + (128,) + _COMBO_IN[5:]
    [why] = combo_escapes(not_pow4, u)
    assert "m_pad=128" in why

    assert "arity" in combo_escapes(_COMBO_IN[:3], u)[0]


def test_journal_escapes_wire_forms():
    from gome_tpu.analysis.surface import _journal_entries, journal_escapes

    entry = {"entry": "frame_dispatch", "key": list(_COMBO_IN)}
    for doc in ([entry],
                {"entries": [entry]},
                {"schema": "gome-compile-journal/1", "entries": [entry]},
                {"compile_journal": {"entries": [entry]}},
                {"journal": {"entries": [entry]}}):
        assert _journal_entries(doc) == [entry]
    assert _journal_entries({"other": 1}) == []
    assert _journal_entries("junk") == []

    u = {"fields": ["n"], "dimensions": {"n": {"kind": "pow2",
                                               "min": 8, "max": 64,
                                               "cardinality": 4}}}
    entries = [
        {"entry": "frame_dispatch", "key": [32]},       # inside
        {"entry": "frame_dispatch", "key": [48]},       # escapes
        {"entry": "frame_dispatch", "key": [48]},       # dup: reported once
        {"entry": "precompile_replay", "key": [999]},   # not a dispatch
        {"entry": "frame_dispatch", "key": "notakey"},  # malformed: skipped
    ]
    escapes = journal_escapes(entries, u)
    assert escapes == [((48,), ["n=48 outside pow2 [8..64]"])]


def test_check_journal_escape_files(tmp_path):
    import json as _json

    from gome_tpu.analysis.surface import check_journal_escape

    journal = tmp_path / "journal.json"
    journal.write_text(_json.dumps(
        {"entries": [{"entry": "frame_dispatch", "key": list(_COMBO_IN)}]}
    ))
    assert check_journal_escape(str(journal)) == []

    missing = check_journal_escape(str(journal),
                                   str(tmp_path / "absent.json"))
    assert rules_of(missing) == ["GL906"]
    assert "no committed combo universe" in missing[0].message

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    broken = check_journal_escape(str(bad))
    assert rules_of(broken) == ["GL906"]
    assert "unreadable" in broken[0].message

    journal.write_text(_json.dumps(
        {"entries": [{"entry": "frame_dispatch",
                      "key": [8, 48] + list(_COMBO_IN[2:])}]}
    ))
    escape = check_journal_escape(str(journal))
    assert rules_of(escape) == ["GL906"]
    assert "escapes the predicted universe" in escape[0].message
    assert "t_grid=48" in escape[0].message


def test_cli_journal_flag(tmp_path):
    import json as _json

    ok = tmp_path / "ok.json"
    ok.write_text(_json.dumps(
        {"entries": [{"entry": "frame_dispatch", "key": list(_COMBO_IN)}]}
    ))
    r = _cli(["gome_tpu/analysis/surface.py", "--select", "GL9",
              "--no-baseline", "--journal", str(ok)])
    assert r.returncode == 0, r.stdout + r.stderr

    bad = tmp_path / "bad.json"
    bad.write_text(_json.dumps(
        {"entries": [{"entry": "frame_dispatch",
                      "key": [8, 48] + list(_COMBO_IN[2:])}]}
    ))
    r = _cli(["gome_tpu/analysis/surface.py", "--select", "GL9",
              "--no-baseline", "--journal", str(bad)])
    assert r.returncode == 1
    assert "GL906" in r.stdout


def test_gl906_dynamic_witness_drill():
    """The runtime half of the contract, end to end on a live engine: a
    discovery run's every recorded combo lies INSIDE the committed
    universe (the static bound is sound for real traffic), and a fresh
    engine that precompiles those combos replays the same flow with the
    compile journal armed and SILENT (zero steady-state dispatches —
    the ROADMAP item 3 property GL906 audits in CI artifacts)."""
    import numpy as np

    from gome_tpu.analysis.surface import (
        combo_escapes,
        journal_escapes,
        load_universe,
    )
    from gome_tpu.engine import frames
    from gome_tpu.engine.batch import BatchEngine
    from gome_tpu.engine.book import BookConfig
    from gome_tpu.engine.frames import precompile_combos
    from gome_tpu.obs import CompileJournal
    from gome_tpu.utils.metrics import Registry

    def mk():
        return BatchEngine(BookConfig(cap=64, max_fills=4,
                                      dtype=jnp.int32),
                           n_slots=16, max_t=8)

    def mixed_frames():
        out = []
        rng = np.random.default_rng(7)
        for i, n in enumerate((64, 17, 128)):
            action = np.ones(n, np.int64)
            action[rng.random(n) < 0.25] = 2  # mixed flow: adds + dels
            out.append(dict(
                n=n,
                action=action,
                side=rng.integers(0, 2, n).astype(np.int64),
                kind=np.zeros(n, np.int64),
                price=rng.integers(99_000, 101_000, n).astype(np.int64),
                volume=rng.integers(1, 10, n).astype(np.int64),
                symbols=[f"s{j}" for j in range(6)],
                symbol_idx=rng.integers(0, 6, n).astype(np.int64),
                uuids=["u0"],
                uuid_idx=np.zeros(n, np.int64),
                oids=np.char.add(
                    "w", np.arange(i * 4096, i * 4096 + n).astype("U8")
                ).astype("S"),
            ))
        return out

    universe = load_universe(os.path.join(
        ROOT, "gome_tpu", "analysis", "combo_universe.json"))
    assert universe is not None

    # Discovery: every combo real traffic mints is inside the bound.
    e1 = mk()
    for f in mixed_frames():
        frames.apply_frame_fast(e1, f)
    discovered = sorted(e1.combos())
    assert discovered, "discovery run recorded no combos"
    for combo in discovered:
        assert combo_escapes(combo, universe) == [], combo

    # Replay: precompile the manifest, arm the journal, re-run the flow.
    e2 = mk()
    assert precompile_combos(e2, e1.shape_manifest()["combos"]) \
        == len(discovered)
    journal = CompileJournal().install(keep_n=64, registry=Registry())
    old = frames.JOURNAL
    frames.JOURNAL = journal  # armed AFTER precompile: boot is off-book
    try:
        for f in mixed_frames():
            frames.apply_frame_fast(e2, f)
    finally:
        frames.JOURNAL = old
        journal.disable()
    dispatches = [e for e in journal.entries()
                  if e["entry"] == "frame_dispatch"]
    assert dispatches == [], dispatches  # zero compiles at steady state
    # and the export wire form the CI artifact check reads is escape-free
    assert journal_escapes(journal.export()["entries"], universe) == []
