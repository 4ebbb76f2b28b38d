"""Measured roofline: programmatic profiler capture + trace-event
attribution (ISSUE 9).

Everything the obs stack reported before this module is ANALYTIC — what
XLA's cost model says an entry *should* cost (``costmodel``), never what
a run *achieved*. This module closes that loop in three pieces:

  * ``capture()`` — a bounded ``jax.profiler`` window
    (``start_trace``/``stop_trace`` with a Perfetto artifact), plus
    helpers to locate the run directory and load the gzipped Chrome
    trace-event JSON back out of it.
  * ``parse_trace_events()`` — a pure parser. Each measured region is
    wrapped in a ``jax.profiler.TraceAnnotation`` named
    ``gome_profile/<entry>`` (``/`` as separator — the TraceMe pipeline
    STRIPS everything before a ``:``), and device time is attributed as
    the **interval union** of XLA op events clipped to the annotation
    windows. Union, not sum: XLA op events nest (a ``call`` contains the
    ``reduce-window`` it calls, with nearly identical duration) and the
    CPU runtime duplicates ``TfrtCpuExecutable::Execute`` across
    threads, so naive summing double-counts.
  * ``measured_entry_report()`` — drives the cost model's own canonical
    entries (the ``analysis.envelope.traced_entries`` memo) inside a
    capture and joins measured device time against the analytic
    flops / bytes-accessed rows: achieved GFLOP/s, achieved GB/s, and
    efficiency vs the machine's roofline ceiling
    (``min(peak_flops, intensity * peak_bw)``; on a TPU the peaks are
    the published ones from ``TPU_PEAKS``, keyed by ``device_kind``; a
    CPU run calibrates its own and says so).

``PROFILER`` is the process singleton behind the ops ``/profile``
endpoint and the ``gome_profile_*`` gauges, armed from the
``ops.profile`` / ``ops.profile_keep`` config knobs (service.app). Same
hot-path contract as TRACER/JOURNAL/TIMELINE: disabled (the default) its
``note_shard_dispatch`` hook — called from ``engine.batch._grid_geometry``
on every dense mesh dispatch — costs one attribute check and ZERO
allocations (pinned by ``sys.getallocatedblocks`` in tests).

Import discipline: NO jax at module scope — ``engine.batch`` imports
``PROFILER`` at import time and the pure parser must stay usable (and
testable) without a backend. jax loads lazily inside ``capture`` /
``measured_entry_report`` / ``machine_peaks``.

Measured scope: only the PUBLIC entries (``costmodel.RATCHET_ENTRIES``).
The ``_donating`` twins donate their argument buffers, and the memo
shares ONE argument set across repeats — executing a twin would
invalidate the very arrays the next repeat needs. CPU wall parity with
the public entries was already shown in PR 4; the twins' win is
footprint (``costmodel.donation_report``), not time.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import tempfile
import threading
from collections import deque

from ..utils.metrics import REGISTRY

#: Annotation-window name prefix. ``/`` by necessity: TraceMe treats
#: ``:`` as a metadata separator and strips everything before it, so a
#: ``gome_profile:lane_scan`` window surfaces as bare ``lane_scan``.
ANNOTATION_PREFIX = "gome_profile/"

#: Host-side event-name prefixes that are runtime plumbing, not compute.
#: Anything containing ``::`` (C++ runtime symbols — TfrtCpuExecutable,
#: ThunkExecutor, ThreadpoolListener) is excluded by rule; these cover
#: the bare-named rest.
_HOST_INFRA_PREFIXES = (
    "PjitFunction",
    "ParseArguments",
    "CopyToDevice",
    "TransferTo",
    "BufferFromHost",
    "ExecuteOptions",
    "RunBackend",
)


# ---------------------------------------------------------------------------
# capture window + artifact plumbing


class Capture:
    """Handle yielded by ``capture()``: where the trace landed."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.run_dir: str | None = None
        self.perfetto: str | None = None


@contextlib.contextmanager
def capture(log_dir: str | None = None):
    """Bounded profiler window. Everything executed inside the ``with``
    lands in one trace run under ``log_dir`` (a fresh temp dir when
    None), with a Perfetto artifact (gzipped Chrome trace-event JSON).
    On exit the handle's ``run_dir``/``perfetto`` point at the capture.
    """
    import jax

    cap = Capture(log_dir or tempfile.mkdtemp(prefix="gome-profile-"))
    jax.profiler.start_trace(
        cap.log_dir, create_perfetto_link=False, create_perfetto_trace=True
    )
    try:
        yield cap
    finally:
        jax.profiler.stop_trace()
        cap.run_dir = latest_run_dir(cap.log_dir)
        cap.perfetto = perfetto_path(cap.run_dir)


def latest_run_dir(log_dir: str | None) -> str | None:
    """The newest profiler run directory under ``log_dir``
    (``plugins/profile/<timestamp>/``), or None."""
    if not log_dir:
        return None
    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*")))
    return runs[-1] if runs else None


def perfetto_path(run_dir: str | None) -> str | None:
    """The Perfetto trace artifact inside a run dir, or None."""
    if not run_dir:
        return None
    hits = sorted(glob.glob(os.path.join(run_dir, "*perfetto_trace.json.gz")))
    return hits[-1] if hits else None


def load_trace_events(run_dir: str | None) -> list[dict]:
    """Trace-event list out of a run dir's Perfetto artifact ([] when
    the capture produced nothing)."""
    path = perfetto_path(run_dir)
    if path is None:
        return []
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        return doc.get("traceEvents", []) or []
    return doc or []


# ---------------------------------------------------------------------------
# pure trace-event parser


def _merge(intervals):
    """Sorted, non-overlapping union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, windows):
    """Intersect op intervals with the (merged) annotation windows."""
    clipped = []
    for s, e in intervals:
        for ws, we in windows:
            cs, ce = max(s, ws), min(e, we)
            if ce > cs:
                clipped.append((cs, ce))
    return clipped


def _union_us(intervals) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _is_compute_op(name: str) -> bool:
    """Host-side heuristic: XLA op events (``fusion.3``, ``call``,
    ``reduce-window.2.clone``, …) vs runtime plumbing. Python-originated
    events are ``$``-prefixed; C++ runtime symbols carry ``::``."""
    if not name or name.startswith("$") or "::" in name:
        return False
    return not name.startswith(_HOST_INFRA_PREFIXES)


def parse_trace_events(
    events: list[dict],
    labels,
    prefix: str = ANNOTATION_PREFIX,
) -> dict[str, dict]:
    """Attribute device time to annotation windows.

    For each label, finds its ``prefix + label`` complete events ("X"
    phase; the bare label is also accepted — older TraceMe pipelines
    strip the prefix at a separator) and computes:

      * ``windows``   — number of annotation windows seen
      * ``wall_us``   — summed window duration
      * ``device_us`` — interval-UNION of compute-op events clipped to
        the windows (nesting- and thread-duplication-safe)
      * ``by_device`` — the same union split per device process (on TPU
        each chip is its own pid; on CPU this degenerates to one host
        row), the per-shard attribution surface
      * ``events``    — number of compute-op events that intersected
      * ``top_op``    — the single longest contributing op name

    Events on processes whose name contains ``/device:`` count as
    compute by construction (real accelerator timelines); host events
    pass the ``_is_compute_op`` heuristic.
    """
    procs: dict = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e.get("pid")] = (e.get("args") or {}).get("name", "")

    want = {}
    for lab in labels:
        want[prefix + lab] = lab
        want.setdefault(lab, lab)

    windows: dict[str, list] = {lab: [] for lab in labels}
    ops: list[tuple[float, float, str, str]] = []  # (start, end, name, proc)
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name", "")
        try:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if name in want:
            windows[want[name]].append((ts, ts + dur))
            continue
        if dur <= 0:
            continue
        pname = procs.get(e.get("pid"), "")
        if "/device:" in pname or _is_compute_op(name):
            ops.append((ts, ts + dur, name, pname or "host"))

    out: dict[str, dict] = {}
    for lab in labels:
        win = _merge(windows[lab])
        if not win:
            out[lab] = {
                "windows": 0, "wall_us": 0.0, "device_us": 0.0,
                "by_device": {}, "events": 0, "top_op": None,
            }
            continue
        hits = []
        by_dev: dict[str, list] = {}
        top_name, top_dur = None, 0.0
        for s, e, name, pname in ops:
            clipped = _clip([(s, e)], win)
            if not clipped:
                continue
            hits.extend(clipped)
            by_dev.setdefault(pname, []).extend(clipped)
            got = sum(ce - cs for cs, ce in clipped)
            if got > top_dur:
                top_name, top_dur = name, got
        out[lab] = {
            "windows": len(windows[lab]),
            "wall_us": round(sum(e - s for s, e in win), 3),
            "device_us": round(_union_us(hits), 3),
            "by_device": {
                d: round(_union_us(iv), 3) for d, iv in sorted(by_dev.items())
            },
            "events": len(hits),
            "top_op": top_name,
        }
    return out


# ---------------------------------------------------------------------------
# machine peaks (roofline ceilings)

_PEAKS_CACHE: dict = {}
_PEAKS_LOCK = threading.Lock()


#: Published per-chip peaks, keyed by jax's ``device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s
#: int8, 819 GB/s HBM. A TPU kind that is not here is an error, not a
#: default: a roofline share against the wrong ceiling is a wrong number.
TPU_PEAKS = {
    "TPU v5 lite": {  # v5e, as jax reports it
        "peak_gflops": 197_000.0,
        "peak_int8_gops": 393_000.0,
        "peak_gbps": 819.0,
        "source": 'table: Google Cloud documentation, "TPU v5e"',
    },
}


def machine_peaks(refresh: bool = False) -> dict:
    """Roofline ceilings for THIS machine. On a TPU backend: the published
    peaks from ``TPU_PEAKS`` by ``device_kind`` (KeyError for a kind not
    in the table). On the CPU backend only — tests and self-checks,
    never a device metric — a one-shot memoized calibration (source
    ``cpu-calibrated``: best-of-N f32 matmul and saxpy sweep), which
    ``GOME_PEAK_GFLOPS`` / ``GOME_PEAK_GBPS`` override (source
    ``cpu-env``)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        if dev.device_kind not in TPU_PEAKS:
            raise KeyError(
                f"no published peaks for device_kind {dev.device_kind!r}; "
                "add it to gome_tpu.obs.profiler.TPU_PEAKS with its source"
            )
        return {**TPU_PEAKS[dev.device_kind], "device_kind": dev.device_kind}
    with _PEAKS_LOCK:
        if _PEAKS_CACHE and not refresh:
            return dict(_PEAKS_CACHE)
        env_f = os.environ.get("GOME_PEAK_GFLOPS")
        env_b = os.environ.get("GOME_PEAK_GBPS")
        if env_f and env_b:
            peaks = {
                "peak_gflops": float(env_f),
                "peak_gbps": float(env_b),
                "source": "cpu-env",
            }
        else:
            peaks = _calibrate()
            if env_f:
                peaks["peak_gflops"] = float(env_f)
            if env_b:
                peaks["peak_gbps"] = float(env_b)
            if env_f or env_b:
                peaks["source"] = "cpu-env+calibrated"
        _PEAKS_CACHE.clear()
        _PEAKS_CACHE.update(peaks)
        return dict(peaks)


def _calibrate() -> dict:
    import time

    import jax
    import jax.numpy as jnp

    n = 512
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(mm(a, a))
    best = min(_timed(lambda: jax.block_until_ready(mm(a, a)), time)
               for _ in range(5))
    peak_gflops = 2.0 * n**3 / best / 1e9

    m = 1 << 22  # 4M f32 lanes: 16 MB operand, past L2 on anything real
    v = jnp.ones((m,), jnp.float32)
    axpy = jax.jit(lambda x: x * 2.0 + 1.0)
    jax.block_until_ready(axpy(v))
    best = min(_timed(lambda: jax.block_until_ready(axpy(v)), time)
               for _ in range(5))
    peak_gbps = 2.0 * 4 * m / best / 1e9  # one read + one write stream

    return {
        "peak_gflops": round(peak_gflops, 3),
        "peak_gbps": round(peak_gbps, 3),
        "source": "cpu-calibrated",
    }


def _timed(fn, time) -> float:
    t0 = time.perf_counter()
    fn()
    return max(time.perf_counter() - t0, 1e-9)


# ---------------------------------------------------------------------------
# the measured roofline report


def measured_entry_report(
    dtype: str = "int32", repeats: int = 8, log_dir: str | None = None
) -> dict:
    """Measure the cost model's canonical entries and join against the
    analytic rows. Compiles (and warms) each public entry OUTSIDE the
    capture window, then runs ``repeats`` block_until_ready'd calls per
    entry inside one ``gome_profile/<entry>`` annotation; the parser's
    per-window device-time union divided by ``repeats`` is the measured
    per-call device time. Achieved GFLOP/s and GB/s use the ANALYTIC
    flops / bytes-accessed (there are no per-op hardware counters on
    CPU, and on TPU the analytic numbers are the roofline's x-axis
    anyway): ``achieved = analytic_work / measured_time``.
    """
    import jax

    from . import costmodel

    analytic = {
        r["entry"]: r for r in costmodel.entry_report(dtype) if "error" not in r
    }
    peaks = machine_peaks()

    from ..analysis.envelope import traced_entries

    # Fresh device copies per CALL, materialized before the capture
    # opens: some entries donate their accumulators (compact_accum), so
    # executing the shared traced_entries memo's args would delete
    # buffers other consumers still hold — and a donated arg can't be
    # passed twice. Copies are tiny (canonical geometry) and keep the
    # capture window free of copy traffic.
    def _fresh(args):
        return jax.tree.map(
            lambda a: jax.numpy.array(a) if isinstance(a, jax.Array) else a,
            args,
        )

    jobs = []
    with costmodel._x64_ctx(dtype):
        for rec in traced_entries(dtype):
            jits = rec.get("jits")
            if not jits or "args" not in rec:
                continue
            for label, fn in jits:
                if label not in costmodel.RATCHET_ENTRIES:
                    continue  # donating twins: see module docstring
                arg_sets = [_fresh(rec["args"]) for _ in range(repeats + 1)]
                try:
                    # compile+warm — per-iteration drain is deliberate
                    # throughout this probe: each call must retire before
                    # the next so the annotation window bounds real
                    # device time, not pipelined overlap.
                    jax.block_until_ready(fn(*arg_sets[0]))  # gomelint: disable=GL504
                except Exception:  # backend-specific gaps mirror costmodel
                    continue
                # (set 0 was donated to the warm call above)
                jax.block_until_ready(arg_sets[1:])  # gomelint: disable=GL504
                jobs.append((label, fn, arg_sets[1:]))
        with capture(log_dir) as cap:
            for label, fn, arg_sets in jobs:
                with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + label):
                    for args in arg_sets:
                        jax.block_until_ready(fn(*args))  # gomelint: disable=GL504

    parsed = parse_trace_events(
        load_trace_events(cap.run_dir), [j[0] for j in jobs]
    )
    entries = {
        label: _roofline_row(label, parsed.get(label), analytic.get(label, {}),
                             repeats, peaks)
        for label, _, _ in jobs
    }
    return {
        "dtype": dtype,
        "repeats": repeats,
        "platform": jax.default_backend(),
        "peaks": peaks,
        "entries": entries,
        "run_dir": cap.run_dir,
        "perfetto_trace": cap.perfetto,
    }


def _roofline_row(label, parsed, analytic, repeats, peaks) -> dict:
    if not parsed or not parsed["windows"]:
        return {"entry": label, "error": "no trace window captured"}
    wall_per_call = parsed["wall_us"] / repeats
    device_us = parsed["device_us"]
    dev_per_call = (device_us or parsed["wall_us"]) / repeats
    row = {
        "entry": label,
        "calls": repeats,
        "wall_us_per_call": round(wall_per_call, 3),
        "device_us_per_call": round(dev_per_call, 3),
        "device_time_source": "xla_ops" if device_us else "annotation_wall",
        "trace_events": parsed["events"],
        "top_op": parsed.get("top_op"),
        "by_device": parsed.get("by_device", {}),
        "flops": analytic.get("flops"),
        "bytes_accessed": analytic.get("bytes_accessed"),
        "arithmetic_intensity": analytic.get("arithmetic_intensity"),
    }
    flops, nbytes = row["flops"], row["bytes_accessed"]
    if dev_per_call > 0:
        if flops is not None:
            # flops per µs → GFLOP/s is ×1e6 / 1e9
            row["achieved_gflops"] = round(flops / dev_per_call * 1e-3, 6)
        if nbytes is not None:
            row["achieved_gbps"] = round(nbytes / dev_per_call * 1e-3, 6)
    pf, pb = peaks.get("peak_gflops"), peaks.get("peak_gbps")
    ai = row["arithmetic_intensity"]
    if pf and pb and ai is not None:
        ceiling = min(pf, ai * pb)
        row["roofline_ceiling_gflops"] = round(ceiling, 3)
        if row.get("achieved_gflops") is not None and ceiling > 0:
            row["efficiency_pct"] = round(
                100.0 * row["achieved_gflops"] / ceiling, 4
            )
    return row


def bench_measured(dtype: str = "int32", repeats: int = 4) -> dict:
    """The compact measured block bench.py folds next to its analytic
    block: per-entry device time, achieved GFLOP/s / GB/s, efficiency.
    Goes through PROFILER when armed (the report rides the ring and the
    gauges update); falls back to a direct capture otherwise."""
    if PROFILER.enabled:
        rep = PROFILER.capture_report(dtype, repeats=repeats)
    else:
        rep = measured_entry_report(dtype, repeats=repeats)
    fields = ("device_us_per_call", "achieved_gflops", "achieved_gbps",
              "efficiency_pct")
    return {
        "dtype": dtype,
        "platform": rep["platform"],
        "peaks": rep["peaks"],
        "entries": {
            k: {f: v.get(f) for f in fields}
            for k, v in rep["entries"].items()
            if "error" not in v
        },
    }


# ---------------------------------------------------------------------------
# the process singleton


def _median(xs):
    if not xs:
        return None
    xs = sorted(xs)
    n = len(xs)
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


class Profiler:
    """Bounded ring of measured-roofline reports + per-shard dispatch
    telemetry behind the ops ``/profile`` endpoint.

    Disabled by default. ``install()`` (service.app, from the
    ``ops.profile`` knob) arms the ring and registers the
    ``gome_profile_*`` gauges; per-entry labeled children appear after
    the first capture. ``note_shard_dispatch`` is the hot-path hook —
    engine.batch calls it on every dense mesh dispatch with values it
    already computed, so the disabled cost is ONE attribute check and
    zero allocations."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._reports: deque | None = None  # guarded by self._lock (armed ⇔ deque)
        self._shards: deque | None = None  # guarded by self._lock
        self._log_dir: str | None = None  # guarded by self._lock
        self._captures = 0  # guarded by self._lock

    @property
    def enabled(self) -> bool:
        return self._reports is not None  # gomelint: disable=GL402

    def install(
        self,
        keep_n: int = 8,
        log_dir: str | None = None,
        registry=None,
    ) -> "Profiler":
        with self._lock:
            keep = deque(self._reports or (), maxlen=max(1, int(keep_n)))
            self._reports = keep
            if self._shards is None:
                self._shards = deque(maxlen=256)
            self._log_dir = log_dir
        self._export(registry or REGISTRY)
        return self

    def disable(self) -> None:
        with self._lock:
            self._reports = None
            self._shards = None

    # ------------------------------------------------------------------
    # hot path

    def note_shard_dispatch(self, n_shards, rows_per_shard, live_counts):
        """Record one dense mesh dispatch's per-shard geometry: shard
        count, per-shard row-block height (the bucketed max), and the
        per-shard LIVE lane counts (``np.bincount`` the caller already
        holds). Disabled: one attribute check, zero allocations."""
        shards = self._shards  # gomelint: disable=GL402 — lock-free fast
        if shards is None:  # check; the locked append below re-validates
            return
        with self._lock:
            if self._shards is not None:
                self._shards.append((
                    int(n_shards),
                    int(rows_per_shard),
                    tuple(int(c) for c in live_counts),
                ))

    # ------------------------------------------------------------------
    # reports

    def shard_report(self) -> dict:
        """Aggregate view of the recent dense mesh dispatches: per-shard
        dispatched rows vs live lanes and the skew ratio
        (max-shard-live / mean-shard-live — 1.0 is perfectly balanced;
        the dense packer's per-shard MAX bucketing makes dispatched rows
        scale with this number)."""
        with self._lock:
            if self._shards is None:
                return {"enabled": False}
            items = list(self._shards)
        if not items:
            return {"enabled": True, "dispatches": 0}
        skews, rows_pll = [], []
        for d, r_s, counts in items:
            live = sum(counts)
            if live:
                skews.append(max(counts) * d / live)
                rows_pll.append(r_s * d / live)
        d, r_s, counts = items[-1]
        live = sum(counts) or 1
        return {
            "enabled": True,
            "dispatches": len(items),
            "last": {
                "n_shards": d,
                "rows_per_shard": r_s,
                "dispatched_rows": d * r_s,
                "live_per_shard": list(counts),
                "skew": round(max(counts) * d / live, 4),
                "rows_per_live_lane": round(d * r_s / live, 4),
            },
            "skew_p50": round(_median(skews), 4) if skews else None,
            "rows_per_live_lane_p50": (
                round(_median(rows_pll), 4) if rows_pll else None
            ),
        }

    def capture_report(self, dtype: str = "int32", repeats: int = 8) -> dict:
        """Run a measured-roofline capture now, push it onto the ring,
        and (re)bind the per-entry gauges. Seconds of work — ops
        surface, never the dispatch path."""
        with self._lock:
            log_dir = self._log_dir
        rep = measured_entry_report(dtype, repeats=repeats, log_dir=log_dir)
        with self._lock:
            if self._reports is not None:
                self._reports.append(rep)
                self._captures += 1
        self._export_entries(rep)
        return rep

    def last_report(self) -> dict | None:
        with self._lock:
            if not self._reports:
                return None
            return self._reports[-1]

    def payload(
        self, dtype: str = "int32", refresh: bool = False, repeats: int = 4
    ) -> dict:
        """The ops ``/profile`` JSON body. Armed with no capture yet (or
        ``?refresh=1``) it captures on demand; the errors a capture can
        hit degrade to an ``error`` field, never a 500."""
        if not self.enabled:
            return {
                "enabled": False, "captures": 0, "report": None,
                "shards": {"enabled": False},
            }
        rep = None if refresh else self.last_report()
        err = None
        if rep is None:
            try:
                rep = self.capture_report(dtype, repeats=repeats)
            except Exception as exc:  # pragma: no cover - backend gaps
                err = f"{type(exc).__name__}: {exc}"
        with self._lock:
            n = self._captures
        out = {"enabled": True, "captures": n, "report": rep,
               "shards": self.shard_report()}
        if err:
            out["error"] = err
        return out

    # ------------------------------------------------------------------
    # gauges

    def _export(self, reg) -> None:
        reg.callback_gauge(
            "gome_profile_captures_total",
            "Measured-roofline captures taken since arm",
            lambda: self._captures,  # gomelint: disable=GL402 — see _export
        )
        reg.callback_gauge(
            "gome_profile_shard_skew",
            "p50 max/mean live-lanes-per-shard over recent dense mesh "
            "dispatches (1.0 = balanced)",
            lambda: self.shard_report().get("skew_p50") or 0.0,
        )
        reg.callback_gauge(
            "gome_profile_shard_rows_per_live_lane",
            "p50 dispatched-rows per live lane over recent dense mesh "
            "dispatches (ROADMAP open item 2 targets <= 2.0)",
            lambda: self.shard_report().get("rows_per_live_lane_p50") or 0.0,
        )
        self._registry = reg  # single-writer: install() caller

    def _export_entries(self, rep: dict) -> None:
        reg = getattr(self, "_registry", None)
        if reg is None:
            return
        specs = (
            ("gome_profile_device_us",
             "Measured per-call device time (us) from the last capture",
             "device_us_per_call"),
            ("gome_profile_achieved_gflops",
             "Achieved GFLOP/s (analytic flops / measured device time)",
             "achieved_gflops"),
            ("gome_profile_achieved_gbps",
             "Achieved GB/s (analytic bytes / measured device time)",
             "achieved_gbps"),
            ("gome_profile_efficiency_pct",
             "Achieved GFLOP/s as % of the roofline ceiling",
             "efficiency_pct"),
        )
        for entry, row in rep.get("entries", {}).items():
            if "error" in row:
                continue
            for name, help_, field in specs:
                reg.callback_gauge(
                    name, help_,
                    lambda e=entry, f=field: self._entry_field(e, f),
                    labels={"entry": entry},
                )

    def _entry_field(self, entry: str, field: str) -> float:
        rep = self.last_report()
        if not rep:
            return 0.0
        v = (rep.get("entries", {}).get(entry) or {}).get(field)
        return float(v) if v is not None else 0.0


PROFILER = Profiler()
