"""Analytic device cost model — XLA cost/memory attribution per entry.

Every compiled engine entry carries an exact, DETERMINISTIC description
of what it costs: XLA's ``cost_analysis()`` (FLOPs, bytes accessed) and
``memory_analysis()`` (argument / output / temp / aliased buffer sizes)
on the compiled executable. The matching engine's throughput story has so
far been wall-clock only — blind
to WHAT the device does per order, and useless as a CI regression signal
(JAX-LOB and CoinTossX both make per-kernel op/memory accounting the
primary honesty check for a vectorized matching engine). This module
turns the attribution into first-class data:

  * :func:`entry_report` — one row per engine device entry (batch_step,
    dense_batch_step, lane_scan, compact_accum, the grid scatter-builder)
    and per donation twin: flops, bytes accessed, arithmetic intensity,
    argument/output/temp/alias bytes, peak HBM, jaxpr op count, and
    per-order normalizations.
  * :func:`donation_report` — each public entry vs its ``_donating``
    twin: alias bytes (what XLA actually reused) and the peak-HBM delta —
    finally measuring the footprint win PR 4 could only argue for
    ("the win is device HBM footprint, which CPU timing cannot see").
  * :func:`ratchet_metrics` — the flat {name: value} dict
    ``scripts/perf_ratchet.py`` gates against ``PERF_BASELINE.json``.
  * :func:`bench_analytics` — the compact block ``bench.py`` folds into
    its JSON payload next to orders/sec.

Geometry and trace reuse: the entries are lowered at the SAME canonical
small geometry as ``analysis.envelope.traced_entries`` (cap=8,
max_fills=4, S=2, T=4), consuming the memo's recorded args directly — the
cost model introduces no new trace geometry, and the per-(entry, dtype)
report is memoized so /cost, bench, and the ratchet share one set of
compiled executables per process. Peak HBM here is the analytic live-set
bound ``argument + output + temp - alias`` (donated/aliased buffers are
shared between an argument and an output, so they count once); on CPU
and TPU alike these numbers come from the compiled executable, not a
measurement, which is what makes them CI-gateable.

Skip-safety: backends may return ``None`` from ``cost_analysis`` /
``memory_analysis``; the report then carries ``None`` fields and callers
(tests, the ratchet) skip those metrics instead of failing.
"""

from __future__ import annotations

import warnings

#: Memoized per (dtype, ) report: one lowering+compile set per process.
_REPORT_CACHE: dict[str, list[dict]] = {}

#: Entries whose jaxpr is a single jit wrapper (batch/dense/kernel
#: steps): the INNER jaxpr carries the real op count; unwrap one level.
_WRAPPER_PRIMS = ("jit", "custom_jvp_call", "custom_vjp_call")


def _x64_ctx(dtype: str):
    import jax

    return jax.enable_x64(dtype == "int64")


def _jaxpr_eqn_count(closed) -> int:
    """Equation count of a closed jaxpr, unwrapping a single top-level
    jit (the jit entries trace to one jit eqn wrapping the real body)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    eqns = list(jaxpr.eqns)
    while len(eqns) == 1 and str(eqns[0].primitive) in _WRAPPER_PRIMS:
        inner = eqns[0].params.get("jaxpr")
        if inner is None:
            break
        jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
        eqns = list(jaxpr.eqns)
    n = len(eqns)
    return n


def compiled_stats(compiled) -> dict:
    """Cost/memory attribution of one compiled executable. Fields are
    None where the backend declines to report (skip-safe)."""
    # None when the backend has no cost model (skip-safe, see docstring).
    cost = compiled.cost_analysis() or {}
    flops = cost.get("flops")
    bytes_accessed = cost.get("bytes accessed")
    out = {
        "flops": float(flops) if flops is not None else None,
        "bytes_accessed": (
            float(bytes_accessed) if bytes_accessed is not None else None
        ),
        "arithmetic_intensity": (
            float(flops) / float(bytes_accessed)
            if flops and bytes_accessed
            else None
        ),
        "argument_bytes": None,
        "output_bytes": None,
        "temp_bytes": None,
        "alias_bytes": None,
        "generated_code_bytes": None,
        "peak_hbm_bytes": None,
    }
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        arg = int(ma.argument_size_in_bytes)
        outb = int(ma.output_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        out.update(
            argument_bytes=arg,
            output_bytes=outb,
            temp_bytes=temp,
            alias_bytes=alias,
            generated_code_bytes=int(ma.generated_code_size_in_bytes),
            # live-set bound: aliased (donated) buffers are one physical
            # buffer serving both an argument and an output
            peak_hbm_bytes=arg + outb + temp - alias,
        )
    return out


def entry_report(dtype: str = "int32") -> list[dict]:
    """One attribution row per compiled engine entry at the canonical
    envelope geometry. Memoized per dtype (the /cost endpoint, bench, and
    the perf ratchet share one compile set)."""
    if dtype in _REPORT_CACHE:
        return _REPORT_CACHE[dtype]
    from ..analysis.envelope import traced_entries

    rows: list[dict] = []
    with _x64_ctx(dtype):
        for rec in traced_entries(dtype):
            jits = rec.get("jits")
            if not jits or "args" not in rec:
                continue
            n_ops = int(rec.get("n_ops", 0)) or None
            for label, fn in jits:
                with warnings.catch_warnings():
                    # donating twins at tiny geometry warn about unusable
                    # donated buffers — deliberate (engine.batch)
                    warnings.simplefilter("ignore")
                    try:
                        lowered = fn.lower(*rec["args"])
                        compiled = lowered.compile()
                    except Exception as exc:  # backend-specific gaps
                        rows.append({
                            "entry": label,
                            "context": rec["context"],
                            "error": f"{type(exc).__name__}: {exc}",
                        })
                        continue
                stats = compiled_stats(compiled)
                stats.update(
                    entry=label,
                    context=rec["context"],
                    n_ops=n_ops,
                    jaxpr_eqns=_jaxpr_eqn_count(rec["closed"]),
                    flops_per_order=(
                        stats["flops"] / n_ops
                        if stats["flops"] is not None and n_ops
                        else None
                    ),
                    bytes_per_order=(
                        stats["bytes_accessed"] / n_ops
                        if stats["bytes_accessed"] is not None and n_ops
                        else None
                    ),
                )
                rows.append(stats)
    _REPORT_CACHE[dtype] = rows
    return rows


#: Donation-report geometry: cap = the engine's smallest cap class
#: (batch.CAP_CLASS_MIN), S=8 lanes, T=32 deep. The envelope memo's toy
#: geometry (cap=8, T=4) is the right cost for the DTYPE audit but too
#: small to measure donation — XLA layout padding at an 8-slot book is
#: tens of bytes either way and swamps the aliasing signal; at the
#: smallest REAL book class the donated-buffer reuse dominates and the
#: twin-vs-public comparison is stable.
_DONATION_GEOMETRY = (64, 8, 32)  # (cap, S, T)

_DONATION_CACHE: dict[str, list[dict]] = {}


def donation_report(dtype: str = "int32") -> list[dict]:
    """Donation effectiveness: each public entry vs its ``_donating``
    twin (engine.batch pairs them; PR 4's GL6xx application), compiled
    at the smallest realistic book class (_DONATION_GEOMETRY). Positive
    ``peak_hbm_saved_bytes`` / nonzero twin ``alias_bytes`` is the
    measured footprint win PR 4 could only argue for; a backend that
    does not implement donation reports zero savings — the twin's peak
    is still never WORSE than the public entry's, which tests pin."""
    if dtype in _DONATION_CACHE:
        return _DONATION_CACHE[dtype]
    import jax
    import jax.numpy as jnp

    from ..engine.batch import (
        batch_step,
        batch_step_donating,
        dense_batch_step,
        dense_batch_step_donating,
        lane_scan,
        lane_scan_donating,
    )
    from ..engine.book import (
        GRID_I32_FIELDS,
        BookConfig,
        DeviceOp,
        init_books,
    )

    cap, s, t = _DONATION_GEOMETRY
    out: list[dict] = []
    with _x64_ctx(dtype):
        config = BookConfig(cap=cap, max_fills=4, dtype=jnp.dtype(dtype))
        dt = jnp.dtype(dtype)
        books = init_books(config, s)
        op_grid = DeviceOp(**{
            f: jnp.zeros(
                (s, t),
                jnp.int32 if f in GRID_I32_FIELDS else dt,
            )
            for f in DeviceOp._fields
        })
        one_book = jax.tree.map(lambda a: a[0], books)
        ops_lane = jax.tree.map(lambda a: a[0], op_grid)
        lane_ids = jnp.zeros((s,), jnp.int32)
        pairs = (
            ("batch_step", batch_step, batch_step_donating,
             (config, books, op_grid)),
            ("dense_batch_step", dense_batch_step,
             dense_batch_step_donating, (config, books, lane_ids, op_grid)),
            ("lane_scan", lane_scan, lane_scan_donating,
             (config, one_book, ops_lane)),
        )
        # pairs is a host tuple (the arrays inside are lowered, never
        # iterated), and this report runs off-clock at boot/scrape time.
        for name, pub_fn, twin_fn, args in pairs:  # gomelint: disable=GL503
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                try:
                    pub = compiled_stats(pub_fn.lower(*args).compile())
                    twin = compiled_stats(twin_fn.lower(*args).compile())
                except Exception as exc:
                    out.append({
                        "entry": name,
                        "error": f"{type(exc).__name__}: {exc}",
                    })
                    continue
            saved = None
            if (
                pub["peak_hbm_bytes"] is not None
                and twin["peak_hbm_bytes"] is not None
            ):
                saved = pub["peak_hbm_bytes"] - twin["peak_hbm_bytes"]
            out.append({
                "entry": name,
                "geometry": {"cap": cap, "s": s, "t": t},
                "public_peak_hbm_bytes": pub["peak_hbm_bytes"],
                "donating_peak_hbm_bytes": twin["peak_hbm_bytes"],
                "peak_hbm_saved_bytes": saved,
                "donating_alias_bytes": twin["alias_bytes"],
                "donation_effective": bool(twin["alias_bytes"]),
            })
    _DONATION_CACHE[dtype] = out
    return out


#: The entries the perf ratchet gates (the engine's hot-path graphs).
RATCHET_ENTRIES = (
    "batch_step", "dense_batch_step", "lane_scan", "compact_accum",
    "scatter_grid",
)


def ratchet_metrics(dtype: str = "int32") -> dict:
    """Flat {metric: value} for scripts/perf_ratchet.py — lower is better
    for every metric. Metrics the backend declines to report are simply
    absent (the ratchet skips them)."""
    out: dict[str, float] = {}
    for r in entry_report(dtype):
        if "error" in r or r["entry"] not in RATCHET_ENTRIES:
            continue
        name = r["entry"]
        if r.get("flops_per_order") is not None:
            out[f"{name}.flops_per_order"] = round(r["flops_per_order"], 3)
        if r.get("bytes_per_order") is not None:
            out[f"{name}.bytes_per_order"] = round(r["bytes_per_order"], 3)
        if r.get("peak_hbm_bytes") is not None:
            out[f"{name}.peak_hbm_bytes"] = int(r["peak_hbm_bytes"])
    return out


def bench_analytics(dtype: str = "int32") -> dict:
    """The compact analytic block bench.py folds into its JSON payload:
    per-entry flops/order, bytes/order, peak HBM, plus the donation
    savings — so BENCH_*.json snapshots carry the analytic trajectory
    alongside wall-clock orders/sec."""
    entries = {}
    for r in entry_report(dtype):
        if "error" in r or r["entry"] not in RATCHET_ENTRIES:
            continue
        entries[r["entry"]] = {
            "flops_per_order": r.get("flops_per_order"),
            "bytes_per_order": r.get("bytes_per_order"),
            "arithmetic_intensity": r.get("arithmetic_intensity"),
            "peak_hbm_bytes": r.get("peak_hbm_bytes"),
        }
    return {
        "dtype": dtype,
        "entries": entries,
        "donation": {
            d["entry"]: d["peak_hbm_saved_bytes"]
            for d in donation_report(dtype)
        },
    }


def clear_cache() -> None:
    """Drop the memoized reports (tests that reconfigure jax call this)."""
    _REPORT_CACHE.clear()
    _DONATION_CACHE.clear()
