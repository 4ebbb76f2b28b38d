"""GL2xx int32-envelope: abstract-eval (jaxpr) dtype audit of the engine.

The matching core's exactness argument (SURVEY §2.2, step.py SAT32_MAX) is
an *integer* argument: every device value is a scaled tick/lot int, depth
prefix sums saturate below 2^31, and nothing ever passes through floating
point. jax, meanwhile, loves to promote — a bare Python float literal, an
accidental `jnp.mean`, or an x64-mode Python int can silently widen an
int32 graph to int64 (2x HBM traffic on every book array — the dtype knob
exists precisely to halve it) or drift it through f32/f64 (silently
*inexact* lots). Dynamic tests only see the dtypes of the outputs they
assert on; this pass abstract-evals the actual jaxprs and audits EVERY
intermediate value:

  GL201  float64 anywhere in an engine graph (never legitimate)
  GL202  any float dtype in the integer matching envelope
  GL203  an integer wider than the declared book dtype (e.g. int64
         intermediates in an int32-mode engine)

Driven by the CLI (`gomelint --jaxpr`) and tests via
:func:`check_engine_envelope`, which traces the real entry points — the
single-op step, the scan x vmap batch step, the dense (gather/scatter)
step, the frame-compaction accumulator, the grid scatter-builder, and the
Pallas kernel in interpret mode — with small int32 geometry. The walk
recurses into nested jaxprs (pjit/scan/cond/pallas_call params), so a
promotion buried four combinators deep still surfaces, attributed to the
`gome_tpu` source line that created the offending equation.
"""

from __future__ import annotations

from .core import Finding, register_rules

register_rules({
    "GL201": "float64 value in an engine jaxpr (x64 creep)",
    "GL202": "float value inside the integer matching envelope",
    "GL203": "integer wider than the declared book dtype in the jaxpr",
})

#: dtype names always allowed in engine graphs regardless of declared
#: width: predicates and sub-word index/code types.
_ALWAYS_OK = {"bool", "int8", "uint8", "int16", "uint16"}

_INT_WIDTH = {"int8": 8, "uint8": 8, "int16": 16, "uint16": 16,
              "int32": 32, "uint32": 32, "int64": 64, "uint64": 64}


def _src_line(eqn) -> tuple[str, int] | None:
    """Best-effort `file:line` for one jaxpr equation, preferring frames
    inside this repo (the traceback also walks jax internals)."""
    try:
        frames = list(eqn.source_info.traceback.frames)
    except Exception:
        return None
    best = None
    for fr in frames:
        fname = getattr(fr, "file_name", "")
        if "gome_tpu" in fname:
            best = (fname, int(getattr(fr, "start_line", 0) or
                               getattr(fr, "line_num", 0)))
            break
        if best is None and "site-packages" not in fname \
                and "jax/_src" not in fname:
            best = (fname, int(getattr(fr, "start_line", 0) or
                               getattr(fr, "line_num", 0)))
    return best


def _iter_jaxprs(params: dict):
    """Yield nested (closed) jaxprs hiding in an eqn's params — pjit's
    `jaxpr`, scan/while's `jaxpr`/`cond_jaxpr`/`body_jaxpr`, cond's
    `branches`, pallas_call's kernel jaxpr."""
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr  # ClosedJaxpr
            elif hasattr(item, "eqns"):  # raw Jaxpr
                yield item


def check_jaxpr(closed, declared_dtype: str, context: str,
                allow_floats: bool = False) -> list[Finding]:
    """Audit one (closed) jaxpr against the declared integer envelope.
    `declared_dtype` is the book dtype name ("int32"/"int64")."""
    findings: list[Finding] = []
    width = _INT_WIDTH[declared_dtype]
    seen: set[tuple] = set()

    def audit_aval(aval, eqn, where: str) -> None:
        dtype = getattr(aval, "dtype", None)
        if dtype is None:
            return
        name = dtype.name
        loc = _src_line(eqn) if eqn is not None else None
        path, line = loc if loc else (f"<jaxpr:{context}>", 0)
        key = (name, path, line, where)
        if key in seen:
            return
        prim = getattr(eqn, "primitive", None)
        prim = f" [{prim}]" if prim is not None else ""
        if name == "float64":
            if (allow_floats and not aval.shape
                    and getattr(aval, "weak_type", False)):
                # A float-tolerant entry (the sim flow generator): a
                # WEAK-typed f64 scalar is a python literal inside jax
                # library code (jax.random defaults under x64) — it
                # demotes against any strong operand and never widens
                # data. Strong float64 is still x64 creep below.
                return
            seen.add(key)
            findings.append(Finding(
                "GL201", path, line, 0,
                f"float64 {where} in {context}{prim}: x64 creep — every "
                "engine value is an exact scaled integer",
            ))
        elif name.startswith(("float", "complex", "bfloat")):
            if not allow_floats:
                seen.add(key)
                findings.append(Finding(
                    "GL202", path, line, 0,
                    f"{name} {where} in {context}{prim}: the matching "
                    "envelope is integer-only (inexact lots otherwise)",
                ))
        elif _INT_WIDTH.get(name, 0) > width and name not in _ALWAYS_OK:
            seen.add(key)
            findings.append(Finding(
                "GL203", path, line, 0,
                f"{name} {where} in {context}{prim}: wider than the "
                f"declared {declared_dtype} book dtype (silent promotion "
                "— 2x HBM traffic and a broken saturation argument)",
            ))

    def walk(jaxpr) -> None:
        for var in list(jaxpr.invars) + list(jaxpr.constvars):
            audit_aval(getattr(var, "aval", None), None, "input")
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                audit_aval(getattr(var, "aval", None), eqn, "value")
            for sub in _iter_jaxprs(eqn.params):
                walk(sub)

    walk(closed.jaxpr if hasattr(closed, "jaxpr") else closed)
    return findings


#: Per-dtype memo of the traced entry records: the GL2xx envelope walk,
#: the GL6xx donation audit, AND the device cost model (gome_tpu.obs.
#: costmodel) all consume these, and the host trace (~seconds on CPU)
#: must be paid once per CLI/CI run, not per family.
_TRACE_CACHE: dict[str, list[dict]] = {}


def traced_entries(dtype: str = "int32") -> list[dict]:
    """Trace the engine's device entry points with small geometry ONCE
    per dtype; returns records ``{"context", "closed", "args"?,
    "params"?, "jits"?, "n_ops"?}``. ``jits`` pairs each record with its
    compiled public entry (and, where one exists, its ``_donating``
    twin) as ``((label, jit_fn), ...)`` — the cost model lowers these
    with the record's own ``args`` so attribution shares this memo's
    canonical geometry; ``n_ops`` is the orders applied per call (the
    per-order normalizer). Imports jax lazily — the pure-AST checkers
    must not pay for it.

    Tracing runs under the dtype's NATIVE x64 mode (int32 books deploy
    with x64 off; int64 books require it — engine/book.py flips it).
    Auditing an int32 graph traced under x64-on would drown the report in
    jnp.sum's int32→int64 promotion, which the deployment configuration
    never executes."""
    if dtype not in _TRACE_CACHE:
        import jax

        with jax.enable_x64(dtype == "int64"):
            _TRACE_CACHE[dtype] = list(_entry_records_x64_scoped(dtype))
    return _TRACE_CACHE[dtype]


def engine_entry_jaxprs(dtype: str = "int32"):
    """Back-compat view of traced_entries: (context, closed_jaxpr)."""
    for rec in traced_entries(dtype):
        yield rec["context"], rec["closed"]


def _entry_records_x64_scoped(dtype: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engine import frames as fr
    from ..engine.batch import (
        _lane_scan_impl,
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
    from ..engine.step import step_impl

    config = BookConfig(cap=8, max_fills=4, dtype=jnp.dtype(dtype))
    dt = jnp.dtype(dtype)
    s, t = 2, 4

    books = init_books(config, s)
    op_grid = DeviceOp(**{
        f: jnp.zeros((s, t), jnp.int32 if f in GRID_I32_FIELDS
                     else dt)
        for f in DeviceOp._fields
    })
    one_book = jax.tree.map(lambda a: a[0], books)
    one_op = jax.tree.map(lambda a: a[0, 0], op_grid)
    ops_lane = jax.tree.map(lambda a: a[0], op_grid)

    yield dict(
        context="engine/step.py:step_impl",
        closed=jax.make_jaxpr(
            lambda b, o: step_impl(config, b, o))(one_book, one_op),
        args=(config, one_book, one_op),
        params=["config", "book", "op"],
        n_ops=1,
    )
    yield dict(
        context="engine/batch.py:batch_step",
        closed=jax.make_jaxpr(
            lambda b, o: batch_step(config, b, o))(books, op_grid),
        args=(config, books, op_grid),
        params=["config", "books", "ops"],
        jits=(
            ("batch_step", batch_step),
            ("batch_step_donating", batch_step_donating),
        ),
        n_ops=s * t,
    )
    lane_ids = jnp.zeros((s,), jnp.int32)
    yield dict(
        context="engine/batch.py:dense_batch_step",
        closed=jax.make_jaxpr(
            lambda b, l_, o: dense_batch_step(config, b, l_, o)
        )(books, lane_ids, op_grid),
        args=(config, books, lane_ids, op_grid),
        params=["config", "books", "lane_ids", "ops"],
        jits=(
            ("dense_batch_step", dense_batch_step),
            ("dense_batch_step_donating", dense_batch_step_donating),
        ),
        n_ops=s * t,
    )
    yield dict(
        context="engine/batch.py:lane_scan",
        closed=jax.make_jaxpr(
            lambda b, o: _lane_scan_impl(config, b, o))(one_book, ops_lane),
        args=(config, one_book, ops_lane),
        params=["config", "book", "ops_lane"],
        jits=(
            ("lane_scan", lane_scan),
            ("lane_scan_donating", lane_scan_donating),
        ),
        n_ops=t,
    )

    # frame compaction accumulator (the fast-path event path)
    from ..engine.book import StepOutput
    wide = jnp.result_type(jnp.int32, dt)
    k = config.max_fills
    outs = StepOutput(**{
        f: (jnp.zeros((s, t), jnp.int32)
            if f in ("n_fills", "fill_overflow", "rested", "book_overflow",
                     "cancel_found", "expired")
            else jnp.zeros((s, t), dt)
            if f in ("taker_remaining", "cancel_volume")
            else jnp.zeros((s, t, k), dt))
        for f in StepOutput._fields
    })
    fills_acc = jnp.zeros((len(fr._FILL_FIELDS), 64), wide)
    cancels_acc = jnp.zeros((len(fr._CANCEL_FIELDS), 64), wide)
    totals_acc = jnp.zeros((8, fr.n_totals(config)), jnp.int32)
    yield dict(
        context="engine/frames.py:compact_accum",
        closed=jax.make_jaxpr(
            lambda o, f, c, tt: fr.compact_accum(config, o, f, c, tt,
                                                 np.int32(0))
        )(outs, fills_acc, cancels_acc, totals_acc),
        args=(config, outs, fills_acc, cancels_acc, totals_acc,
              np.int32(0)),
        jits=(("compact_accum", fr.compact_accum),),
        n_ops=s * t,
    )

    # device-side grid scatter-builder
    scatter = fr._scatter_grid_fn(dt.name, 2, 4)
    cols = jnp.zeros((7, 64), dt)
    flat = jnp.full((64,), 8, jnp.int32)
    yield dict(
        context="engine/frames.py:_scatter_grid_fn",
        closed=jax.make_jaxpr(scatter)(cols, flat),
        args=(cols, flat),
        jits=(("scatter_grid", scatter),),
        n_ops=64,
    )

    # Pallas kernel, interpret mode (same jaxpr the TPU lowering consumes)
    try:
        from ..ops.pallas_match import pallas_batch_step
        yield dict(
            context="ops/pallas_match.py:pallas_batch_step",
            closed=jax.make_jaxpr(
                lambda b, o: pallas_batch_step(config, b, o, block_s=2,
                                               interpret=True)
            )(books, op_grid),
        )
    except Exception:  # pragma: no cover - interpret support varies
        pass

    # Simulator flow generator (gome_tpu.sim): the emitted op grid must
    # honor the same envelope as the engine that consumes it. Hawkes
    # intensities are float32 BY DESIGN (the stochastic model, never book
    # state), so GL202 is waived for this entry; GL201 (f64 creep) and
    # GL203 (int widening) still audit the integer grid path.
    from ..sim.flow import FlowConfig, flow_init, gen_ops
    fcfg = FlowConfig(n_lanes=s, t_bins=t)
    fstate = flow_init(fcfg, jax.random.PRNGKey(0))
    yield dict(
        context="sim/flow.py:gen_ops",
        closed=jax.make_jaxpr(
            lambda st, b: gen_ops(fcfg, st, b))(fstate, books),
        allow_floats=True,
    )


def check_engine_envelope(dtype: str = "int32") -> list[Finding]:
    """The whole-engine envelope audit the CLI and CI run."""
    findings: list[Finding] = []
    for rec in traced_entries(dtype):
        findings.extend(check_jaxpr(
            rec["closed"], dtype, rec["context"],
            allow_floats=bool(rec.get("allow_floats", False)),
        ))
    return findings
