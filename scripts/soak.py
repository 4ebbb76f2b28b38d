"""Soak driver — the steady-state proof (ROADMAP open item 5, ISSUE 6).

Runs REAL gateway-step -> bus -> consumer -> engine traffic (the mixed
reference-driver-shaped flow from bench.py: Zipf symbols, ~45% cancels
incl. same-frame races, ~25% markets) on a WALL CLOCK for `--seconds`,
with the host-side timeline sampler (gome_tpu.obs.timeline) recording
RSS, getrusage deltas, live-buffer counts, compile totals, queue depth,
and the geometry-manifest hash throughout. The run ends in a VERDICT
block — pass/fail, machine-checkable, computed from the recorded series
and the obs leak detector:

  live_buffers_flat   obs.live.assert_steady_state on the post-soak
                      pipeline: N further frames leave the live device-
                      buffer count at its baseline (a growing count is a
                      leaked buffer);
  rss_bounded         host-memory growth over the steady window (first
                      40% of samples dropped as warm-in) is bounded:
                      least-squares slope under `--rss-slope-mb-per-min`,
                      OR absolute growth under `--rss-growth-mb` (short
                      runs: a slope over seconds is noise), OR growth
                      per PROCESSED ORDER under `--rss-bytes-per-order`.
                      The per-order bound is the contract the engine can
                      actually promise: the oid/uid interner tables are
                      grow-only BY DESIGN (every unique order id is
                      interned for cancel routing + event decode), so a
                      wall-clock soak's RSS slope is order-rate-
                      proportional — measured here at ~80 B/order on the
                      mixed flow — while a real leak (a retained device
                      buffer, an unbounded ring) blows through the
                      per-order budget as well;
  geometry_stable     the geometry-manifest hash holds still across the
                      last half of the run — a drifting hash means the
                      flow is still minting compiled shapes (~1s host
                      re-trace each), which a steady state cannot carry;
  zero_breaker_trips  no degraded-mode entries, no retryable rejects, no
                      spilled frames, no failed consumer steps.

`--latency-configs` then MEASURES the latency story (the "sub-100ms p50"
projection cited depth-1 / 16K-frame configurations no run had ever
executed — VERDICT r5): for each `<depth>x<frame>` config a fresh
closed-loop pipeline runs the mixed flow with the order-lifecycle tracer
armed, reporting end-to-end order->publish p50/p90/p99 AND the per-stage
breakdown (pad_pack / compile / device_execute / decode / publish) from
the PR 2 stage histograms. Every number in the payload is measured on
this host; `"measured": true` is asserted by tests/test_soak.py against
the committed SOAK_r01.json.

Usage:
    python scripts/soak.py --seconds 60 --out SOAK_r01.json
    python scripts/soak.py --seconds 10 --frame 512 --symbols 16  # smoke

Exit code 0 iff every verdict passed. CI (tier1.yml soak job) runs a
~60 s budget and uploads the SOAK + timeline artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Default to the CPU backend: the soak measures HOST steady state (RSS,
# allocations, shape churn) and must run in CI; SOAK_PLATFORM=tpu runs
# the same driver against the real chip.
os.environ.setdefault("JAX_PLATFORMS", os.environ.get("SOAK_PLATFORM", "cpu"))

import numpy as np


def _parse_configs(spec: str) -> list[tuple[int, int]]:
    """"1x16384,2x16384" -> [(pipeline_depth, frame_orders), ...]."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        d, _, f = part.partition("x")
        out.append((int(d), int(f)))
    return out


def _counter_value(name: str) -> int:
    from gome_tpu.utils.metrics import REGISTRY

    return int(REGISTRY.counter(name).value())


_FAULT_COUNTERS = (
    "gome_gateway_retryable_rejects_total",
    "gome_gateway_spilled_frames_total",
    "gome_consumer_step_failures_total",
)


def _rss_fit(samples: list[dict]) -> dict:
    """Least-squares RSS slope (bytes/s), total growth, and growth per
    processed order over the given sample window (the samples' "orders"
    field is cumulative, so the window's order count is a diff)."""
    t = np.asarray([s["t"] for s in samples], np.float64)
    rss = np.asarray([s["rss_bytes"] for s in samples], np.float64)
    if len(t) >= 2 and t[-1] > t[0]:
        slope = float(np.polyfit(t - t[0], rss, 1)[0])
    else:
        slope = 0.0
    growth = int(rss[-1] - rss[0]) if len(rss) else 0
    orders = (
        int(samples[-1]["orders"] - samples[0]["orders"]) if samples else 0
    )
    return {
        "samples": len(samples),
        "window_s": round(float(t[-1] - t[0]), 3) if len(t) else 0.0,
        "slope_bytes_per_s": round(slope, 1),
        "slope_mb_per_min": round(slope * 60 / 2**20, 3),
        "growth_bytes": growth,
        "window_orders": orders,
        "growth_bytes_per_order": round(growth / max(orders, 1), 2),
        "first_bytes": int(rss[0]) if len(rss) else 0,
        "last_bytes": int(rss[-1]) if len(rss) else 0,
    }


def _build_stack(args, pipeline_depth: int, seed: int):
    """One gateway-step -> bus -> consumer -> engine pipeline plus its
    mixed-flow generator (fresh books; the caller warms it)."""
    import jax.numpy as jnp

    from bench import _MixedFlow
    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer

    engine = MatchEngine(
        config=BookConfig(cap=args.cap, max_fills=16, dtype=jnp.int32),
        n_slots=args.symbols,
        max_t=32,
        kernel=args.kernel,
    )
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
        pipeline_depth=pipeline_depth,
    )
    flow = _MixedFlow(np.random.default_rng(seed), args.symbols)
    return engine, bus, consumer, flow


def run_soak(args) -> dict:
    """The wall-clock soak phase: warm the pipeline, arm the timeline,
    drive the mixed stream until the budget expires, then compute the
    verdict block from the recorded series."""
    import jax

    from bench import _svc_gateway_step, _svc_warmup
    from gome_tpu.obs import live
    from gome_tpu.obs.compile_journal import JOURNAL
    from gome_tpu.obs.timeline import TIMELINE, service_timeline
    from gome_tpu.utils.trace import TRACER

    engine, bus, consumer, flow = _build_stack(
        args, pipeline_depth=args.pipeline, seed=11
    )
    symbols = [f"sym{i}" for i in range(args.symbols)]
    make_frame = lambda: flow.frame(args.frame)

    # Warmup off the record: compiles, book fill-in, geometry margining.
    JOURNAL.install(keep_n=256)
    t0 = time.perf_counter()
    n_warm = _svc_warmup(engine, consumer, bus, make_frame, symbols)
    warm_s = time.perf_counter() - t0

    # Arm + start the sampler AFTER warmup: the rusage/RSS baseline and
    # every verdict window then describe the steady flow, not the
    # compile storm.
    TIMELINE.install(interval_s=args.interval, keep_n=args.timeline_keep)
    import types

    service_timeline(types.SimpleNamespace(engine=engine, bus=bus))
    faults0 = {name: _counter_value(name) for name in _FAULT_COUNTERS}
    TIMELINE.sample()
    TIMELINE.start()

    # The soak loop: closed-loop wall-clock traffic. One frame published
    # per iteration, one consumer step drained (with pipelining, frames
    # overlap exactly as in production), the match queue drained like a
    # real feed, and BOTH in-memory logs compacted past their committed
    # offsets — a wall-clock soak on an uncompacted in-process bus would
    # measure its own harness's retention, not the engine's steady
    # state. The deadline, not an order count, ends the run.
    from gome_tpu.bus.colwire import decode_event_frame

    deadline = time.monotonic() + args.seconds
    frames = orders = done = events = 0
    ev_off = bus.match_queue.end_offset()
    t0 = time.perf_counter()
    while time.monotonic() < deadline:
        cols = make_frame()
        _svc_gateway_step(cols, symbols, engine.pre_pool, bus.order_queue)
        frames += 1
        orders += int(cols["n"])
        done += consumer.run_once()
        for m in bus.match_queue.read_from(ev_off, 1 << 20):
            events += len(decode_event_frame(m.body))
            ev_off = m.offset + 1
        bus.match_queue.commit(ev_off)
        bus.match_queue.compact()
        bus.order_queue.compact()
    done += consumer.drain()
    for m in bus.match_queue.read_from(ev_off, 1 << 20):
        events += len(decode_event_frame(m.body))
        ev_off = m.offset + 1
    elapsed = time.perf_counter() - t0
    TIMELINE.stop()
    TIMELINE.sample()
    assert done == orders, (done, orders)

    series = TIMELINE.series()
    faults = {
        name: _counter_value(name) - faults0[name]
        for name in _FAULT_COUNTERS
    }

    # -- verdicts ----------------------------------------------------------
    verdicts: dict = {}

    def step():
        cols = make_frame()
        _svc_gateway_step(cols, symbols, engine.pre_pool, bus.order_queue)
        consumer.drain()

    try:
        leak = live.assert_steady_state(step, steps=6, settle=3)
        verdicts["live_buffers_flat"] = {
            "pass": True,
            "leaked": leak["leaked"],
            "baseline": leak["baseline"],
            "counts": leak["counts"],
        }
    except AssertionError as exc:
        verdicts["live_buffers_flat"] = {"pass": False, "detail": str(exc)}

    steady = series[max(len(series) * 2 // 5, 1):] or series
    fit = _rss_fit(steady)
    fit["pass"] = (
        fit["slope_mb_per_min"] <= args.rss_slope_mb_per_min
        or fit["growth_bytes"] <= args.rss_growth_mb * 2**20
        # The per-order budget: interner tables grow ~80 B per unique
        # order id by design (see module docstring); a leak grows faster.
        or fit["growth_bytes_per_order"] <= args.rss_bytes_per_order
    )
    verdicts["rss_bounded"] = fit

    tail = [
        s["engine"]["geometry_hash"]
        for s in series[len(series) // 2:]
        if isinstance(s.get("engine"), dict) and "geometry_hash" in s["engine"]
    ]
    verdicts["geometry_stable"] = {
        "pass": bool(tail) and len(set(tail)) == 1,
        "hashes": sorted(set(tail)),
        "window_samples": len(tail),
    }

    degraded = sum(
        1 for s in series
        if isinstance(s.get("batcher"), dict) and s["batcher"].get("degraded")
    )
    verdicts["zero_breaker_trips"] = {
        "pass": degraded == 0 and all(v == 0 for v in faults.values()),
        "degraded_samples": degraded,
        "fault_counter_deltas": faults,
    }

    verdicts["pass"] = all(
        v["pass"] for k, v in verdicts.items() if isinstance(v, dict)
    )
    st = engine.stats
    report = {
        "seconds_requested": args.seconds,
        "seconds_elapsed": round(elapsed, 3),
        "warmup_frames": n_warm,
        "warmup_s": round(warm_s, 3),
        "frames": frames,
        "orders": orders,
        "events": events,
        "throughput_orders_per_sec": round(orders / max(elapsed, 1e-9)),
        "engine": {
            "device_calls": st.device_calls,
            "cap_escalations": st.cap_escalations,
            "frame_fallbacks": st.frame_fallbacks,
            "cap": engine.config.cap,
        },
        "compile_journal": JOURNAL.summary(),
        "verdicts": verdicts,
        "timeline": series,
        "platform": jax.devices()[0].platform,
    }
    TIMELINE.disable()
    JOURNAL.disable()
    TRACER.disable()
    return report


def run_latency(args) -> dict:
    """The measured latency story: for each (depth, frame) config, a
    fresh closed-loop pipeline over the mixed flow with the order-
    lifecycle tracer armed — end-to-end order->publish percentiles plus
    the per-stage breakdown, every number measured on this host."""
    import jax

    from bench import _svc_gateway_step, _svc_warmup
    from gome_tpu.utils.metrics import Registry
    from gome_tpu.utils.trace import TRACER, FlightRecorder

    configs = []
    for depth, frame_n in _parse_configs(args.latency_configs):
        engine, bus, consumer, flow = _build_stack(
            args, pipeline_depth=depth, seed=11
        )
        symbols = [f"sym{i}" for i in range(args.symbols)]
        make_frame = lambda: flow.frame(frame_n)  # noqa: B023 — used eagerly
        _svc_warmup(engine, consumer, bus, make_frame, symbols)

        # Private registry per config: frame sizes must not pollute each
        # other's stage histograms.
        TRACER.install(FlightRecorder(keep_n=8), registry=Registry())
        n_frames = max(depth + 2, args.latency_orders // frame_n)
        frames = [make_frame() for _ in range(n_frames)]
        pub_t: list = []
        done_t: list = []
        t0 = time.perf_counter()
        for cols in frames:
            pub_t.append(time.perf_counter())
            _svc_gateway_step(
                cols, symbols, engine.pre_pool, bus.order_queue
            )
            n = consumer.run_once()
            now = time.perf_counter()
            for _ in range(n // frame_n):
                done_t.append(now)
        while len(done_t) < n_frames:
            n = consumer.run_once()
            now = time.perf_counter()
            for _ in range(n // frame_n):
                done_t.append(now)
        elapsed = time.perf_counter() - t0
        total = n_frames * frame_n
        rate = total / elapsed

        # Per-order latency: arrivals spread uniformly over each frame's
        # accumulation window at the sustained rate (bench --latency's
        # method — the batching bridge's wait is deliberately included).
        offs = (np.arange(frame_n, dtype=np.float64)[::-1] + 1) / rate
        lat = np.concatenate(
            [d - (p - offs) for p, d in zip(pub_t, done_t)]
        )
        p50, p90, p99 = np.percentile(lat, [50, 90, 99])

        # Corrected (intended-start) percentiles, ISSUE 17: the numbers
        # above anchor arrivals to each frame's ACTUAL publish, so a
        # pipeline stall slips the arrivals with it and queueing delay
        # escapes the percentiles (coordinated omission). The corrected
        # recorder charges every order from a FIXED open-loop schedule at
        # the sustained rate anchored at run start.
        from gome_tpu.obs.capacity import LogHistogram, OpenLoopSchedule

        sched = OpenLoopSchedule(rate, t0=t0)
        chist = LogHistogram(rel_err=0.01, min_value=1e-7, max_value=600.0)
        for f, d in enumerate(done_t):
            base = f * frame_n
            for v in (
                d - (t0 + (np.arange(frame_n) + base + 1) * sched.interval)
            ).tolist():
                chist.record(v if v > 0 else 0.0)
        cp50, cp90, cp99 = chist.percentiles((0.5, 0.9, 0.99))
        stages = {
            stage: {
                "count": v["count"],
                "mean_us": round(v["mean"] * 1e6, 1),
                "p50_us": round(v["p50"] * 1e6, 1),
                "p90_us": round(v["p90"] * 1e6, 1),
                "p99_us": round(v["p99"] * 1e6, 1),
            }
            for stage, v in sorted(
                TRACER.stage_percentiles((0.5, 0.9, 0.99)).items()
            )
        }
        TRACER.disable()
        configs.append({
            "label": f"depth{depth}_frame{frame_n}",
            "pipeline_depth": depth,
            "frame_orders": frame_n,
            "orders": total,
            "measured": True,
            "throughput_orders_per_sec": round(rate),
            "p50_ms": round(p50 * 1e3, 2),
            "p90_ms": round(p90 * 1e3, 2),
            "p99_ms": round(p99 * 1e3, 2),
            "closed_loop": {
                "p50_ms": round(p50 * 1e3, 2),
                "p90_ms": round(p90 * 1e3, 2),
                "p99_ms": round(p99 * 1e3, 2),
                "method": "arrivals anchored to actual publishes",
            },
            "corrected": {
                "p50_ms": round(cp50 * 1e3, 2),
                "p90_ms": round(cp90 * 1e3, 2),
                "p99_ms": round(cp99 * 1e3, 2),
                "method": (
                    "open-loop intended schedule at sustained rate "
                    "(coordinated-omission-safe)"
                ),
                "histogram_rel_err": 0.01,
            },
            "stages": stages,
        })
        print(
            f"# latency {configs[-1]['label']}: p50={p50 * 1e3:.1f}ms "
            f"p90={p90 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms at "
            f"{rate / 1e3:.0f}K orders/sec",
            file=sys.stderr,
        )
    return {
        "method": (
            "closed-loop mixed stream; per-order latency = frame "
            "resolve+publish time minus a synthetic arrival spread "
            "uniformly over the frame's accumulation window at the "
            "sustained rate; stages from the order-lifecycle tracer's "
            "histograms; each config also labels closed_loop vs "
            "corrected (intended-start, coordinated-omission-safe) "
            "percentile blocks"
        ),
        "platform": jax.devices()[0].platform,
        "configs": configs,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=60.0,
                    help="soak wall-clock budget")
    ap.add_argument("--frame", type=int, default=4096,
                    help="orders per soak frame")
    ap.add_argument("--symbols", type=int, default=256)
    # The mixed flow's hot Zipf lane is depth-stationary around ~300
    # resting orders (bench._MixedFlow): cap 512 covers it WITHOUT a
    # mid-soak escalation, so the geometry-stability verdict measures
    # the flow, not a deliberately undersized book.
    ap.add_argument("--cap", type=int, default=512)
    ap.add_argument("--pipeline", type=int, default=2,
                    help="soak-phase cross-frame pipeline depth")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="timeline sampling period (s)")
    ap.add_argument("--timeline-keep", type=int, default=4096)
    ap.add_argument("--rss-slope-mb-per-min", type=float, default=8.0)
    ap.add_argument("--rss-growth-mb", type=float, default=8.0,
                    help="absolute steady-window RSS growth bound")
    ap.add_argument("--rss-bytes-per-order", type=float, default=256.0,
                    help="steady-window RSS growth budget per processed "
                         "order (covers the grow-only interner tables, "
                         "~80 B/order measured)")
    ap.add_argument("--latency-configs", default="1x16384,2x16384",
                    help='comma list of "<depth>x<frame>" latency runs')
    ap.add_argument("--latency-orders", type=int, default=65_536,
                    help="timed orders per latency config")
    ap.add_argument("--skip-latency", action="store_true")
    ap.add_argument("--out", default="SOAK_r01.json")
    ap.add_argument("--timeline-out", default=None,
                    help="separate timeline artifact (default: "
                         "<out stem>_timeline.json)")
    args = ap.parse_args(argv)

    import jax

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    args.kernel = "pallas" if jax.default_backend() == "tpu" else "scan"

    doc = {
        "meta": {
            "generated_unix": round(time.time(), 1),
            "argv": sys.argv[1:],
            "jax": jax.__version__,
            "platform": jax.default_backend(),
            "kernel": args.kernel,
            "frame": args.frame,
            "symbols": args.symbols,
            "cap": args.cap,
            "pipeline": args.pipeline,
        },
        "soak": run_soak(args),
    }
    if not args.skip_latency:
        doc["latency"] = run_latency(args)

    timeline_out = args.timeline_out or (
        os.path.splitext(args.out)[0] + "_timeline.json"
    )
    with open(timeline_out, "w") as f:
        json.dump({"samples": doc["soak"]["timeline"]}, f, indent=1)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, default=str)

    v = doc["soak"]["verdicts"]
    summary = {
        "metric": (
            f"soak {args.seconds:g}s mixed stream, {args.symbols} "
            f"symbols, {args.frame}-order frames, pipeline depth "
            f"{args.pipeline}, {args.kernel} kernel"
        ),
        "pass": v["pass"],
        "throughput_orders_per_sec":
            doc["soak"]["throughput_orders_per_sec"],
        "verdicts": {
            k: d["pass"] for k, d in v.items() if isinstance(d, dict)
        },
        "out": args.out,
    }
    print(json.dumps(summary))
    if not v["pass"]:
        print(f"# SOAK FAILED: {json.dumps(v, default=str)}",
              file=sys.stderr)
    return 0 if v["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
