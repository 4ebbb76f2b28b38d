"""Profile the service's host CPU (dev tool, rebased onto obs.hostprof).

Two drills:

  consumer (default)   replicate bench.py service_main's setup, then
      profile the timed consumer drain. Sampling mode (obs.hostprof's
      in-process sampler — near-zero skew, per-stage ns/order + collapsed
      stacks) is the default; ``--deterministic`` keeps the old cProfile
      run (exact call counts, uniform ~2x inflation).

  --gateway            profile the admit loop specifically: the
      deterministic host-only gateway drill (no engine, no jax) under
      SIGPROF sampling — measured admit ns/order, achievable
      orders/sec/core, the function-by-function stage split, and the
      host-vs-device roofline. ``--out HOSTPROF_r01.json`` writes the
      committed artifact payload.

    python scripts/profile_consumer.py                     # sampled drain
    python scripts/profile_consumer.py --deterministic     # cProfile drain
    python scripts/profile_consumer.py --gateway           # admit drill
    python scripts/profile_consumer.py --gateway --out HOSTPROF_r01.json
    python scripts/profile_consumer.py --gateway --columnar \
        --out HOSTPROF_r02.json                            # columnar admit
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = int(os.environ.get("SVC_ORDERS", 524_288))
FRAME = int(os.environ.get("SVC_FRAME", 262_144))
S = int(os.environ.get("SVC_SYMBOLS", 10_240))
CAP = int(os.environ.get("SVC_CAP", 256))
PIPE = int(os.environ.get("SVC_PIPELINE", 2))


def gateway_main(args) -> int:
    """The admit-loop drill: host-only (no jax import), deterministic
    request stream, SIGPROF sampling. Emits the HOSTPROF_r01 payload
    (scalar path) or, with --columnar, the HOSTPROF_r02 payload (the
    same seeded flow through the array-native batch admit core)."""
    from gome_tpu.obs import hostprof

    doc = hostprof.hostprof_artifact(
        n_orders=args.orders or 30_000,
        seed=args.seed,
        min_samples=args.min_samples,
        # Columnar rounds are ~100x shorter, so the sample budget needs
        # far more of them.
        max_rounds=48 if args.columnar else 8,
        artifact="HOSTPROF_r02" if args.columnar else "HOSTPROF_r01",
        path="columnar" if args.columnar else "scalar",
        batch_n=args.batch_n,
    )
    drill = doc["drill"]
    print(
        f"gateway admit: {drill['orders']} orders in {drill['wall_s']}s "
        f"-> {drill['admit_ns_per_order']} ns/order "
        f"({drill['admit_orders_per_sec_per_core']} orders/sec/core), "
        f"{drill['sampler']['samples']} samples "
        f"({drill['sampler']['mode']} mode), "
        f"coverage {drill['coverage_pct']}%",
        file=sys.stderr,
    )
    for st, row in drill["stages"].items():
        print(
            f"  {st:<14} {row['pct']:>6.2f}%  "
            f"{row['ns_per_order']:>9.1f} ns/order "
            f"({row['samples']} samples)",
            file=sys.stderr,
        )
    body = json.dumps(doc, indent=1, default=str)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(body + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(body)
    return 0


def _consumer_setup():
    """bench.py service_main's setup: pallas engine at the service
    geometry, persisted-manifest precompile, warm-until-stable frames."""
    import bench
    from bench import _svc_columns, _svc_gateway_step, _svc_warmup

    from gome_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    if os.environ.get("PROF_PLATFORM"):
        import jax

        jax.config.update("jax_platforms", os.environ["PROF_PLATFORM"])

    import jax.numpy as jnp
    import numpy as np

    from gome_tpu.bus import MemoryQueue, QueueBus
    from gome_tpu.engine import BookConfig
    from gome_tpu.engine.orchestrator import MatchEngine
    from gome_tpu.service.consumer import OrderConsumer

    engine = MatchEngine(
        config=BookConfig(cap=CAP, max_fills=16, dtype=jnp.int32),
        n_slots=S, max_t=32, kernel="pallas",
        dense_t_max=int(os.environ.get("SVC_DENSE_T", 8192)),
    )
    # Load the service bench's persisted geometry manifest when its path
    # is given (SVC_GEOMETRY, as in bench.py --service) so the profile
    # sees converged shapes, not trace/compile noise.
    geom = os.environ.get("SVC_GEOMETRY")
    if geom:
        n_pre = engine.load_geometry(geom)
        print(f"precompiled {n_pre} combos from {geom}", file=sys.stderr)
    bus = QueueBus(MemoryQueue("doOrder"), MemoryQueue("matchOrder"))
    consumer = OrderConsumer(
        engine, bus, batch_n=1, batch_wait_s=0, match_wire="frame",
        pipeline_depth=PIPE,
    )

    rng = np.random.default_rng(7)
    symbols = [f"sym{i}" for i in range(S)]
    frame_n = min(FRAME, N)
    # Same warm-until-stable + margin-pinning as bench.py service_main:
    # profile only steady-state frames. PROF_MIXED=1 profiles the mixed
    # (headline) stream instead of the clean one.
    oid_box = [1]
    if os.environ.get("PROF_MIXED"):
        flow = bench._MixedFlow(rng, S)
        make_frame = lambda: flow.frame(frame_n)
    else:
        def make_frame():
            cols = _svc_columns(rng, frame_n, S, oid_box[0])
            oid_box[0] += frame_n
            return cols

    n_warm = _svc_warmup(
        engine, consumer, bus, make_frame, symbols, margin=n_pre == 0
    )
    print(f"warm_frames={n_warm}", file=sys.stderr)

    frames_cols = [make_frame() for _ in range(-(-N // frame_n))]
    for cols in frames_cols:
        _svc_gateway_step(cols, symbols, engine.pre_pool, bus.order_queue)
    return consumer


def consumer_main(args) -> int:
    from gome_tpu.engine import frames as engine_frames
    from gome_tpu.obs import hostprof

    consumer = _consumer_setup()
    engine_frames.FETCH_SECONDS = 0.0

    prof = None
    sampler = None
    if args.deterministic:
        import cProfile

        prof = cProfile.Profile()
    else:
        sampler = hostprof.HostSampler(hz=args.hz)

    t0 = time.perf_counter()
    c0 = time.process_time()
    if prof is not None:
        prof.enable()
    else:
        sampler.start()
    n_done = consumer.drain()
    if prof is not None:
        prof.disable()
    else:
        sampler.stop()
    cpu = time.process_time() - c0
    wall = time.perf_counter() - t0
    print(
        f"orders={n_done} wall={wall:.3f}s cpu={cpu:.3f}s "
        f"fetch={engine_frames.FETCH_SECONDS:.3f}s "
        f"-> {n_done / cpu / 1e6:.2f}M orders/sec/core "
        f"({cpu / n_done * 1e6:.3f} us/order)",
        file=sys.stderr,
    )
    if prof is not None:
        import pstats

        st = pstats.Stats(prof, stream=sys.stderr)
        st.sort_stats("cumulative").print_stats(30)
        st.sort_stats("tottime").print_stats(30)
        return 0
    join = hostprof.stage_join(
        sampler.counts(), n_orders=n_done, window_ns=wall * 1e9
    )
    print(
        f"sampled {sampler.samples} stacks ({sampler.mode_used} mode, "
        f"{args.hz} Hz), stage coverage {join['coverage_pct']}%",
        file=sys.stderr,
    )
    for stage, row in join["stages"].items():
        print(
            f"  {stage:<14} {row['pct']:>6.2f}%  "
            f"{row.get('ns_per_order', 0):>9.1f} ns/order "
            f"({row['samples']} samples)",
            file=sys.stderr,
        )
    print("# top collapsed stacks:", file=sys.stderr)
    for line in sampler.collapsed(max_lines=20).splitlines():
        print(f"  {line}", file=sys.stderr)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(sampler.collapsed())
        print(f"wrote collapsed stacks -> {args.out}", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="profile_consumer",
                                 description=__doc__)
    ap.add_argument("--gateway", action="store_true",
                    help="profile the gateway admit loop (host-only "
                         "drill) instead of the consumer drain")
    ap.add_argument("--columnar", action="store_true",
                    help="--gateway: drive the columnar batch admit "
                         "core (DoOrderBatch -> GCO4) and emit the "
                         "HOSTPROF_r02 payload")
    ap.add_argument("--batch-n", type=int, default=1024,
                    help="--gateway --columnar: orders per "
                         "OrderBatchRequest")
    ap.add_argument("--deterministic", action="store_true",
                    help="consumer drill: cProfile instead of sampling")
    ap.add_argument("--out", default="",
                    help="--gateway: write the HOSTPROF_r01 payload "
                         "here; consumer sampling: write collapsed "
                         "stacks here")
    ap.add_argument("--orders", type=int, default=0,
                    help="--gateway drill size (default 30000)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--min-samples", type=int, default=800,
                    help="--gateway: keep re-running rounds until the "
                         "sampler holds this many stacks")
    ap.add_argument("--hz", type=float, default=997.0,
                    help="sampler cadence")
    args = ap.parse_args(argv)
    if args.gateway:
        return gateway_main(args)
    return consumer_main(args)


if __name__ == "__main__":
    sys.exit(main())
