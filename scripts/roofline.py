"""Device-kernel roofline probe (ARCHITECTURE.md's roofline section).

Two parts:

  * the ANALYTIC per-entry roofline table — arithmetic intensity
    (flops / bytes accessed) straight from the compiled executables via
    gome_tpu.obs.costmodel, replacing the hand-derived estimates this
    script used to carry. Printed first on every run; `--table` prints
    it alone (works on any backend, CPU included).
  * the MEASURED roofline (`--measured`): a jax.profiler capture over
    the same canonical entries, joined against the analytic table —
    per-entry device time, achieved GFLOP/s / GB/s, and efficiency vs
    the machine ceiling (gome_tpu.obs.profiler; any backend). `--table`
    stays the analytic-only fallback.
  * the MEASURED sweep: times the compiled Pallas match kernel at the
    headline shape while sweeping the knobs that distinguish the
    candidate ceilings:

      - cap sweep    — per-step work is O(cap) vector ops over
                       [block_s, cap] tiles; if throughput scales ~1/cap
                       the kernel is compute/dependency-bound, not
                       launch-bound;
      - block_t sweep — deeper time blocks amortize grid/launch overhead;
                       a plateau means launches are not the ceiling;
      - block_s sweep — more lanes per block raises SIMD utilization.

    Prints one JSON line per point: {cap, block_t, block_s,
    orders_per_sec, cycles_per_block_step} (cycles = block_s * f /
    throughput, f = 940 MHz for v5e — the serial per-step critical path
    the dependency chain pays).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import build_grids

from gome_tpu.utils.jaxcache import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from gome_tpu.engine import BookConfig, init_books
from gome_tpu.engine.book import DeviceOp
from gome_tpu.ops import pallas_batch_step

F_HZ = float(os.environ.get("ROOFLINE_CLOCK_HZ", 940e6))  # v5e TensorCore
S = int(os.environ.get("ROOFLINE_SYMBOLS", 10240))
T = int(os.environ.get("ROOFLINE_T", 16))
G = int(os.environ.get("ROOFLINE_GRIDS", 24))
REPEATS = int(os.environ.get("ROOFLINE_REPEATS", 3))


def run_point(cap, block_s, block_t):
    config = BookConfig(cap=cap, max_fills=16, dtype=jnp.int32)
    stepper = jax.jit(
        lambda books, ops: pallas_batch_step(
            config, books, ops, block_s=block_s, block_t=block_t
        ),
        donate_argnums=(0,),
    )
    fold = jax.jit(lambda o: jnp.sum(o.n_fills))
    raw = build_grids(S, T, G + 2, dtype=np.int32)
    for d in raw:
        d["volume"] = (d["volume"] // 1_000_000).astype(np.int32)
    grids = [jax.device_put(DeviceOp(**d)) for d in raw]
    jax.block_until_ready(grids)
    books = init_books(config, S)
    books, outs = stepper(books, grids[0])
    acc = fold(outs)
    books, outs = stepper(books, grids[1])
    int(acc + fold(outs))
    books0 = jax.tree.map(jnp.copy, books)
    int(jnp.sum(books0.count))
    best = float("inf")
    for _ in range(REPEATS):
        books = jax.tree.map(jnp.copy, books0)
        int(jnp.sum(books.count))
        acc = None
        t0 = time.perf_counter()
        for g in grids[2:]:
            books, outs = stepper(books, g)
            f = fold(outs)
            acc = f if acc is None else acc + f
        int(acc)  # completion barrier
        best = min(best, time.perf_counter() - t0)
    rate = S * T * G / best
    # Cycles each serial time step costs one lane block: rate = (S/B_s
    # blocks advance in parallel is FALSE — blocks are grid-parallel in
    # sequence on one core) => time = (S/block_s) * T * C / f;
    # C = f * block_s / rate.
    cycles = F_HZ * block_s / rate
    print(
        json.dumps(
            dict(
                cap=cap,
                block_s=block_s,
                block_t=block_t,
                orders_per_sec=round(rate),
                cycles_per_block_step=round(cycles, 1),
            )
        ),
        flush=True,
    )
    return rate


def analytic_table(dtype="int32"):
    """Per-entry roofline table from the compiled executables'
    cost/memory analysis (gome_tpu.obs.costmodel) — the measured
    arithmetic intensity each entry presents to the memory system, not a
    hand count. An intensity far below the machine balance (~100s of
    flops/byte on TPU) confirms these integer kernels are bandwidth/
    dependency-bound, which is why the sweeps below probe launch and
    blocking overheads rather than FLOP ceilings."""
    from gome_tpu.obs import costmodel

    rows = [r for r in costmodel.entry_report(dtype) if "error" not in r]
    print(f"# analytic roofline ({dtype}, canonical envelope geometry)")
    print(
        "# {:<26} {:>10} {:>12} {:>10} {:>12} {:>10}".format(
            "entry", "flops/ord", "bytes/ord", "flops/byte", "peak_hbm_B",
            "jaxpr_ops",
        )
    )
    for r in rows:
        fmt = lambda v, p=1: "-" if v is None else f"{v:.{p}f}"
        print(
            "# {:<26} {:>10} {:>12} {:>10} {:>12} {:>10}".format(
                r["entry"],
                fmt(r.get("flops_per_order")),
                fmt(r.get("bytes_per_order")),
                fmt(r.get("arithmetic_intensity"), 3),
                str(r.get("peak_hbm_bytes")),
                str(r.get("jaxpr_eqns")),
            )
        )
    for d in costmodel.donation_report(dtype):
        if "error" not in d:
            print(
                f"# donation {d['entry']}: peak "
                f"{d['public_peak_hbm_bytes']} -> "
                f"{d['donating_peak_hbm_bytes']} B "
                f"(saved {d['peak_hbm_saved_bytes']})"
            )


def measured_table(dtype="int32"):
    """The MEASURED roofline joined against the analytic table
    (gome_tpu.obs.profiler): a jax.profiler capture drives the same
    canonical entries the analytic table reports, attributes per-entry
    device time from the trace events, and divides the analytic work by
    it — achieved GFLOP/s, achieved GB/s, and efficiency vs the
    machine's roofline ceiling (min(peak_flops, intensity * peak_bw);
    published peaks by device_kind on a TPU, a labelled calibration on
    the CPU backend — gome_tpu.obs.profiler.machine_peaks)."""
    from gome_tpu.obs.profiler import measured_entry_report

    rep = measured_entry_report(
        dtype, repeats=int(os.environ.get("ROOFLINE_PROFILE_REPEATS", 8))
    )
    pk = rep["peaks"]
    print(
        f"# measured roofline ({dtype}, {rep['platform']}; peaks "
        f"{pk['peak_gflops']} GFLOP/s, {pk['peak_gbps']} GB/s, "
        f"{pk['source']})"
    )
    print(
        "# {:<26} {:>10} {:>12} {:>10} {:>12} {:>8}".format(
            "entry", "dev_us", "ach_GFLOP/s", "ach_GB/s", "ceil_GFLOP/s",
            "eff_%",
        )
    )
    fmt = lambda v, p=3: "-" if v is None else f"{v:.{p}f}"
    for name, r in rep["entries"].items():
        if "error" in r:
            print(f"# {name:<26} error: {r['error']}")
            continue
        print(
            "# {:<26} {:>10} {:>12} {:>10} {:>12} {:>8}".format(
                name,
                fmt(r.get("device_us_per_call")),
                fmt(r.get("achieved_gflops")),
                fmt(r.get("achieved_gbps")),
                fmt(r.get("roofline_ceiling_gflops")),
                fmt(r.get("efficiency_pct"), 4),
            )
        )
    print(f"# perfetto trace: {rep['perfetto_trace']}")


def main():
    dtype = os.environ.get("ROOFLINE_DTYPE", "int32")
    analytic_table(dtype)
    if "--table" in sys.argv:
        return
    if "--measured" in sys.argv:
        measured_table(dtype)
        return
    # Headline point + cap sweep at fixed blocking.
    for cap in (64, 128, 256, 512):
        run_point(cap, 128, min(T, 16))
    # block_t sweep at headline cap.
    for bt in (1, 2, 4, 8, 16):
        if T % bt == 0:
            run_point(256, 128, bt)


if __name__ == "__main__":
    main()
