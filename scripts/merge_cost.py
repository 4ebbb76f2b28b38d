"""What a small frame costs as ONE merged grid at its deepest class against
one grid a class (ISSUE 44; frames.pack_frame_grids, MERGE_MAX_CELLS): the
wall time from submit_frame to resolve_frame's return, which holds the host's
dispatches and the device's work, on a venue whose one deep lane rests just
over the class below and whose other lanes are shallow. `python
scripts/merge_cost.py [--classes 1024,4096] [--lanes 16,64,128] [--frames N]
[--interpret]` prints one JSON line a point: rows x depth x class of the
merged grid, its cells, and the median milliseconds a frame both ways. A
device number on the chip only; `--interpret` (the CPU, toy sizes) proves the
script runs."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", default="1024,4096")
    ap.add_argument("--lanes", default="16,64,128")  # 2 x (lanes + 3) ops
    ap.add_argument("--frames", type=int, default=120)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from gome_tpu.bus import colwire
    from gome_tpu.engine import BatchEngine, BookConfig, frames
    from gome_tpu.types import Action, Order, Side

    frames.MERGE_MAX_CELLS = 1 << 62  # measure past the bound too
    real_pack = frames.pack_frame_grids

    def packer(merge: bool):
        if merge:
            return real_pack
        return lambda eng, a, on_device=True, small=False: real_pack(
            eng, a, on_device
        )

    def order(sym, oid, price, action=Action.ADD):
        return Order(uuid="u", oid=oid, symbol=sym, side=Side.BUY,
                     price=price, volume=1, action=action)

    def one(cls: int, lanes: int, merge: bool) -> dict:
        frames.pack_frame_grids = packer(merge)
        eng = BatchEngine(
            BookConfig(cap=cls, max_fills=16, dtype=jnp.int32),
            n_slots=1024, max_t=32, kernel="pallas",
            pallas_interpret=args.interpret,
        )
        # The deep lane comes to rest just over the class below, the others
        # three deep, in frames of the steady shape (so the listing sets no
        # floor the steady frames would not).
        deep, n = cls // 4 + 40, 0
        while n < deep:
            listing = [order("deep", f"d{n + i}", 900_000 - n - i)
                       for i in range(8)]
            if n < 24:
                listing += [order(f"s{k}", f"s{k}.{n}", 900_000 - n)
                            for k in range(lanes - 1)]
            frames.apply_frame_fast(eng, colwire.orders_to_cols(listing))
            n += 8
        syms = ["deep"] * 4 + [f"s{k}" for k in range(lanes - 1)]
        took, prev, shape = [], [], None
        for f in range(args.frames + 20):
            adds = [order(sym, f"f{f}.{j}", 800_000 - j % 5)
                    for j, sym in enumerate(syms)]
            cols = colwire.orders_to_cols(
                adds + [order(o.symbol, o.oid, o.price, Action.DEL)
                        for o in prev]
            )
            prev = adds
            t0 = time.perf_counter_ns()
            pend = frames.submit_frame(eng, cols)
            frames.resolve_frame(eng, pend)
            took.append((time.perf_counter_ns() - t0) / 1e6)
            if not pend.one_phase:
                raise SystemExit(f"{lanes} lanes: the frame is over the "
                                 "one-phase rule, nothing to compare")
            shape = [(int(m["_n_rows"]), s[0]) for m, s in pend.items]
        eng.verify_books()
        assert eng.stats.frame_fallbacks == 0
        steady = took[20:]
        return dict(
            ms_median=round(statistics.median(steady), 4),
            ms_p10=round(statistics.quantiles(steady, n=10)[0], 4),
            grids=shape, merged=eng.stats.fast_frames_merged,
            frames=eng.stats.fast_frames,
        )

    dev = jax.devices()[0]
    print(json.dumps(dict(platform=dev.platform, kind=dev.device_kind)),
          flush=True)
    for cls in map(int, args.classes.split(",")):
        for lanes in map(int, args.lanes.split(",")):
            merged, parted = one(cls, lanes, True), one(cls, lanes, False)
            rows, depth = merged["grids"][-1]
            print(json.dumps(dict(
                cls=cls, lanes=lanes, rows=rows, depth=depth,
                cells=rows * depth * cls, merged=merged, partitioned=parted,
                merged_minus_partitioned_ms=round(
                    merged["ms_median"] - parted["ms_median"], 4),
            )), flush=True)


if __name__ == "__main__":
    main()
