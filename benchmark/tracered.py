"""From a profiler trace to numbers: device busy and idle time, kernel time,
the idle gaps attributed to the host span covering them, the top device ops.

`extract` reads the profiler's .xplane.pb with jax alone and keeps only
[name, start_ns, duration_ns] rows; `reduce` works on those rows, so a small
recorded trace (tests/benchmark/data/) checks it without a chip.
"""

from __future__ import annotations

import glob
import os

import numpy as np

#: The benchmark's own host span, opened at a known CLOCK_MONOTONIC instant.
MARK = "bench_mark"

#: The line of a device plane that holds the ops as they ran.
OPS_LINE = "XLA Ops"
#: A Pallas kernel is a custom call to this target, whatever jit it sits in
#: (the full-grid step names its op pallas_batch_step.N, the dense one
#: pallas_call.N); its short name gets this prefix.
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
KERNEL_PREFIX = "tpu_custom_call:"


def short_name(hlo: str) -> str:
    """'%fusion.3 = s32[8]{0} fusion(...)' -> 'fusion.3'; a Pallas kernel's
    name gets KERNEL_PREFIX."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return KERNEL_PREFIX + name if KERNEL_TARGET in hlo else name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str, span_names) -> dict:
    """{"devices": [[[name, start, dur], ...] per chip], "host": [[name,
    start, dur], ...] for the host events named in span_names, "lines": the
    planes and lines seen (for a look by hand)}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host, lines = [], [], []
    wanted = set(span_names)
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU")
        ops = []
        for line in plane.lines:
            lines.append(f"{plane.name} | {line.name}")
            if is_device:
                if line.name != OPS_LINE:
                    continue
                names: dict[str, str] = {}
                for e in line.events:
                    short = names.get(e.name)
                    if short is None:
                        short = names[e.name] = short_name(e.name)
                    ops.append([short, int(e.start_ns), int(e.duration_ns)])
            elif wanted:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name in wanted
                )
        if is_device and ops:
            devices.append(ops)
    return dict(devices=devices, host=host, lines=lines)


def busy_union(ops, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
    """Merged busy intervals of one device inside [lo, hi]: (starts, ends)."""
    if not ops:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    s = np.clip(np.array([o[1] for o in ops], np.int64), lo, hi)
    e = np.clip(np.array([o[1] + o[2] for o in ops], np.int64), lo, hi)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    first = np.r_[True, s[1:] > e[:-1]]
    starts = s[first]
    ends = np.r_[e[np.flatnonzero(first)[1:] - 1], e[-1]]
    keep = ends > starts
    return starts[keep], ends[keep]


def attribute_gaps(starts, ends, host, lo: int, hi: int) -> dict:
    """Seconds of device idle time inside [lo, hi] by the host span covering
    them: the innermost (shortest) span wins, and what no span covers is
    "waiting"."""
    gap_s = np.r_[lo, ends]
    gap_e = np.r_[starts, hi]
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    spans = sorted(host, key=lambda h: -h[2])  # longest first, shortest last
    cuts = np.unique(np.r_[
        gap_s, gap_e,
        [np.clip(h[1], lo, hi) for h in spans],
        [np.clip(h[1] + h[2], lo, hi) for h in spans],
    ]).astype(np.int64)
    seg_len = np.diff(cuts)
    label = np.zeros(len(seg_len), np.int64)  # 0: waiting
    names = ["waiting"]
    index = {}
    for name, start, dur in spans:
        i = index.setdefault(name, len(names))
        if i == len(names):
            names.append(name)
        a, b = np.searchsorted(cuts, [np.clip(start, lo, hi),
                                      np.clip(start + dur, lo, hi)])
        label[a:b] = i
    in_gap = np.zeros(len(seg_len), bool)
    for a, b in zip(np.searchsorted(cuts, gap_s), np.searchsorted(cuts, gap_e)):
        in_gap[a:b] = True
    out = {}
    for i, name in enumerate(names):
        out[name] = float(seg_len[in_gap & (label == i)].sum()) / 1e9
    return out


def reduce(trace: dict, kernel_prefix: str = KERNEL_PREFIX,
           top: int = 10) -> dict:
    """The traced window's numbers. The window runs from the first to the last
    event kept (device ops and named host spans)."""
    devices, host = trace["devices"], trace["host"]
    spans: dict[str, list] = {}
    for name, _start, dur in host:
        c = spans.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += dur / 1e9
    every = [o for ops in devices for o in ops] + host
    if not every or not devices:
        return dict(busy_s=0.0, window_s=0.0, devices=len(devices),
                    spans=spans)
    lo = min(o[1] for o in every)
    hi = max(o[1] + o[2] for o in every)
    busy, idle = [], {}
    for ops in devices:
        starts, ends = busy_union(ops, lo, hi)
        busy.append(float((ends - starts).sum()) / 1e9)
        for name, s in attribute_gaps(starts, ends, host, lo, hi).items():
            idle[name] = idle.get(name, 0.0) + s / len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    by_name: dict[str, float] = {}
    kernel_s, kernel_events = 0.0, 0
    for ops in devices:
        for name, _start, dur in ops:
            by_name[name] = by_name.get(name, 0.0) + dur / 1e9 / len(devices)
            if name.startswith(kernel_prefix):
                kernel_s += dur / 1e9 / len(devices)
                kernel_events += 1
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        busy_s=busy_s, window_s=window_s,
        idle_share=1.0 - busy_s / window_s if window_s else None,
        kernel_s=kernel_s, kernel_events=kernel_events, spans=spans,
        devices=len(devices), lo_ns=lo, hi_ns=hi,
        breakdown=dict(
            device_ops=[[k, v] for k, v in rank(by_name)],
            idle_gaps=[[k, v] for k, v in rank(idle)],
        ),
    )
