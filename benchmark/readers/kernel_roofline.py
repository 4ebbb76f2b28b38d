"""The match kernel's share of its roofline: the least time the chip could
take for the grids dispatched (bytes and operations from their shapes,
benchmark/peaks.py, against the published peaks) over the kernel's device
time. Mean least time per grid over the window's grids, times the kernel
events traced."""

from benchmark import peaks


def read(run, meta):
    trace = run["trace"]
    win = run["win"]
    grids = [g for g in run["grids"] if win["t0_ns"] <= g[0] <= win["t1_ns"]]
    if (not trace or not trace.get("kernel_events") or not grids
            or run["rehearsal"]):
        return None
    engine = run["cell"]["config"]["service"]["engine"]
    least = [
        peaks.kernel_min_seconds(run["device_kind"], rows, t, cap,
                                 engine["max_fills"])[0]
        for _t, rows, t, cap, _n in grids
    ]
    per_grid = sum(least) / len(least)
    return 100.0 * per_grid * trace["kernel_events"] / trace["kernel_s"]
