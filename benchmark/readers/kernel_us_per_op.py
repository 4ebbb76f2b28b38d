"""Device time of the match kernel's events in the traced window over the ops
they carried: events x (ops per kernel grid, from the program's counters over
the whole window)."""


def read(run, meta):
    trace = run["trace"]
    c0, c1 = run["win"]["c0"], run["win"]["c1"]
    grids = c1["kernel_grids"] - c0["kernel_grids"]
    ops = c1["kernel_ops"] - c0["kernel_ops"]
    if not trace or not trace.get("kernel_events") or grids <= 0 or ops <= 0:
        return None
    return trace["kernel_s"] * 1e6 / (trace["kernel_events"] * ops / grids)
