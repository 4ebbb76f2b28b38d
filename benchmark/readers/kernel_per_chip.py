"""The match kernel's two numbers for one chip of a venue whose lane axis is
split over D chips (D = the devices in the trace). tracered.reduce gives the
kernel's seconds as the mean over the chips and counts its events over all of
them; the program's counters and the grids noted round BatchEngine._step are
global (all chips). A chip runs events / D of the kernel's calls, each on
rows / D of a grid's rows and, on average, a D-th of its ops.

`quantity` of the metric file:
  us_per_op: the kernel's device time on a chip over the ops that chip carried;
  roofline:  the least time for [rows / D, t] at the grid's cap class
             (benchmark/peaks.py, untouched) times the events a chip ran, over
             the kernel's time on a chip, in %.
With D = 1 they are kernel_us_per_op and kernel_roofline."""

from benchmark import peaks


def read(run, meta):
    trace = run["trace"]
    if not trace or not trace.get("kernel_events") or not trace.get("devices"):
        return None
    chips = trace["devices"]
    events = trace["kernel_events"] / chips  # a chip's
    win = run["win"]
    if meta["quantity"] == "us_per_op":
        c0, c1 = win["c0"], win["c1"]
        grids = c1["kernel_grids"] - c0["kernel_grids"]
        ops = c1["kernel_ops"] - c0["kernel_ops"]
        if grids <= 0 or ops <= 0:
            return None
        return trace["kernel_s"] * 1e6 / (events * ops / grids / chips)
    grids = [g for g in run["grids"] if win["t0_ns"] <= g[0] <= win["t1_ns"]]
    if not grids or run["rehearsal"]:
        return None
    engine = run["cell"]["config"]["service"]["engine"]
    least = [
        peaks.kernel_min_seconds(run["device_kind"], rows // chips, t, cap,
                                 engine["max_fills"])[0]
        for _t, rows, t, cap, _n in grids
    ]
    return 100.0 * sum(least) / len(least) * events / trace["kernel_s"]
