"""1 minus the union of device-op intervals over the traced window."""


def read(run, meta):
    trace = run["trace"]
    if not trace or not trace.get("window_s"):
        return None
    return trace["idle_share"]
