"""Mean duration of one of the program's host spans in the traced window."""


def read(run, meta):
    trace = run["trace"]
    if not trace or meta["span"] not in trace.get("spans", {}):
        return None
    count, seconds = trace["spans"][meta["span"]]
    return seconds / count * 1e3 if count else None
