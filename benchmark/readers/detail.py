"""A number the harness took itself for this kind of window (the generator's
lateness in an open loop, the credit window's fill in a closed one)."""


def read(run, meta):
    return run["detail"].get(meta["key"])
