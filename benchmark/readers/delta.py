"""A counter's growth over the window."""


def read(run, meta):
    c0, c1 = run["win"]["c0"], run["win"]["c1"]
    if meta["counter"] not in c1:
        return None
    return c1[meta["counter"]] - c0[meta["counter"]]
