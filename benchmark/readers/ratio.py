"""A program or harness counter's growth over the window, per unit of another's."""


def read(run, meta):
    c0, c1 = run["win"]["c0"], run["win"]["c1"]
    den = c1.get(meta["denominator"], 0) - c0.get(meta["denominator"], 0)
    if meta["numerator"] not in c1 or den <= 0:
        return None
    num = c1[meta["numerator"]] - c0[meta["numerator"]]
    return num / den * meta.get("scale", 1)
