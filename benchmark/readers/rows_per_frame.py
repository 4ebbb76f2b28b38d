"""Rows dispatched a frame: the rows of every grid the engine dispatched in
the window (all chips: a grid's global row count, as the wrapper round
BatchEngine._step notes it) over the frames committed in it. Against what one
chip dispatches on the same stream, it is the padding a mesh and its lane
placement add, in one ratio."""


def read(run, meta):
    c0, c1 = run["win"]["c0"], run["win"]["c1"]
    frames = c1.get("frames", 0) - c0.get("frames", 0)
    if not run["grids"] or frames <= 0:
        return None
    return sum(rows for _t, rows, _depth, _cap, _n in run["grids"]) / frames
