"""Simulator host layer, the window scan's set-major layout
(``cachesim_vec._contested_sd``: the set sort, slot and window arrays):
self time of the program's ``sim.scan.layout`` spans, in seconds per
million trace references."""

NAME = "sim.scan.layout"


def read(ctx):
    mine = [s for s in ctx.spans
            if s.name == NAME or s.name.startswith(NAME + ".")]
    if not ctx.refs or not mine:
        return None
    return sum(s.self_us for s in mine) / 1e6 / (ctx.refs / 1e6)
