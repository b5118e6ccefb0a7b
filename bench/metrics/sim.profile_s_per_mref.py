"""Simulator host layer, stream-profile build
(``cachesim_vec.StreamProfile``): self time of the program's
``sim.profile`` span and its stages (``sim.profile.collapse``,
``.order``, ``.prev``), in seconds per million trace references."""

NAME = "sim.profile"


def read(ctx):
    mine = [s for s in ctx.spans
            if s.name == NAME or s.name.startswith(NAME + ".")]
    if not ctx.refs or not mine:
        return None
    return sum(s.self_us for s in mine) / 1e6 / (ctx.refs / 1e6)
