"""Capture layer, address materialization (``capture.grid._walk`` when
it emits addresses): self time of the program's ``capture.walk.emit``
spans, in seconds per million trace references."""

NAME = "capture.walk.emit"


def read(ctx):
    mine = [s for s in ctx.spans
            if s.name == NAME or s.name.startswith(NAME + ".")]
    if not ctx.refs or not mine:
        return None
    return sum(s.self_us for s in mine) / 1e6 / (ctx.refs / 1e6)
