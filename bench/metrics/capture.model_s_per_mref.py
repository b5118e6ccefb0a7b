"""Capture layer, whole-model capture (``repro.capture.model``): self time
of the program's ``capture.model.`` spans (the step's trace, its jaxpr
walk and a windowed pass's placement), in seconds per million trace
references characterized."""

PREFIX = "capture.model."


def read(ctx):
    mine = [s for s in ctx.spans if s.name.startswith(PREFIX)]
    if not ctx.refs or not mine:
        return None
    return sum(s.self_us for s in mine) / 1e6 / (ctx.refs / 1e6)
