"""Capture layer (``repro.capture.grid``, ``repro.capture.model``): self
time of the program's ``capture.*`` spans, in seconds per million trace
references characterized."""

from bench import spans


def read(ctx):
    if not ctx.refs or not any(s.name.startswith("capture.")
                               for s in ctx.spans):
        return None
    return spans.self_seconds(ctx.spans, ("capture.",)) / (ctx.refs / 1e6)
