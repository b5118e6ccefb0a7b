"""Simulator host layer (``core.cachesim_vec``, ``core.cachesim_stream``):
self time of the program's ``sim.*`` and ``profile.*`` spans, in seconds
per million trace references.  It holds the host's waits on the device
scan, and not the capture walks nested inside a streamed simulation."""

from bench import spans

LAYER = ("sim.", "profile.")


def read(ctx):
    if not ctx.refs or not any(s.name.startswith(LAYER) for s in ctx.spans):
        return None
    return spans.self_seconds(ctx.spans, LAYER) / (ctx.refs / 1e6)
