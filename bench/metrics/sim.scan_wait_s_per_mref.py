"""Device scan kernel, as the host sees it: self time of the program's
``sim.scan.wait`` spans (the host blocked on one launch's counts coming
back from the device), in seconds per million trace references."""

NAME = "sim.scan.wait"


def read(ctx):
    mine = [s for s in ctx.spans
            if s.name == NAME or s.name.startswith(NAME + ".")]
    if not ctx.refs or not mine:
        return None
    return sum(s.self_us for s in mine) / 1e6 / (ctx.refs / 1e6)
