"""Device scan kernel: executions of the jitted ``kern`` on the chip, per
million trace references."""

KERNEL = "jit_kern"


def read(ctx):
    launches, _ = ctx.device.module_time(KERNEL)
    if not launches or not ctx.refs:
        return None
    return launches / (ctx.refs / 1e6)
