"""Device scan kernel (``cachesim_vec._jax_window_kernel``, the jitted
``kern``): its device time in the profiler trace, in milliseconds per
million trace references."""

KERNEL = "jit_kern"


def read(ctx):
    launches, ns = ctx.device.module_time(KERNEL)
    if not launches or not ctx.refs:
        return None
    return ns / 1e6 / (ctx.refs / 1e6)
