"""Device: the share of the traced window in which no operation ran on the
chip (1 - busy union over the window), in percent."""


def read(ctx):
    d = ctx.device
    if not d.busy_ns or d.window_ns <= 0:
        return None
    return 100.0 * (1.0 - d.busy_s / (d.window_ns / 1e9))
