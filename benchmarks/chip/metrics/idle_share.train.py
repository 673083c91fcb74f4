"""Share of the traced window in which no operation ran on the device, in %."""


def read(ctx):
    s = ctx.tracer.summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
