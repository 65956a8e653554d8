"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window."""
LAYER = "device"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "device_trace"


def read(ctx):
    from bench import tracing
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace.window()
    return 100.0 * (1.0 - tracing.busy_ns(ctx.trace) / (t1 - t0))
