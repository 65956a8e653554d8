"""Device time of the cohort program per wave: the trace's program events
of ``CohortEngine``'s jitted ``run`` (named ``jit_run`` in the trace), over
the window's waves."""
LAYER = "cohort step"
UNIT = "ms"
MOVES = "updates_per_s"
SOURCE = "device_trace"
PROGRAMS = ("jit_run",)


def read(ctx):
    from bench import tracing
    if ctx.trace is None or not ctx.counters["waves"]:
        return None
    ns, n = tracing.module_ns(ctx.trace, PROGRAMS)
    if not n:
        return None
    return ns * 1e-6 / ctx.counters["waves"]
