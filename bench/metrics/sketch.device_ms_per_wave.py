"""Device time of the batched client sketch per wave: the trace's program
events of the vmapped ``client_sketch`` that
``simulator.make_sketch_fn_flat`` jits (its lambda, named ``jit__lambda``
in the trace), over the window's waves."""
LAYER = "sketch + eval"
UNIT = "ms"
MOVES = "updates_per_s"
SOURCE = "device_trace"
PROGRAMS = ("jit__lambda",)


def read(ctx):
    from bench import tracing
    if ctx.trace is None or not ctx.counters["waves"]:
        return None
    ns, n = tracing.module_ns(ctx.trace, PROGRAMS)
    if not n:
        return None
    return ns * 1e-6 / ctx.counters["waves"]
