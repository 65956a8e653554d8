"""Device time of the scanned FedPSA ingest per client update: the trace's
program events of ``PolicyServer``'s batched step (``_scan_many``'s
``many``, named ``jit_many``), over the updates ingested in the window.
The global sketch's refresh and the ``buffer_agg`` apply run inside it."""
LAYER = "policy ingest"
UNIT = "ms"
MOVES = "updates_per_s"
SOURCE = "device_trace"
PROGRAMS = ("jit_many",)


def read(ctx):
    from bench import tracing
    if ctx.trace is None or not ctx.counters["updates"]:
        return None
    ns, n = tracing.module_ns(ctx.trace, PROGRAMS)
    if not n:
        return None
    return ns * 1e-6 / ctx.counters["updates"]
