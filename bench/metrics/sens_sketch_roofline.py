"""``sens_sketch``'s share of its roofline by bytes: each sketched model
reads theta, g and the Fisher diagonal (``bench.flops.sens_sketch_bytes``)
over HBM bandwidth, over the kernel's device time in the window. Sketched
models: the rows the batched client sketch is handed (its bucketed wave)
and one global model per aggregation. The kernel's hashing (16 Rademacher
rows per element) is vector-unit work the table has no peak for, so this
share says how far the kernel is from streaming its inputs, not how busy
its vector units are. Kernel events: operations named after the Pallas
kernel ``_sens_sketch_kernel``."""
LAYER = "kernels"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "device_trace"
OPS = ("sens_sketch",)


def read(ctx):
    from bench import flops, tracing
    rows = ctx.counters["sketch_rows"] + ctx.counters["aggregations"]
    if ctx.trace is None or not rows:
        return None
    ns, n = tracing.op_ns(ctx.trace, OPS)
    if not n:
        return None
    need = flops.sens_sketch_bytes(ctx.params, rows)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (ns * 1e-9)
