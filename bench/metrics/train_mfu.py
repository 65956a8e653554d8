"""The whole run's share of the chip's peak: the operations the window's
useful local SGD requires (three forward passes' worth for every real
sample of every real step; padded rows and padded steps count nothing,
``bench.flops``), per second of window, over the bf16 peak of the device
(``bench.peaks``)."""
LAYER = "device"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "program_counter"


def read(ctx):
    from bench import flops
    samples = ctx.counters["samples"]
    if not samples or not ctx.peaks:
        return None
    rate = flops.train_flops(ctx.config, samples) / ctx.window_s
    return 100.0 * rate / ctx.peaks["flops_bf16"]
