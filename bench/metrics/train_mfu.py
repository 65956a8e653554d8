"""The whole run's share of the chip's peak: the operations the window's
useful local SGD requires (the configuration's family module counts them,
``train_flops``: for the CNN three forward passes' worth for every real
sample of every real step; padded rows and padded steps count nothing),
per second of window, over the bf16 peak of the device
(``bench.peaks``)."""
LAYER = "device"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "program_counter"


def read(ctx):
    from bench import families
    if not ctx.counters["samples"] or not ctx.peaks:
        return None
    rate = families.load(ctx.config).train_flops(
        ctx.config, ctx.traffic, ctx.counters) / ctx.window_s
    return 100.0 * rate / ctx.peaks["flops_bf16"]
