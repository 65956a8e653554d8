"""Host time per wave that no layer call covers: the simulator's own loop
(event timeline, wave assembly, snapshot gathers, flush bookkeeping). The
window's wall time minus the union of the benchmark's spans around
``dispatch_many``, ``cohort_update``, the batched sketch, ``receive_many``
and the evaluation, over the waves in the window."""
LAYER = "host loop"
UNIT = "ms"
MOVES = "updates_per_s"
SOURCE = "program_span"


def read(ctx):
    waves = ctx.counters["waves"]
    if not waves:
        return None
    covered = 0.0
    end = ctx.t_start
    for t0, t1 in sorted((max(s[1], ctx.t_start), min(s[2], ctx.t_end))
                         for s in ctx.spans):
        if t1 <= end:
            continue
        covered += t1 - max(t0, end)
        end = t1
    return (ctx.window_s - covered) * 1e3 / waves
