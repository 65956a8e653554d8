"""Host work the chip waits on, per wave: from the end of the host's last
wait on the device (an ``ingest.wait`` or ``eval`` span) to the end of the
next ``cohort.enqueue`` span, which hands the device its next long
program. In between lie the copies of the ingest's results back to the
host and its per-arrival loop, the replacement dispatch, the next wave's
assembly and gathers, and the cohort step's schedules and uploads. Summed over the window's waves (each
``cohort.enqueue`` that ends in it, the first measured from the window's
start) over their number. Spans: the program's own
(``repro.common.obs``); nothing to read where it keeps none."""
LAYER = "host loop"
UNIT = "ms"
MOVES = "updates_per_s"
SOURCE = "program_span"
WAITS = ("ingest.wait", "eval")
ENQUEUE = "cohort.enqueue"


def read(ctx):
    try:
        from repro.common import obs
    except ImportError:
        return None
    spans = obs.spans(ctx.t_start, ctx.t_end, names=WAITS + (ENQUEUE,))
    waits = sorted(t1 for name, _, t1, _ in spans if name in WAITS)
    total, waves = 0.0, 0
    for name, _, t1, _ in spans:
        if name != ENQUEUE or not ctx.t_start <= t1 < ctx.t_end:
            continue
        start = max([ctx.t_start] + [w for w in waits if w <= t1])
        total += t1 - start
        waves += 1
    if not waves:
        return None
    return total * 1e3 / waves
