"""Share of the local steps the cohort step runs for its real members that
train: over the window's ``cohort.wave`` records (``repro.common.obs``),
the members' own local steps over members times the schedule every row
runs (the longest client's). Step padding alone; ``cohort.row_fill_share``
is the other factor of ``cohort.useful_step_share`` (their product over
100). Nothing to read where the program keeps no such records."""
LAYER = "cohort step"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "program_counter"


def read(ctx):
    try:
        from repro.common import obs
    except ImportError:
        return None
    waves = obs.records("cohort.wave", ctx.t_start, ctx.t_end)
    run = sum(w["members"] * w["schedule"] for w in waves)
    if not run:
        return None
    return 100.0 * sum(w["steps"] for w in waves) / run
