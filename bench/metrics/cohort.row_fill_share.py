"""Share of the cohort step's rows that hold a real client: over the
window's ``cohort.wave`` records (one per ``cohort_update``, written by the
engine through ``repro.common.obs``), the members over the bucketed rows.
Row padding alone; ``cohort.step_fill_share`` is the other factor of
``cohort.useful_step_share`` (their product over 100). Nothing to read
where the program keeps no such records."""
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
    rows = sum(w["rows"] for w in waves)
    if not rows:
        return None
    return 100.0 * sum(w["members"] for w in waves) / rows
