"""Share of the member-steps the cohort step executes that train a real
client: per wave, the sum of its members' local steps
(``CohortEngine.steps_per_client``) over the bucketed rows
(``cohort.bucket_size``) times the wave's trip count (the ``schedule`` of
the program's ``cohort.wave`` record, its longest member's steps), summed
over the window's waves (``bench.probe``). ``cohort.row_fill_share`` and
``cohort.step_fill_share`` are its two factors."""
LAYER = "cohort step"
UNIT = "%"
MOVES = "updates_per_s"
SOURCE = "program_counter"


def read(ctx):
    c = ctx.counters
    if not c["executed_steps"]:
        return None
    return 100.0 * c["useful_steps"] / c["executed_steps"]
