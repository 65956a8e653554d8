"""Programs built inside the measured window: backend compiles and loads
from the persistent compilation cache, from JAX's
``backend_compile_duration`` events. Every one is a shape the warm-up
missed; the count should be 0."""
LAYER = "entry"
UNIT = "count"
MOVES = "updates_per_s"
SOURCE = "program_counter"


def read(ctx):
    return float(ctx.compiles_window)
