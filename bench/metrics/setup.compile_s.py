"""Seconds of backend compilation (persistent-cache loads included) from
process start to the window's start: the part of set-up that compiling or
loading programs takes. JAX's ``backend_compile_duration`` events."""
LAYER = "entry"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return ctx.compile_setup_s
