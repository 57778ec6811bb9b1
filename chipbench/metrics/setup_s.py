"""Seconds from the process's start to the window's open: imports, weights
made on the chip, engine, warm-up (compiles or cache loads) and the
admission of the first ``num_slots`` requests."""


def compute(run):
    return run.setup_s
