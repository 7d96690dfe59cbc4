"""Seconds from process start to the start of the measured window: start-up,
data generation, building the system, warming every shape and, in a run
whose compile cache is cold, compiling."""


def read(run):
    return run.setup_s
