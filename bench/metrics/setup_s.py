"""Seconds from the start of the process to the opening of the window:
imports, weight draw, scheduler build, compiles or cache loads, and the
warm-up requests."""


def read(run):
    return run.setup_s
