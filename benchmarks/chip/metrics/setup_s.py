"""Seconds from process start to the window's start: weights, engine
construction, compiles or cache loads and the warm-up (host clock)."""


def read(run):
    return run.setup_s
