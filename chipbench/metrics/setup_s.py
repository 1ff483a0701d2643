"""Seconds from process start to the window's opening: weights, engine,
warm-up and any compilation."""


def read(run):
    return run.setup_s
