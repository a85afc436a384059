"""Completed queries per second of the window."""


def read(run):
    return len(run.done) / run.window_s
