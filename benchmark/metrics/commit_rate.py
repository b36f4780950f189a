"""Entries acknowledged durable in the window over the window's wall time."""


def read(run):
    if run.window_s <= 0:
        return None
    return run.acked / run.window_s
