"""Tokens generated in the measured window over the window's seconds (host clock)."""


def read(run):
    return run.tokens / run.window_s
