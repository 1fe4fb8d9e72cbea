"""Training tokens stepped in the measured window over its seconds (host clock);
each step's batch is made on the host inside the window."""


def read(run):
    return run.tokens / run.window_s
