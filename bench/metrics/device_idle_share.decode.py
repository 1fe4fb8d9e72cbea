"""Share of the traced window in which no operation ran on the device, in %:
1 - busy / window, busy the union of device op intervals (benchlib.trace)."""


def read(run):
    return 100.0 * run.red.idle_share
