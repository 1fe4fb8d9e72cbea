"""Idle device time inside the benchmark's ``engine.step`` spans, per step, in ms:
what the engine's host work (admission, scheduling, the logits' trip to the
host, sampling) costs the device in each step."""
from benchlib.trace import span_idle_seconds


def read(run):
    idle, n = span_idle_seconds(run.td, run.red, "engine.step")
    return 1e3 * idle / n if n else None
