"""Idle device ms inside the engine's ``serve.logits_to_host`` and
``serve.sample`` spans, per ``serve.decode`` span, in the window: what the
logits' trip to the host and sampling on the host cost the device in each
decode step. None where the program writes no such span."""
from benchlib.trace import span_idle_seconds

HOST_SPANS = ("serve.logits_to_host", "serve.sample")


def read(run):
    _, steps = span_idle_seconds(run.td, run.red, "serve.decode")
    if not steps:
        return None
    idle = sum(span_idle_seconds(run.td, run.red, name)[0] for name in HOST_SPANS)
    return 1e3 * idle / steps
