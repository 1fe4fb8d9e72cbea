"""Device ms per execution of the train step (``jit_step_fn``) under the
program's ``mod.router`` and ``mod.dispatch`` scopes: the routers' scores and
token top-k, the routed rows' gather and gated scatter-add, forward and
backward (benchlib.scopes)."""
from benchlib import scopes

SCOPES = ("mod.router", "mod.dispatch")


def read(run):
    ps = scopes.of_run(run, "jit_step_fn(")
    return None if ps is None else ps.ms_per_execution(SCOPES)
