"""Device ms per execution of the train step (``jit_step_fn``) under the
program's ``attention`` scope: Q/K/V projections, scores, softmax and output
projection of every block, forward, recompute and backward
(benchlib.scopes)."""
from benchlib import scopes


def read(run):
    ps = scopes.of_run(run, "jit_step_fn(")
    return None if ps is None else ps.ms_per_execution(("attention",))
