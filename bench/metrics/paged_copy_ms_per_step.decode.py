"""Device ms per execution of the decode program (``jit_step``, the engine's
paged decode step) under the program's ``paged.materialize`` and
``paged.writeback`` scopes: the paged pool gathered into the step's cache and
the step's new rows scattered back (benchlib.scopes)."""
from benchlib import scopes

SCOPES = ("paged.materialize", "paged.writeback")


def read(run):
    ps = scopes.of_run(run, "jit_step(")
    return None if ps is None else ps.ms_per_execution(SCOPES)
