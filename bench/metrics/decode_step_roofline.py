"""The decode program's share of its roofline, in %: the least time of every
decode step in the window (the larger of its FLOPs over the bf16 peak and its
least bytes, every weight once plus the live K/V of the active rows, over the
HBM bandwidth) over the device time of the decode program (``jit_step``, the engine's paged decode step)."""
from benchlib.flops import least_time_s
from benchlib.trace import module_seconds

PROGRAM = "jit_step("


def read(run):
    dev = module_seconds(run.td, PROGRAM, run.red.lo, run.red.hi)
    if not run.decode_work or dev <= 0:
        return None
    pk = run.peaks
    least = sum(least_time_s(f, b, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
                for f, b in run.decode_work)
    return 100.0 * least / dev
