"""Model FLOPs of all the work the window did (decode steps and prefill chunks,
MoD-aware: routed blocks count only their routed rows and tokens) over the
window's seconds times the chip's bf16 peak, in %."""


def read(run):
    if not run.decode_work:
        return None
    work = sum(f for f, _ in run.decode_work) + sum(run.chunk_flops)
    return 100.0 * work / (run.window_s * run.peaks["bf16_flops_per_s"])
