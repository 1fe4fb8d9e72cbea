"""Model FLOPs of the window's training steps (forward and backward, MoD-aware,
recomputation not counted) over the window's seconds times the chip's bf16
peak, in %."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * run.steps * run.step_flops / (run.window_s * run.peaks["bf16_flops_per_s"])
