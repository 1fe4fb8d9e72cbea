"""The training check at a tiny size on the CPU: the program passes; the
reference computed through float8 (the control), a step that returns its
state unchanged, and a step that leaves out half of the batch all come out
not correct. The harness's look for a chip is skipped; the rest of a run is
driven as ``bench/run.py`` drives it."""
import jax
import pytest

import run as R
import tiny

SEED = 3_000_000_029
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.01, "change_gap": 0.01}


def frozen(step):
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    return lambda state, batch: step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))


@pytest.mark.parametrize("case", ["program", "control", "frozen", "half_batch"])
def test_training_check(case):
    kw = {"control": {"control": "fp8"}, "frozen": {"fault": frozen},
          "half_batch": {"fault": half_batch}}.get(case, {})
    cell = tiny.train_cell(LIMITS)
    cell.end_to_end = [{"name": "train_tokens_per_s", "unit": "tokens/s"}]
    out, checks = R.measure(cell, SEED, 0.5, False, jax.devices()[:1], strict=False,
                            cache=False, **kw)
    over = {k for k, c in checks.items() if c["value"] > c["limit"]}
    if case == "program":
        assert out["correct"] and not over
        assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    else:
        assert not out["correct"] and over
        if case == "frozen":
            assert checks["change_gap"]["value"] == pytest.approx(1.0)
