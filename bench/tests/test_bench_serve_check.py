"""The serving check at a tiny size on the CPU: the program passes; its own
int8 path (the control), a served token altered where it is produced, decode
routing the rows that score lowest, and a decode step the recorder does not
see all come out not correct. The harness's look for a chip is skipped; the
rest of a run is driven as ``bench/run.py`` drives it."""
import jax
import numpy as np
import pytest

import run as R
import tiny
from benchlib import harness as H

MT = H.family("mod_transformer")
reversed_ranking = MT.reversed_ranking

SEED = 3_000_000_023
LIMITS = {"logit_gap_mean": 3e-4, "route_margin_mean": 0.05, "decode_margin_mean": 0.05,
          "decode_rows_off": 0}


def measure(**kw):
    cell = tiny.serve_cell(LIMITS)
    cell.end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"}]
    return R.measure(cell, SEED, 1.0, False, jax.devices()[:1], strict=False, cache=False, **kw)


CASES = {"program": ({}, None), "control": ({"control": "int8"}, "logit_gap_mean"),
         "token_altered": ({"fault": tiny.altered}, "logit_gap_mean"),
         "reversed_ranking": ({"fault": reversed_ranking}, "decode_margin_mean"),
         "step_unseen": ({"fault": tiny.unseen}, "decode_rows_off")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_serving_check(case):
    kw, fails = CASES[case]
    out, checks = measure(**kw)
    if fails is None:
        assert out["correct"]
        assert checks["logit_gap_mean"]["value"] < LIMITS["logit_gap_mean"] / 2
        assert checks["decode_margin_mean"]["value"] < LIMITS["decode_margin_mean"] / 2
        assert out["readings"]["decode_rankings"] > 0
        assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        assert not out["correct"]
        assert not checks[fails]["value"] <= LIMITS[fails]


def test_decode_margin_is_zero_for_the_top_rows_and_grows_when_reversed():
    scores = {7: np.array([[0.0, 3.0, 1.0, 2.0]]), 8: np.array([[0.0, 0.0, 0.5, 4.0]])}
    live = [(0, 7, 1), (1, 7, 2), (2, 7, 3), (3, 8, 3)]  # live scores 3, 1, 2, 4
    top = np.array([[1, 0, 0, 1]])
    low = np.array([[0, 1, 1, 0]])
    assert MT.decode_margins([(live, top)], scores).tolist() == [0.0]
    (m,) = MT.decode_margins([(live, low)], scores)
    assert np.isclose(m, (4 - 1) / np.std([3, 1, 2, 4]))
    # a block that routed every live row ranks nothing
    assert MT.decode_margins([(live, np.ones((1, 4)))], scores).size == 0
