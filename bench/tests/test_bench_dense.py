"""The MoD transformer family's dense reading (the vanilla twin) at a tiny
size on the CPU: its reference against the program's prefill and decode
through the cache, and its serving check. The program passes; its own int8
path, the reference computed through float8 in its place (the controls), a
served token altered where it is produced, and a decode step the recorder
does not see come out not correct."""
import jax
import numpy as np
import pytest

import run as R
import tiny
from benchlib import harness as H
from benchlib import weights as W

MT = H.family("mod_transformer")
SEED = 3_000_000_037
LIMITS = {"logit_gap_mean": 1e-3, "decode_rows_off": 0}


def dense_config(dtype="bfloat16") -> dict:
    conf = tiny.serve_cell().config
    conf.update(arch="mod-paper-60m-vanilla", dtype=dtype)
    del conf["model"]["mod"]
    return conf


def test_the_dense_reference_matches_the_programs_prefill_and_decode():
    """Float32 weights from the seed: the program's logits after a prefill and
    after each decode step through its cache against the reference's full
    forward over the same tokens."""
    from repro.models import api

    conf = dense_config("float32")
    s, cfg = MT.spec(conf), MT.program_config(conf, strict=False)
    assert not s.routed and not cfg.mod.enabled and s.n_groups == s.n_layers
    P = W.params_fn(s, False)(W.seed_key(SEED))
    L, n, ctx = 21, 9, 64
    prompt = np.random.default_rng(0).integers(0, s.vocab, L).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, caches = api.model_prefill(P, cfg, {"tokens": prompt[None]}, ctx)
        got = [np.asarray(logits[0, -1])]
        toks = [int(np.argmax(got[-1]))]
        for i in range(n - 1):
            out, caches, _ = api.model_decode(P, caches, cfg, np.array([[toks[-1]]], np.int32),
                                              np.array([L + i], np.int32))
            got.append(np.asarray(out[0]))
            toks.append(int(np.argmax(got[-1])))
    fed = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    ref, margin, score = jax.jit(lambda P, t: MT.serve_logits(P, s, t))(
        W.params_fn(s, True)(W.seed_key(SEED)), fed)
    assert margin is None and score is None
    ref = np.asarray(ref)[L - 1:]
    scale = float(np.abs(ref).max())
    assert np.abs(np.stack(got) - ref).max() <= 1e-5 * scale
    assert toks == list(np.argmax(ref, axis=-1))


CASES = {"program": ({}, None), "control": ({"control": "int8"}, "logit_gap_mean"),
         "control_fp8": ({"control": "fp8"}, "logit_gap_mean"),
         "token_altered": ({"fault": tiny.altered}, "logit_gap_mean"),
         "step_unseen": ({"fault": tiny.unseen}, "decode_rows_off")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_serving_check(case):
    kw, fails = CASES[case]
    cell = tiny.serve_cell(LIMITS)
    cell.config = dense_config()
    cell.end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"}]
    out, checks = R.measure(cell, SEED, 1.0, False, jax.devices()[:1], strict=False,
                            cache=False, **kw)
    assert set(checks) == set(LIMITS)
    assert {"logit_gap_max", "token_flip_share"} <= set(out["readings"])
    if fails is None:
        assert out["correct"]
        assert checks["logit_gap_mean"]["value"] < LIMITS["logit_gap_mean"] / 2
        assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        assert not out["correct"]
        assert not checks[fails]["value"] <= LIMITS[fails]
