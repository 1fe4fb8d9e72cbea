"""The program's own tracing: the layer scopes in the compiled HLO of the
serving and training programs, and the serving engine's host spans in a
profiler trace (padded paged, ragged and speculative steps)."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimConfig, TrainConfig
from repro.models import api
from repro.optim import adamw_init
from repro.serve import EngineConfig, Request, ServingEngine
from repro.train.loop import make_train_step
from tests.helpers import batch_for, tiny_cfg

MODEL_SCOPES = ("attention", "mod.router", "mod.dispatch", "mlp", "lm_head")
STEP_SPANS = ("serve.admit", "serve.pages", "serve.decode", "serve.logits_to_host",
              "serve.sample", "serve.invariants")


def scopes_in(hlo_text: str) -> set:
    """Every scope name in the text's ``op_name`` paths, transformation
    wrappers (``jvp(...)``, ``transpose(...)``) taken off."""
    names = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in op.split("/"):
            while True:
                m = re.fullmatch(r"[\w.\-]*\((.*)\)", part)
                if not m:
                    break
                part = m.group(1)
            names.add(part)
    return names


def engine(**kw):
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    conf = dict(batch_size=4, ctx=32, page_size=4, prefill_chunk=4)
    conf.update(kw)
    return ServingEngine(params, cfg, engine=EngineConfig(**conf))


def test_paged_decode_step_and_prefill_chunk_carry_the_layer_scopes():
    eng = engine()
    pool, B = eng.pool, eng.batch_size
    step = eng._step_fn.lower(
        eng.params, pool.pages, pool.scales, pool.resid, pool.device_table(),
        jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
    ).compile().as_text()
    # the step writes its rows into the pool in place (paged.writeback); it
    # gathers no logical cache, so paged.materialize is gone from it
    assert set(MODEL_SCOPES) | {"paged.writeback"} <= scopes_in(step)
    assert "paged.materialize" not in scopes_in(step)
    chunk = eng._chunk_fn.lower(
        eng.params, pool.read_slot(0), jnp.zeros((1, 4), jnp.int32), jnp.int32(0),
        jnp.int32(4),
    ).compile().as_text()
    assert set(MODEL_SCOPES) <= scopes_in(chunk)
    assert "paged.materialize" not in scopes_in(chunk)


def test_train_step_carries_the_layer_scopes_and_the_optimizer():
    cfg = tiny_cfg()
    step = jax.jit(make_train_step(cfg, TrainConfig(global_batch=2, seq_len=32,
                                                    optim=OptimConfig())))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}
    text = step.lower(state, batch_for(cfg, 2, 32)).compile().as_text()
    assert set(MODEL_SCOPES) | {"optimizer"} <= scopes_in(text)


def host_line(trace_dir: str, caller: str):
    """(name, start, end, args) of the host line that holds ``caller``."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats) if e.name.startswith(("serve.", caller)) else {})
                  for e in line.events]
            if any(name == caller for name, _, _, _ in ev):
                return ev
    raise AssertionError(f"no host line holds {caller!r}")


@pytest.mark.parametrize("path", ["paged", "ragged", "speculative"])
def test_engine_steps_write_their_host_spans_inside_the_callers(path, tmp_path):
    kw = {"paged": {}, "ragged": {"ragged": True}, "speculative": {"speculate": 2}}[path]
    eng = engine(**kw)
    rng = np.random.default_rng(0)
    for n in (5, 9):
        eng.submit(Request(tokens=rng.integers(1, 90, size=n).astype(np.int32),
                           max_new_tokens=6))
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation("caller", i=i):
            eng.step()
    jax.profiler.stop_trace()
    ev = host_line(str(tmp_path), "caller")
    calls = [(s, e) for name, s, e, _ in ev if name == "caller"]
    spans = [x for x in ev if x[0].startswith("serve.")]
    assert {name for name, *_ in spans} >= set(STEP_SPANS)
    for name, s, e, _ in spans:
        assert any(cs <= s and e <= ce for cs, ce in calls), name
    decode = [args for name, _, _, args in spans if name == "serve.decode"]
    assert len(decode) == 3 and all(args["live"] >= 1 for args in decode)
    assert [args["step"] for args in decode] == sorted(args["step"] for args in decode)
    assert all("scrubbed" in args for name, _, _, args in spans if name == "serve.pages")
    if path != "ragged":  # the mixed step ingests prompts in its own segments
        prefill = [(s, e, args) for name, s, e, args in spans if name == "serve.prefill"]
        assert sorted(args["tokens"] for _, _, args in prefill) == [5, 9]
        assert all("uid" in args for _, _, args in prefill)
        admits = [(s, e) for name, s, e, _ in spans if name == "serve.admit"]
        assert all(any(a <= s and e <= b for a, b in admits) for s, e, _ in prefill)
