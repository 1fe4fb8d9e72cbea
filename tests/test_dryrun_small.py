"""Multi-pod dry-run machinery at test scale: a subprocess with 8 fake
devices lowers + compiles a reduced arch on a (2, 2, 2) pod/data/model mesh
— validating the same code path as the 512-chip production dry-run without
its cost."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax

    from repro.config import get_config, smoke_config, SHAPES, TrainConfig, MeshConfig
    from repro.distributed.sharding import state_shardings, batch_shardings, cache_shardings, param_shardings
    from repro.models import api
    from repro.train.loop import make_train_step, train_state_specs
    from repro.launch.mesh import make_mesh

    mcfg = MeshConfig(pod=2, data=2, model=2, fsdp=True)
    mesh = make_mesh(mcfg)
    cfg = dataclasses.replace(smoke_config(get_config("{arch}")), remat="none")
    out = {{}}

    # --- train step ---
    B, S = 8, 32
    tcfg = TrainConfig(global_batch=B, seq_len=S, microbatches=2)
    specs = {{
        "tokens": jax.ShapeDtypeStruct((B, S), "int32"),
        "labels": jax.ShapeDtypeStruct((B, S), "int32"),
    }}
    st = train_state_specs(jax.random.PRNGKey(0), cfg)
    st_sh = state_shardings(st, mesh, mcfg)
    b_sh = batch_shardings(specs, mesh)
    with jax.set_mesh(mesh):
        c = jax.jit(make_train_step(cfg, tcfg), in_shardings=(st_sh, b_sh),
                    out_shardings=(st_sh, None), donate_argnums=(0,)).lower(st, specs).compile()
    out["train_flops"] = float(c.cost_analysis().get("flops", 0))
    out["train_temp"] = int(c.memory_analysis().temp_size_in_bytes)

    # --- serve step ---
    ps = jax.eval_shape(lambda k: api.init_model(k, cfg), jax.random.PRNGKey(0))
    p_sh = param_shardings(ps, mesh, mcfg)
    caches = api.make_caches(cfg, B, S, specs=True)
    c_sh = cache_shardings(caches, mesh, cfg, B)
    tok = jax.ShapeDtypeStruct((B, 1), "int32")
    pos = jax.ShapeDtypeStruct((B,), "int32")
    tp_sh = batch_shardings({{"token": tok, "pos": pos}}, mesh)
    def serve(p, c, t, q):
        return api.model_decode(p, c, cfg, t, q)
    with jax.set_mesh(mesh):
        c2 = jax.jit(serve, in_shardings=(p_sh, c_sh, tp_sh["token"], tp_sh["pos"]),
                     out_shardings=(None, c_sh, None), donate_argnums=(1,)).lower(
                         ps, caches, tok, pos).compile()
    out["serve_ok"] = True
    print(json.dumps(out))
    """
)

ARCHS = ["granite-8b", "mamba2-1.3b", "olmoe-1b-7b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_multipod_lower_compile(arch):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch)],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["train_flops"] > 0
    assert out["serve_ok"]
