"""Pipeline parallelism: GPipe schedule correctness on a 4-device subprocess
mesh (ppermute needs real devices), plus the bubble accounting, plus a
compressed-psum smoke under shard_map."""
import json
import os
import subprocess
import sys
import textwrap

from repro.distributed.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == 0.75
    assert bubble_fraction(8, 4) == 3 / 11
    assert bubble_fraction(8, 1) == 0.0


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.distributed.pipeline import pipeline_forward
    from repro.optim.compression import compressed_psum, init_error_feedback
    from jax.sharding import AxisType
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    out = {}

    # --- pipeline: 4 stages of y = x @ W_i + b_i, compare vs sequential ----
    n_stages, n_micro, B, D = 4, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    Ws = jax.random.normal(ks[0], (n_stages, D, D)) * 0.3
    bs = jax.random.normal(ks[1], (n_stages, D)) * 0.1
    x = jax.random.normal(ks[2], (n_micro, B, D))

    def stage_fn(p, h, idx):
        return jnp.tanh(h @ p["W"] + p["b"])

    got = pipeline_forward(stage_fn, {"W": Ws, "b": bs}, x, mesh, axis="pod")
    want = x
    for i in range(n_stages):
        want = jnp.tanh(want @ Ws[i] + bs[i])
    out["pipeline_err"] = float(jnp.max(jnp.abs(got - want)))

    # --- compressed psum under shard_map ------------------------------------
    g = jax.random.normal(ks[0], (4, 16))  # one row per device

    def reduce_fn(g_local, e_local):
        avg, new_e = compressed_psum({"g": g_local[0]}, "pod", {"g": e_local[0]})
        return avg["g"][None], new_e["g"][None]

    fn = jax.shard_map(reduce_fn, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")), check_vma=False)
    avg, err = fn(g, jnp.zeros_like(g))
    true_mean = jnp.mean(g, axis=0)
    # each device holds the same (approximate) mean
    out["psum_err"] = float(jnp.max(jnp.abs(avg - true_mean[None])))
    out["psum_scale"] = float(jnp.max(jnp.abs(g)) / 127.0)
    print(json.dumps(out))
    """
)


def test_pipeline_and_compressed_psum_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["pipeline_err"] < 1e-5, out
    # int8 quantization: error bounded by ~a quantization bin of the max
    assert out["psum_err"] < 4 * out["psum_scale"], out
