"""SPMD routed execution: single-device vs multi-device equivalence.

The mesh-execution tests need a real multi-device runtime — run them via

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_routing_spmd.py

(scripts/ci.sh's ``spmd`` stage and the CI workflow's 8-device lane do
exactly this); on fewer devices they skip and only the partitioned-
semantics tests (pure policy, no mesh) run.

What is pinned, per DESIGN.md §SPMD routed execution:

- ``token_topk`` is per-sequence, so the per-shard decision is *bitwise*
  the single-device decision; whole-model forward + grads agree to
  reduction-order tolerance (the model axis splits contractions).
- ``batch_capacity`` under SPMD uses the *partitioned* selection semantics
  (top round(ratio·B/d) per contiguous shard group, global budget
  d·kb_local). A ``ShardCtx(mesh=None, data_shards=d)`` runs the same
  semantics on one device; mesh execution must match it — for the serving
  engine, token-for-token.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MeshConfig, get_config, smoke_config
from repro.core import router as R
from repro.core import routing as ROUT
from repro.distributed.sharding import (
    ShardCtx,
    batch_shardings,
    param_shardings,
    shard_ctx,
)
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models import blocks as BLK
from tests.helpers import batch_for, tiny_cfg

NDEV = jax.device_count()
needs8 = pytest.mark.skipif(
    NDEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(scripts/ci.sh spmd stage / CI 8-device lane)",
)


def mesh42():
    return make_mesh(MeshConfig(pod=1, data=4, model=2))


def _place(params, batch, mesh, data=4, model=2):
    mcfg = MeshConfig(pod=1, data=data, model=model, fsdp=False)
    p = jax.device_put(params, param_shardings(params, mesh, mcfg))
    b = jax.device_put(batch, batch_shardings(batch, mesh))
    return p, b


def _tree_allclose(a, b, atol, rtol=1e-5):
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    for (path, va), vb in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(va, np.float32),
            np.asarray(vb, np.float32),
            atol=atol,
            rtol=rtol,
            err_msg=jax.tree_util.keystr(path),
        )


# ---------------------------------------------------------------------------
# Partitioned batch_capacity semantics — pure policy, runs on any device count
# ---------------------------------------------------------------------------


def test_batch_select_partitioned_semantics():
    scores = jnp.asarray([0.9, 0.1, 0.2, 0.8, 0.3, 0.7, 0.95, 0.05])
    # global: top-2 of the whole batch
    np.testing.assert_array_equal(np.asarray(R.batch_select(scores, 2)), [0, 6])
    # partitioned, 4 groups of 2: each group's own top-1
    np.testing.assert_array_equal(
        np.asarray(R.batch_select(scores, 1, data_shards=4)), [0, 3, 5, 6]
    )
    # 2 groups of 4: per-group top-2, globally sorted
    np.testing.assert_array_equal(
        np.asarray(R.batch_select(scores, 2, data_shards=2)), [0, 3, 5, 6]
    )


def test_batch_capacity_k_global_budget():
    cfg = tiny_cfg()  # ratio 0.25
    assert ROUT.batch_capacity_k(cfg, 8) == 2
    # partitioned budget is d·round(ratio·B/d): the ≥1-row-per-shard floor
    # can push it above the unsharded round(ratio·B) ...
    assert ROUT.batch_capacity_k(cfg, 8, data_shards=4) == 4
    assert ROUT.batch_capacity_k(cfg, 16, data_shards=4) == 4
    assert ROUT.batch_capacity_k(cfg, 16, data_shards=2) == 4
    # ... and per-shard rounding can land below it at large ratios
    big = dataclasses.replace(cfg, mod=dataclasses.replace(cfg.mod, capacity_ratio=0.7))
    assert ROUT.batch_capacity_k(big, 8) == 6
    assert ROUT.batch_capacity_k(big, 8, data_shards=4) == 4


def test_decide_batch_partitioned_matches_per_group_topk():
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    gp = jax.tree.map(lambda a: a[0], params["groups"]["mod"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, cfg.d_model), jnp.float32)
    d_plain = ROUT.decide_batch(gp, x, cfg)
    d_part = ROUT.decide_batch(gp, x, cfg, data_shards=4)
    # same scores, different selection sets
    np.testing.assert_allclose(
        np.asarray(d_plain.scores), np.asarray(d_part.scores), rtol=1e-6
    )
    scores = np.asarray(d_part.scores)
    want = [g * 2 + int(np.argmax(scores[g * 2 : (g + 1) * 2])) for g in range(4)]
    np.testing.assert_array_equal(np.asarray(d_part.idx), want)
    assert np.asarray(d_part.mask).sum() == 4
    # data_shards=1 keeps the original global top-k behaviour
    np.testing.assert_array_equal(
        np.asarray(d_plain.idx),
        np.sort(np.argsort(scores)[-ROUT.batch_capacity_k(cfg, 8) :]),
    )


def test_decide_batch_partitioned_active_mask_per_group():
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    gp = jax.tree.map(lambda a: a[0], params["groups"]["mod"])
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 1, cfg.d_model), jnp.float32)
    active = jnp.asarray([True, False] * 4)  # one live slot per group
    d = ROUT.decide_batch(gp, x, cfg, active=active, data_shards=4)
    # each group must route its single live row, never the padding row
    np.testing.assert_array_equal(np.asarray(d.idx), [0, 2, 4, 6])


def test_fused_dispatch_mesh_compat_gate():
    cfg = dataclasses.replace(
        tiny_cfg(), mod=dataclasses.replace(tiny_cfg().mod, backend="pallas_fused")
    )
    assert BLK.fused_dispatch_supported(cfg)  # no mesh: unchanged
    dp = shard_ctx(make_mesh(MeshConfig(pod=1, data=1, model=1)))
    assert BLK.fused_dispatch_supported(cfg, dp)  # pure DP: fuses per shard
    if NDEV >= 2:
        # a >1 model axis splits the fused dims -> explicit fallback
        tp = shard_ctx(make_mesh(MeshConfig(pod=1, data=1, model=2)))
        assert not BLK.fused_dispatch_supported(cfg, tp)
    fsdp = dataclasses.replace(dp, fsdp=True)
    assert not BLK.fused_dispatch_supported(cfg, fsdp)
    moe_cfg = dataclasses.replace(cfg, family="moe")
    assert not BLK.fused_dispatch_supported(moe_cfg, dp)


# ---------------------------------------------------------------------------
# Mesh execution — 8-device lane
# ---------------------------------------------------------------------------


@needs8
def test_decide_tokens_spmd_bitwise():
    mesh = mesh42()
    ctx = shard_ctx(mesh)
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    gp = jax.tree.map(lambda a: a[0], params["groups"]["mod"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, cfg.d_model), jnp.float32)

    ref = ROUT.decide_tokens(gp, x, cfg)

    @jax.jit
    def spmd_decide(p, xx):
        d = ROUT.decide_tokens(p, xx, cfg, spmd=ctx)
        return d.idx, d.gate, d.mask, d.logits

    idx, gate, mask, logits = spmd_decide(gp, x)
    np.testing.assert_array_equal(np.asarray(ref.idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(ref.gate), np.asarray(gate))
    np.testing.assert_array_equal(np.asarray(ref.mask), np.asarray(mask))
    np.testing.assert_array_equal(np.asarray(ref.logits), np.asarray(logits))


@pytest.mark.parametrize("arch", ["dense", "moe"])
@needs8
def test_forward_and_grad_allclose_vs_single_device(arch):
    mesh = mesh42()
    ctx = shard_ctx(mesh)
    cfg = tiny_cfg() if arch == "dense" else tiny_cfg(family="moe")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, B=8, S=32)

    loss_ref, aux_ref = jax.jit(lambda p, b: api.model_loss(p, cfg, b))(params, batch)
    g_ref = jax.jit(jax.grad(lambda p, b: api.model_loss(p, cfg, b)[0]))(params, batch)

    p_sh, b_sh = _place(params, batch, mesh)
    loss_s, aux_s = jax.jit(lambda p, b: api.model_loss(p, cfg, b, spmd=ctx))(
        p_sh, b_sh
    )
    g_s = jax.jit(jax.grad(lambda p, b: api.model_loss(p, cfg, b, spmd=ctx)[0]))(
        p_sh, b_sh
    )

    np.testing.assert_allclose(float(loss_ref), float(loss_s), rtol=2e-5)
    np.testing.assert_allclose(
        float(aux_ref["ce"]), float(aux_s["ce"]), rtol=2e-5
    )
    _tree_allclose(g_ref, g_s, atol=2e-5)


@needs8
def test_forward_fused_dispatch_per_shard_pure_dp():
    """Under pure DP (model axis 1) the fused-dispatch kernels run
    per data shard inside shard_map; forward must match the single-device
    fused path (f32 kernels are bitwise — allow reduction-order slack for
    the surrounding ops)."""
    mesh = make_mesh(MeshConfig(pod=1, data=8, model=1))
    ctx = shard_ctx(mesh)
    cfg = tiny_cfg()
    cfg = dataclasses.replace(cfg, mod=dataclasses.replace(cfg.mod, backend="pallas_fused"))
    assert BLK.fused_dispatch_supported(cfg, ctx)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, B=8, S=32)

    ref, _ = jax.jit(lambda p, b: api.model_loss(p, cfg, b))(params, batch)
    p_sh, b_sh = _place(params, batch, mesh, data=8, model=1)
    got, _ = jax.jit(lambda p, b: api.model_loss(p, cfg, b, spmd=ctx))(p_sh, b_sh)
    np.testing.assert_allclose(float(ref), float(got), rtol=2e-5)


@needs8
def test_decode_step_spmd_matches_partitioned_reference():
    mesh = mesh42()
    ctx_m = shard_ctx(mesh)
    ctx_ref = shard_ctx(None, data_shards=4)
    cfg = tiny_cfg()
    B, L = 8, 32
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    caches = api.make_caches(cfg, B, L)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab)
    pos = jnp.zeros((B,), jnp.int32)
    active = jnp.asarray([True] * 6 + [False] * 2)

    lr, cr, ar = jax.jit(
        lambda p, c, t, q, a: api.model_decode(p, c, cfg, t, q, a, spmd=ctx_ref)
    )(params, caches, tok, pos, active)
    ls, cs, as_ = jax.jit(
        lambda p, c, t, q, a: api.model_decode(p, c, cfg, t, q, a, spmd=ctx_m)
    )(params, caches, tok, pos, active)

    # identical routing decisions; numerics to TP-reduction tolerance
    np.testing.assert_array_equal(
        np.asarray(ar["mod/decode_routed"]), np.asarray(as_["mod/decode_routed"])
    )
    np.testing.assert_array_equal(
        np.argmax(np.asarray(lr), -1), np.argmax(np.asarray(ls), -1)
    )
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ls), atol=1e-5)
    _tree_allclose(cr, cs, atol=1e-5)


@pytest.mark.parametrize("arch", ["mod-paper-60m", "olmoe-1b-7b"])
@needs8
def test_serving_engine_spmd_token_streams_identical(arch):
    """The acceptance gate: a request stream served over the (4, 2) mesh is
    token-for-token the single-device run with the same partitioned
    routing semantics — through admission, slot churn, and termination."""
    from repro.launch.mesh import auto_mesh
    from repro.serve import Request, ServingEngine

    cfg = dataclasses.replace(smoke_config(get_config(arch)), dtype="float32")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    mesh = auto_mesh(model_axis=2)  # (4, 2) under the forced-8 lane
    prompts = np.random.default_rng(3).integers(0, cfg.vocab, size=(12, 8)).astype(
        np.int32
    )

    def serve(**kw):
        eng = ServingEngine(params, cfg, batch_size=8, ctx=24, **kw)
        outs = eng.run_stream(
            [Request(tokens=prompts[i], max_new_tokens=8) for i in range(12)],
            arrival_every=2,
        )
        return {o.uid: o.tokens.tolist() for o in outs}, eng

    ref, eng_ref = serve(data_shards=4)
    got, eng_mesh = serve(mesh=mesh)
    assert ref == got, "mesh decode diverged from the partitioned reference"
    # both budgets are the global d·kb_local, and the pool really is sharded
    assert eng_ref.scheduler.routed_capacity == eng_mesh.scheduler.routed_capacity
    assert eng_mesh.scheduler.routed_capacity == ROUT.batch_capacity_k(
        cfg, 8, data_shards=4
    )
    leaf = jax.tree.leaves(eng_mesh.pool.caches)[0]
    assert len(leaf.sharding.device_set) > 1, "cache pool is not sharded"


@needs8
def test_train_step_spmd_smoke():
    """One jitted train step over the mesh: loss finite, grads applied."""
    from repro.config import OptimConfig, TrainConfig
    from repro.train.loop import make_train_state, make_train_step

    mesh = mesh42()
    ctx = shard_ctx(mesh)
    cfg = tiny_cfg()
    tcfg = TrainConfig(
        global_batch=8, seq_len=32, optim=OptimConfig(lr=1e-3, total_steps=10)
    )
    from repro.distributed.sharding import state_shardings

    state = make_train_state(jax.random.PRNGKey(0), cfg)
    batch = batch_for(cfg, B=8, S=32)
    mcfg = MeshConfig(pod=1, data=4, model=2, fsdp=False)
    s_sh = jax.device_put(state, state_shardings(state, mesh, mcfg))
    b_sh = jax.device_put(batch, batch_shardings(batch, mesh))
    step = jax.jit(make_train_step(cfg, tcfg, spmd=ctx))
    new_state, metrics = step(s_sh, b_sh)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state["step"]) == 1
