"""Backend equivalence for the routed-execution engine (core/routing.py).

The pallas kernels (interpret mode on CPU) must match the xla backend and
the kernels/ref.py oracles bit-for-bit on gather and gated scatter-add, and
the full `execute_routed` forward + grad must agree across backends, over
capacity ratios {0.125, 0.5, 1.0} and dtypes {f32, bf16}.

The `pallas_fused` backend (fused-dispatch routed attention + routed MLP
with scatter epilogue) is held to the same contract with one calibrated
carve-out: all comparisons run under jit (transcendentals round differently
eager-vs-compiled), and in bf16 the end-to-end spread vs xla is bounded by
one bf16 ulp — XLA re-places bf16 convert/dot pairs per fusion context, a
spread the pre-existing xla↔pallas backend pair exhibits identically (the
fused kernels themselves are asserted bit-for-bit against the xla
composition in BOTH dtypes).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.config import MoDConfig, with_mod_backend
from repro.core import router as R
from repro.core import routing as ROUT
from repro.kernels import flash_attention as KFA
from repro.kernels import ref as KREF
from repro.kernels.ops import routed_mlp_scatter_op
from repro.kernels.routing import gather_rows, scatter_add_rows
from repro.models import blocks as BLK
from tests.helpers import tiny_cfg

RATIOS = [0.125, 0.5, 1.0]
DTYPES = [jnp.float32, jnp.bfloat16]


def _routing_case(ratio, dtype, b=2, s=32, d=24, seed=0):
    k = max(1, int(round(ratio * s)))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, s, d)).astype(dtype)
    logits = jax.random.normal(ks[1], (b, s))
    _, idx = jax.lax.top_k(logits, k)
    idx = jnp.sort(idx, axis=-1).astype(jnp.int32)
    delta = jax.random.normal(ks[2], (b, k, d)).astype(dtype)
    gate = jax.random.normal(ks[3], (b, k))
    return x, idx, delta, gate


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gather_bit_for_bit(ratio, dtype):
    x, idx, _, _ = _routing_case(ratio, dtype)
    pallas = gather_rows(x, idx, interpret=True)
    xla = jnp.take_along_axis(x, idx[..., None], axis=1)
    ref = KREF.gather_rows_ref(x, idx)
    assert pallas.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(pallas), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(xla))


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gated_scatter_add_bit_for_bit(ratio, dtype):
    x, idx, delta, gate = _routing_case(ratio, dtype)
    pallas = scatter_add_rows(x, idx, delta, gate, interpret=True)
    upd = (gate[..., None] * delta.astype(jnp.float32)).astype(x.dtype)
    xla = x.at[jnp.arange(x.shape[0])[:, None], idx].add(upd)
    ref = KREF.scatter_add_rows_ref(x, idx, delta, gate)
    assert pallas.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(pallas), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(xla))
    # unrouted rows pass through untouched
    mask = np.zeros(x.shape[:2], bool)
    np.put_along_axis(mask, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(np.asarray(pallas)[~mask], np.asarray(x)[~mask])


def _mod_cfg(ratio, dtype):
    return tiny_cfg(
        dtype="float32" if dtype == jnp.float32 else "bfloat16",
        mod=MoDConfig(enabled=True, capacity_ratio=ratio, every=2, round_to=1),
    )


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_execute_routed_forward_matches(ratio, dtype):
    cfg = _mod_cfg(ratio, dtype)
    B, S, D = 2, 32, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (B, S, D)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    params = {"router": R.init_router(ks[1], cfg)}
    w = jax.random.normal(ks[2], (D, D)).astype(dtype) * 0.1

    def delta_fn(xs, ps):
        return jnp.tanh(xs @ w), {}

    outs = {}
    for backend in ("xla", "pallas"):
        bcfg = with_mod_backend(cfg, backend)
        decision = ROUT.decide_tokens(params, x, bcfg)
        outs[backend], _ = ROUT.execute_routed(decision, x, delta_fn, bcfg, pos)
    np.testing.assert_array_equal(np.asarray(outs["xla"]), np.asarray(outs["pallas"]))


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_execute_routed_grad_matches(ratio, dtype):
    cfg = _mod_cfg(ratio, dtype)
    B, S, D = 2, 32, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (B, S, D)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    params = {"router": R.init_router(ks[1], cfg)}
    w = jax.random.normal(ks[2], (D, D)).astype(dtype) * 0.1

    def loss(params, x, w, bcfg):
        def delta_fn(xs, ps):
            return jnp.tanh(xs @ w), {}

        out, _ = ROUT.apply_mod(params, x, pos, delta_fn, bcfg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grads = {}
    for backend in ("xla", "pallas"):
        bcfg = with_mod_backend(cfg, backend)
        grads[backend] = jax.grad(loss, argnums=(0, 1, 2))(params, x, w, bcfg)
    gx, _ = ravel_pytree(grads["xla"])
    gp, _ = ravel_pytree(grads["pallas"])
    # grads route through a custom VJP on the pallas side: numerically equal
    # up to cotangent-accumulation rounding in the activation dtype. bf16's
    # bound is calibrated against the spread between two pure-autodiff
    # formulations (take_along_axis vs one-hot einsum) on the same case —
    # the backend pair must not be noisier than that baseline.
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gp), rtol=2e-5, atol=2e-6)
    else:
        np.testing.assert_allclose(
            np.asarray(gx, np.float32), np.asarray(gp, np.float32), rtol=0.25, atol=0.05
        )


# ---------------------------------------------------------------------------
# pallas_fused backend: fused-dispatch kernels
# ---------------------------------------------------------------------------


def _fused_case(ratio, dtype, b=2, s=32, seed=3, **cfg_kw):
    """A real transformer block + router, the fused backend's native unit."""
    cfg = _mod_cfg(ratio, dtype)
    if cfg_kw:
        cfg = dataclasses.replace(cfg, **cfg_kw)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, s, cfg.d_model)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    params = {"block": BLK.init_block(ks[1], cfg), "router": R.init_router(ks[2], cfg)}
    return cfg, params, x, pos


def _run_backend(backend, cfg, params, x, pos):
    """apply_mod through a given backend, wired exactly like transformer.py."""
    bcfg = with_mod_backend(cfg, backend)

    def delta_fn(xs, ps):
        return BLK.block_delta(params["block"], xs, ps, bcfg)

    fused_fn = None
    if BLK.fused_dispatch_supported(bcfg):
        def fused_fn(xf, decision, pf):
            return BLK.block_delta_fused(params["block"], xf, pf, decision, bcfg)

    out, _ = ROUT.apply_mod(params, x, pos, delta_fn, bcfg, fused_block_fn=fused_fn)
    return out


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_fused_forward_matches_xla(ratio, dtype):
    """Forward equivalence of the fused backend on a real block.

    f32: bit-for-bit across all three backends. bf16: one-ulp bound vs
    xla, calibrated by the xla↔pallas baseline spread (XLA's bf16
    convert/dot placement varies with fusion context; the fused backend
    must not be noisier than the pre-existing backend pair)."""
    cfg, params, x, pos = _fused_case(ratio, dtype)
    outs = {
        b: jax.jit(functools.partial(_run_backend, b, cfg, params))(x, pos)
        for b in ("xla", "pallas", "pallas_fused")
    }
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(outs["xla"]), np.asarray(outs["pallas"]))
        np.testing.assert_array_equal(np.asarray(outs["xla"]), np.asarray(outs["pallas_fused"]))
    else:
        # calibrated bound: the fused↔xla spread must stay within the
        # xla↔pallas baseline spread on the same case (×2 margin), with a
        # one-bf16-ulp floor relative to the output scale for cases where
        # the baseline pair happens to agree exactly
        ref = np.asarray(outs["xla"], np.float32)
        spread_f = np.abs(np.asarray(outs["pallas_fused"], np.float32) - ref).max()
        spread_p = np.abs(np.asarray(outs["pallas"], np.float32) - ref).max()
        ulp = 2.0 ** -7  # bf16 mantissa
        assert spread_f <= max(2.0 * spread_p, ulp * np.abs(ref).max()), (
            spread_f, spread_p,
        )


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_fused_kernels_bitexact_vs_xla_composition(ratio, dtype):
    """The fused kernels themselves are bit-for-bit equal (both dtypes) to
    the xla composition — gather (take_along_axis) -> rmsnorm ->
    self_attention / mlp -> gated at[].add — compiled standalone. This is
    the kernel-level contract; any end-to-end bf16 spread is XLA fusion
    placement, not kernel rounding."""
    cfg, params, x, pos = _fused_case(ratio, dtype)
    decision = ROUT.decide_tokens(params, x, cfg)
    idx, gate = decision.idx, decision.gate
    pos_sub = ROUT.gather_positions(pos, idx)
    p = params["block"]
    a_k, h_k = BLK.A.routed_self_attention(p["attn"], p["ln1"], x, idx, pos_sub, cfg)
    spec = KFA.RoutedAttnSpec(
        cfg.attn.n_heads, cfg.attn.n_kv_heads, cfg.head_dim,
        1.0 / (cfg.head_dim**0.5), True, 0, cfg.attn.rope_theta, "rope",
        cfg.norm_eps, KFA.ROUTED_BLOCK_K, True,
    )
    ap = {"ln": p["ln1"]["scale"], "wq": p["attn"]["wq"], "wk": p["attn"]["wk"],
          "wv": p["attn"]["wv"], "wo": p["attn"]["wo"]}
    a_m, h_m = jax.jit(
        lambda x_, p_: KFA._routed_attention_host(x_, idx, pos_sub, p_, spec)
    )(x, ap)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_m))
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_m))

    mp = {"ln": p["ln2"]["scale"], **p["mlp"]}
    o_k = routed_mlp_scatter_op(x, h_k, a_k, idx, gate, mp, eps=cfg.norm_eps)
    from repro.kernels import swiglu as KSW

    mspec = KSW.RoutedMlpSpec("silu", cfg.norm_eps, 256, True)
    o_m = jax.jit(
        lambda *a: KSW._routed_mlp_host(a[0], a[1], a[2], idx, a[3], a[4], mspec)
    )(x, h_k, a_k, gate, mp)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_m))


@pytest.mark.parametrize("block_k", [8, 16])
def test_fused_padding_tail(block_k):
    """Capacity NOT a multiple of the kernel's capacity tile: k=20 over
    block_k ∈ {8, 16} pads the q-tile axis (idx/pos = -1). Padded rows must
    neither perturb real rows (f32 bit-for-bit vs xla) nor leak through the
    scatter.

    The bound is a tight f32 tolerance, not bit equality: XLA 0.9 fuses
    the xla backend's composition differently from the kernel's (same op
    sequence, different fusion), and the two drift by ulps (measured
    <= 7.6e-6 absolute on 0.5% of elements). A padded row leaking into a
    real one would be an O(1) error, far outside it."""
    cfg, params, x, pos = _fused_case(0.625, jnp.float32)  # k = 20 of S = 32
    assert cfg.mod.capacity(x.shape[1]) % block_k != 0
    old = KFA.ROUTED_BLOCK_K
    KFA.ROUTED_BLOCK_K = block_k
    try:
        out_f = jax.jit(functools.partial(_run_backend, "pallas_fused", cfg, params))(x, pos)
    finally:
        KFA.ROUTED_BLOCK_K = old
    out_x = jax.jit(functools.partial(_run_backend, "xla", cfg, params))(x, pos)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_f), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_fused_grad_matches(ratio, dtype):
    """Grad equivalence through both custom VJPs.

    In f32, pallas_fused must match the pallas backend (both route
    cotangents through kernel VJPs) to a tight f32 tolerance: XLA 0.9
    fuses the two backward graphs differently, which drifts them by ulps
    (measured <= 7.6e-6 absolute, 2.5e-6 relative), so bit equality no
    longer holds. Vs xla's pure autodiff the existing calibrated bounds
    apply (see test_execute_routed_grad_matches — the fused backend must
    not be noisier than that baseline)."""
    cfg, params, x, pos = _fused_case(ratio, dtype, seed=4)

    def loss(backend, params, x):
        out = _run_backend(backend, cfg, params, x, pos)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grads = {
        b: jax.jit(jax.grad(functools.partial(loss, b), argnums=(0, 1)))(params, x)
        for b in ("xla", "pallas", "pallas_fused")
    }
    gx, _ = ravel_pytree(grads["xla"])
    gp, _ = ravel_pytree(grads["pallas"])
    gf, _ = ravel_pytree(grads["pallas_fused"])
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gf), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gf), rtol=2e-5, atol=2e-6)
    else:
        # bf16: bound the fused↔xla spread by the pre-existing pallas↔xla
        # baseline on the same case (×4 margin) with a 1%-of-grad-scale
        # floor — the fused VJP must not be categorically noisier than the
        # backend pair that was already accepted.
        fx = np.asarray(gx, np.float32)
        spread_f = np.abs(np.asarray(gf, np.float32) - fx).max()
        spread_p = np.abs(np.asarray(gp, np.float32) - fx).max()
        assert spread_f <= max(4.0 * spread_p, 1e-2 * np.abs(fx).max()), (
            spread_f, spread_p,
        )


def test_fused_fallback_without_fused_fn():
    """pallas_fused without a fused_block_fn (generic delta_fns, SSM/encdec
    blocks, prefill) must fall back to the pallas dispatch kernels
    bit-for-bit."""
    cfg = _mod_cfg(0.25, jnp.float32)
    B, S, D = 2, 32, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (B, S, D))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    params = {"router": R.init_router(ks[1], cfg)}
    w = jax.random.normal(ks[2], (D, D)) * 0.1

    def delta_fn(xs, ps):
        return jnp.tanh(xs @ w), {}

    outs = {}
    for backend in ("pallas", "pallas_fused"):
        bcfg = with_mod_backend(cfg, backend)
        decision = ROUT.decide_tokens(params, x, bcfg)
        outs[backend], _ = ROUT.execute_routed(decision, x, delta_fn, bcfg, pos)
    np.testing.assert_array_equal(
        np.asarray(outs["pallas"]), np.asarray(outs["pallas_fused"])
    )


@pytest.mark.parametrize("backend", ["xla", "pallas", "pallas_fused"])
def test_train_step_refuses_compiled_fused_kernels(backend, monkeypatch):
    """Off the CPU the fused kernels would have to compile, which they
    cannot: building a pallas_fused train step there fails with the clear
    error before anything is traced; the other backends build."""
    from repro.config import TrainConfig
    from repro.kernels import ops
    from repro.train.loop import make_train_step

    cfg = with_mod_backend(tiny_cfg(), backend)
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    if backend == "pallas_fused":
        with pytest.raises(ValueError, match="pallas_fused"):
            make_train_step(cfg, TrainConfig())
    else:
        assert callable(make_train_step(cfg, TrainConfig()))


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_fused_transformer_forward_matches(family):
    """Whole-model equivalence: transformer.forward logits under
    pallas_fused == xla bit-for-bit (f32). MoE blocks exercise the partial
    fusion path (fused attention + expert MLP + pallas scatter)."""
    from repro.config import MoEConfig
    from repro.models import transformer as T

    kw = dict(mod=MoDConfig(enabled=True, capacity_ratio=0.25, every=2, round_to=1))
    if family == "moe":
        kw["family"] = "moe"
        kw["moe"] = MoEConfig(enabled=True, n_experts=4, top_k=2, d_ff_expert=64)
    cfg = tiny_cfg(**kw)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)

    def fwd(backend, params, tokens):
        logits, _ = T.forward(params, with_mod_backend(cfg, backend), tokens=tokens)
        return logits

    out_x = jax.jit(functools.partial(fwd, "xla"))(params, tokens)
    out_f = jax.jit(functools.partial(fwd, "pallas_fused"))(params, tokens)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_f))


@pytest.mark.parametrize("sampling", ["predictor", "aux_loss"])
def test_decide_batch_matches_legacy_contract(sampling):
    """batch_capacity decisions: static shapes, causal scores, sorted idx."""
    cfg = tiny_cfg(
        mod=MoDConfig(enabled=True, capacity_ratio=0.25, round_to=1, sampling=sampling)
    )
    B = 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, 1, cfg.d_model))
    params = {"router": R.init_router(key, cfg), "predictor": R.init_predictor(key, cfg)}
    d = ROUT.decide_batch(params, x, cfg)
    kb = max(1, int(round(cfg.mod.capacity_ratio * B)))
    assert d.strategy == "batch_capacity"
    assert d.idx.shape == (kb,)
    assert int(d.mask.sum()) == kb
    assert (np.diff(np.asarray(d.idx)) > 0).all() if kb > 1 else True
