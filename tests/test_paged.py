"""Paged KV pool: kernel oracles, paged-vs-contiguous token identity across
families (greedy + seeded sampling in one stream), chunked prefill, prefix
cache reuse, page-exhaustion preemption, and page accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MoDConfig, SSMConfig
from repro.kernels import ops, ref
from repro.models import api
from repro.serve import EngineConfig, Request, ServingEngine
from repro.serve.cache import NULL_PAGE, SCRATCH_PAGE, PagedCachePool
from repro.serve.scheduler import PREFILL
from tests.helpers import tiny_cfg

# ---------------------------------------------------------------------------
# Kernels: xla == pallas == ref oracle
# ---------------------------------------------------------------------------


def test_paged_kernels_match_ref_and_xla():
    rng = np.random.default_rng(0)
    N, p, F, B, P = 9, 4, 6, 3, 2
    pages = jnp.asarray(rng.normal(size=(N, p, F)), jnp.float32)
    table = jnp.asarray(rng.integers(0, N, size=(B, P)), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    pos = jnp.asarray([1, 7, 2], jnp.int32)

    g_ref = np.asarray(ref.paged_gather_ref(pages, table))
    g_xla = np.asarray(ops.paged_gather_op(pages, table, backend="xla"))
    g_pl = np.asarray(
        ops.paged_gather_op(pages, table, backend="pallas", interpret=True)
    )
    np.testing.assert_array_equal(g_ref, g_xla)
    np.testing.assert_array_equal(g_ref, g_pl)

    s_ref = np.asarray(ref.paged_scatter_rows_ref(pages, table, rows, pos))
    s_xla = np.asarray(ops.paged_scatter_rows_op(pages, table, rows, pos, backend="xla"))
    s_pl = np.asarray(
        ops.paged_scatter_rows_op(pages, table, rows, pos, backend="pallas", interpret=True)
    )
    np.testing.assert_array_equal(s_ref, s_xla)
    np.testing.assert_array_equal(s_ref, s_pl)


def test_paged_kernels_lead_dims():
    """Cache leaves carry layer-group lead dims; the ops wrappers fold them."""
    rng = np.random.default_rng(1)
    G, N, p, nkv, hd, B, P = 2, 7, 4, 2, 3, 3, 2
    pages = jnp.asarray(rng.normal(size=(G, N, p, nkv, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(0, N, size=(B, P)), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(G, B, nkv, hd)), jnp.float32)
    pos = jnp.asarray([0, 5, 3], jnp.int32)
    for fn, args in (
        (ops.paged_gather_op, (pages, table)),
        (ops.paged_scatter_rows_op, (pages, table, rows, pos)),
    ):
        x = np.asarray(fn(*args, page_axis=1, backend="xla"))
        y = np.asarray(fn(*args, page_axis=1, backend="pallas", interpret=True))
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Engine: paged == contiguous token streams
# ---------------------------------------------------------------------------


def _family_cfg(family):
    if family == "ssm":
        return dataclasses.replace(
            tiny_cfg(), family="ssm",
            ssm=SSMConfig(enabled=True, d_state=16, head_dim=32, chunk=16),
        )
    if family == "hybrid":
        return dataclasses.replace(
            tiny_cfg(), family="hybrid", hybrid_attn_every=2,
            ssm=SSMConfig(enabled=True, d_state=16, head_dim=32, chunk=16),
        )
    if family == "encdec":
        return dataclasses.replace(tiny_cfg(), family="encdec")
    if family == "moe":
        return dataclasses.replace(tiny_cfg(), family="moe")
    return tiny_cfg()


def _mixed_requests(cfg, family, n=3, seed=3):
    """Greedy and seeded-sampled requests in one stream (slot churn at B=2)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        kw = {}
        if family == "encdec":
            kw["enc_emb"] = np.asarray(
                jax.random.normal(
                    jax.random.PRNGKey(i), (cfg.enc_seq_len, cfg.d_model)
                ) * 0.02
            )
        reqs.append(
            Request(
                tokens=rng.integers(0, cfg.vocab, size=4 + i).astype(np.int32),
                max_new_tokens=4,
                temperature=0.0 if i % 2 == 0 else 0.8,
                key=jax.random.PRNGKey(100 + i),
                **kw,
            )
        )
    return reqs


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "encdec"])
def test_paged_engine_token_identity(family):
    """The paged pool must be invisible: token streams (greedy AND seeded
    sampling, under slot churn) bit-identical to the contiguous pool, with
    the decode step still compiling exactly once."""
    cfg = _family_cfg(family)
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    outs = {}
    for paged in (False, True):
        kw = {"page_size": 4} if paged else {}
        eng = ServingEngine(params, cfg, batch_size=2, ctx=16, **kw)
        for r in _mixed_requests(cfg, family):
            eng.submit(r)
        outs[paged] = {o.uid: o.full_sequence.tolist() for o in eng.run()}
        if paged and eng.decode_compilations is not None:
            assert eng.decode_compilations <= 1
    assert outs[False] == outs[True]


def test_paged_engine_token_identity_hybrid():
    """Hybrid rides along: shared-attn KV pages + SSM residual state."""
    cfg = _family_cfg("hybrid")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    outs = {}
    for paged in (False, True):
        kw = {"page_size": 4} if paged else {}
        eng = ServingEngine(params, cfg, batch_size=2, ctx=16, **kw)
        for r in _mixed_requests(cfg, "hybrid"):
            eng.submit(r)
        outs[paged] = {o.uid: o.full_sequence.tolist() for o in eng.run()}
    assert outs[False] == outs[True]


def test_paged_pallas_backend_matches_xla():
    """The pallas paged gather/scatter variant drives the same engine to the
    same tokens as the xla reference backend."""
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    outs = {}
    for backend in ("xla", "pallas"):
        eng = ServingEngine(
            params, cfg, batch_size=2, ctx=16, page_size=4, paged_backend=backend
        )
        for r in _mixed_requests(cfg, "dense", n=2):
            eng.submit(r)
        outs[backend] = {o.uid: o.full_sequence.tolist() for o in eng.run()}
    assert outs["xla"] == outs["pallas"]


# ---------------------------------------------------------------------------
# In-place padded decode: live-page attention, row writes, donation
# ---------------------------------------------------------------------------


def _live_pool(rng, L, N, p, nkv, hd, P, q_pos, free, shuffle=False, share=None):
    """A layer-stacked pool as the decode step sees it: each live slot maps
    its pages up to its position (plus one scrubbed look-ahead page), rows
    hold their own positions up to the slot's and -1 after, the unmapped
    tail is NULL; free slots map SCRATCH, whose rows hold garbage.
    ``shuffle`` hands out the physical pages in a random order;
    ``share=(a, b)`` maps slot b's first page to slot a's (a shared prefix)."""
    k = rng.normal(size=(L, N, p, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(L, N, p, nkv, hd)).astype(np.float32)
    pos = np.full((L, N, p), -1, np.int32)
    k[:, NULL_PAGE] = v[:, NULL_PAGE] = 0.0
    pos[:, SCRATCH_PAGE] = rng.integers(1, 3 * p, size=p)
    pos[:, SCRATCH_PAGE, 0] = 0
    table = np.full((len(q_pos), P), NULL_PAGE, np.int32)
    ids = np.arange(2, N)
    if shuffle:
        rng.shuffle(ids)
    free_ids = iter(ids)
    for b, qp in enumerate(q_pos):
        if b in free:
            table[b] = SCRATCH_PAGE
            continue
        n = qp // p + 1
        for i in range(min(P, n + 1)):
            if share is not None and (b, i) == (share[1], 0):
                table[b, 0] = table[share[0], 0]
                continue
            nxt = next(free_ids)
            table[b, i] = nxt
            t = i * p + np.arange(p)
            pos[:, nxt] = np.where(t <= qp, t, -1)
    return k, v, pos, table


# pool shapes of the live-page oracle test: in-page rows p, head size hd,
# pages per slot P, dtype of pool and query. "small" heads (hd 8) take the
# per-page read; heads of 128 the hand-copied blocks, "served" at the decode
# cells' tiling (16-row pages, bf16, 8 pages = 128 keys a block)
_LIVE_LAYOUTS = {
    "small": dict(p=4, hd=8, P=6),
    "served": dict(p=16, hd=128, P=16, dtype=jnp.bfloat16),
    "shuffled": dict(p=8, hd=128, P=6, shuffle=True, share=(1, 2)),
    # a plane too large for VMEM (budget 0): the per-page read at heads of
    # 128, pages spread over full 128-page windows and the ragged last one
    "big-plane": dict(p=8, hd=128, P=6, shuffle=True, N=300, plane_bytes=0),
}


@pytest.mark.parametrize(
    "nq,nkv,causal,window,ppb,layout",
    [(4, 2, True, 0, 2, "small"), (2, 2, True, 0, 4, "small"), (4, 2, True, 5, 2, "small"),
     (4, 4, False, 0, 3, "small"), (2, 2, True, 0, None, "served"),
     (4, 4, False, 0, 4, "shuffled"), (8, 2, True, 0, None, "served"),
     (4, 2, True, 5, 2, "big-plane")],
    ids=["gqa", "mha-blocks-of-4", "window", "bidirectional-blocks-of-3",
         "served-tiling-bf16", "shuffled-shared-prefix", "gqa-4-hd128-bf16",
         "positions-per-page"],
)
def test_live_page_decode_attention_matches_its_xla_oracle(
    nq, nkv, causal, window, ppb, layout, monkeypatch
):
    """The live-page kernel (interpret mode) against the gather + attend
    formulation the step runs off TPU, on lengths at and across page and
    block boundaries, NULL-mapped tails, a full slot and a free slot on
    SCRATCH, reading layer 1 of a two-layer stack.

    bf16 layouts hold the kernel to the oracle run in float32 on the same
    bf16 values, at the same 1e-5: the kernel's products of bf16 operands
    are exact and it accumulates and weighs in f32, whereas ``attend`` in
    bf16 rounds scores, weights and output to bf16 (2**-9 relative each),
    which would take a tolerance near 1e-2, loose enough to hide a page
    read twice or a row masked wrongly."""
    from repro.config import AttentionConfig
    from repro.kernels import paged as PG
    from repro.models import paged_kv as PKV

    lay = dict(_LIVE_LAYOUTS[layout])
    dtype = lay.pop("dtype", jnp.float32)
    if "plane_bytes" in lay:
        monkeypatch.setattr(PG, "_POS_PLANE_BYTES", lay.pop("plane_bytes"))
    rng = np.random.default_rng(7)
    L, p, hd, P = 2, lay["p"], lay["hd"], lay["P"]
    bp = (ppb or 128 // p) * p  # keys a block
    q_pos = ([0, p - 1, p, bp - 1, bp + 1, P * p - 1, 0] if ppb else
             [0, p - 1, bp - 1, bp, P * p - 1, 0])
    free = {len(q_pos) - 1}
    N = lay.get("N") or 2 + sum(
        min(P, qp // p + 2) for b, qp in enumerate(q_pos) if b not in free)
    k, v, pos, table = _live_pool(rng, L, N, p, nkv, hd, P, q_pos, free,
                                  shuffle=lay.get("shuffle", False), share=lay.get("share"))
    if "share" in lay:
        assert table[2, 0] == table[1, 0]
    q = jnp.asarray(rng.normal(size=(len(q_pos), nq, hd)), dtype)
    qp = jnp.asarray(q_pos, jnp.int32)
    layer = jnp.int32(1)
    stacks = [PKV.to_leaf(jnp.asarray(a, a.dtype if a.dtype == np.int32 else dtype), 1)
              for a in (k, v, pos)]
    cfg = tiny_cfg(attn=AttentionConfig(
        n_heads=nq, n_kv_heads=nkv, head_dim=hd, causal=causal, window=window))
    # the oracle in float32 on the operands' own values
    cache = {n: PKV.PagedLeaf(a.astype(jnp.float32) if n != "pos" else a,
                              jnp.asarray(table), layer)
             for n, a in zip(("k", "v", "pos"), stacks)}
    want = PKV.attend_paged(q[:, None].astype(jnp.float32), cache, qp[:, None], cfg)
    got = PG.paged_decode_attention(
        q, *stacks, jnp.asarray(table), layer, qp, causal=causal, window=window,
        pages_per_block=ppb, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got).reshape(want.shape), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_split_weights_sum_back_to_their_f32_values():
    """The live-page kernel weighs bf16 values with three bf16 parts of each
    f32 softmax weight: their sum must give the weight back to f32 rounding,
    or ``@ v`` would round the weights below f32."""
    from repro.kernels.paged import _split3

    rng = np.random.default_rng(3)
    e = np.concatenate([rng.random(4096), rng.random(256) * 1e-20, [0.0, 1.0]])
    e = jnp.asarray(e, jnp.float32)[None, None]
    parts = np.asarray(_split3(e).astype(jnp.float32))  # (1, 3, n)
    assert parts.shape == (1, 3, e.shape[-1])
    back = (parts[:, 0] + parts[:, 1]) + parts[:, 2]
    np.testing.assert_allclose(back, np.asarray(e)[:, 0], rtol=2.0**-24, atol=0)


def _decode_engine(B=3, **kw):
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, engine=EngineConfig(
        batch_size=B, ctx=16, page_size=4, prefill_chunk=4, **kw))
    rng = np.random.default_rng(11)
    for n, m in ((5, 6), (3, 9), (7, 4), (2, 7)):
        eng.submit(Request(tokens=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                           max_new_tokens=m))
    return cfg, eng


def _spy_steps(eng, check):
    """Wrap the engine's decode call: ``check(args, out)`` sees each step's
    inputs (taken before the call, which donates the pages) and outputs."""
    step_fn = eng._step_fn
    calls = []

    def spy(*args):
        # copies: a numpy view of a CPU buffer would pin it against donation
        before = tuple([np.array(a, copy=True) for a in args[i]] for i in (1, 3))
        out = step_fn(*args)
        check(args, before, out)
        calls.append(1)
        return out

    eng._step_fn = spy
    return calls


def test_inplace_decode_leaves_the_pages_of_materialize_and_writeback():
    """Step after step, the in-place row writes leave every page a request
    can read (all but SCRATCH) bit-equal to the materialise -> decode ->
    write-back sequence on the same inputs, with the same residual state and
    the same logits in every live row."""
    from repro.serve.cache import paged_materialize_q, paged_writeback_q

    cfg, eng = _decode_engine()
    spec = eng.pool.step_spec()

    @jax.jit
    def old(p, pages, resid, table, t, pos, act):
        caches = paged_materialize_q(spec, pages, [], resid, table)
        logits, new, _ = api.model_decode(p, caches, cfg, t, pos, act)
        return (logits,) + paged_writeback_q(spec, new, pages, [], table, pos)[:2]

    def check(args, before, out):
        p, _, _, _, table, t, pos, act = args
        pages0, resid0 = ([jnp.asarray(a) for a in x] for x in before)
        logits, pages, resid = old(p, pages0, resid0, table, t, pos, act)
        live = np.asarray(act)
        np.testing.assert_array_equal(np.asarray(out[0])[live], np.asarray(logits)[live])
        for got, want, ax in zip(out[1], pages, spec.paged_axes):
            np.testing.assert_array_equal(
                np.delete(np.asarray(got), SCRATCH_PAGE, axis=ax),
                np.delete(np.asarray(want), SCRATCH_PAGE, axis=ax))
        for got, want in zip(out[2], resid):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    calls = _spy_steps(eng, check)
    eng.run()
    assert len(calls) >= 8


def test_paged_step_donates_its_pages_and_keeps_the_residual_pool():
    """The decode step takes the pages (its writes land in the pool's own
    buffers) and leaves the residual leaves readable: callers such as a
    benchmark's recorder hold the MoD cursors from before a step."""
    _, eng = _decode_engine()
    seen = []

    def check(args, before, out):
        seen.append(1)
        assert all(a.is_deleted() for a in args[1])
        for a, b in zip(args[3], before[1]):
            assert not a.is_deleted()
            np.testing.assert_array_equal(np.asarray(a), b)

    _spy_steps(eng, check)
    for _ in range(6):
        eng.step()
    assert seen


def test_decode_live_page_share_counts_the_pages_the_step_reads():
    """stats()["decode_live_page_share"]: the mean over decode steps of the
    live pages (up to each slot's position, free slots one page) over B * P;
    every page where a quantized pool's step gathers the pool."""
    from repro.serve.quant import QuantConfig

    _, eng = _decode_engine()
    P, p = eng.pool.pages_per_slot, eng.pool.page_size
    shares = []

    def check(args, before, out):
        pos = np.asarray(args[6])
        shares.append(np.minimum(pos // p + 1, P).sum() / (pos.size * P))

    _spy_steps(eng, check)
    assert eng.stats()["decode_live_page_share"] == 0.0
    eng.run()
    share = eng.stats()["decode_live_page_share"]
    assert 0.0 < share < 1.0
    assert share == pytest.approx(float(np.mean(shares)))

    _, qeng = _decode_engine(quant=QuantConfig(kv="int8"))
    for _ in range(4):
        qeng.step()
    assert qeng.stats()["decode_live_page_share"] == 1.0


# ---------------------------------------------------------------------------
# Chunked prefill + prefix cache
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_unchunked_dense():
    """MoD off: per-chunk routing can't differ, so chunked prefill must
    reproduce the unchunked engine's greedy streams exactly."""
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32) for n in (9, 5, 11)]
    outs = {}
    for chunk in (None, 4):
        eng = ServingEngine(
            params, cfg, batch_size=2, ctx=24, page_size=4, prefill_chunk=chunk
        )
        for p in prompts:
            eng.submit(Request(tokens=p, max_new_tokens=5))
        outs[chunk] = {o.uid: o.full_sequence.tolist() for o in eng.run()}
    assert outs[None] == outs[4]


def test_chunked_prefill_mod_runs_and_fills_caches():
    """MoD on: routing is chunk-local (documented trade-off), but the
    engine must still produce valid streams, and chunk-size-1 sanity."""
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    p = np.random.default_rng(6).integers(0, cfg.vocab, size=7).astype(np.int32)
    for chunk in (1, 4):
        eng = ServingEngine(
            params, cfg, batch_size=1, ctx=16, page_size=4, prefill_chunk=chunk
        )
        eng.submit(Request(tokens=p, max_new_tokens=4))
        out = eng.run()[0]
        assert out.tokens.shape == (4,)
        assert (out.tokens >= 0).all() and (out.tokens < cfg.vocab).all()


def test_prefix_cache_identical_tokens_fewer_prefill_tokens():
    """Shared-prefix requests: the prefix cache must change nothing about
    the tokens (reuse restores the exact chunk-boundary state) while
    measurably cutting prefill compute, and page tables must share the
    prefix's physical pages across slots."""
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab, size=3).astype(np.int32)])
        for _ in range(4)
    ]
    outs, engines = {}, {}
    for prefix in (False, True):
        eng = ServingEngine(
            params, cfg, batch_size=2, ctx=24, page_size=4,
            prefill_chunk=4, prefix_cache=prefix,
        )
        for p in prompts:
            eng.submit(Request(tokens=p, max_new_tokens=5))
        outs[prefix] = {o.uid: o.full_sequence.tolist() for o in eng.run()}
        engines[prefix] = eng
    assert outs[False] == outs[True]
    cold = engines[False].stats()["prefill_tokens_computed"]
    warm = engines[True].stats()["prefill_tokens_computed"]
    assert warm < cold, (warm, cold)
    assert engines[True].stats()["prefix_hit_rate"] > 0.0


def test_prefix_cache_same_prompt_reuses_pages():
    """Submitting the same prompt twice sequentially: the second admission
    hits the chunk-aligned prefix and computes only the ragged tail."""
    cfg = tiny_cfg()
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    p = np.random.default_rng(8).integers(0, cfg.vocab, size=10).astype(np.int32)
    eng = ServingEngine(
        params, cfg, batch_size=1, ctx=16, page_size=4,
        prefill_chunk=4, prefix_cache=True,
    )
    eng.submit(Request(tokens=p, max_new_tokens=3))
    first = eng.run()[0]
    computed_first = eng.stats()["prefill_tokens_computed"]
    eng.submit(Request(tokens=p, max_new_tokens=3))
    second = eng.run()[1]
    computed_second = eng.stats()["prefill_tokens_computed"] - computed_first
    np.testing.assert_array_equal(first.tokens, second.tokens)
    # 10-token prompt, chunk 4 -> boundary at 8 cached; only 2 recomputed
    assert computed_first == 10 and computed_second == 2, (
        computed_first, computed_second)


# ---------------------------------------------------------------------------
# Admission gate + preemption
# ---------------------------------------------------------------------------


def test_page_exhaustion_preempts_youngest_back_to_queue():
    """Cross-wave overcommit (worst-case availability is checked, not
    reserved): when lazy growth exhausts the pool, the youngest slot is
    preempted with pages released, re-queued at the *front*, and the final
    streams still match the contiguous engine exactly (MoD off: admission
    pattern cannot couple rows)."""
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=4).astype(np.int32) for _ in range(2)]

    def reqs():
        return [Request(tokens=p, max_new_tokens=12) for p in prompts]

    # 6 allocatable pages; each request's worst case is 4 pages -> both
    # admitted a wave apart, combined growth hits the ceiling
    eng = ServingEngine(params, cfg, batch_size=2, ctx=16, page_size=4, n_pages=8)
    outs = {o.uid: o.full_sequence.tolist() for o in eng.run_stream(reqs(), 2)}
    assert eng.preemptions >= 1
    ref_eng = ServingEngine(params, cfg, batch_size=2, ctx=16)
    ref_outs = {o.uid: o.full_sequence.tolist() for o in ref_eng.run_stream(reqs(), 2)}
    assert outs == ref_outs
    # pool drained clean: nothing referenced after the last release
    assert eng.stats()["pages_in_use"] == 0.0
    eng.scheduler.check_invariants(eng.slots, len(outs))


def test_preemption_mid_chunked_prefill_resumes_bit_identical():
    """Preemption landing *mid-prompt*: the ragged engine ingests prompts
    one segment per step, so an older slot's lazy growth can exhaust the
    pool while a younger slot is still chunk-prefilling. The victim must
    requeue with its pages released and — on re-admission — produce a
    stream bit-identical to an uninterrupted run (prefill restarts from
    token 0, which recomputes the exact same caches)."""
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    pa = rng.integers(1, cfg.vocab - 1, size=4).astype(np.int32)
    pb = rng.integers(1, cfg.vocab - 1, size=14).astype(np.int32)

    def reqs():
        return [
            Request(tokens=pa, max_new_tokens=12),  # grows to 4 pages
            Request(tokens=pb, max_new_tokens=2),  # 4-step prefill, 4 pages
        ]

    def run(**kw):
        eng = ServingEngine(params, cfg, batch_size=2, ctx=32, page_size=4,
                            ragged=True, ragged_segments=1, **kw)
        victim_states = []
        orig = eng._preempt
        eng._preempt = lambda s: (victim_states.append(s.state), orig(s))[1]
        for r in reqs():
            eng.submit(r)
        outs = {o.uid: o.full_sequence.tolist() for o in eng.run()}
        return outs, eng, victim_states

    # 5 allocatable pages: A's lazy growth collides with B's 4th prefill
    # chunk at the step B would have completed its prompt
    outs, eng, victim_states = run(n_pages=7)
    assert eng.preemptions >= 1
    assert PREFILL in victim_states, "preemption never landed mid-prefill"
    ref_outs, ref_eng, ref_states = run()  # default pool: no pressure
    assert ref_eng.preemptions == 0 and not ref_states
    assert outs == ref_outs
    assert eng.stats()["pages_in_use"] == 0.0
    eng.scheduler.check_invariants(eng.slots, len(outs))


def test_admission_gate_blocks_oversized_and_transient_requests():
    """Worst-case page admission: a request that can *never* fit fails fast
    at submit (run() would otherwise spin to its step budget with an
    opaque error); one that fits but finds the pool busy waits queued."""
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    # 2 allocatable pages, request worst case = 4 pages -> impossible ever
    eng = ServingEngine(params, cfg, batch_size=1, ctx=16, page_size=4, n_pages=4)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(
            tokens=np.arange(8, dtype=np.int32) % cfg.vocab, max_new_tokens=8))
    # fits the pool's total but not while the first request holds it:
    # stays queued (head-of-line) until pages free, then completes
    eng2 = ServingEngine(params, cfg, batch_size=2, ctx=16, page_size=4, n_pages=6)
    a = Request(tokens=np.arange(4, dtype=np.int32), max_new_tokens=12)  # 4 pages
    b = Request(tokens=np.arange(4, dtype=np.int32), max_new_tokens=12)
    eng2.submit(a)
    eng2.step()
    eng2.submit(b)
    eng2.step()
    assert len(eng2.scheduler.queue) == 1  # gated while A runs
    outs = eng2.run()
    assert len(outs) == 2


# ---------------------------------------------------------------------------
# Pool accounting (host-side unit tests, no model)
# ---------------------------------------------------------------------------


def test_pool_page_accounting_and_prefix_eviction():
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    pool = PagedCachePool(cfg, batch_size=2, ctx=16, page_size=4, n_pages=8,
                          prefix_chunk=4)
    assert pool.available_pages() == 6
    pool.acquire(0)
    assert (pool.table_np[0] == NULL_PAGE).all()
    assert pool.alloc_pages(0, 9)  # 3 pages
    assert pool.available_pages() == 3
    assert int(pool.n_mapped[0]) == 3
    # register a 2-page (8-token) prefix; release keeps its pages cached
    toks = np.arange(12, dtype=np.int32)
    work = pool.read_slot(0)
    pool.prefix_register(0, toks, {4: pool.snapshot_resid(work),
                                   8: pool.snapshot_resid(work)})
    pool.release(0)
    assert (pool.table_np[0] == SCRATCH_PAGE).all()
    stats = pool.page_stats()
    assert stats["pages_in_use"] == 0 and stats["pages_cached_only"] == 2
    assert pool.available_pages() == 6  # cached pages are evictable
    # exhausting the free list evicts LRU prefix entries
    pool.acquire(0)
    assert pool.alloc_pages(0, 16)  # 4 pages: 4 free + evict
    pool.acquire(1)
    assert pool.alloc_pages(1, 8)  # remaining 2 via eviction
    assert not pool.alloc_pages(1, 12)  # nothing left anywhere
    assert pool.prefix_evictions >= 1
    pool.release(0)
    assert pool.alloc_pages(1, 12)


def test_pool_rejects_bad_geometry():
    cfg = tiny_cfg(mod=MoDConfig(enabled=False))
    with pytest.raises(ValueError):
        PagedCachePool(cfg, 2, 16, page_size=5)
    with pytest.raises(ValueError):
        PagedCachePool(cfg, 2, 16, page_size=4, prefix_chunk=6)
    with pytest.raises(ValueError):
        ServingEngine(None, cfg, 2, 16, prefix_cache=True)
