"""Compile-only checks of the served-path Pallas kernels for a TPU v5e.

Each test compiles one kernel (one, the engine's whole decode step) for a
*described* (not attached) v5e chip at the widths of ``mod-paper-1b``
(d_model 1792, 14 heads of 128, bf16):
Mosaic refuses here, in a second or two, what would otherwise fail on the
chip — block shapes off the (8, 128) tiling, stores at sublane offsets it
cannot prove aligned, more VMEM than a kernel may use. Nothing runs, so
these say nothing about results or speed; the interpret-mode tests hold
the kernels to their oracles.

The topology is described inside a module fixture, never at import, so
every pytest worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import paged as pg
from repro.kernels import ragged as rg
from repro.kernels import routing as rt
from repro.kernels import swiglu as sw

# mod-paper-1b widths (configs/mod_paper.py) and the serving shapes of
# chip_smoke.py: 8 slots, ctx 576 in 16-token pages, 12 layer groups
D, F, H, HD = 1792, 7168, 14, 128
B, S, K = 8, 2048, 256  # train-size routed stream, capacity 0.125 * S
GROUPS, PAGE, N_PAGES, PAGES_PER_SLOT = 12, 16, 8 * 36 + 2, 36
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from a persistent
    # cache, so keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not an XLA fallback


@pytest.mark.parametrize("batch,seq,k", [(B, S, K), (1, 512, 128)], ids=["train", "prefill"])
def test_routing_gather_scatter(one_chip, batch, seq, k):
    _compile(one_chip, lambda x, i: rt.gather_rows(x, i),
             ((batch, seq, D), BF16), ((batch, k), I32))
    _compile(one_chip, lambda x, i, d, g: rt.scatter_add_rows(x, i, d, g),
             ((batch, seq, D), BF16), ((batch, k), I32), ((batch, k, D), BF16),
             ((batch, k), F32))


KV_PAGES = ((GROUPS, N_PAGES, PAGE, H, HD), BF16)
POS_PAGES = ((GROUPS, N_PAGES, PAGE), I32)
TABLE = ((B, PAGES_PER_SLOT), I32)


@pytest.mark.parametrize("leaf", ["kv", "pos"])
def test_paged_gather_and_scatter(one_chip, leaf):
    pages = KV_PAGES if leaf == "kv" else POS_PAGES
    rows = ((GROUPS, B) + pages[0][3:], pages[1])
    # the leaf-shaped wrappers the pool calls; interpret=False because this
    # process's default backend is the CPU
    _compile(
        one_chip,
        lambda p, t: ops.paged_gather_op(p, t, page_axis=1, backend="pallas", interpret=False),
        pages, TABLE,
    )
    _compile(
        one_chip,
        lambda p, t, r, pos: ops.paged_scatter_rows_op(
            p, t, r, pos, page_axis=1, backend="pallas", interpret=False
        ),
        pages, TABLE, rows, ((B,), I32),
    )


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn], ids=["int8", "fp8"])
def test_paged_quantized(one_chip, dtype):
    Fc = GROUPS * H * HD
    _compile(one_chip, lambda p, s, t: pg.paged_gather_dequant_pallas(p, s, t),
             ((N_PAGES, PAGE, Fc), dtype), ((N_PAGES, PAGE, GROUPS * H), F32), TABLE)
    _compile(one_chip, lambda p, t, r, pos: pg.paged_scatter_rows_pallas(p, t, r, pos),
             ((N_PAGES, PAGE, Fc), dtype), TABLE, ((B, Fc), dtype), ((B,), I32))


def test_ragged_paged_scatter(one_chip):
    W = B + 4 * PAGE  # decode rows + 4 prefill segments of one page each
    _compile(one_chip, lambda p, pid, off, r: rg.ragged_paged_scatter_rows_pallas(p, pid, off, r),
             ((N_PAGES, PAGE, GROUPS * H * HD), BF16), ((W,), I32), ((W,), I32),
             ((W, GROUPS * H * HD), BF16))


def test_ragged_dispatch(one_chip):
    T, n_seg, k = 1024, 4, 32
    _compile(one_chip, lambda x, i: rg.ragged_gather_rows(x, i),
             ((T, D), BF16), ((n_seg, k), I32))
    _compile(one_chip, lambda x, i, d, g: rg.ragged_scatter_add_rows(x, i, d, g),
             ((T, D), BF16), ((n_seg, k), I32), ((n_seg, k, D), BF16), ((n_seg, k), F32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_ragged_attention(one_chip, quant):
    T, n_seg, C = 1024, 4, 256
    kv_dtype = jnp.int8 if quant else BF16
    shapes = [((T, H, HD), BF16), ((N_PAGES, PAGE, H, HD), kv_dtype),
              ((N_PAGES, PAGE, H, HD), kv_dtype), ((N_PAGES, PAGE), I32), TABLE,
              ((n_seg + 1,), I32), ((n_seg,), I32), ((T,), I32)]
    if quant:
        shapes += [((N_PAGES, PAGE, H), F32)] * 2

    def attn(*a):
        scales = dict(zip(("k_scales", "v_scales"), a[8:]))
        return rg.ragged_paged_flash_attention(*a[:8], seg_cap=C, **scales)

    _compile(one_chip, attn, *shapes)


def test_paged_decode_attention(one_chip):
    """The padded decode step's live-page read at the decode cell's size:
    16 slots, ctx 2048 in 16-token pages, 12 stacked layers, pool in the
    layout the step hands it (in-page axis next to the last)."""
    slots, P = 16, 2048 // PAGE
    N = slots * P + 2
    _compile(one_chip, lambda *a: pg.paged_decode_attention(*a),
             ((slots, H, HD), BF16), ((GROUPS, N, H, PAGE, HD), BF16),
             ((GROUPS, N, H, PAGE, HD), BF16), ((GROUPS, PAGE, N), I32),
             ((slots, P), I32), ((), I32), ((slots,), I32))


def test_paged_decode_step_reads_the_pool_in_place(one_chip):
    """The engine's padded paged decode step compiled for the chip: the
    live-page kernel reads the pool through the table, and no full ring
    ``(B, ctx, nkv, hd)`` nor stacked ``(G, B, ctx, ...)`` K/V buffer is
    built."""
    from repro.models import api
    from repro.serve import EngineConfig, ServingEngine
    from tests.helpers import tiny_cfg

    cfg = tiny_cfg()
    slots, ctx = 4, 32
    eng = ServingEngine(api.init_model(jax.random.PRNGKey(0), cfg), cfg,
                        engine=EngineConfig(batch_size=slots, ctx=ctx, page_size=4,
                                            prefill_chunk=4))
    pool = eng.pool
    args = (eng.params, pool.pages, pool.scales, pool.resid, pool.device_table(),
            jnp.zeros((slots, 1), I32), jnp.zeros((slots,), I32), jnp.ones((slots,), bool))
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args)
    text = eng._step_fn.lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    nkv, hd, G = cfg.attn.n_kv_heads, cfg.head_dim, cfg.n_layers // 2
    assert f"[{slots},{ctx},{nkv},{hd}]" not in text
    assert f"[{G},{slots},{ctx}," not in text


def test_fused_dispatch_refuses_to_compile(one_chip):
    """pallas_fused's kernels run only in interpret mode; asked to compile
    for the chip they raise the clear error, not an opaque Mosaic one."""
    spec = fa.RoutedAttnSpec(
        n_heads=H, n_kv_heads=H, head_dim=HD, scale=HD**-0.5, causal=True, window=0,
        rope_theta=1e4, pos_emb="rope", eps=1e-5, block_k=128, interpret=False,
    )
    x = jax.ShapeDtypeStruct((1, 512, D), BF16, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((1, 128), I32, sharding=one_chip)
    attn = {k: jax.ShapeDtypeStruct((D, D), BF16, sharding=one_chip)
            for k in ("wq", "wk", "wv", "wo")}
    attn["ln"] = jax.ShapeDtypeStruct((D,), BF16, sharding=one_chip)
    with pytest.raises(NotImplementedError, match="pallas_fused"):
        jax.jit(lambda *a: fa.routed_attention(*a, spec)).lower(x, idx, idx, attn)
    mspec = sw.RoutedMlpSpec(act="silu", eps=1e-5, block_s=256, interpret=False)
    sub = jax.ShapeDtypeStruct((1, 128, D), BF16, sharding=one_chip)
    gate = jax.ShapeDtypeStruct((1, 128), F32, sharding=one_chip)
    mlp = {"ln": attn["ln"], "w_up": jax.ShapeDtypeStruct((D, F), BF16, sharding=one_chip),
           "w_gate": jax.ShapeDtypeStruct((D, F), BF16, sharding=one_chip),
           "w_down": jax.ShapeDtypeStruct((F, D), BF16, sharding=one_chip)}
    with pytest.raises(NotImplementedError, match="pallas_fused"):
        jax.jit(lambda *a: sw.routed_mlp_scatter(*a, mspec)).lower(
            x, sub, sub, idx, gate, mlp
        )
