"""Continuous-batching serving engine over the MoD routing engine.

The engine drives a single jitted decode step of fixed shape ``(B, 1)``
against one pooled ``(B, ctx)`` cache (:class:`repro.serve.cache.CachePool`)
and keeps that batch full by admitting queued requests into slots as other
requests terminate — the scheduler/slot machinery lives in
:mod:`repro.serve.scheduler`. Shapes never change after the first step, so
the decode step compiles exactly once no matter how requests arrive, finish,
or interleave (asserted in ``tests/test_serve.py``).

Prefill/decode interleaving
---------------------------
Two admission paths, chosen per family (``prefill="auto"``):

- **batched prefill** (dense / MoE): the prompt runs through the jitted
  ``model_prefill`` once (token_topk MoD routing, capacity-sized cache
  writes), the resulting batch-1 cache is scattered into the slot, and the
  first new token is sampled from the prefill's last-position logits — the
  last prompt token is *not* re-decoded.
- **stepped ingestion** (SSM / hybrid / enc-dec / VLM): the slot feeds one
  prompt token per engine step through the shared decode step, interleaved
  with other slots' decode traffic. Ingesting slots compete with decoding
  slots for the ``batch_capacity`` router's ``kb`` routed rows — which is
  what the ``mod_aware`` scheduling policy budgets for.

MoD-awareness
-------------
Every step the engine passes an ``active`` mask so padding rows never win
routed capacity (see ``core/routing.decide_batch``), and reads back the
per-sequence ``mod/decode_scores`` / ``mod/decode_routed`` telemetry that
``decode_aux`` surfaces — per-request routed fractions land in
:class:`repro.serve.request.RequestOutput`, and the scheduler uses the
router's kb as its prefill-admission budget.

Sampling is host-side: greedy argmax, or per-request
``fold_in(key, token_index)`` categorical sampling — deterministic per
request regardless of batch composition. The (B, V) logits round-trip to
host once per step; at smoke scale that is noise, on an accelerator you
would fold sampling into the step.

Paged serving
-------------
``ServingEngine(page_size=...)`` swaps the contiguous pool for the
block-paged :class:`repro.serve.cache.PagedCachePool`: full-attention KV
lives in refcounted pages mapped lazily as sequences grow, admission is
page-aware (worst-case availability), pool exhaustion preempts the
youngest slot back to the queue front, ``prefill_chunk`` ingests dense/MoE
prompts in fixed-shape pieces, and ``prefix_cache=True`` reuses
chunk-aligned shared prompt prefixes (pages + residual-state snapshot)
bit-identically to a cold run. The decode step remains a single jitted
fixed-shape function, and it works on the pages in place: each
full-attention layer writes its new rows into the slots' tail pages and
reads their live pages through the page table (models/paged_kv.py); the
step takes the pages donated. Quantized pools still gather the pool into
a logical cache (materialize) and scatter rows back (writeback) inside
the step (DESIGN.md §Serving engine).

SPMD serving
------------
``ServingEngine(mesh=...)`` drives the same engine multi-device: params
are placed per ``distributed.sharding`` rules, the ``CachePool`` is
batch-sharded over the mesh's data axes, decode inputs are placed
batch-sharded each step, and ``batch_capacity`` routing runs shard-locally
with the partitioned semantics (top ``round(ratio·B/d)`` per shard group —
DESIGN.md §SPMD routed execution). The scheduler budget becomes the global
``d·round(ratio·B/d)``. ``ServingEngine(data_shards=d)`` without a mesh
runs identical routing semantics on one device; the SPMD tests pin the two
token-for-token.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core.routing import batch_capacity_k, capacity_ladder
from repro.serve.cache import (
    CachePool,
    PagedCachePool,
    inplace_decode,
    paged_collect_rows,
    paged_materialize_q,
    paged_rings,
    paged_scatter_rows_q,
    paged_split,
    paged_writeback_q,
    paged_writeback_tokens_q,
    quant_roundtrip,
    slot_slice,
    slot_update,
)
from repro.models import api
from repro.serve.config import EngineConfig
from repro.serve.quant import dequantize_params, quantize_params
from repro.serve.overload import CapacityController, EngineOverloaded, default_levels
from repro.serve.request import (
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_EXPIRED,
    FINISH_LENGTH,
    PRIORITY_LATENCY,
    Request,
    RequestOutput,
    pad_outputs,
)
from repro.serve.scheduler import FREE, GENERATE, PREFILL, Scheduler, Slot

# Families whose prompts can run through model_prefill in one shot. VLM is
# excluded: its prefill path expects pre-merged embeddings + M-RoPE position
# ids, while stepped decode builds them internally.
_BATCH_PREFILL_FAMILIES = ("dense", "moe")

# Jitted step/prefill functions shared across engine instances with the same
# config (ModelConfig is frozen/hashable), so tearing an engine down and
# building another — per sweep point in benchmarks/serving.py, per call in
# greedy_generate — reuses compiled executables instead of re-tracing.
# Bounded LRU: benchmark sweeps mint one entry per (cfg, ctx)/(cfg, spmd)
# key forever, so an unbounded dict leaks executables across long sweeps.
# Evicting only drops the cache's reference — live engines keep their own.
# Chunked prefill traces per fixed chunk size (not per prompt length), so
# prompt-length diversity can't mint entries either.
_JIT_CACHE: "OrderedDict[Any, Callable]" = OrderedDict()
_JIT_CACHE_MAX = 32

# Host spans of one engine step, on the profiler's clock beside the device's
# ops (``serve.admit``, ``serve.prefill``, ``serve.pages``, ``serve.decode``,
# ``serve.logits_to_host``, ``serve.sample``, ``serve.invariants``). Outside
# a trace each costs about a microsecond.
_span = jax.profiler.TraceAnnotation


def _cached_jit(kind: str, key: Any, make: Callable[[], Callable],
                donate_argnums: Tuple[int, ...] = ()) -> Callable:
    from repro.serve.cache import lru_cached

    return lru_cached(
        _JIT_CACHE, (kind, key),
        lambda: jax.jit(make(), donate_argnums=donate_argnums), _JIT_CACHE_MAX,
    )


# One process-wide deprecation notice for legacy ServingEngine(**kwargs)
# construction — every test/benchmark that still uses the old surface would
# otherwise print it per engine build.
_WARNED_LEGACY_KWARGS = False


def _warn_legacy_kwargs() -> None:
    global _WARNED_LEGACY_KWARGS
    if not _WARNED_LEGACY_KWARGS:
        _WARNED_LEGACY_KWARGS = True
        warnings.warn(
            "ServingEngine(batch_size=..., ctx=..., **kwargs) is deprecated; "
            "pass ServingEngine(params, cfg, engine=EngineConfig(...)) "
            "(repro.serve.EngineConfig) instead",
            DeprecationWarning,
            stacklevel=3,
        )


def _decode_aux_to_host(aux: Dict[str, Any]):
    """A decode step's routing telemetry on the host: per-row routed shares,
    per-row scores and the routed fraction, each None where the step
    reports none."""
    return tuple(None if aux.get(k) is None else np.asarray(aux[k])
                 for k in ("mod/decode_routed", "mod/decode_scores",
                           "mod/decode_routed_frac"))


class _PoolExhausted(RuntimeError):
    """Internal: a gate-passed admission lost its pages (e.g. another
    admission in the same wave evicted the prefix entry its page discount
    relied on). Caught in _admit, which unwinds the admission gracefully."""


def routed_capacity(
    cfg: ModelConfig, batch_size: int, data_shards: int = 1
) -> Optional[int]:
    """*Global* kb of the batch_capacity router
    (core/routing.batch_capacity_k); None when MoD is off.

    Under a batch-sharded pool each of the ``data_shards`` shard groups
    routes ``round(ratio·B/d)`` of its own slots, so the global budget the
    scheduler must count against is the sum over shards — NOT
    ``round(ratio·B)`` (e.g. B=8, d=4, ratio=0.125 routes 4 slots per step,
    not 1, because every shard routes at least one row)."""
    if not cfg.mod.enabled:
        return None
    return batch_capacity_k(cfg, batch_size, data_shards)


class ServingEngine:
    """Continuous-batching decode over a fixed (batch_size, ctx) pool."""

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        batch_size: Optional[int] = None,
        ctx: Optional[int] = None,
        *,
        engine: Optional[EngineConfig] = None,
        **kwargs: Any,
    ):
        """The canonical surface is ``ServingEngine(params, cfg,
        engine=EngineConfig(...))`` — every model-independent setting
        lives on the frozen :class:`repro.serve.config.EngineConfig`
        (validated at construction). Legacy keyword construction
        (``batch_size=..., ctx=..., page_size=..., ...``) still works: the
        kwargs build the same EngineConfig internally, with a one-time
        DeprecationWarning. Mixing both forms is an error.

        ``mesh`` makes the engine multi-device: params are placed per the
        sharding rules, the cache pool is batch-sharded over the mesh's data
        axes, and the decode step routes ``batch_capacity`` shard-locally
        (DESIGN.md §SPMD routed execution). ``data_shards`` without a mesh
        runs the *same partitioned routing semantics* on one device — the
        reference configuration the SPMD tests compare token streams
        against. With both given they must agree.

        ``page_size`` switches the engine to the block-paged KV pool
        (:class:`repro.serve.cache.PagedCachePool`): full-attention KV
        lives in refcounted pages allocated lazily as sequences grow,
        admission is page-aware (worst-case page availability), pool
        exhaustion preempts the youngest slot back to the queue, and —
        with ``prefix_cache`` — chunk-aligned prompt prefixes are reused
        across requests. ``prefill_chunk`` caps how much prompt one
        admission ingests per jitted call (fixed-shape chunks, so the
        retrace cache can't grow with prompt-length diversity); prefix
        caching requires it page-aligned and defaults it to ``page_size``.
        Token streams are bit-identical to the contiguous pool at equal
        prefill settings (tests/test_paged.py).

        ``ragged=True`` (paged, dense/MoE only) replaces the two separate
        jitted entry points — per-admission chunked prefill plus the (B, 1)
        decode step — with ONE jitted mixed step per engine step: up to
        ``ragged_segments`` fixed-size prefill segments (each a
        ``prefill_chunk``-token slice of some slot's prompt, several
        consecutive segments per slot allowed) run as a flat token stream
        alongside the decode rows, and a single ragged write-back scatters
        every produced KV row into the pool's pages. Admission is budgeted
        by free segment tokens rather than free slots, prompts no longer
        stall decode (no off-path prefill calls), and token streams stay
        bit-identical to the padded engine (tests/test_serve_ragged.py).
        DESIGN.md §Serving engine, "Flat-token layout".

        ``speculate=n`` (paged, dense/MoE only) switches decode to
        self-speculative rounds: one jitted step drafts ``n`` tokens per
        slot with the model itself at ``mod.capacity_ratio=draft_ratio``
        (0.0 = the pure residual-skip path — no second model, no extra
        weights), then verifies the window with ``n+1`` full-capacity
        decode steps batched into the same call. The host accepts the
        longest prefix on which its sampled tokens agree with the drafts
        (capped batch-globally so composition stays aligned), rolls the
        rejected tail back by truncating page tables
        (``PagedCachePool.truncate``) and restoring the in-window
        residual snapshot, and advances up to ``n+1`` tokens per
        host↔device round trip. Greedy streams are bit-identical to
        ``speculate=None`` under upfront submission
        (tests/test_speculative.py). ``spec_verify_budget`` caps
        admissions so active slots × (n+1) verify positions never exceed
        it. DESIGN.md §Self-speculative decoding.

        ``adaptive_capacity=True`` arms the overload controller
        (:class:`repro.serve.overload.CapacityController`): under sustained
        queue/latency pressure the engine walks down a discrete, bounded
        ladder of MoD capacity levels (``capacity_levels`` scales, default
        full/half/quarter) — each level exactly one lazily-compiled decode
        step — and shrinks the batch-tier admission budget by the same
        factor. ``latency``-priority requests are exempt: any step with a
        latency-tier slot active decodes at level 0 and their admissions
        bypass the degraded budget. ``max_queue`` bounds the queue
        (``submit`` raises :class:`EngineOverloaded` instead of queueing
        unboundedly); ``fault_injector`` threads a scheduled fault matrix
        through the step (detection/containment are always on, injector or
        not); ``clock`` overrides the deadline clock (``time.monotonic``)
        — benchmarks pass a step-counting clock for determinism.
        DESIGN.md §Overload control.

        ``EngineConfig.quant`` (a :class:`repro.serve.quant.QuantConfig`)
        stores the paged pool's full-attention K/V pages in int8/fp8 with
        per-page-row pow2 scales, dequantized inside the gather/attention
        kernels (DESIGN.md §Quantized KV); ``quant.weights="int8"``
        additionally serves from int8 parameters dequantized at step
        entry."""
        if engine is not None:
            if batch_size is not None or ctx is not None or kwargs:
                raise ValueError(
                    "pass either engine=EngineConfig(...) or legacy "
                    "batch_size/ctx keyword arguments, not both"
                )
            ecfg = engine
        else:
            _warn_legacy_kwargs()
            ecfg = EngineConfig(batch_size=batch_size, ctx=ctx, **kwargs)
        self.engine_config = ecfg
        batch_size, ctx = ecfg.batch_size, ecfg.ctx
        policy, prefill = ecfg.policy, ecfg.prefill
        mesh, data_shards = ecfg.mesh, ecfg.data_shards
        page_size, n_pages = ecfg.page_size, ecfg.n_pages
        prefix_cache, prefill_chunk = ecfg.prefix_cache, ecfg.prefill_chunk
        paged_backend = ecfg.paged_backend
        ragged, ragged_segments = ecfg.ragged, ecfg.ragged_segments
        speculate, draft_ratio = ecfg.speculate, ecfg.draft_ratio
        spec_verify_budget = ecfg.spec_verify_budget
        adaptive_capacity = ecfg.adaptive_capacity
        capacity_levels = ecfg.capacity_levels
        capacity_controller = ecfg.capacity_controller
        max_queue, fault_injector = ecfg.max_queue, ecfg.fault_injector
        clock = ecfg.clock
        self.quant = ecfg.quant if ecfg.quant.enabled else None
        self._logit_tap = ecfg.logit_tap
        from repro.distributed.sharding import shard_ctx

        self.mesh = mesh
        self.spmd = (
            shard_ctx(mesh, data_shards) if (mesh is not None or data_shards) else None
        )
        if self.spmd is not None:
            self.spmd.check_batch(batch_size)
        shards = self.spmd.data_shards if self.spmd is not None else 1
        if mesh is not None:
            from jax.sharding import NamedSharding

            from repro.config import MeshConfig
            from repro.distributed.sharding import param_shardings

            mcfg = MeshConfig(
                pod=1, data=shards, model=self.spmd.model_shards, fsdp=False
            )
            params = jax.device_put(params, param_shardings(params, mesh, mcfg))
            # decode-step inputs are placed every step (tokens (B,1),
            # pos/active (B,)) — build their shardings once, not per step
            self._input_shardings = {
                nd: NamedSharding(mesh, self.spmd.data_spec(nd)) for nd in (1, 2)
            }
        if ecfg.quant.weights == "int8":
            if mesh is not None:
                raise NotImplementedError(
                    "weight quantization + SPMD mesh: the narrow tree "
                    "needs its own sharding rules"
                )
            # serve from int8 weights: every jitted entry point dequantizes
            # at trace time (quant.dequantize_params — identity on
            # unquantized trees), so the fp32 copy is never resident
            params = quantize_params(params)
        self.params = params
        self.cfg = cfg
        self.batch_size = batch_size
        self.ctx = ctx

        self._batch_prefill = (
            prefill == "batch"
            or (prefill == "auto" and cfg.family in _BATCH_PREFILL_FAMILIES)
        )
        if self._batch_prefill and cfg.family not in _BATCH_PREFILL_FAMILIES:
            raise ValueError(f"family {cfg.family!r} has no batched prefill")

        self._paged = page_size is not None
        if prefill_chunk is not None and not self._batch_prefill:
            raise ValueError(
                "prefill_chunk applies to batched-prefill families (dense/MoE); "
                f"family {cfg.family!r} ingests prompts through decode steps"
            )
        if prefix_cache:
            if not self._batch_prefill:
                raise ValueError("prefix_cache requires a batched-prefill family")
            if prefill_chunk is None:
                prefill_chunk = page_size  # page-aligned boundaries by default
        if self._paged and mesh is not None:
            raise NotImplementedError("paged pool + SPMD mesh: shard the pages")
        self._ragged = ragged
        self._ragged_segments = int(ragged_segments)
        if ragged:
            if not self._batch_prefill:
                raise ValueError(
                    "ragged=True needs a batched-prefill family (dense/MoE): "
                    "prefill segments replay model_prefill_chunk inside the step"
                )
            if mesh is not None or data_shards:
                raise NotImplementedError(
                    "ragged mixed step + SPMD mesh/data_shards"
                )
            if prefill_chunk is None:
                prefill_chunk = page_size
        self._speculate = None if speculate is None else int(speculate)
        self._draft_ratio = float(draft_ratio)
        if self._speculate is not None:
            if not self._batch_prefill:
                raise ValueError(
                    "speculate needs a batched-prefill family (dense/MoE): "
                    "stepped prompt ingestion would draft prompt tokens"
                )
            if not cfg.attn.causal:
                raise ValueError(
                    "speculate requires causal attention: rolled-back rows "
                    "inside the last kept page are hidden by the causal mask "
                    "until the accepted stream overwrites them"
                )
            if mesh is not None or data_shards:
                raise NotImplementedError("speculative rounds + SPMD mesh/data_shards")
        self._prefix_cache = prefix_cache
        self._prefill_chunk = prefill_chunk

        if self._paged:
            self.pool: Any = PagedCachePool(
                cfg, batch_size, ctx, page_size,
                n_pages=n_pages,
                prefix_chunk=prefill_chunk if prefix_cache else None,
                backend=paged_backend,
                quant=self.quant,
            )
        else:
            self.pool = CachePool(cfg, batch_size, ctx, mesh=mesh)
        self._inplace_decode = self._paged and inplace_decode(self.pool.step_spec())
        self.scheduler = Scheduler(
            batch_size, policy, routed_capacity(cfg, batch_size, shards),
            verify_token_budget=spec_verify_budget,
            max_queue=max_queue,
        )
        self.slots = [Slot(i) for i in range(batch_size)]
        self.finished: List[RequestOutput] = []
        self.step_count = 0
        self.generated_tokens = 0
        self.preemptions = 0  # mid-generation evictions (pages exhausted)
        self.admission_aborts = 0  # gate-passed admissions unwound pre-batch
        self._prefill_tokens_computed = 0
        # fixed-shape steps always compute full (B·1 / segment-grid) token
        # grids; these two split the grid into real vs padding positions so
        # stats() can report padded_token_fraction — the batching-overhead
        # number the ragged layout exists to shrink
        self._positions_computed = 0
        self._positions_wasted = 0
        self._routed_frac_sum = 0.0
        self._routed_frac_steps = 0
        # padded paged decode: share of the page table the attention reads
        # (live pages over B * P), summed per decode step
        self._live_page_share_sum = 0.0
        self._live_page_steps = 0
        self._occupancy_sum = 0
        # speculative telemetry: accept rate = accepted draft tokens over
        # drafted tokens (the MoD "confident tokens need less depth" signal)
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted_drafts = 0
        self._spec_emitted = 0
        self._uid = 0
        self._used_uids: set = set()
        self._wall_s = 0.0

        # -- overload control / robustness ------------------------------
        self._clock = clock if clock is not None else time.monotonic
        self._faults = fault_injector
        adaptive = adaptive_capacity or capacity_controller is not None
        if adaptive and self._speculate is not None:
            raise NotImplementedError(
                "adaptive_capacity + speculate: a speculative round already "
                "runs two capacity ratios; composing the ladder with the "
                "rollback machinery is future work"
            )
        if adaptive and (mesh is not None or data_shards):
            raise NotImplementedError("adaptive_capacity + SPMD mesh/data_shards")
        scales = (
            tuple(float(x) for x in capacity_levels)
            if capacity_levels is not None
            else default_levels()
        )
        # validates the ladder shape even when MoD is off (dense engines
        # still degrade their host-side admission budgets by the scales)
        self._level_cfgs = capacity_ladder(cfg, scales) if adaptive else (cfg,)
        self._capacity_scales = scales if adaptive else (1.0,)
        if adaptive:
            self._controller = capacity_controller or CapacityController(
                n_levels=len(scales),
                queue_high=2 * batch_size,
                queue_low=max(1, batch_size // 2),
            )
        else:
            self._controller = None
        # monotone robustness counters (stats() — always present)
        self._degraded_decode_steps = 0
        self.last_step_level = 0  # ladder level of the most recent decode step
        self._n_shed = 0
        self._n_expired = 0
        self._n_cancelled = 0
        self._n_failed = 0

        # The decode step every slot shares lives in _build_step_fn so the
        # capacity ladder can mint one compiled step per level; level 0
        # (the full config) is built eagerly here.
        self._paged_backend = paged_backend
        if self._ragged:
            self._ragged_spec = self.pool.step_spec()
        self._step_fn = self._build_step_fn(cfg)
        # capacity ladder: one compiled step per level, minted lazily on
        # first degraded step; level cfgs only shrink the router's kb (no
        # decode shape depends on capacity_ratio), so pool state built
        # under the full cfg stays valid at every level
        self._level_fns: Dict[int, Callable] = {0: self._step_fn}
        self._spec_fn = None
        if self._speculate is not None:
            pspec = self.pool.step_spec()
            n_spec = self._speculate
            draft_cfg = dataclasses.replace(
                cfg, mod=dataclasses.replace(cfg.mod, capacity_ratio=self._draft_ratio)
            )

            # When the drafter is the verifier (dense family, or draft
            # ratio == the engine ratio) the two-pass shape would run the
            # same model twice over the same window — fuse draft+verify
            # into one autoregressive scan (n+1 model steps per round
            # instead of 2n+1, bit-identical by construction).
            fused = (not cfg.mod.enabled
                     or self._draft_ratio == cfg.mod.capacity_ratio)
            # positions the round's fixed grid computes per batch row
            # (padded_token_fraction accounting)
            self._spec_grid = (n_spec + 1) if fused else (2 * n_spec + 1)

            def _make_spec_step():
                # One fixed-shape speculative round: materialize once, draft
                # n tokens cheaply, verify the n+1-token window at full
                # capacity, and hand the host everything its accept loop
                # needs — per-step logits, per-step residual snapshots (the
                # rollback restore point), and every step's KV rows for one
                # ragged page scatter. Rows for rejected positions land on
                # mapped lookahead pages as stale-but-causally-masked data;
                # truncate() releases the tail after the host picks the
                # acceptance point.
                def step(p, pages, scales, resid, table, t, pos, act, limit):
                    p = dequantize_params(p)
                    caches0 = paged_materialize_q(pspec, pages, scales, resid, table)

                    post_step = None
                    if pspec.quant is not None:
                        # quantized pool: after each in-window step's own
                        # attention (which sees its fresh full-precision
                        # row, exactly like a plain decode step), the row
                        # at p_step round-trips through the narrow dtype —
                        # so step k+1 attends to what a plain engine would
                        # have re-materialized from its pages. Positions
                        # past ctx match nothing (no-op), and collect runs
                        # after this, so the scattered rows re-quantize to
                        # identical bits (pow2 idempotency).
                        ctx_len = table.shape[1] * pspec.page_size

                        def post_step(c2, p_step):
                            m = (
                                jnp.arange(ctx_len, dtype=jnp.int32)[None, :]
                                == p_step[:, None].astype(jnp.int32)
                            )
                            return quant_roundtrip(pspec, c2, m)

                    def collect(c2, p_step):
                        rows = paged_collect_rows(pspec, c2, p_step)
                        leaves = jax.tree_util.tree_leaves(c2)
                        res = tuple(leaves[i] for i in pspec.resid_ids)
                        return (tuple(rows), res)

                    if fused:
                        drafts, logits, aux, (rows, resids) = (
                            api.model_fused_window(
                                p, cfg, caches0, t, pos, act, n_spec,
                                collect=collect, post_step=post_step,
                            )
                        )
                    else:
                        drafts = api.model_draft_window(
                            p, draft_cfg, caches0, t, pos, act, n_spec
                        )
                        feed = jnp.concatenate([t[:, 0][None], drafts], axis=0)
                        logits, aux, (rows, resids) = api.model_verify_window(
                            p, cfg, caches0, feed, pos, act,
                            collect=collect, post_step=post_step,
                        )
                    B = pos.shape[0]
                    offs = jnp.arange(n_spec + 1, dtype=jnp.int32)
                    w_slot = jnp.tile(jnp.arange(B, dtype=jnp.int32), n_spec + 1)
                    w_pos = (pos[None, :].astype(jnp.int32) + offs[:, None]).reshape(-1)
                    # ``limit`` = each slot's mapped-token extent
                    # (min(total_len, ctx)): verify positions past a slot's
                    # own budget have no page mapped — the accept cap
                    # discards their tokens, and masking them here keeps
                    # the scatter off the NULL page
                    w_valid = (
                        act[None, :] & (pos[None, :] + offs[:, None] < limit[None, :])
                    ).reshape(-1)
                    # merge the (step, slot) axes of each collected row
                    # stack into the scatter's flat row dim (index s·B + b)
                    flat_rows = [
                        jnp.moveaxis(r, 0, ax).reshape(
                            r.shape[1 : ax + 1] + (-1,) + r.shape[ax + 2 :]
                        )
                        for r, ax in zip(rows, pspec.paged_axes)
                    ]
                    new_pages, new_scales = paged_scatter_rows_q(
                        pspec, flat_rows, pages, scales, table,
                        w_slot, w_pos, w_valid
                    )
                    return drafts, logits, resids, new_pages, new_scales, aux

                return step

            self._spec_fn = _cached_jit(
                "spec_step",
                (cfg, self._draft_ratio, n_spec, ctx, page_size,
                 self.pool.n_pages, paged_backend, self.pool.quant),
                _make_spec_step,
            )
            self._spec_spec = pspec
        # Batch-1 prefill; retraced per distinct prompt length only.
        self._prefill_fn = _cached_jit(
            "prefill", (cfg, ctx),
            lambda: lambda p, toks: api.model_prefill(
                dequantize_params(p), cfg, {"tokens": toks}, ctx
            ),
        )
        if prefill_chunk is not None:
            # fixed (1, chunk) shape + traced start/length scalars: exactly
            # one trace per (cfg, ctx, chunk) no matter the prompt mix
            qspec = (
                self.pool.step_spec()
                if self._paged and self.pool.quant is not None
                else None
            )

            def _make_chunk():
                def chunk(p, c, toks, start, nv):
                    p = dequantize_params(p)
                    lg, new_c = api.model_prefill_chunk(p, cfg, c, toks, start, nv)
                    if qspec is not None:
                        # chunk-boundary round trip: the rows this chunk
                        # wrote go through the narrow dtype now, so the
                        # next chunk attends to exactly what a prefix-cache
                        # warm restore would read back from the pool's
                        # quantized pages (cache.quant_roundtrip docstring)
                        j = jnp.arange(ctx, dtype=jnp.int32)
                        m = ((j >= start) & (j < start + nv))[None, :]
                        new_c = quant_roundtrip(qspec, new_c, m)
                    return lg, new_c

                return chunk

            self._chunk_fn = _cached_jit(
                "prefill_chunk",
                (cfg, ctx, prefill_chunk,
                 self.pool.quant if self._paged else None),
                _make_chunk,
            )
        if cfg.family == "encdec":
            from repro.models import encdec as ED

            self._cross_fn = _cached_jit(
                "cross", (cfg, ctx),
                lambda: lambda p, c, e: ED.prefill_cross(
                    dequantize_params(p), c, e, cfg
                ),
            )
        self._step_signatures0 = self._step_signatures()

    # ------------------------------------------------------------------
    # Step-function construction (per capacity-ladder level)
    # ------------------------------------------------------------------

    def _build_step_fn(self, cfg: ModelConfig) -> Callable:
        """Build (or fetch from the shared jit cache) the decode step for
        one ``cfg``. Called once at construction with the full config, and
        lazily per capacity-ladder level with that level's reduced
        ``capacity_ratio`` cfg (``core/routing.capacity_ladder``) — levels
        change only the router's kb, never a shape, so every level drives
        the same pool state and jax compiles each exactly once. In the
        ragged mixed step only the *decode* rows degrade: prefill segments
        always run the full config (``self.cfg``), because chunk
        boundaries become cached/restorable state — ingesting a prompt at
        reduced capacity would poison it non-restorably."""
        spmd = self.spmd
        if self._ragged:
            spec = self._ragged_spec
            pf_cfg = self.cfg  # prefill segments never degrade
            C = self._prefill_chunk
            S = self._ragged_segments
            ctx_len = self.ctx

            def _make_ragged_step():
                # One fixed-shape mixed step. Inputs beyond the decode
                # triple: a flat (S·C,) prefill token stream plus per-segment
                # (slot, start, len, flat-offset) descriptors; dead segments
                # carry len 0 and are exact no-ops on the caches (masked
                # chunk positions never write — tests/test_serve_ragged.py).
                def step(p, pages, scales, resid, table, dec_t, dec_pos,
                         dec_act, pf_tokens, seg_slot, seg_start, seg_len,
                         seg_off):
                    p = dequantize_params(p)
                    caches = paged_materialize_q(spec, pages, scales, resid, table)
                    T = pf_tokens.shape[0]
                    # logits aval of one chunk call — the dead branch of the
                    # per-segment cond must return the exact shape/dtype
                    lg_aval = jax.eval_shape(
                        lambda c: api.model_prefill_chunk(
                            p, pf_cfg, slot_slice(spec, c, jnp.int32(0)),
                            jnp.zeros((1, C), jnp.int32),
                            jnp.int32(0), jnp.int32(0),
                        )[0],
                        caches,
                    )

                    def seg_body(carry, xs):
                        slot, start, ln, off = xs
                        j = jnp.arange(C, dtype=jnp.int32)
                        chunk = jnp.where(
                            j < ln, jnp.take(pf_tokens, jnp.clip(off + j, 0, T - 1)), 0
                        )[None]

                        def live(c):
                            sub = slot_slice(spec, c, slot)
                            lg, new_sub = api.model_prefill_chunk(
                                p, pf_cfg, sub, chunk, start, ln
                            )
                            if spec.quant is not None:
                                # quantization boundary: each ingested chunk
                                # round-trips through the narrow dtype, so a
                                # ragged prefill is bit-identical to the
                                # padded chunked path (and to a prefix-cache
                                # warm restore, which reads back quantized
                                # pages)
                                jq = jnp.arange(ctx_len, dtype=jnp.int32)
                                m = ((jq >= start) & (jq < start + ln))[None]
                                new_sub = quant_roundtrip(spec, new_sub, m)
                            # per-segment residual snapshot: prefix
                            # boundaries land mid-scan, so the host can't
                            # slice them from the pool after the step
                            # (later segments of the same slot have
                            # already advanced it)
                            res = tuple(
                                jax.tree_util.tree_leaves(new_sub)[i]
                                for i in spec.resid_ids
                            )
                            return slot_update(spec, c, new_sub, slot), lg[0], res

                        def dead(c):
                            # a real runtime skip (cond, not select): decode-
                            # heavy steps don't pay for idle segment slots
                            leaves = jax.tree_util.tree_leaves(c)
                            res = tuple(
                                jax.lax.dynamic_slice_in_dim(
                                    leaves[i], 0, 1, axis=spec.axes[i]
                                )
                                for i in spec.resid_ids
                            )
                            return c, jnp.zeros(lg_aval.shape[1:], lg_aval.dtype), res

                        new_carry, lg, res = jax.lax.cond(ln > 0, live, dead, carry)
                        return new_carry, (lg, res)

                    caches, (seg_logits, seg_resid) = jax.lax.scan(
                        seg_body, caches, (seg_slot, seg_start, seg_len, seg_off)
                    )
                    dlogits, dec_caches, aux = api.model_decode(
                        p, caches, cfg, dec_t, dec_pos, dec_act, spmd=None
                    )
                    # decode ran over every row; keep its cache writes only
                    # where a row actually decoded, so slots mid-prefill
                    # never absorb the garbage decode row
                    dl = jax.tree_util.tree_leaves(dec_caches)
                    pl = jax.tree_util.tree_leaves(caches)
                    merged = jax.tree_util.tree_unflatten(
                        spec.treedef,
                        [
                            jnp.where(
                                dec_act.reshape(
                                    (1,) * ax + (-1,) + (1,) * (d.ndim - ax - 1)
                                ),
                                d, c,
                            )
                            for d, c, ax in zip(dl, pl, spec.axes)
                        ],
                    )
                    B = dec_pos.shape[0]
                    arC = jnp.arange(C, dtype=jnp.int32)
                    w_slot = jnp.concatenate(
                        [jnp.arange(B, dtype=jnp.int32), jnp.repeat(seg_slot, C)]
                    )
                    w_pos = jnp.concatenate(
                        [dec_pos.astype(jnp.int32),
                         (seg_start[:, None] + arC[None]).reshape(-1)]
                    )
                    w_valid = jnp.concatenate(
                        [dec_act, (arC[None] < seg_len[:, None]).reshape(-1)]
                    )
                    new_pages, new_resid, new_scales = paged_writeback_tokens_q(
                        spec, merged, pages, scales, table, w_slot, w_pos, w_valid
                    )
                    return (dlogits, seg_logits, seg_resid, new_pages,
                            new_resid, new_scales, aux)

                return step

            return _cached_jit(
                "ragged_step",
                (cfg, pf_cfg, self.ctx, self.pool.page_size,
                 self.pool.n_pages, self._paged_backend, C, S,
                 self.pool.quant),
                _make_ragged_step,
            )
        if self._paged:
            spec = self.pool.step_spec()

            def _make_paged_step():
                if inplace_decode(spec):
                    # the pages ride the layer scan in place: each full
                    # layer writes its rows into them and reads its live
                    # pages through the table (models/paged_kv.py)
                    def step(p, pages, scales, resid, table, t, pos, act):
                        p = dequantize_params(p)
                        logits, new_caches, aux = api.model_decode(
                            p, paged_rings(spec, pages, resid, table), cfg, t,
                            pos, act, spmd=spmd,
                        )
                        new_pages, new_resid = paged_split(spec, new_caches)
                        return logits, new_pages, new_resid, scales, aux

                    return step

                def step(p, pages, scales, resid, table, t, pos, act):
                    p = dequantize_params(p)
                    caches = paged_materialize_q(spec, pages, scales, resid, table)
                    logits, new_caches, aux = api.model_decode(
                        p, caches, cfg, t, pos, act, spmd=spmd
                    )
                    new_pages, new_resid, new_scales = paged_writeback_q(
                        spec, new_caches, pages, scales, table, pos
                    )
                    return logits, new_pages, new_resid, new_scales, aux

                return step

            # the pages are donated: the step's row writes then land in the
            # pool's own buffers instead of a copy of the whole pool. The
            # residual leaves are not (callers may hold them across a step).
            return _cached_jit(
                "paged_step",
                (cfg, spmd, self.ctx, self.pool.page_size,
                 self.pool.n_pages, self._paged_backend, self.pool.quant),
                _make_paged_step,
                donate_argnums=(1,),
            )
        return _cached_jit(
            "step", (cfg, spmd),
            lambda: lambda p, c, t, pos, act: api.model_decode(
                dequantize_params(p), c, cfg, t, pos, act, spmd=spmd
            ),
        )

    def _level_fn(self, level: int) -> Callable:
        """The compiled step for one capacity-ladder level, minted lazily
        on first use (the ladder is discrete and bounded, so the jit cache
        grows by at most ``len(capacity_levels) - 1`` extra entries)."""
        if level not in self._level_fns:
            self._level_fns[level] = self._build_step_fn(self._level_cfgs[level])
        return self._level_fns[level]

    def _capacity_level(self) -> int:
        """Ladder level for this step's decode. Level 0 (full capacity)
        unless the controller is degraded AND no latency-tier request is
        active — latency-priority work always decodes at full capacity, so
        a mixed batch runs level 0 and only pure batch-tier steps degrade.
        Dense families always step at level 0 (the ladder only scales
        MoD's capacity_ratio); their degradation is the host-side
        admission-budget scaling in :meth:`_batch_admission_cap`."""
        if self._controller is None or self._controller.level == 0:
            return 0
        if not self.cfg.mod.enabled:
            return 0
        if any(
            s.active and s.req.priority == PRIORITY_LATENCY for s in self.slots
        ):
            return 0
        return min(self._controller.level, len(self._level_cfgs) - 1)

    def _batch_admission_cap(self) -> Optional[int]:
        """Degraded per-wave admission budget for *batch-tier* requests
        (None = uncapped): the prefill-chunk-budget half of a capacity
        level. Admission waves shrink by the level's scale so prompt
        ingestion drains at the degraded rate; latency-tier admissions
        bypass the cap in the scheduler. Deliberately a per-wave budget,
        not a concurrency cap: throttling in-flight batch work below the
        pool's own admission gate just trades tail latency for idle
        slots — the ladder's job is cheaper steps, not fewer of them."""
        if self._controller is None or self._controller.level == 0:
            return None
        lvl = min(self._controller.level, len(self._capacity_scales) - 1)
        scale = self._capacity_scales[lvl]
        base = self._ragged_segments if self._ragged else self.batch_size
        return max(1, int(round(base * scale)))

    # ------------------------------------------------------------------
    # Submission
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> int:
        """Queue a request; returns its uid. Tokens stream/complete via
        :meth:`step` / :meth:`run`."""
        if req.total_len > self.ctx:
            raise ValueError(
                f"request needs {req.total_len} positions but engine ctx is {self.ctx}"
            )
        if self._paged and self.pool.pages_needed(req.total_len) > self.pool.allocatable_pages:
            # fail fast: the admission gate would block this forever and
            # run() would only report an opaque step-budget overflow
            raise ValueError(
                f"request needs {self.pool.pages_needed(req.total_len)} pages "
                f"worst-case but the pool has {self.pool.allocatable_pages}"
            )
        if req.deadline_s is not None and req.deadline_s <= 0.0:
            # never-servable, like the pages check above: the first
            # lifecycle sweep would shed it before it could run at all
            raise ValueError(
                f"deadline_s must be positive, got {req.deadline_s}: the "
                "deadline has already elapsed at submit"
            )
        if self.scheduler.queue_full:
            # bounded backpressure: reject-with-reason instead of letting
            # the queue (and every queued request's wait) grow unboundedly
            self._n_shed += 1
            raise EngineOverloaded(
                f"queue depth {len(self.scheduler.queue)} is at max_queue="
                f"{self.scheduler.max_queue}; request rejected, retry later"
            )
        if req.uid is None:
            req.uid = self._uid
        elif req.uid in self._used_uids:
            raise ValueError(f"request uid {req.uid} already submitted")
        self._used_uids.add(req.uid)
        self._uid = max(self._uid, req.uid) + 1
        req._submitted_step = self.step_count  # type: ignore[attr-defined]
        if req.deadline_s is not None:
            # absolute deadline on the engine clock, armed at submit —
            # queue wait counts against it (that's the shedding signal)
            req._deadline_t = self._clock() + req.deadline_s  # type: ignore[attr-defined]
        self.scheduler.submit(req)
        return req.uid

    def cancel(self, uid: int) -> bool:
        """Client cancellation by uid. Marks the request; the next step's
        lifecycle sweep finishes it with ``FINISH_CANCELLED`` — queued
        requests shed without ever prefilling, running ones release their
        pages/snapshots through the normal finish path and report their
        partial output. Returns False for an unknown or already-finished
        uid (cancellation racing completion is benign: the client gets
        the completed output it was sent)."""
        for r in self.scheduler.queue:
            if r.uid == uid:
                r.cancel()
                return True
        for s in self.slots:
            if s.active and s.req.uid == uid:
                s.req.cancel()
                return True
        return False

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------

    def _page_gate(self) -> Optional[Callable]:
        """Admission gate for the paged pool: a request enters only if its
        *worst-case* page count (ceil(total_len / page_size), no prefix
        discount — conservative) is obtainable right now, net of pages the
        same admission wave already claimed. Availability, not reservation:
        running slots still grow lazily, so the preemption path remains the
        backstop for overcommit."""
        if not self._paged:
            return None
        claimed = [0]

        def gate(req: Request) -> bool:
            need = self.pool.pages_needed(req.total_len)
            if self._prefix_cache:
                # a cached prefix covers part of the worst case for free
                # (telemetry-free probe; the real match happens at prefill)
                need -= self.pool.prefix_probe_pages(np.asarray(req.tokens))
            ok = need <= self.pool.available_pages() - claimed[0]
            if ok:
                claimed[0] += need
            return ok

        return gate

    def _admit_ragged(self, max_admissions: Optional[int] = None) -> None:
        """Token-budget admission for the ragged mixed step: a request is
        admitted only while the step has free prefill segments left after
        the slots already mid-prompt — free *slots* are not the scarce
        resource, segment tokens are. Admitted slots enter PREFILL with no
        off-path compute; their prompts drain through the mixed step.
        ``max_admissions`` tightens the wave further (the speculative path
        passes its verify-token budget cap)."""
        n_prefilling = sum(1 for s in self.slots if s.state == PREFILL)
        cap = max(0, self._ragged_segments - n_prefilling)
        if max_admissions is not None:
            cap = min(cap, max_admissions)
        plans = self.scheduler.plan_admissions(
            self.slots,
            stepped_prefill=False,
            page_gate=self._page_gate(),
            max_admissions=cap,
            batch_cap=self._batch_admission_cap(),
        )
        for slot, req in plans:
            self.pool.acquire(slot.idx)
            slot.req = req
            slot.generated = []
            slot.admitted_step = self.step_count
            slot.first_token_step = -1
            slot.routed_sum, slot.routed_steps = 0.0, 0
            slot.score, slot.score_sum, slot.score_steps = float("nan"), 0.0, 0
            slot.state = PREFILL
            slot.pos = 0
            slot.prompt_idx = 0
            slot.next_token = 0
            if self._prefix_cache:
                m = self.pool.prefix_match(np.asarray(req.tokens))
                if m is not None:
                    prefix_key, entry = m
                    resid_snap = self.pool.prefix_attach(slot.idx, prefix_key)
                    self.pool.overlay_resid_slot(slot.idx, resid_snap)
                    slot.prompt_idx = entry.n_tokens
                    slot.pos = entry.n_tokens

    def _admit(self, max_admissions: Optional[int] = None) -> None:
        plans = self.scheduler.plan_admissions(
            self.slots,
            stepped_prefill=not self._batch_prefill,
            page_gate=self._page_gate(),
            max_admissions=max_admissions,
            batch_cap=self._batch_admission_cap(),
        )
        for slot, req in plans:
            if self._paged:
                self.pool.acquire(slot.idx)
            else:
                self.pool.reset(slot.idx)
            slot.req = req
            slot.generated = []
            slot.admitted_step = self.step_count
            slot.first_token_step = -1
            slot.routed_sum, slot.routed_steps = 0.0, 0
            slot.score, slot.score_sum, slot.score_steps = float("nan"), 0.0, 0
            if self.cfg.family == "encdec" and req.enc_emb is not None:
                sub = self._cross_fn(
                    self.params, self.pool._template, jnp.asarray(req.enc_emb)[None]
                )
                self.pool.write_slot(slot.idx, sub)
            if self._batch_prefill:
                try:
                    with _span("serve.prefill", uid=req.uid, tokens=req.prompt_len):
                        logits_row = self._prefill(slot, req)
                except _PoolExhausted:
                    self._abort_admission(slot, req)
                    continue
                if not np.isfinite(logits_row).all():
                    # finiteness police at admission: a numerically
                    # poisoned prompt fails its own request right here,
                    # before the slot ever enters the decode batch
                    self._finish(
                        slot, FINISH_ERROR,
                        error="non-finite prefill logits",
                    )
                    continue
                slot.pos = req.prompt_len
                slot.prompt_idx = req.prompt_len
                # first new token comes from the prefill's last-position
                # logits — no re-decode of the last prompt token
                tok = self._sample(req, logits_row, 0)
                self._push_token(slot, tok)
                if slot.req is not None:  # not finished at admission
                    slot.state = GENERATE
                    slot.next_token = tok
            else:
                if self._paged and not self.pool.alloc_pages(slot.idx, 1):
                    self._abort_admission(slot, req)
                    continue
                slot.state = PREFILL
                slot.pos = 0
                slot.prompt_idx = 0
                slot.next_token = int(req.tokens[0])

    def _abort_admission(self, slot: Slot, req: Request) -> None:
        """A gate-passed admission lost its pages before entering the batch
        (same-wave prefix eviction, lazy-growth races): unwind it instead
        of crashing — pages released, request back to the queue front, a
        later step's gate re-decides with the pages it actually has."""
        self.pool.release(slot.idx)
        slot.req = None
        slot.state = FREE
        slot.generated = []
        self.scheduler.requeue(req)
        # not a preemption — the request never entered the decode batch
        self.admission_aborts += 1

    def _prefill(self, slot: Slot, req: Request) -> np.ndarray:
        """Batch prefill of an admitted request: chunked, or the prompt in
        one call; returns the last-position logits row."""
        if self._prefill_chunk is not None:
            return self._chunked_prefill(slot, req)
        logits, sub = self._prefill_fn(self.params, jnp.asarray(req.tokens)[None])
        if self._paged and not self.pool.alloc_pages(slot.idx, req.prompt_len):
            raise _PoolExhausted
        self.pool.write_slot(slot.idx, sub)
        self._prefill_tokens_computed += req.prompt_len
        self._positions_computed += req.prompt_len
        return np.asarray(logits[0, -1])

    def _chunked_prefill(self, slot: Slot, req: Request) -> np.ndarray:
        """Ingest the prompt in fixed ``prefill_chunk`` pieces against the
        slot's working cache; returns the last-position logits row.

        With the prefix cache on, the longest chunk-aligned cached prefix
        is restored first (shared pages attached + residual snapshot
        overlaid) and only the remainder is computed; every chunk boundary
        prefilled here is registered for future requests. Reuse is
        bit-identical to recomputing: the restored state *is* the state a
        cold run would have produced at that boundary.
        """
        tokens = np.asarray(req.tokens)
        L = req.prompt_len
        C = self._prefill_chunk
        start_tok = 0
        prefix_key = None
        if self._paged and self._prefix_cache:
            m = self.pool.prefix_match(tokens)
            if m is not None:
                prefix_key, entry = m
                start_tok = entry.n_tokens
        # shared prefix pages attach first (logical pages 0..n), then the
        # suffix's own pages are allocated after them
        if prefix_key is not None:
            resid_snap = self.pool.prefix_attach(slot.idx, prefix_key)
        if self._paged:
            if not self.pool.alloc_pages(slot.idx, L):
                raise _PoolExhausted
            work = self.pool.read_slot(slot.idx)
            if prefix_key is not None:
                work = self.pool.overlay_resid(work, resid_snap)
        else:
            work = self.pool._template
        boundary_resids: Dict[int, Any] = {}
        logits = None
        off = start_tok
        while off < L:
            nv = min(C, L - off)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :nv] = tokens[off : off + nv]
            logits, work = self._chunk_fn(
                self.params, work, jnp.asarray(chunk),
                jnp.int32(off), jnp.int32(nv),
            )
            off += nv
            self._prefill_tokens_computed += nv
            self._positions_computed += C
            self._positions_wasted += C - nv
            if self._paged and self._prefix_cache and off % C == 0:
                boundary_resids[off] = self.pool.snapshot_resid(work)
        if self._paged:
            self.pool.write_slot(
                slot.idx, work, start_page=start_tok // self.pool.page_size
            )
            if self._prefix_cache:
                self.pool.prefix_register(slot.idx, tokens, boundary_resids)
        else:
            self.pool.write_slot(slot.idx, work)
        assert logits is not None  # lookup never matches the whole prompt
        return np.asarray(logits[0])

    def _place(self, host_arr) -> jax.Array:
        """Host array -> device; batch-sharded over the mesh's data axes
        when the engine is multi-device (leading dim = the slot dim)."""
        arr = jnp.asarray(host_arr)
        if self.mesh is None:
            return arr
        return jax.device_put(arr, self._input_shardings[arr.ndim])

    # ------------------------------------------------------------------
    # Sampling / termination
    # ------------------------------------------------------------------

    def _sample(self, req: Request, logits_row: np.ndarray, token_index: int) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        key = req.key if req.key is not None else jax.random.PRNGKey(req.uid)
        key = jax.random.fold_in(key, token_index)
        return int(
            jax.random.categorical(key, jnp.asarray(logits_row) / req.temperature)
        )

    def _push_token(self, slot: Slot, tok: int) -> None:
        """Record a sampled token; finish + free the slot if terminal."""
        req = slot.req
        slot.generated.append(tok)
        self.generated_tokens += 1
        if slot.first_token_step < 0:
            slot.first_token_step = self.step_count
        if req.stream is not None:
            req.stream(req.uid, tok)
        if tok == req.eos_id:
            self._finish(slot, FINISH_EOS)
        elif len(slot.generated) >= req.max_new_tokens:
            self._finish(slot, FINISH_LENGTH)

    def _finish(self, slot: Slot, reason: str, error: Optional[str] = None) -> None:
        """Terminal transition for a running slot: build the output (with
        whatever tokens were generated — expiry/cancellation/error deliver
        the partial stream), free the slot, release its pages. The one
        path every terminal reason goes through, so pool bookkeeping can't
        diverge between success and failure."""
        req = slot.req
        self.finished.append(
            RequestOutput(
                uid=req.uid,
                prompt=np.asarray(req.tokens),
                tokens=np.asarray(slot.generated, np.int32),
                finish_reason=reason,
                submitted_step=getattr(req, "_submitted_step", 0),
                admitted_step=slot.admitted_step,
                first_token_step=slot.first_token_step,
                finished_step=self.step_count,
                routed_frac=(
                    slot.routed_sum / slot.routed_steps
                    if slot.routed_steps
                    else float("nan")
                ),
                mean_score=(
                    # score_steps, not routed_steps: the two aux keys are
                    # surfaced under independent presence checks, so the
                    # mean must use its own counter
                    slot.score_sum / slot.score_steps
                    if slot.score_steps
                    else float("nan")
                ),
                error=error,
            )
        )
        self._tally(reason)
        slot.req = None
        slot.state = FREE
        slot.generated = []
        if self._paged:
            self.pool.release(slot.idx)

    def _tally(self, reason: str) -> None:
        if reason == FINISH_EXPIRED:
            self._n_expired += 1
        elif reason == FINISH_CANCELLED:
            self._n_cancelled += 1
        elif reason == FINISH_ERROR:
            self._n_failed += 1

    def _finish_queued(self, req: Request, reason: str,
                       error: Optional[str]) -> None:
        """Terminal output for a request shed straight from the queue:
        never admitted, no slot, no prefill, no tokens —
        ``admitted_step == finished_step`` and ``first_token_step == -1``
        mark the never-ran lifecycle (request.py docstring)."""
        self.scheduler.drop(req)
        self.finished.append(
            RequestOutput(
                uid=req.uid,
                prompt=np.asarray(req.tokens),
                tokens=np.asarray([], np.int32),
                finish_reason=reason,
                submitted_step=getattr(req, "_submitted_step", 0),
                admitted_step=self.step_count,
                first_token_step=-1,
                finished_step=self.step_count,
                routed_frac=float("nan"),
                mean_score=float("nan"),
                error=error,
            )
        )
        self._n_shed += 1
        self._tally(reason)

    def _police(self) -> None:
        """Terminal-lifecycle sweep at the top of every step: cancelled /
        deadline-expired requests leave *now*. Queued ones are shed
        without ever prefilling (the overload-control half: prefilling
        work that is already past its deadline is pure waste), running
        ones finish with their partial output and release pages/prefix
        snapshots through the normal :meth:`_finish` path. The clock is
        read at most once per sweep, and only when some request actually
        carries a deadline."""
        now = None

        def expired(r: Request) -> bool:
            nonlocal now
            t = getattr(r, "_deadline_t", None)
            if t is None:
                return False
            if now is None:
                now = self._clock()
            return now >= t

        for r in [r for r in self.scheduler.queue if r.cancelled or expired(r)]:
            if r.cancelled:
                self._finish_queued(r, FINISH_CANCELLED, None)
            else:
                self._finish_queued(
                    r, FINISH_EXPIRED, "deadline expired while queued"
                )
        for s in self.slots:
            if not s.active:
                continue
            if s.req.cancelled:
                self._finish(s, FINISH_CANCELLED)
            elif expired(s.req):
                self._finish(
                    s, FINISH_EXPIRED,
                    error=f"deadline expired at step {self.step_count}",
                )

    def _step_prologue(self) -> None:
        """Shared head of every step path: the lifecycle sweep, then
        scheduled fault injection — faults fire against the post-sweep
        state, so an injected storm can't mask a pending expiry."""
        self._police()
        if self._faults is not None:
            self._faults.on_step_start(self)

    def _step_epilogue(self, t0: float) -> None:
        """Shared tail of every step path: wall-clock accounting plus one
        controller observation (queue depth + this step's latency) per
        engine step."""
        dt = time.perf_counter() - t0
        self._wall_s += dt
        if self._controller is not None:
            self._controller.observe(len(self.scheduler.queue), dt)

    def _check_invariants(self) -> None:
        with _span("serve.invariants"):
            self.scheduler.check_invariants(self.slots, len(self.finished))

    def _map_pages(self, plan: Callable[[], Any]) -> Any:
        """Run a page-mapping pass (lazy growth or the mixed step's segment
        plan) inside a ``serve.pages`` span that records the pages it
        scrubbed."""
        with _span("serve.pages") as span:
            before = self.pool.scrubbed_pages
            out = plan()
            span.set_metadata(scrubbed=self.pool.scrubbed_pages - before)
        return out

    def _preempt(self, slot: Slot) -> None:
        """Page-pool OOM backstop: evict the youngest-admitted slot back to
        the *front* of the queue with its pages released. The request
        restarts from scratch on re-admission; per-request keyed sampling
        (``fold_in(key, token_index)``) regenerates the identical stream,
        though a ``stream`` callback will see the replay."""
        req = slot.req
        self.pool.release(slot.idx)
        # modlint: disable=counter-decrement -- not a monotone counter here:
        # preemption restarts the request from scratch, so its tokens leave
        # the book and are re-counted on replay; net totals stay exact
        self.generated_tokens -= len(slot.generated)  # regenerated later
        slot.req = None
        slot.state = FREE
        slot.generated = []
        self.scheduler.requeue(req)
        self.preemptions += 1

    def _grow_pages(self, lookahead: int = 1) -> None:
        """Map each active slot's next ``lookahead`` write pages before the
        step (speculative rounds pass ``speculate + 1`` — every verify
        position must be mapped up front, or its in-step scatter would
        corrupt the NULL page); on pool exhaustion (free list empty,
        nothing evictable) preempt the youngest-admitted active slot and
        retry — the oldest request always keeps making progress."""
        def upto(s: Slot) -> int:
            # never demand pages past the slot's own budget (total_len):
            # a lookahead window that overshoots it could exceed the
            # pool's worst case that submit() admitted against
            return min(s.pos + lookahead, s.req.total_len, self.ctx)

        while True:
            needy = [
                s for s in self.slots
                if s.active
                and self.pool.pages_needed(upto(s)) > int(self.pool.n_mapped[s.idx])
            ]
            for s in needy:
                if not self.pool.alloc_pages(s.idx, upto(s)):
                    victim = max(
                        (t for t in self.slots if t.active),
                        key=lambda t: (t.admitted_step, t.idx),
                    )
                    self._preempt(victim)
                    break  # re-scan: the victim may have been in `needy`
            else:
                return

    def _plan_segments(self) -> List[tuple]:
        """Greedy FCFS segment plan for the mixed step's prefill budget
        (``ragged_segments`` segments × ``prefill_chunk`` tokens): oldest
        mid-prompt slot first, several consecutive segments per slot
        allowed (the in-step scan runs them in order). Also maps every
        page the step will write — the planned prefill extent plus each
        decoding slot's next row; on pool exhaustion the youngest active
        slot (possibly mid-prefill) is preempted and planning restarts,
        so the oldest request always keeps making progress."""
        C = self._prefill_chunk
        while True:
            segs: List[tuple] = []
            planned_end: Dict[int, int] = {}
            budget = self._ragged_segments
            for s in sorted(
                (t for t in self.slots if t.state == PREFILL),
                key=lambda t: (t.admitted_step, t.idx),
            ):
                off = s.prompt_idx
                L = s.req.prompt_len
                while budget > 0 and off < L:
                    nv = min(C, L - off)
                    segs.append((s, off, nv))
                    off += nv
                    budget -= 1
                if off > s.prompt_idx:
                    planned_end[s.idx] = off
                if budget <= 0:
                    break
            ok = True
            for s in self.slots:
                need = None
                if s.state == GENERATE:
                    need = s.pos + 1
                elif s.idx in planned_end:
                    need = planned_end[s.idx]
                if need is not None and not self.pool.alloc_pages(s.idx, need):
                    ok = False
                    break
            if ok:
                return segs
            victim = max(
                (t for t in self.slots if t.active),
                key=lambda t: (t.admitted_step, t.idx),
            )
            self._preempt(victim)

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------

    @property
    def has_work(self) -> bool:
        return bool(self.scheduler.queue) or any(s.active for s in self.slots)

    def step(self) -> List[RequestOutput]:
        """Admit + one decode step + per-slot host update.

        Returns the requests that finished during this call.
        """
        if self._speculate is not None:
            return self._step_speculative()
        if self._ragged:
            return self._step_ragged()
        done_before = len(self.finished)
        t0 = time.perf_counter()
        self._step_prologue()
        with _span("serve.admit"):
            self._admit()
        if self._paged:
            self._map_pages(self._grow_pages)  # may preempt; precedes the active scan
        active_slots = [s for s in self.slots if s.active]
        if not active_slots:
            self.last_step_level = 0  # no decode ran: nothing was degraded
            self.step_count += 1
            self._step_epilogue(t0)
            self._check_invariants()
            return self.finished[done_before:]

        B = self.batch_size
        with _span("serve.decode", step=self.step_count, live=len(active_slots)):
            tokens = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            for s in active_slots:
                tokens[s.idx, 0] = s.next_token
                pos[s.idx] = s.pos
                active[s.idx] = True

            lvl = self._capacity_level()
            self.last_step_level = lvl  # which ladder level priced this step
            step_fn = self._level_fn(lvl) if lvl else self._step_fn
            if lvl:
                self._degraded_decode_steps += 1
            if self._paged:
                self._live_page_share_sum += self._live_page_share(pos)
                self._live_page_steps += 1
                (logits, self.pool.pages, self.pool.resid, self.pool.scales,
                 aux) = step_fn(
                    self.params, self.pool.pages, self.pool.scales,
                    self.pool.resid, self.pool.device_table(),
                    jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(active),
                )
            else:
                logits, self.pool.caches, aux = step_fn(
                    self.params, self.pool.caches, self._place(tokens),
                    self._place(pos), self._place(active),
                )
        with _span("serve.logits_to_host"):
            logits_np = np.asarray(logits)
            routed_np, scores_np, frac_np = _decode_aux_to_host(aux)
        if self._logit_tap is not None and active_slots:
            self._logit_tap(logits_np)
        if self._faults is not None:
            logits_np = self._faults.corrupt_logits(self, logits_np)
        self._positions_computed += B
        self._positions_wasted += B - len(active_slots)

        if frac_np is not None:
            self._routed_frac_sum += float(frac_np)
            self._routed_frac_steps += 1
        self._occupancy_sum += len(active_slots)
        with _span("serve.sample"):
            self._sample_decoded(active_slots, logits_np, routed_np, scores_np)

        self.step_count += 1
        self._step_epilogue(t0)
        self._check_invariants()
        return self.finished[done_before:]

    def _live_page_share(self, pos: np.ndarray) -> float:
        """Pages of the table a padded decode step's attention reads, over
        all of them: each slot's live pages (up to its position) where the
        step reads in place, every page where it gathers the pool."""
        P = self.pool.pages_per_slot
        if not self._inplace_decode:
            return 1.0
        live = np.minimum(pos // self.pool.page_size + 1, P)
        return float(live.sum()) / (len(pos) * P)

    def _sample_decoded(self, active_slots: List[Slot], logits_np: np.ndarray,
                        routed_np: Optional[np.ndarray],
                        scores_np: Optional[np.ndarray]) -> None:
        """Per-slot host update after a decode step: finiteness police,
        routing telemetry, prompt ingestion or sampling."""
        for s in active_slots:
            if not np.isfinite(logits_np[s.idx]).all():
                # finiteness police: a poisoned row fails only its own
                # request — rows are independent (per-row attention; MoD
                # routing couples rows only through *selection*), so no
                # other slot's cache absorbed the corruption
                self._finish(
                    s, FINISH_ERROR,
                    error=f"non-finite logits at step {self.step_count}",
                )
                continue
            if routed_np is not None:
                s.routed_sum += float(routed_np[s.idx])
                s.routed_steps += 1
            if scores_np is not None:
                s.score = float(scores_np[s.idx])
                s.score_sum += s.score
                s.score_steps += 1
            s.pos += 1
            if s.state == PREFILL:
                s.prompt_idx += 1
                if s.prompt_idx < s.req.prompt_len:
                    s.next_token = int(s.req.tokens[s.prompt_idx])
                else:
                    # fed the last prompt token this step: its logits give
                    # the first generated token
                    tok = self._sample(s.req, logits_np[s.idx], 0)
                    self._push_token(s, tok)
                    if s.req is not None:
                        s.state = GENERATE
                        s.next_token = tok
            else:
                tok = self._sample(s.req, logits_np[s.idx], len(s.generated))
                self._push_token(s, tok)
                if s.req is not None:
                    s.next_token = tok

    def _step_ragged(self, admit: bool = True) -> List[RequestOutput]:
        """One mixed prefill+decode step: admit by token budget, plan the
        prefill segment grid, run the single jitted step, then advance
        every slot host-side. Token streams are bit-identical to the
        padded engine: each segment replays the exact ``prefill_chunk``
        call the padded path would have made (same chunk boundaries, same
        batch-1 cache state), and decode rows see the same pool state.
        ``admit=False``: the speculative path already admitted this step
        and fell back here because prompts are still draining."""
        done_before = len(self.finished)
        t0 = time.perf_counter()
        if admit:
            # admit=False means the speculative path already ran the
            # prologue (police + faults) and admission for this step
            self._step_prologue()
            with _span("serve.admit"):
                self._admit_ragged()
        segs = self._map_pages(self._plan_segments)  # may preempt mid-prefill
        active_slots = [s for s in self.slots if s.active]
        if not active_slots:
            self.last_step_level = 0  # no decode ran: nothing was degraded
            self.step_count += 1
            self._step_epilogue(t0)
            self._check_invariants()
            return self.finished[done_before:]

        B = self.batch_size
        C = self._prefill_chunk
        S = self._ragged_segments
        decode_slots = [s for s in self.slots if s.state == GENERATE]
        with _span("serve.decode", step=self.step_count, live=len(active_slots)):
            dec_tokens = np.zeros((B, 1), np.int32)
            dec_pos = np.zeros((B,), np.int32)
            dec_act = np.zeros((B,), bool)
            for s in decode_slots:
                dec_tokens[s.idx, 0] = s.next_token
                dec_pos[s.idx] = s.pos
                dec_act[s.idx] = True

            # dead segments (slot 0, len 0) are exact cache no-ops in-step
            pf_tokens = np.zeros((S * C,), np.int32)
            seg_slot = np.zeros((S,), np.int32)
            seg_start = np.zeros((S,), np.int32)
            seg_len = np.zeros((S,), np.int32)
            seg_off = np.zeros((S,), np.int32)
            for k, (s, start, nv) in enumerate(segs):
                seg_slot[k] = s.idx
                seg_start[k] = start
                seg_len[k] = nv
                seg_off[k] = k * C
                pf_tokens[k * C : k * C + nv] = np.asarray(
                    s.req.tokens[start : start + nv]
                )

            lvl = self._capacity_level()
            self.last_step_level = lvl  # which ladder level priced this step
            step_fn = self._level_fn(lvl) if lvl else self._step_fn
            if lvl:
                self._degraded_decode_steps += 1
            (logits, seg_logits, seg_resid, self.pool.pages, self.pool.resid,
             self.pool.scales, aux) = step_fn(
                self.params, self.pool.pages, self.pool.scales, self.pool.resid,
                self.pool.device_table(),
                jnp.asarray(dec_tokens), jnp.asarray(dec_pos), jnp.asarray(dec_act),
                jnp.asarray(pf_tokens), jnp.asarray(seg_slot),
                jnp.asarray(seg_start), jnp.asarray(seg_len), jnp.asarray(seg_off),
            )
        with _span("serve.logits_to_host"):
            logits_np = np.asarray(logits)
            seg_logits_np = np.asarray(seg_logits)
            routed_np, scores_np, frac_np = _decode_aux_to_host(aux)
        if self._logit_tap is not None and decode_slots:
            self._logit_tap(logits_np)
        if self._faults is not None:
            logits_np = self._faults.corrupt_logits(self, logits_np)

        n_pf = sum(nv for _, _, nv in segs)
        self._prefill_tokens_computed += n_pf
        # dead segments (len 0) are skipped at runtime by the in-step cond,
        # so only live segments' chunk grids count as computed positions
        self._positions_computed += len(segs) * C + B
        self._positions_wasted += (len(segs) * C - n_pf) + (B - len(decode_slots))
        self._occupancy_sum += len(active_slots)

        if decode_slots and frac_np is not None:
            self._routed_frac_sum += float(frac_np)
            self._routed_frac_steps += 1

        # prefill slots: advance prompt progress, register every chunk
        # boundary a segment completed (per-segment residual snapshots come
        # out of the in-step scan — the pool itself has already advanced
        # past mid-step boundaries), then sample first tokens where the
        # prompt completed — from that slot's last segment's logits (the
        # padded path's "no re-decode of the last prompt token" invariant)
        last_seg: Dict[int, int] = {}
        for k, (s, start, nv) in enumerate(segs):
            s.prompt_idx = start + nv
            s.pos = start + nv
            last_seg[s.idx] = k
        if self._prefix_cache:
            resid_ids = self._ragged_spec.resid_ids
            for k, (s, start, nv) in enumerate(segs):
                end = start + nv
                if end % C == 0:
                    snap = {i: seg_resid[j][k] for j, i in enumerate(resid_ids)}
                    self.pool.prefix_register(
                        s.idx, np.asarray(s.req.tokens), {end: snap}
                    )
        with _span("serve.sample"):
            for s in [t for t in self.slots if t.state == PREFILL]:
                if s.idx not in last_seg:
                    continue  # over budget this step; waits for the next
                if s.prompt_idx >= s.req.prompt_len:
                    row = seg_logits_np[last_seg[s.idx]]
                    if not np.isfinite(row).all():
                        self._finish(
                            s, FINISH_ERROR,
                            error="non-finite prefill-segment logits at step "
                                  f"{self.step_count}",
                        )
                        continue
                    tok = self._sample(s.req, row, 0)
                    self._push_token(s, tok)
                    if s.req is not None:
                        s.state = GENERATE
                        s.next_token = tok
            # decode rows: the padded step's per-slot update (poisoned rows
            # fail only their own request — rows are independent)
            self._sample_decoded(decode_slots, logits_np, routed_np, scores_np)

        self.step_count += 1
        self._step_epilogue(t0)
        self._check_invariants()
        return self.finished[done_before:]

    def _step_speculative(self) -> List[RequestOutput]:
        """One self-speculative round: draft ``n`` tokens per slot at the
        aggressive capacity ratio, verify the ``n+1``-token window at full
        capacity inside the same jitted call, accept the longest prefix on
        which the host's sampled tokens agree with the drafts, and roll
        the rejected tail back (page-table truncation + residual-snapshot
        restore).

        Acceptance is **batch-global**: every slot advances by the same
        ``a = min`` over per-slot acceptance counts, additionally capped
        at the earliest in-window termination (EOS / token budget). The
        cap is what keeps batch composition — and therefore MoD
        ``batch_capacity`` routing — aligned step-for-step with the
        non-speculative engine, which is exactly why greedy streams stay
        bit-identical under upfront submission (a per-slot acceptance
        would let one slot outrun a termination and change the active
        mask other slots' routing depends on). In ragged mode the round
        falls back to the normal mixed step while any prompt is still
        draining; speculation only covers pure-decode steps."""
        done_before = len(self.finished)
        t0 = time.perf_counter()
        self._step_prologue()
        n = self._speculate
        cap = self.scheduler.speculative_admission_cap(
            sum(1 for s in self.slots if s.active), n + 1
        )
        if self._ragged:
            with _span("serve.admit"):
                self._admit_ragged(max_admissions=cap)
            if any(s.state == PREFILL for s in self.slots):
                self._wall_s += time.perf_counter() - t0
                return self._step_ragged(admit=False)
        else:
            with _span("serve.admit"):
                self._admit(max_admissions=cap)
        # every verify position this round writes a KV row: map the whole
        # window's pages up front (capped at each slot's own budget)
        self._map_pages(lambda: self._grow_pages(lookahead=n + 1))
        active_slots = [s for s in self.slots if s.active]
        if not active_slots:
            self.last_step_level = 0  # no decode ran: nothing was degraded
            self.step_count += 1
            self._step_epilogue(t0)
            self._check_invariants()
            return self.finished[done_before:]

        B = self.batch_size
        with _span("serve.decode", step=self.step_count, live=len(active_slots)):
            tokens = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            limit = np.zeros((B,), np.int32)
            for s in active_slots:
                tokens[s.idx, 0] = s.next_token
                pos[s.idx] = s.pos
                active[s.idx] = True
                limit[s.idx] = min(s.req.total_len, self.ctx)

            (drafts, logits, resids, self.pool.pages, self.pool.scales,
             aux) = self._spec_fn(
                self.params, self.pool.pages, self.pool.scales, self.pool.resid,
                self.pool.device_table(), jnp.asarray(tokens),
                jnp.asarray(pos), jnp.asarray(active), jnp.asarray(limit),
            )
        with _span("serve.logits_to_host"):
            drafts_np = np.asarray(drafts)  # (n, B)
            logits_np = np.asarray(logits)  # (n+1, B, V)
            # (n+1, B), (n+1, B), (n+1,)
            routed_np, scores_np, frac_np = _decode_aux_to_host(aux)
        if self._faults is not None:
            logits_np = self._faults.corrupt_logits(self, logits_np)
        # finiteness police over the whole verify window: a poisoned row
        # fails only its own request, and leaves the accept loop before it
        # can drag the batch-global acceptance down with it
        with _span("serve.sample"):
            ok_slots = []
            for s in active_slots:
                if np.isfinite(logits_np[:, s.idx]).all():
                    ok_slots.append(s)
                else:
                    self._finish(
                        s, FINISH_ERROR,
                        error=f"non-finite verify logits at step {self.step_count}",
                    )
        active_slots = ok_slots
        if not active_slots:
            # every active row failed: nothing was accepted, so there is
            # nothing to roll back — the failed slots' pages (including
            # the window's scattered lookahead rows) were released by
            # _finish, and pool.resid still holds the pre-round state
            self.step_count += 1
            self._step_epilogue(t0)
            self._check_invariants()
            return self.finished[done_before:]

        # Per-slot acceptance: emitted token k+1 samples from the verify
        # logits L_k, which are valid iff every earlier emitted token
        # matched its draft (the fed window is [cur, d_1..d_n]).
        # Sampling is fold_in(key, token_index)-deterministic, so tokens
        # sampled past the global cap are re-sampled identically from the
        # same logits next round.
        with _span("serve.sample"):
            emitted: Dict[int, List[int]] = {}
            a = n + 1
            for s in active_slots:
                toks: List[int] = []
                c_s = n + 1
                for k in range(n + 1):
                    e = self._sample(s.req, logits_np[k, s.idx], len(s.generated) + k)
                    toks.append(e)
                    if (
                        e == s.req.eos_id
                        or len(s.generated) + k + 1 >= s.req.max_new_tokens
                    ):
                        c_s = k + 1  # in-window termination caps the batch
                        break
                    if k < n and e != int(drafts_np[k, s.idx]):
                        c_s = k + 1  # draft mismatch: L_{k+1}.. are invalid
                        break
                emitted[s.idx] = toks
                a = min(a, c_s)

            if frac_np is not None:
                self._routed_frac_sum += float(frac_np[:a].sum())
                self._routed_frac_steps += a
            self._occupancy_sum += len(active_slots) * a
            # the round's fixed grid is n+1 verify positions per row, plus the
            # n-step draft grid when drafting is a separate pass (_spec_grid);
            # only the accepted tokens of active rows carried real work —
            # rejected verify positions and any draft grid count as
            # speculation overhead in padded_token_fraction
            self._positions_computed += self._spec_grid * B
            self._positions_wasted += self._spec_grid * B - a * len(active_slots)

            for s in active_slots:
                for k in range(a):
                    if routed_np is not None:
                        s.routed_sum += float(routed_np[k, s.idx])
                        s.routed_steps += 1
                    if scores_np is not None:
                        s.score = float(scores_np[k, s.idx])
                        s.score_sum += s.score
                        s.score_steps += 1
                    s.pos += 1
                    self._push_token(s, emitted[s.idx][k])
                    if s.req is None:
                        # the global cap places any termination at k == a-1
                        assert k == a - 1, (k, a)
                        break
                    s.next_token = emitted[s.idx][k]

        # rollback: restore the residual stack (MoD rings + cursors) to
        # the state after exactly `a` verify steps, and release the
        # rejected tail's pages; stale rows inside the last kept page are
        # causally masked until the real stream overwrites them
        self.pool.resid = [r[a - 1] for r in resids]
        for s in active_slots:
            if s.req is not None:  # finished slots already released
                self.pool.truncate(s.idx, s.pos)

        self._spec_rounds += 1
        self._spec_drafted += n * len(active_slots)
        self._spec_accepted_drafts += (a - 1) * len(active_slots)
        self._spec_emitted += a
        self.step_count += a
        self._step_epilogue(t0)
        self._check_invariants()
        return self.finished[done_before:]

    def run(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        """Step until queue and slots drain; returns all finished outputs."""
        budget = max_steps if max_steps is not None else self._step_budget()
        while self.has_work:
            if budget <= 0:
                raise RuntimeError("serving engine exceeded its step budget")
            self.step()
            budget -= 1
        return self.finished

    def run_stream(
        self, requests: List[Request], arrival_every: int
    ) -> List[RequestOutput]:
        """Offered-load helper: submit one request every ``arrival_every``
        engine steps (<= 0 submits everything upfront) and run to drain.
        The one arrival-schedule implementation shared by ``launch/serve.py``
        and ``benchmarks/serving.py``, so their latency numbers agree."""
        if arrival_every <= 0:
            for r in requests:
                self.submit(r)
            return self.run()
        budget = 4 * (sum(r.total_len for r in requests) + self.batch_size) + 64
        outputs: List[RequestOutput] = []
        submitted = 0
        while submitted < len(requests) or self.has_work:
            if budget <= 0:
                raise RuntimeError("serving engine exceeded its step budget")
            # arithmetic (not modulo) arrival check: a speculative round
            # advances step_count by several steps at once, which could
            # jump over a modulo boundary; for step-at-a-time engines the
            # two are identical
            if submitted < len(requests) and submitted * arrival_every <= self.step_count:
                self.submit(requests[submitted])
                submitted += 1
            outputs.extend(self.step())
            budget -= 1
        return outputs

    def _step_budget(self) -> int:
        pending = list(self.scheduler.queue) + [
            s.req for s in self.slots if s.req is not None
        ]
        per_req = sum(r.total_len for r in pending)
        return 4 * (per_req + self.batch_size) + 64

    # ------------------------------------------------------------------
    # Convenience + telemetry
    # ------------------------------------------------------------------

    def generate(
        self,
        prompts: jax.Array,  # (N, S0)
        n_tokens: int,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        eos_id: Optional[int] = None,
    ) -> jax.Array:
        """Batch-generate: submit N requests, run to completion, return the
        (N, S0 + n_tokens) sequences (uid order; early-EOS rows padded)."""
        prompts = np.asarray(prompts)
        n, s0 = prompts.shape
        uids = []
        for i in range(n):
            key = None if rng is None else jax.random.fold_in(rng, i)
            uids.append(
                self.submit(
                    Request(
                        tokens=prompts[i],
                        max_new_tokens=n_tokens,
                        temperature=temperature,
                        key=key,
                        eos_id=eos_id,
                    )
                )
            )
        uid_set = set(uids)  # built once: the per-element rebuild was O(N^2)
        outs = [o for o in self.run() if o.uid in uid_set]
        return jnp.asarray(pad_outputs(outs, s0 + n_tokens))

    def _step_signatures(self) -> Optional[int]:
        total = 0
        # dict.fromkeys dedups: dense ladder levels share one callable
        # (identical cfg -> identical jit-cache key)
        fns = list(dict.fromkeys(self._level_fns.values()))
        if self._spec_fn is not None:
            fns.append(self._spec_fn)
        for fn in fns:
            try:
                total += fn._cache_size()
            except AttributeError:
                return None
        return total

    @property
    def decode_compilations(self) -> Optional[int]:
        """Decode-step signatures traced since this engine was built —
        at most 1 (static shapes; 0 when another engine with the same
        config and batch size already compiled it). A speculative ragged
        engine has two entry points (mixed step for prompt drain +
        speculative round), so its bound is 2; an adaptive-capacity MoD
        engine adds at most one per *visited* ladder level. None if jax
        doesn't expose cache sizes."""
        now = self._step_signatures()
        if now is None or self._step_signatures0 is None:
            return None
        return now - self._step_signatures0

    def stats(self) -> Dict[str, Any]:
        steps = max(1, self.step_count)
        cb = self.pool.cache_bytes()
        out = {
            "steps": float(self.step_count),
            "generated_tokens": float(self.generated_tokens),
            "finished_requests": float(len(self.finished)),
            "wall_s": self._wall_s,
            "tokens_per_s": self.generated_tokens / self._wall_s if self._wall_s else 0.0,
            "mean_occupancy": self._occupancy_sum / steps,
            "mean_routed_frac": (
                self._routed_frac_sum / self._routed_frac_steps
                if self._routed_frac_steps
                else float("nan")
            ),
            # per-leaf-kind byte split: kv_bytes shrinks under quantized
            # KV (narrow pages + f32 scales), resid_bytes never does
            "kv_cache_bytes": cb["total"],
            "kv_bytes": cb["kv_bytes"],
            "resid_bytes": cb["resid_bytes"],
            "quant_kv": self.quant.kv if self.quant is not None else "none",
            "prefill_tokens_computed": float(self._prefill_tokens_computed),
            # fraction of fixed-shape step positions that carried no real
            # token (inactive decode rows, dead/padded prefill segments)
            "padded_token_fraction": (
                self._positions_wasted / self._positions_computed
                if self._positions_computed
                else 0.0
            ),
            # latest per-slot batch_capacity scores (NaN = free / MoD off):
            # what the router is currently ranking live slots by
            "slot_scores": [s.score for s in self.slots],
            # robustness counters (monotone; always present): shed counts
            # requests that left without ever occupying a slot (queue
            # drops + backpressure rejections); the other three count
            # terminal outputs by finish_reason
            "shed": float(self._n_shed),
            "expired": float(self._n_expired),
            "cancelled": float(self._n_cancelled),
            "failed": float(self._n_failed),
        }
        if self._paged:
            # mean share of the page table the padded decode step read
            out["decode_live_page_share"] = (
                self._live_page_share_sum / self._live_page_steps
                if self._live_page_steps
                else 0.0
            )
            out["preemptions"] = float(self.preemptions)
            out["admission_aborts"] = float(self.admission_aborts)
            out.update(self.pool.page_stats())
        if self._controller is not None:
            # steps that actually decoded degraded (latency-tier exemption
            # and dense families keep this below the controller's count)
            out["degraded_decode_steps"] = float(self._degraded_decode_steps)
            out.update(self._controller.stats())
        if self._speculate is not None:
            out["speculative_rounds"] = float(self._spec_rounds)
            # fraction of drafted tokens the verifier accepted — the
            # per-token "confident tokens need less depth" signal
            out["speculative_accept_rate"] = (
                self._spec_accepted_drafts / self._spec_drafted
                if self._spec_drafted
                else float("nan")
            )
            # mean accepted window per round — engine steps each slot
            # advances per host<->device round trip (1.0 = speculation
            # never beat plain decode; max is speculate + 1)
            out["speculative_tokens_per_round"] = (
                self._spec_emitted / self._spec_rounds
                if self._spec_rounds
                else 0.0
            )
        return out
