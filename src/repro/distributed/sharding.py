"""Logical sharding rules: param/optimizer/batch/cache pytrees -> NamedSharding.

Rules are (path-regex -> trailing-dim spec) applied to flattened param paths;
leading scan-stack dims (layer groups, hybrid segments) are always unsharded.
Every rule is validated for divisibility against the actual mesh — a dim
that does not divide evenly falls back to replication instead of failing,
which is what makes one rule table serve all 10 architectures (e.g.
whisper's 6 kv heads or granite-20b's MQA simply replicate K/V under a
16-way model axis).

Axis semantics:
  "model"          tensor/expert parallelism (TP within a pod row)
  "data" (+"pod")  data parallelism; with ``fsdp=True`` params and optimizer
                   state are also sharded over "data" (ZeRO-3 style:
                   all-gather on use, reduce-scatter on grad)
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ModelConfig
from repro.utils import flatten_dict

# trailing-dim templates; "F" is replaced by "data" under fsdp else None
_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    # tok: shard D over model (gather over a vocab-sharded table forces
    # involuntary replication in SPMD); unemb: V over model so logits and
    # the CE logsumexp stay vocab-sharded.
    (r"embed/tok$", (None, "model")),
    (r"embed/unemb$", ("F", "model")),
    (r"x?attn/w[qkv]$", ("F", "model")),
    (r"x?attn/b[qkv]$", ("model",)),
    (r"x?attn/wo$", ("model", "F")),
    (r"moe/w_(up|gate)$", ("model", "F", None)),
    (r"moe/w_down$", ("model", None, "F")),
    (r"moe/router_w$", (None, None)),
    (r"mlp/w_(up|gate)$", ("F", "model")),
    (r"mlp/w_down$", ("model", "F")),
    (r"ssm/w_[zx]$", ("F", "model")),
    (r"ssm/w_(B|C|dt)$", ("F", None)),
    (r"ssm/conv_x$", (None, "model")),
    (r"ssm/conv_(B|C)$", (None, None)),
    (r"ssm/conv_bx$", ("model",)),
    (r"ssm/conv_b[BC]$", (None,)),
    (r"ssm/(A_log|skip_D|dt_bias)$", ("model",)),
    (r"ssm/norm/scale$", ("model",)),
    (r"ssm/out_proj$", ("model", "F")),
    (r"(router|predictor)/", (None,)),  # routers: tiny, replicated
)


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in (name if isinstance(name, tuple) else (name,))]))


def _validated(spec, shape, mesh: Mesh):
    out = []
    for dim, ax in zip(shape, spec):
        if ax is not None and dim % _axis_size(mesh, ax) != 0:
            ax = None  # fall back to replication
        out.append(ax)
    # drop trailing Nones for cleanliness
    return P(*out)


def param_pspec(path: str, shape: Tuple[int, ...], mesh: Mesh, mesh_cfg: MeshConfig) -> P:
    for pat, trailing in _RULES:
        if re.search(pat, path):
            t = tuple(("data" if mesh_cfg.fsdp else None) if a == "F" else a for a in trailing)
            full = (None,) * max(0, len(shape) - len(t)) + t[: len(shape)]
            return _validated(full, shape, mesh)
    return P(*([None] * len(shape)))


def param_shardings(tree: Any, mesh: Mesh, mesh_cfg: MeshConfig) -> Any:
    """Pytree of NamedShardings matching `tree` (arrays or ShapeDtypeStructs)."""
    flat = flatten_dict(tree)
    out = {
        k: NamedSharding(mesh, param_pspec(k, v.shape, mesh, mesh_cfg)) for k, v in flat.items()
    }
    from repro.utils import unflatten_dict

    return unflatten_dict(out)


def state_shardings(state_tree: Any, mesh: Mesh, mesh_cfg: MeshConfig) -> Any:
    """Train state {params, opt{m,v,count}, step}: moments mirror params."""
    ps = param_shardings(state_tree["params"], mesh, mesh_cfg)
    scalar = NamedSharding(mesh, P())
    return {
        "params": ps,
        "opt": {"m": ps, "v": ps, "count": scalar},
        "step": scalar,
    }


# ---------------------------------------------------------------------------
# SPMD routed-execution context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context for SPMD routed execution (DESIGN.md §SPMD routed
    execution).

    Separates two orthogonal things:

    - **semantics** (``data_shards``): the ``batch_capacity`` decode
      strategy partitions the batch into ``data_shards`` contiguous groups
      and routes the top ``kb_local = round(ratio·B/d)`` sequences *within
      each group*, preserving the global ``ratio·B`` budget without any
      cross-group communication. ``token_topk`` is per-sequence, so its
      semantics never depend on the partitioning.
    - **execution** (``mesh``): when a real :class:`Mesh` is attached, the
      routing decision and the gather/gated-scatter dispatch run per-shard
      inside ``shard_map`` over ``data_axes`` (the ``(B, S, D)`` stream is
      never resharded across devices), while ``model_axis`` stays under
      GSPMD ("auto") so routed block deltas keep the existing
      tensor-parallel layouts — psum only where the dense path already
      implies it.

    A ``ShardCtx(mesh=None, data_shards=d)`` runs the *same partitioned
    semantics* on a single device — the reference the SPMD equivalence
    tests compare against (``tests/test_routing_spmd.py``).
    """

    mesh: Optional[Mesh] = None
    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    data_shards: int = 1
    # params sharded over the data axes too (ZeRO-3): per-shard fused
    # kernels would see weight fragments, so fused dispatch must fall back
    fsdp: bool = False

    @property
    def spmd(self) -> bool:
        """True when dispatch should actually run per-shard via shard_map."""
        return self.mesh is not None and bool(self.data_axes)

    @property
    def model_shards(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return int(self.mesh.shape[self.model_axis])

    def data_spec(self, ndim: int, batch_axis: int = 0) -> P:
        """PartitionSpec sharding ``batch_axis`` over the data axes."""
        spec: list = [None] * ndim
        if self.data_axes:
            spec[batch_axis] = self.data_axes
        return P(*spec)

    def check_batch(self, batch: int) -> None:
        if self.data_shards > 1 and batch % self.data_shards != 0:
            raise ValueError(
                f"batch {batch} not divisible by data_shards={self.data_shards}"
            )

    def semantic_only(self) -> "ShardCtx":
        """Same partitioned routing semantics, but dispatch under GSPMD
        instead of shard_map. Blocks whose inner compute cannot run in a
        manual region on this XLA version (expert top-k lowers to a sort,
        which the partitioner rejects inside a manual subgroup) downgrade
        to this — routing decisions, budgets, and token streams are
        unchanged; only the shard-locality guarantee of the dispatch is
        delegated to the GSPMD partitioner."""
        return dataclasses.replace(self, mesh=None)


def shard_ctx(
    mesh: Optional[Mesh], data_shards: Optional[int] = None, fsdp: bool = False
) -> ShardCtx:
    """Build a :class:`ShardCtx` from a mesh (or a bare shard count).

    ``shard_ctx(mesh)`` — SPMD execution: batch over the present
    ``("pod", "data")`` axes, ``"model"`` (if present) left to GSPMD.
    ``shard_ctx(None, data_shards=d)`` — partitioned semantics only
    (single-device reference).
    """
    if mesh is None:
        return ShardCtx(data_shards=int(data_shards or 1), fsdp=fsdp)
    if any(t != AxisType.Auto for t in mesh.axis_types):
        # the block code is written for GSPMD propagation: under explicit
        # axes jax refuses its gathers and model-sharded contractions
        raise ValueError(
            f"mesh axis types {mesh.axis_types} are not all Auto; build the "
            "mesh with repro.launch.mesh (jax.make_mesh defaults to Explicit)"
        )
    bd = batch_axes(mesh)
    d = int(np.prod([mesh.shape[a] for a in bd])) if bd else 1
    if data_shards is not None and int(data_shards) != d:
        raise ValueError(f"data_shards={data_shards} != mesh data degree {d}")
    model = "model" if "model" in mesh.shape else None
    return ShardCtx(mesh=mesh, data_axes=bd, model_axis=model, data_shards=d, fsdp=fsdp)


# ---------------------------------------------------------------------------
# Batch / activation shardings
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def constrain_replicated(x: jax.Array) -> jax.Array:
    """All-gather a tensor to full replication under the ambient mesh.

    Used on the token-embedding table before the lookup: gathering from a
    sharded table makes the SPMD partitioner reshard the gather *output*,
    which both replicates involuntarily and can emit an invalid
    dynamic-slice. All-gathering the (comparatively tiny) table first keeps
    the gather local. No-op without a mesh context.
    """
    if not jax.sharding.get_abstract_mesh().axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))


def constrain_spec(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint under the ambient mesh, with divisibility
    validation (falls back to None per-dim). No-op outside a mesh context."""
    mesh = jax.sharding.get_abstract_mesh()  # no axes outside jax.set_mesh
    if not mesh.axis_names:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax is not None:
            names = ax if isinstance(ax, tuple) else (ax,)
            if not all(a in mesh.axis_names for a in names):
                ax = None
            elif dim % int(np.prod([mesh.shape[a] for a in names])) != 0:
                ax = None
        spec.append(ax)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Pin an activation to P((pod, data), None, ...) under the ambient mesh.

    Scan carries need a *consistent* sharding across iterations: the embed
    output is D-sharded (model) while block outputs are batch-sharded; left
    alone, the SPMD partitioner resolves the mismatch by replicating the
    whole loop state (observed: one unsharded f32 (B,S,D) buffer per
    device). Model code calls this on scan carries; it is a no-op outside a
    mesh context (single-device tests).
    """
    mesh = jax.sharding.get_abstract_mesh()  # no axes outside jax.set_mesh
    if not mesh.axis_names:
        return x
    bd = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not bd:
        return x
    size = int(np.prod([mesh.shape[a] for a in bd]))
    if x.ndim == 0 or x.shape[0] % size != 0 or x.shape[0] == 0:
        return x
    spec = P(bd, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def batch_shardings(batch_tree: Any, mesh: Mesh) -> Any:
    """Shard the leading batch dim over (pod, data); VLM M-RoPE positions
    (3, B, S) shard dim 1."""
    bd = batch_axes(mesh)
    bd_size = _axis_size(mesh, tuple(bd))

    def one(path, v):
        if path.endswith("positions") and v.ndim == 3 and v.shape[0] == 3:
            spec = (None, bd, None) if v.shape[1] % bd_size == 0 else (None, None, None)
        else:
            lead = bd if v.shape[0] % bd_size == 0 else None
            spec = (lead,) + (None,) * (v.ndim - 1)
        return NamedSharding(mesh, P(*spec))

    flat = flatten_dict(batch_tree)
    from repro.utils import unflatten_dict

    return unflatten_dict({k: one(k, v) for k, v in flat.items()})


def cache_shardings(cache_tree: Any, mesh: Mesh, cfg: ModelConfig, batch: int) -> Any:
    """Decode-cache shardings.

    Batched serving (B divisible by DP degree): batch over (pod, data),
    head_dim over "model" (uniform across GQA/MQA since every head_dim here
    divides 16; kv-head counts often don't).

    B=1 long-context: sequence dim of KV caches over "data" (sequence
    parallelism); SSM state heads over "model".
    """
    bd = batch_axes(mesh)
    bd_size = _axis_size(mesh, tuple(bd))
    b_ok = batch % bd_size == 0

    def one(path, v):
        leaf = path.rsplit("/", 1)[-1]
        nd = v.ndim
        spec: list = [None] * nd
        if leaf in ("k", "v"):  # (..., B, C, nkv, hd)
            if b_ok:
                spec[nd - 4] = bd
            else:
                spec[nd - 3] = "data"  # sequence-parallel cache
            if v.shape[nd - 1] % _axis_size(mesh, "model") == 0:
                spec[nd - 1] = "model"
        elif leaf == "pos":  # (..., B, C)
            if b_ok:
                spec[nd - 2] = bd
            else:
                spec[nd - 1] = "data"
        elif leaf == "cursor":  # (..., B)
            if b_ok:
                spec[nd - 1] = bd
        elif leaf == "state":  # (..., B, H, hd, ds)
            if b_ok:
                spec[nd - 4] = bd
            if v.shape[nd - 3] % _axis_size(mesh, "model") == 0:
                spec[nd - 3] = "model"
        elif leaf.startswith("conv_"):  # (..., B, W-1, C)
            if b_ok:
                spec[nd - 3] = bd
            if v.shape[nd - 1] % _axis_size(mesh, "model") == 0:
                spec[nd - 1] = "model"
        return NamedSharding(mesh, _validated(tuple(spec), v.shape, mesh))

    flat = flatten_dict(cache_tree)
    from repro.utils import unflatten_dict

    return unflatten_dict({k: one(k, v) for k, v in flat.items()})
