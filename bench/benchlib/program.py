"""The program's side of a cell: its model config, built from the file.

The configuration file is the truth. Its numbers are laid over the arch that
it names in the program's registry; with ``strict`` every number must already
agree with the registered arch, so the cell runs the model the program ships.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax

from .spec import ModelSpec
from .weights import leaves


def model_config(conf: Dict[str, Any], strict: bool = True) -> Tuple[Any, ModelSpec]:
    from repro.config import get_config

    m, mod = conf["model"], conf["model"]["mod"]
    base = get_config(conf["arch"])
    cfg = dataclasses.replace(
        base,
        n_layers=m["n_layers"], d_model=m["d_model"], d_ff=m["d_ff"], vocab=m["vocab"],
        max_seq_len=m["max_seq_len"], norm_eps=m["norm_eps"], act=m["act"], glu=m["glu"],
        tie_embeddings=m["tie_embeddings"], dtype=conf["dtype"], remat=m["remat"],
        attn=dataclasses.replace(base.attn, n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                                 head_dim=m["head_dim"], rope_theta=m["rope_theta"]),
        mod=dataclasses.replace(
            base.mod, enabled=True, capacity_ratio=mod["capacity_ratio"], every=mod["every"],
            gate=mod["gate"], sampling=mod["sampling"], predictor_hidden=mod["predictor_hidden"],
            round_to=mod["round_to"], router_type=mod["router_type"],
            aux_loss_weight=mod["aux_loss_weight"], backend=mod["backend"]),
    )
    if strict and dataclasses.replace(base, dtype=conf["dtype"]) != cfg:
        raise ValueError(f"{conf['name']}: the file's sizes differ from the program's "
                         f"{conf['arch']!r}")
    return cfg, ModelSpec.from_file(m, conf["dtype"])


def check_layout(cfg: Any, spec: ModelSpec) -> None:
    """The benchmark's weights have exactly the program's parameter layout."""
    from repro.models import api

    want = jax.eval_shape(lambda k: api.init_model(k, cfg), jax.random.PRNGKey(0))
    flat = {tuple(getattr(p, "key") for p in path): (tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(want)[0]}
    mine = {path: (tuple(shape), str(jax.numpy.dtype(dt))) for path, shape, dt, _, _ in leaves(spec)}
    if flat != mine:
        diff = sorted(set(flat.items()) ^ set(mine.items()))
        raise ValueError(f"weight layout differs from the program's: {diff[:6]}")
