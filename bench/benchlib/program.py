"""The program's side of a cell: its parameter layout against the family's.

The model family builds the program's config from the configuration file
(``program_config``); this checks that the weights the benchmark makes have
exactly the layout that config gives the program.
"""
from __future__ import annotations

from typing import Any

import jax


def check_layout(cfg: Any, spec: Any) -> None:
    """The benchmark's weights have exactly the program's parameter layout."""
    from repro.models import api

    want = jax.eval_shape(lambda k: api.init_model(k, cfg), jax.random.PRNGKey(0))
    flat = {tuple(getattr(p, "key") for p in path): (tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(want)[0]}
    mine = {path: (tuple(shape), str(jax.numpy.dtype(dt))) for path, shape, dt, _, _ in spec.leaves()}
    if flat != mine:
        diff = sorted(set(flat.items()) ^ set(mine.items()))
        raise ValueError(f"weight layout differs from the program's: {diff[:6]}")
