"""The model as a configuration file states it: plain numbers, no program.

``ModelSpec`` is what the weight generator, the reference and the FLOP/byte
counts read. The harness checks that the program's own config agrees with it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    max_seq_len: int
    norm_eps: float
    rope_theta: float
    capacity_ratio: float  # MoD: share of a sequence (or batch) routed
    every: int  # MoD: every other block is routed
    round_to: int  # MoD capacities round to a multiple of this
    predictor_hidden: int
    aux_loss_weight: float
    dtype: str  # the dtype the weights are served and trained in

    @classmethod
    def from_file(cls, model: Dict[str, Any], dtype: str) -> "ModelSpec":
        mod = model["mod"]
        return cls(
            n_layers=int(model["n_layers"]), d_model=int(model["d_model"]),
            n_heads=int(model["n_heads"]), n_kv_heads=int(model["n_kv_heads"]),
            head_dim=int(model["head_dim"]), d_ff=int(model["d_ff"]),
            vocab=int(model["vocab"]), max_seq_len=int(model["max_seq_len"]),
            norm_eps=float(model["norm_eps"]), rope_theta=float(model["rope_theta"]),
            capacity_ratio=float(mod["capacity_ratio"]), every=int(mod["every"]),
            round_to=int(mod["round_to"]), predictor_hidden=int(mod["predictor_hidden"]),
            aux_loss_weight=float(mod["aux_loss_weight"]), dtype=dtype,
        )

    @property
    def n_groups(self) -> int:
        """Layer pairs: one full block and one routed block each."""
        assert self.every == 2 and self.n_layers % 2 == 0, "paper layout only"
        return self.n_layers // 2

    def capacity(self, seq_len: int) -> int:
        """Routed tokens of a ``seq_len``-token sequence (or ring capacity):
        ``round(ratio * S)``, rounded down to a multiple of ``round_to`` but
        never below it once ``S >= round_to``."""
        c = int(round(self.capacity_ratio * seq_len))
        if seq_len >= self.round_to:
            c = max(self.round_to, (c // self.round_to) * self.round_to)
        return max(1, min(c, seq_len))

    def batch_capacity(self, batch: int) -> int:
        """Rows routed per decode step: ``max(1, round(ratio * B))``."""
        return max(1, int(round(self.capacity_ratio * batch)))
