"""The one traffic generator. A traffic file (``bench/traffic/<name>.json``)
holds only parameters; this module turns them and a seed into requests.

A serving mix is a backlog: every request is queued up front. Every seed gets
the same set of sizes, in another order: sizes come from a grid of ``block``
quantiles of the stated distribution, and each run of ``block`` consecutive
requests holds every quantile once, shuffled by the seed. So any window of the
stream sees nearly the same mix whatever the seed, and the seed changes only
the order and the token ids.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class ServeRequest:
    prompt: np.ndarray  # int32 token ids
    max_new: int
    warmup: bool


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def quantile_grid(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n of ``dist``, clipped to its
    ``min``/``max`` and rounded to whole numbers."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        v = float(dist["min"]) + (float(dist["max"]) - float(dist["min"])) * u
    elif kind == "linspace":
        v = np.linspace(float(dist["min"]), float(dist["max"]), n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    v = np.clip(v, float(dist.get("min", -np.inf)), float(dist.get("max", np.inf)))
    return np.rint(v).astype(np.int64)


def _stratified(dist: Dict[str, Any], count: int, block: int, g: np.random.Generator) -> np.ndarray:
    grid = quantile_grid(dist, block)
    out = [grid[g.permutation(block)] for _ in range(-(-count // block))]
    return np.concatenate(out)[:count] if out else np.zeros(0, np.int64)


def serve_requests(spec: Dict[str, Any], seed: int, vocab: int) -> List[ServeRequest]:
    """Warm-up requests first, then the backlog."""
    if spec["kind"] != "serve":
        raise ValueError(f"not a serving traffic mix: {spec['kind']!r}")
    block = int(spec["block"])
    g = rng(seed, 0)
    out: List[ServeRequest] = []
    warm = spec.get("warmup", {})
    n_warm = int(warm.get("requests", 0))
    if n_warm:
        w_prompt = _stratified(warm.get("prompt", spec["prompt"]), n_warm, n_warm, g)
        w_out = quantile_grid(warm["output"], n_warm)[g.permutation(n_warm)]
    n = int(spec["requests"])
    prompts = _stratified(spec["prompt"], n, block, g)
    outputs = _stratified(spec["output"], n, block, g)
    tok = rng(seed, 1)
    for i in range(n_warm):
        out.append(ServeRequest(tok.integers(0, vocab, int(w_prompt[i]), dtype=np.int32),
                                int(w_out[i]), True))
    for i in range(n):
        out.append(ServeRequest(tok.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                                int(outputs[i]), False))
    return out


def train_batch(spec: Dict[str, Any], seed: int, step: int, vocab: int) -> Dict[str, np.ndarray]:
    """Step ``step``'s rows: uniform token ids, every row its own draw."""
    if spec["kind"] != "train":
        raise ValueError(f"not a training job: {spec['kind']!r}")
    B, S = int(spec["batch"]), int(spec["seq_len"])
    x = rng(seed, 2, step).integers(0, vocab, (B, S + 1), dtype=np.int32)
    return {"tokens": x[:, :-1], "labels": x[:, 1:]}
