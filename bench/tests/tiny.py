"""Cells at a size the CPU runs in seconds, for the benchmark's own tests,
and faults of the timed serving path.

They keep the real cells' layout and settings (MoD every other block, paged
pool, chunked prefill, the same traffic and check code) at tiny widths.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchlib.harness import Cell

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {
    "n_layers": 4, "d_model": 64, "n_heads": 2, "n_kv_heads": 2, "head_dim": 32,
    "d_ff": 128, "vocab": 512, "max_seq_len": 256,
}


def _config(name: str) -> dict:
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    conf["model"].update(TINY_MODEL)
    return conf


def serve_cell(limits=None) -> Cell:
    conf = _config("mod-paper-1b")
    conf["engine"].update({"slots": 4, "ctx": 256, "page_size": 16, "prefill_chunk": 32})
    mix = json.loads((BENCH / "traffic" / "decode-saturated.json").read_text())
    mix.update({"requests": 144, "block": 8,
                "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.7, "min": 16, "max": 96},
                "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 64},
                "warmup": {"requests": 4, "output": {"dist": "linspace", "min": 4, "max": 12}},
                "check": {"requests": 3, "rank_steps": 8}})
    limits = limits or {"logit_gap_mean": 0.05, "route_margin_mean": 0.05,
                        "decode_margin_mean": 0.05, "decode_rows_off": 0}
    return Cell({"name": "tiny.serve", "chips": 1}, conf, mix, [], [], dict(limits))


def train_cell(limits=None) -> Cell:
    conf = _config("mod-paper-220m")
    mix = json.loads((BENCH / "traffic" / "train-seq2048.json").read_text())
    mix.update({"seq_len": 64, "batch": 4, "reference_rows": 2})
    limits = limits or {"loss_gap": 0.01, "grad_gap": 0.05, "change_gap": 0.1}
    return Cell({"name": "tiny.train", "chips": 1}, conf, mix, [], [], dict(limits))



def altered(engine) -> None:
    """A fault: the sixth token of every request, plus one, where it is
    sampled."""
    sample, vocab = engine._sample, engine.cfg.vocab
    engine._sample = lambda req, row, i: (sample(req, row, i) + (i == 5)) % vocab


def unseen(engine) -> None:
    """A fault: decode through the engine's own step, past the recorder."""
    engine._step_fn = engine._level_fns[0]
