"""The MoD-aware FLOP and byte counts against hand counts on a tiny config."""
import pytest

from benchlib import flops as F
from benchlib import harness as H

MT = H.family("mod_transformer")
S = MT.Spec(n_layers=2, d_model=4, n_heads=1, n_kv_heads=1, head_dim=4, d_ff=8, vocab=10,
              max_seq_len=64, norm_eps=1e-5, rope_theta=1e4, capacity_ratio=0.25, every=2,
              round_to=8, predictor_hidden=2, aux_loss_weight=0.01, dtype="bfloat16")


def test_per_token_pieces():
    # q, k, v, o projections 4x4 each, and a gated MLP 4x8 three times
    assert F.block_token_flops(S) == 2 * (4 * 16) + 2 * 3 * 32
    assert F.attn_pair_flops(S) == 4 * 4  # q.k and p.v over one 4-wide head
    assert MT.router_flops(S) == 8
    assert MT.predictor_flops(S) == 2 * 4 * 2 + 2 * 2
    assert F.unembed_flops(S) == 2 * 4 * 10


def test_capacities():
    assert S.capacity(4) == 1  # round(0.25 * 4), below round_to
    assert S.capacity(64) == 16  # a multiple of round_to
    assert S.capacity(12) == 8  # never below round_to once S >= round_to
    assert S.batch_capacity(16) == 4 and S.batch_capacity(1) == 1


def test_decode_step_counts_routed_rows_only():
    # two live rows at positions 3 and 5; one routed row whose ring holds 2
    got = MT.decode_step_flops(S, [3, 5], [[2]])
    full = 2 * 320 + 16 * (4 + 6)  # both rows, 4 and 6 keys
    routed = 320 + 16 * 2
    scoring = 2 * (8 + 20)  # router and predictor on both rows
    assert got == full + routed + scoring + 2 * 80


def test_decode_step_bytes():
    weights = sum(2 * n for n in (
        4 * 10, 4, 4 * 4 * 4 * 2, 4 * 4, 8 * 4 * 3 * 2)) + 4 * (2 + 4 * 2 + 2 + 4)
    # the leaves: unembed; final norm; q,k,v,o of two blocks; four norms;
    # three MLP matrices of two blocks; f32 router and predictor
    assert F.weight_bytes(S, 0) == weights
    assert F.weight_bytes(S, 2) == weights + 2 * 4 * 2  # two embedding rows
    kv = 2 * 4 * 2  # K and V of one token, one block, bf16
    got = MT.decode_step_bytes(S, [3, 5], [[2]])
    assert got == weights + 16 + kv * (4 + 6 + 2) + kv * (2 + 1) + 2 * 10 * 4


def test_chunk_counts_valid_tokens_and_routed_rings():
    # 3 valid tokens from position 8: 3 * 8 + 6 causal pairs; routed: one
    # token whose ring holds 5 entries
    got = MT.chunk_flops(S, 8, 3, [[5]])
    assert got == 3 * 320 + 16 * (24 + 6) + 320 + 16 * 5 + 3 * 8 + 80


def test_train_step_three_times_forward_without_recompute():
    seq, k = 8, S.capacity(8)
    fwd = (8 * 320 + 36 * 16) + (k * 320 + (k * (k + 1) // 2) * 16) + 8 * 8 + 8 * 80
    assert MT.train_step_flops(S, 2, seq) == 2 * (3 * fwd + 2 * 8 * 20)


def test_least_time_takes_the_larger_bound():
    assert F.least_time_s(2e12, 1e9, 1e12, 1e9) == pytest.approx(2.0)
    assert F.least_time_s(1e12, 4e9, 1e12, 1e9) == pytest.approx(4.0)


def test_every_leaf_is_counted_once():
    paths = [p for p, *_ in S.leaves()]
    assert len(paths) == len(set(paths)) == 3 + 9 + 9 + 4
