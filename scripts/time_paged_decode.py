#!/usr/bin/env python
"""Time the live-page decode kernel alone, on a TPU, at the decode cells' shapes.

Builds a layer-stacked bf16 pool of 14 heads of 128 in 16-row pages with
16 slots of up to 128 pages (ctx 2048): 12 layers of 2,050 pages
(``mod-paper-1b``) and 24 of 1,538 (its dense twin). Slot lengths are drawn
so that about 0.27 of the table's pages are live, the share the decode
cells read, and each slot's pages are scattered over the pool. For each
shape it times one call per layer, as the decode step makes them, for

- ``per_page``: the formulation with each page a grid-pipelined input
  (``kernels/paged._per_page_attention``, the read kept for head sizes that
  are not whole 128-lane tiles, pages whose rows are not a multiple of 8 and
  pools whose position plane does not fit VMEM);
- ``blocks``: ``paged_decode_attention`` as the step runs it (live pages
  copied by hand, one softmax update per block of 128 keys);

checks both against the XLA formulation in float32, and prints one JSON
line per shape and formulation: µs per call, µs per live page, and the
K/V read time of a page at the chip's HBM rate (819 GB/s on a v5e).

    python scripts/time_paged_decode.py [--reps 40] [--seed 0]

Writes the lines to ``chiprun_out/time_paged_decode.jsonl`` as well.
Refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.kernels import paged as PG  # noqa: E402

SLOTS, PAGE, NKV, HD, P = 16, 16, 14, 128, 128
SHAPES = {"mod-paper-1b": (12, 2050), "mod-paper-1b-vanilla": (24, 1538)}
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")


def build(L, N, seed):
    """Pool, position plane, table and query positions of one shape."""
    rng = np.random.default_rng(seed)
    # lengths 64 .. 1040 of the 2048 rows: 0.27 of the pages live on average
    ctx = P * PAGE
    q_pos = rng.integers(ctx // 32, ctx * 65 // 128, size=SLOTS).astype(np.int32)
    table = np.zeros((SLOTS, P), np.int32)  # NULL page past the live length
    pos = np.full((PAGE, N), -1, np.int32)
    ids = rng.permutation(np.arange(2, N))
    n = 0
    for b, qp in enumerate(q_pos):
        for i in range(qp // PAGE + 1):
            table[b, i] = ids[n]
            t = i * PAGE + np.arange(PAGE)
            pos[:, ids[n]] = np.where(t <= qp, t, -1)
            n += 1
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (L, N, NKV, PAGE, HD)
    k = jax.random.normal(k0, shape, jnp.bfloat16)
    v = jax.random.normal(k1, shape, jnp.bfloat16)
    q = jax.random.normal(k2, (SLOTS, NKV, HD), jnp.bfloat16)
    pos = jnp.asarray(np.broadcast_to(pos, (L, PAGE, N)))
    live = sum(qp // PAGE + 1 for qp in q_pos)
    return q, k, v, pos, jnp.asarray(table), jnp.asarray(q_pos), live


def per_page(q, k, v, pos, table, layer, q_pos):
    return PG._per_page_attention(
        q, k, v, pos, table, layer, q_pos, causal=True, window=0,
        scale=HD**-0.5, ppb=8, interpret=False)


def blocks(q, k, v, pos, table, layer, q_pos):
    return PG.paged_decode_attention(q, k, v, pos, table, layer, q_pos)


def oracle(q, k, v, pos, table, layer, q_pos):
    """The XLA formulation (gather by the table, then a masked softmax) in f32."""
    kk = jnp.take(k[layer], table, axis=0).astype(jnp.float32)  # (B, P, nkv, p, hd)
    vv = jnp.take(v[layer], table, axis=0).astype(jnp.float32)
    pp = jnp.take(pos[layer].T, table, axis=0)  # (B, P, p)
    s = jnp.einsum("bhd,bihrd->bhir", q.astype(jnp.float32), kk,
                   precision=jax.lax.Precision.HIGHEST) * HD**-0.5
    ok = (pp >= 0) & (pp <= q_pos[:, None, None])
    s = jnp.where(ok[:, None], s, -1e30)
    w = jax.nn.softmax(s.reshape(*s.shape[:2], -1), axis=-1).reshape(s.shape)
    return jnp.einsum("bhir,bihrd->bhd", w, vv, precision=jax.lax.Precision.HIGHEST)


FORMULATIONS = {"per_page": jax.jit(per_page), "blocks": jax.jit(blocks)}
ORACLE = jax.jit(oracle)


def every_layer(fn, L):
    """One call per layer, as the step's layer scan makes them."""

    def run(q, k, v, pos, table, q_pos):
        def body(acc, layer):
            return acc + fn(q, k, v, pos, table, layer, q_pos), None

        acc, _ = jax.lax.scan(body, jnp.zeros(q.shape, jnp.float32), jnp.arange(L, dtype=jnp.int32))
        return acc

    return jax.jit(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=40, help="timed scans over every layer")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"time_paged_decode: needs a TPU, found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(os.path.join("chiprun_out", "time_paged_decode.jsonl"), "w")
    page_read_us = 2 * NKV * PAGE * HD * 2 / HBM_BYTES_PER_S * 1e6
    for name, (L, N) in SHAPES.items():
        q, k, v, pos, table, q_pos, live = build(L, N, args.seed)
        last = jnp.int32(L - 1)
        want = np.asarray(ORACLE(q, k, v, pos, table, last, q_pos))
        for label, fn in FORMULATIONS.items():
            got = np.asarray(fn(q, k, v, pos, table, last, q_pos))
            err = float(np.max(np.abs(got - want)))
            run = every_layer(fn, L)
            run(q, k, v, pos, table, q_pos).block_until_ready()  # compile, warm
            t0 = time.perf_counter()
            for _ in range(args.reps):
                acc = run(q, k, v, pos, table, q_pos)
            acc.block_until_ready()
            call_us = (time.perf_counter() - t0) / (args.reps * L) * 1e6
            line = {
                "shape": name, "formulation": label, "layers": L, "pages": N,
                "live_pages": int(live), "live_share": round(live / (SLOTS * P), 4),
                "us_per_call": round(call_us, 2),
                "us_per_live_page": round(call_us / live, 4),
                "kv_read_us_per_page": round(page_read_us, 4),
                "max_abs_err_vs_f32": err, "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
        del k, v
    out.close()


if __name__ == "__main__":
    main()
