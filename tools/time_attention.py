"""Time the Pallas attention kernel alone on the chip, per tile plan.

    python tools/time_attention.py [--out results/attention_tiles.jsonl]
        [--only-default]

For each benchmark cell's attention shape (sst2-k5, long2k, Yi-6B stage)
it times `ops.attention(impl="pallas")` (the kernel as the model calls
it) and, where the tree has `make_plan`, each candidate plan, as the mean
of `n` back-to-back calls in one jitted loop (host clock, after a warm-up
call). It also checks a few edge shapes (decode, kv tail, window,
sq < skv) against `ref.attention_ref` at `highest` precision. Needs a TPU.
One JSON line per timing goes to `--out`.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref

# (name, q shape, kv shape, calls per timed loop)
SHAPES = [("sst2-k5", (40, 12, 64, 64), (40, 12, 64, 64), 400),
          ("long2k", (4, 12, 2048, 64), (4, 12, 2048, 64), 40),
          ("yi-squad512", (8, 32, 512, 128), (8, 4, 512, 128), 40)]

CANDIDATES = {   # explicit (blk_q, blk_k, rows, heads) beside the default
    "sst2-k5": [(64, 64, r, 1) for r in (1, 4, 8, 16, 32, 60, 120)],
    "long2k": [(128, 128, 1, 1), (256, 256, 1, 1), (256, 512, 1, 1),
               (512, 256, 1, 1), (512, 512, 1, 1), (512, 512, 2, 1),
               (1024, 512, 1, 1), (1024, 1024, 1, 1)],
    "yi-squad512": [(512, 512, 1, 8), (256, 512, 1, 8), (128, 512, 1, 8),
                    (256, 256, 1, 8), (128, 128, 1, 8), (512, 512, 1, 4),
                    (512, 512, 1, 2), (512, 512, 1, 1), (128, 512, 2, 8)],
}

# (b, hq, hkv, sq, skv, d, window): shapes only the chip can check
EDGES = [(1, 4, 2, 1, 64, 32, None), (2, 4, 4, 1000, 1000, 64, None),
         (1, 8, 8, 2048, 2048, 64, 300), (1, 4, 4, 700, 1500, 64, None),
         (16, 8, 2, 64, 64, 64, None)]


def _inputs(qs, ks, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, qs, jnp.float32),
            jax.random.normal(kk, ks, jnp.float32),
            jax.random.normal(kv, ks, jnp.float32))


def _time(attn, q, k, v, n, reps=5):
    loop = jax.jit(lambda q, k, v: jax.lax.fori_loop(
        0, n, lambda i, x: attn(x, k, v), q))
    jax.block_until_ready(loop(q, k, v))                 # compile, warm
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(loop(q, k, v))
        ts.append((time.perf_counter() - t) / n)
    return statistics.median(ts), min(ts)


def _err(out, q, k, v, window):
    with jax.default_matmul_precision("highest"):
        want = ref.attention_ref(q, k, v, causal=True, window=window)
    return float(jnp.max(jnp.abs(out - want)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/attention_tiles.jsonl")
    ap.add_argument("--only-default", action="store_true")
    a = ap.parse_args()
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"needs a TPU, found {dev.platform}"
    planned = hasattr(fa, "make_plan")
    rows = []

    def emit(**row):
        row.update(device=dev.device_kind)
        rows.append(row)
        print(json.dumps(row), flush=True)

    for name, qs, ks, n in SHAPES:
        q, k, v = _inputs(qs, ks)
        default = lambda x, k, v: ops.attention(x, k, v, causal=True,
                                                impl="pallas")
        med, best = _time(default, q, k, v, n)
        plan = {}
        if planned:
            p = fa.attention_tile_plan(qs[0], qs[1], ks[1], qs[2], ks[2],
                                       qs[3])
            plan = dict(blk_q=p.blk_q, blk_k=p.blk_k, rows=p.rows,
                        heads=p.heads, steps=p.steps, dead=p.dead,
                        vmem_mib=p.vmem_bytes / 2**20)
        emit(shape=name, plan="default", ms=1e3 * med, ms_min=1e3 * best,
             err=_err(default(q, k, v), q, k, v, None), **plan)
        if not planned or a.only_default:
            continue
        for tq, tk, r, h in CANDIDATES[name]:
            p = fa.make_plan(qs[0], qs[1], ks[1], qs[2], ks[2], qs[3],
                             ks[3], True, None, blk_q=tq, blk_k=tk, rows=r,
                             heads=h)
            attn = lambda x, k, v, p=p: fa.flash_attention_planned(
                x, k, v, p, causal=True)
            try:
                med, best = _time(attn, q, k, v, n)
                err = _err(attn(q, k, v), q, k, v, None)
            except Exception as e:  # noqa: BLE001 - a plan Mosaic refuses
                emit(shape=name, plan=[tq, tk, r, h], error=str(e)[:300])
                continue
            emit(shape=name, plan=[tq, tk, r, h], ms=1e3 * med,
                 ms_min=1e3 * best, err=err, steps=p.steps,
                 vmem_mib=p.vmem_bytes / 2**20)

    for b, hq, hkv, sq, skv, d, window in EDGES:
        q, k, v = _inputs((b, hq, sq, d), (b, hkv, skv, d), seed=1)
        out = ops.attention(q, k, v, causal=True, window=window,
                            impl="pallas")
        emit(shape=[b, hq, hkv, sq, skv, d, window], plan="edge",
             err=_err(out, q, k, v, window))

    with open(a.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
