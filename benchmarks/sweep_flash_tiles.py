"""Chip sweep of the flash-attention train kernels' tile sizes.

Times ``flash_fwd``, the one-pass backward ``flash_bwd`` and the split
backward pair ``flash_bwd_dq`` + ``flash_bwd_dkv`` (its fallback where the
resident dq does not fit) per call on a TPU at the benchmark's attention
shapes (smollm-360m: 4 x 4096 tokens, 15 heads over 5 KV heads of 64,
causal; CLIP ViT-Huge: 128 x 129 vision tokens, 16 heads of 80,
non-causal, and 128 x 77 text tokens, 16 heads of 64, causal), for every (Q cap, K cap) pair of ``--caps`` through
``ops.seq_tile`` and every explicit ``--tiles`` pair. Operands are padded
to the tiles as ``ops.py`` pads them. A pair the compiler refuses (scoped
VMEM) is recorded with its error. Exits non-zero without a TPU.

    python benchmarks/sweep_flash_tiles.py --caps 256,512,1024 \\
        --tiles 128:128 --out chiprun_out/flash_tiles.json
"""
from __future__ import annotations

import argparse
import importlib
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as FA

K = importlib.import_module("repro.kernels.flash_attention.flash_attention")

# name: (B, H, KV, hd, S, causal)
SHAPES = {"smollm": (4, 15, 5, 64, 4096, True),
          "clip_vision": (128, 16, 16, 80, 129, False),
          "clip_text": (128, 16, 16, 64, 77, True)}


def _time_ms(fn, args, iters, reps=3):
    jax.block_until_ready(fn(*args))
    per = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fn(*args)
        jax.block_until_ready(y)
        per.append((time.perf_counter() - t0) * 1e3 / iters)
    return statistics.median(per)


def sweep_shape(name, pairs, iters):
    B, H, KV, hd, S, causal = SHAPES[name]
    rows = []
    for bq, bk in pairs:
        Sq, Sk = -(-S // bq) * bq, -(-S // bk) * bk
        key = jax.random.PRNGKey(0)
        q, do = (jax.random.normal(k, (B, H, Sq, hd), jnp.bfloat16)
                 for k in jax.random.split(key, 2))
        kk, vv = (jax.random.normal(k, (B, KV, Sk, hd), jnp.bfloat16)
                  for k in jax.random.split(jax.random.PRNGKey(1), 2))
        stat = jnp.zeros((B, H, Sq, 1), jnp.float32)
        kw = dict(causal=causal, kv_valid=S, scale=hd ** -0.5,
                  block_q=bq, block_k=bk)
        kernels = {
            "fwd": (jax.jit(lambda q, k, v: K.flash_fwd(q, k, v, **kw)),
                    (q, kk, vv)),
            "dq": (jax.jit(lambda *a: K.flash_bwd_dq(*a, **kw)),
                   (q, kk, vv, do, stat, stat)),
            "dkv": (jax.jit(lambda *a: K.flash_bwd_dkv(*a, **kw)),
                    (q, kk, vv, do, stat, stat)),
            "bwd": (jax.jit(lambda *a: K.flash_bwd(*a, **kw)),
                    (q, kk, vv, do, stat, stat)),
        }
        row = {"shape": name, "block_q": bq, "block_k": bk,
               "grid_steps": B * KV * (Sq // bq) * (Sk // bk)}
        for kind, (fn, args) in kernels.items():
            try:
                row[f"{kind}_ms"] = _time_ms(fn, args, iters)
            except Exception as e:          # compile refused: record it
                row[f"{kind}_ms"] = None
                row[f"{kind}_error"] = str(e).splitlines()[0][:200]
        if row["dq_ms"] is not None and row["dkv_ms"] is not None:
            row["dq_plus_dkv_ms"] = row["dq_ms"] + row["dkv_ms"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--caps", default="256,512,1024")
    ap.add_argument("--tiles", default="128:128",
                    help="explicit block_q:block_k pairs, comma-separated")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    caps = [int(c) for c in args.caps.split(",") if c]
    explicit = [tuple(int(x) for x in t.split(":"))
                for t in args.tiles.split(",") if t]
    rows = []
    for name in args.shapes.split(","):
        S = SHAPES[name][4]
        pairs = explicit + [(FA.seq_tile(S, cq), FA.seq_tile(S, ck))
                            for cq, ck in itertools.product(caps, repeat=2)]
        rows += sweep_shape(name, list(dict.fromkeys(pairs)), args.iters)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
