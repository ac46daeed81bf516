"""Attention kernel benchmark: fwd / bwd / decode cost vs backend across
(Sq, Sk, H, hd, GQA ratio) — the flash-attention analogue of
``bench_switchback_ops``.

No TPU in this container, so the xla-vs-pallas contrast is
roofline-derived from the paths' HBM traffic and FLOPs (the same
819 GB/s / 197 TFLOP/s model as §Roofline):

* **xla flash_scan** re-materialises the (B, H, Sq, chunk) score/prob
  tile and rewrites the (m, l, acc) carry to HBM every scan step, and
  pays the GQA ``jnp.repeat`` K/V expansion (H/KV× the cache bytes).
* **pallas fused** reads Q once, streams K/V tiles at KV-head width (one
  re-stream per query head × Q tile — counted, not idealised away), keeps
  scores and the online-softmax state in VMEM, writes O (+lse) once;
  causal tiles above the diagonal are neither fetched nor computed.
* **decode**: the dense re-attend touches all S_max cache cells per step;
  the decode kernel's dynamic tile skip touches ceil(len/block) tiles —
  modeled at the expected steady-state fill len = S_max/2.
* **paged** (``kind: "paged"``): the block-table kernels at query widths
  Sq in (1, 4, 8) — Sq=1 is the paged decode step, Sq>1 the chunked-
  prefill / speculative-verify shape (Sq = spec_k + 1). The xla oracle
  gathers the whole table into a dense window; the kernel streams only
  live blocks via scalar-prefetch index maps. ``modeled: true`` on this
  CPU container, with a measured dispatch-layer row alongside.

Wall-clock is additionally measured through the dispatch layer
(kernels/flash_attention/ops.py) for every backend that can run here:
``xla`` always, ``pallas`` only on a TPU, ``pallas_interpret`` only as a
tiny plumbing smoke (the interpreter is orders of magnitude slower —
numbers are not meaningful).

    PYTHONPATH=src python -m benchmarks.bench_attention \
        --out results/bench/attention.json
    PYTHONPATH=src python -m benchmarks.bench_attention --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.distributed.roofline import HBM_BW, PEAK_BF16
from repro.kernels.flash_attention import ops as FA


def _t(flops: float, bytes_: float) -> float:
    return max(flops / PEAK_BF16, bytes_ / HBM_BW)


def model_times(B, Sq, Sk, H, KV, hd, causal, *, chunk=1024, kind="fwd"):
    """Roofline times (s) for one attention op on each backend path."""
    causal_frac = 0.5 if (causal and Sq == Sk) else 1.0
    flops = 4.0 * B * Sq * Sk * H * hd * causal_frac          # QKᵀ + PV
    if kind == "bwd":
        flops *= 2.5                                           # dq+dk+dv
    q_bytes = 2 * B * Sq * H * hd
    kv_bytes = 2 * 2 * B * Sk * KV * hd
    o_bytes = 2 * B * Sq * H * hd
    lse_bytes = 4 * B * H * Sq
    n_chunks = max(1, -(-min(Sk, Sq if causal else Sk) // chunk))
    bq, bk = FA.choose_attn_blocks(Sq, Sk, group=H // KV, hd=hd)
    n_q_t, n_k_t = -(-Sq // bq), -(-Sk // bk)
    # xla scan: expanded K/V (H heads), f32 score+prob tiles written+read,
    # (m, l, acc) carry rewritten per chunk
    xla_bytes = (q_bytes + kv_bytes * (H // KV) + o_bytes
                 + n_chunks * (2 * 4 * B * H * Sq * chunk      # s, p
                               + 2 * 4 * B * H * Sq * (hd + 2)))  # carry
    if kind == "bwd":
        xla_bytes *= 2.5
    # pallas fwd: Q/O once; each KV tile re-streamed once per Q tile (the
    # grid walks KV heads and the in-kernel group loop shares the tile
    # across the head's whole GQA query group); causal skips dead tiles
    kv_stream = kv_bytes * n_q_t * causal_frac
    pallas_bytes = q_bytes + o_bytes + lse_bytes + kv_stream
    if kind == "bwd":
        # dq kernel: q/do/dq + lse/di once, KV re-streamed as in fwd;
        # dkv kernel: K/V once + f32 dk/dv out, q/do re-streamed per KV
        # tile (grid (B, KV, nk, nq))
        pallas_bytes = (3 * q_bytes + 2 * lse_bytes + kv_stream
                        + 3 * kv_bytes
                        + 2 * q_bytes * n_k_t * causal_frac)
    return {"xla": _t(flops, xla_bytes), "pallas": _t(flops, pallas_bytes)}


def model_decode_times(B, S_max, H, KV, hd, *, block=FA.DECODE_BLOCK):
    """Per-step decode attention: dense full-window vs length-bounded
    tiles at the steady-state expected fill S_max/2. Charging the kernel
    only live-tile bytes is faithful: the scalar-prefetch index maps
    clamp dead tiles so their HBM fetch never happens (flash_attention.py
    decode_fwd), not just their FLOPs."""
    flops_full = 4.0 * B * S_max * H * hd
    cache = 2 * 2 * B * S_max * KV * hd
    xla = _t(flops_full, cache * (H // KV) + 4 * B * H * S_max)
    live = -(-(S_max // 2) // block) * block
    pallas = _t(flops_full * live / S_max,
                2 * 2 * B * live * KV * hd + 2 * 2 * B * H * hd)
    return {"xla": xla, "pallas": pallas}


def model_paged_times(B, Sq, nb, bs, H, KV, hd):
    """Paged attention at query width Sq over an nb-block table (fill
    L = nb*bs/2, the steady state): Sq=1 is the decode step, Sq>1 is the
    chunked-prefill / speculative-verify shape (Sq = spec_k + 1 scores
    the whole draft in one pass). The xla oracle gathers the full table
    into a dense (B, nb*bs) window — pool read + dense write + GQA
    expansion + f32 scores over every cell; the kernel streams only the
    slot's live blocks through the table's scalar-prefetch index maps
    (dead blocks skip DMA *and* FLOPs), re-streamed once per Q tile
    (one tile for Sq <= 8)."""
    win = nb * bs
    live = win // 2 + Sq
    live_b = -(-live // bs) * bs                   # block-granular stream
    flops_live = 4.0 * B * H * hd * Sq * live
    q_bytes = 2 * B * Sq * H * hd
    pool_kv = 2 * 2 * B * win * KV * hd
    xla_bytes = (2 * pool_kv + pool_kv * (H // KV)  # gather + expand
                 + 2 * 4 * B * H * Sq * win         # f32 scores r/w
                 + 2 * q_bytes)                     # q + o
    pallas_bytes = 2 * q_bytes + 2 * 2 * B * live_b * KV * hd
    return {"xla": _t(4.0 * B * H * hd * Sq * win, xla_bytes),
            "pallas": _t(flops_live, pallas_bytes)}


def _wallclock(f, *args, iters=3):
    y = jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        y = jax.block_until_ready(f(*args))
    del y
    return (time.perf_counter() - t0) / iters


def measure(backend, B, Sq, Sk, H, KV, hd, causal, iters=3):
    """Measured fwd/bwd/decode wall-clock through the dispatch layer."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=causal, backend=backend))
    bwd = jax.jit(jax.grad(lambda q, k, v: jnp.sum(FA.flash_attention(
        q, k, v, causal=causal, backend=backend).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    qd = jax.random.normal(ks[3], (B, 1, H, hd), jnp.bfloat16)
    lens = jnp.full((B,), Sk // 2, jnp.int32)
    dec = jax.jit(lambda q, k, v, n: FA.decode_attention(
        q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), n,
        backend=backend))
    return {
        "fwd_s": _wallclock(fwd, q, k, v, iters=iters),
        "bwd_s": _wallclock(bwd, q, k, v, iters=iters),
        "decode_s": _wallclock(dec, qd, k, v, lens, iters=iters),
    }


def measure_paged(backend, B, Sq, nb, bs, H, KV, hd, iters=3):
    """Measured paged decode (Sq=1) / prefill (Sq>1) wall-clock through
    the dispatch layer at fill = half the window."""
    from repro.kernels.paged_attention import ops as PA
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kp = jax.random.normal(ks[0], (B * nb + 1, KV, bs, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[1], kp.shape, jnp.bfloat16)
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    off = jnp.full((B,), nb * bs // 2, jnp.int32)
    q = jax.random.normal(ks[2], (B, Sq, H, hd), jnp.bfloat16)
    if Sq == 1:
        f = jax.jit(lambda q, k, v, t, n: PA.paged_decode_attention(
            q, k, v, t, n, backend=backend))
        return _wallclock(f, q, kp, vp, tables, off + 1, iters=iters)
    f = jax.jit(lambda q, k, v, t, o, n: PA.paged_prefill_attention(
        q, k, v, t, o, n, backend=backend))
    return _wallclock(f, q, kp, vp, tables, off, off + Sq, iters=iters)


def run(out_json=None, smoke=False):
    on_tpu = jax.default_backend() == "tpu"
    # (B, Sq, Sk, H, KV, hd, causal) — ViT-Huge-ish train, GQA LM train,
    # MQA long-prefill, cross-attention
    grid = [
        (8, 256, 256, 16, 16, 80, False),     # CLIP ViT-H patches
        (4, 4096, 4096, 16, 16, 64, True),    # train_4k dense heads
        (4, 4096, 4096, 32, 8, 128, True),    # train_4k GQA 4:1
        (1, 32768, 32768, 32, 8, 128, True),  # prefill_32k
    ]
    if smoke:
        grid = grid[:1] + grid[1:2]
    rows = []
    print(f"{'shape (B,Sq,Sk,H,KV,hd)':>28} {'kind':>6} | {'xla(model)':>11} "
          f"{'pallas(model)':>13} {'speedup':>8}")
    for (B, Sq, Sk, H, KV, hd, causal) in grid:
        for kind in ("fwd", "bwd"):
            t = model_times(B, Sq, Sk, H, KV, hd, causal, kind=kind)
            rows.append({"bench": "attention", "kind": kind, "B": B,
                         "Sq": Sq, "Sk": Sk, "H": H, "KV": KV, "hd": hd,
                         "causal": causal, "modeled_xla_s": t["xla"],
                         "modeled_pallas_s": t["pallas"],
                         "modeled_speedup": t["xla"] / t["pallas"]})
            print(f"{str((B, Sq, Sk, H, KV, hd)):>28} {kind:>6} | "
                  f"{t['xla']*1e3:10.3f}m {t['pallas']*1e3:12.3f}m "
                  f"{t['xla']/t['pallas']:7.2f}x")
        td = model_decode_times(max(B, 8), min(Sk, 4096), H, KV, hd)
        rows.append({"bench": "attention", "kind": "decode",
                     "B": max(B, 8), "Sq": 1, "Sk": min(Sk, 4096), "H": H,
                     "KV": KV, "hd": hd, "causal": False,
                     "modeled_xla_s": td["xla"],
                     "modeled_pallas_s": td["pallas"],
                     "modeled_speedup": td["xla"] / td["pallas"]})
        print(f"{str((max(B, 8), 1, min(Sk, 4096), H, KV, hd)):>28} "
              f"{'decode':>6} | {td['xla']*1e3:10.3f}m "
              f"{td['pallas']*1e3:12.3f}m {td['xla']/td['pallas']:7.2f}x")

    # paged rows: decode (Sq=1) and the k-query verify / chunked-prefill
    # widths (Sq=4, 8) through the block-table kernels, modeled the same
    # way (measured below through the dispatch layer where runnable)
    pB, pnb, pbs, pH, pKV, phd = (2, 8, 8, 4, 2, 32) if smoke else \
        (8, 64, 16, 32, 8, 128)
    for Sq in (1, 4, 8):
        tp = model_paged_times(pB, Sq, pnb, pbs, pH, pKV, phd)
        rows.append({"bench": "attention", "kind": "paged", "modeled": True,
                     "B": pB, "Sq": Sq, "num_blocks": pnb,
                     "block_size": pbs, "H": pH, "KV": pKV, "hd": phd,
                     "modeled_xla_s": tp["xla"],
                     "modeled_pallas_s": tp["pallas"],
                     "modeled_speedup": tp["xla"] / tp["pallas"]})
        print(f"{str((pB, Sq, pnb * pbs, pH, pKV, phd)):>28} {'paged':>6} | "
              f"{tp['xla']*1e3:10.3f}m {tp['pallas']*1e3:12.3f}m "
              f"{tp['xla']/tp['pallas']:7.2f}x")
    paged_rows = [r for r in rows if r["kind"] == "paged"]
    pok = all(r["modeled_speedup"] >= 1.0 for r in paged_rows)
    print(f"CLAIM paged kernel no slower than gather-then-dense at "
          f"Sq in (1, 4, 8): {'PASS' if pok else 'FAIL'} (min "
          f"{min(r['modeled_speedup'] for r in paged_rows):.2f}x)")

    # acceptance: at training shapes (B·Sq >= 4096) the fused path must
    # model no slower than the xla scan on every row
    train_rows = [r for r in rows if r["kind"] != "decode"
                  and r["B"] * r["Sq"] >= 4096]
    ok = all(r["modeled_speedup"] >= 1.0 for r in train_rows)
    print(f"CLAIM pallas flash attention no slower than xla at training "
          f"shapes (B·Sq >= 4096): {'PASS' if ok else 'FAIL'} "
          f"(min speedup {min(r['modeled_speedup'] for r in train_rows):.2f}x"
          f" over {len(train_rows)} rows)")

    # measured wall-clock through the dispatch layer
    mB, mSq, mH, mKV, mhd = (2, 128, 4, 2, 32) if smoke else \
        (4, 512, 8, 4, 64)
    backends = ["xla"] + (["pallas"] if on_tpu else [])
    measured = {be: measure(be, mB, mSq, mSq, mH, mKV, mhd, True)
                for be in backends}
    # interpret-mode plumbing smoke at a tiny shape (never timed for real)
    measured["pallas_interpret"] = measure("pallas_interpret",
                                           1, 16, 16, 2, 1, 8, True, iters=1)
    for be, m in measured.items():
        print(f"measured [{be}] " + "  ".join(
            f"{k}={v*1e3:.2f}ms" for k, v in m.items()))
    rows.append({"bench": "attention", "kind": "measured",
                 "B": mB, "Sq": mSq, "H": mH, "KV": mKV, "hd": mhd,
                 "measured_s": measured, "tpu": on_tpu})

    # measured paged wall-clock at the same Sq grid (pallas on TPU only;
    # a tiny interpret smoke proves the kernel grid still runs)
    pgB, pgnb, pgbs, pgH, pgKV, pghd = (2, 4, 8, 4, 2, 32)
    paged_measured = {}
    for be in backends:
        paged_measured[be] = {
            f"Sq{Sq}_s": measure_paged(be, pgB, Sq, pgnb, pgbs, pgH,
                                       pgKV, pghd)
            for Sq in (1, 4, 8)}
    paged_measured["pallas_interpret"] = {
        "Sq4_s": measure_paged("pallas_interpret", 1, 4, 2, 8, 2, 1, 8,
                               iters=1)}
    for be, m in paged_measured.items():
        print(f"measured paged [{be}] " + "  ".join(
            f"{k}={v*1e3:.2f}ms" for k, v in m.items()))
    rows.append({"bench": "attention", "kind": "paged_measured",
                 "B": pgB, "num_blocks": pgnb, "block_size": pgbs,
                 "H": pgH, "KV": pgKV, "hd": pghd,
                 "measured_s": paged_measured, "tpu": on_tpu})

    if out_json:
        os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(rows, f, indent=1)
    if not ok:
        raise SystemExit("modeled pallas slower than xla at training shapes")
    if not pok:
        raise SystemExit("modeled paged kernel slower than the dense oracle")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid + tiny measured shapes (CI lane)")
    a = ap.parse_args()
    run(out_json=a.out, smoke=a.smoke)
