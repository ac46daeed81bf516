"""Serving launcher — continuously-batched decode through the ServeEngine.

Every mode runs the same jitted, donated, sharded decode step the tests
and benchmarks exercise; ``--quant-mode int8_switchback`` +
``--kernel-backend pallas_interpret`` serves through the SwitchBack int8
kernels (DESIGN.md §8).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-reduced \
        --max-batch 4 --n-requests 8 --new-tokens 16

    # sharded serving on forced host devices:
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-reduced \
        --mesh test --devices 8 --n-requests 8

``--arch NAME`` serves the published config of NAME; ``NAME-reduced`` (the
default, ``smollm-360m-reduced``) is its shrunken CPU-sized twin.
"""
from __future__ import annotations

import argparse

from repro.host_devices import force_host_device_count

# must run before the jax import below: REPRO_DRYRUN_DEVICES / --devices N
force_host_device_count()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import ALL_ARCHS, REDUCED_ARCHS, get_config  # noqa: E402
from repro.configs.base import ServeConfig  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serve import make_serve_engine  # noqa: E402
from repro.telemetry import Telemetry, parse_profile_steps  # noqa: E402


def decode_step_fallback(cfg, args, *, reason: str):
    """Batched greedy decode via the training-side ``decode_step`` for
    archs the ServeEngine can't prefill (recurrent state instead of a KV
    cache). No continuous batching: one fixed batch, token by token."""
    import time

    import jax.numpy as jnp

    from repro.configs.base import ParallelConfig
    from repro.core.precision import QuantPolicy
    from repro.models import transformer as TF
    from repro.models.params import init_params

    if getattr(cfg, "family", "") in ("clip", "encdec"):
        raise SystemExit(f"--arch {args.arch}: {reason}")
    print(f"[serve] {args.arch}: no engine path ({reason}); "
          "falling back to the decode_step loop")
    pol = QuantPolicy(args.quant_mode, backend=args.kernel_backend)
    par = ParallelConfig(remat="none")
    params = init_params(build(cfg).param_specs,
                         jax.random.PRNGKey(args.seed))
    B = args.max_batch
    state = TF.init_decode_state(cfg, B, args.prompt_len + args.new_tokens)
    decode = jax.jit(lambda p, s, t: TF.decode_step(p, s, t, cfg, pol, par))
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (B, args.prompt_len), 0, cfg.vocab_size)
    logits = None
    for t in range(args.prompt_len):                 # stepwise "prefill"
        logits, state = decode(params, state, prompts[:, t:t + 1])
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    jax.block_until_ready(tok)
    t0 = time.time()
    n = 0
    for _ in range(args.new_tokens - 1):
        logits, state = decode(params, state, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        n += B
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"[serve] {n} new tokens in {dt:.2f}s ({n/max(dt,1e-9):.0f} "
          f"tok/s, {args.quant_mode}, batch {B}, no continuous batching)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-reduced",
                    choices=ALL_ARCHS + REDUCED_ARCHS)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode-batch slots (continuous batching width)")
    ap.add_argument("--max-len", type=int, default=128,
                    help="ring KV cache cells per slot")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="synthetic prompt length (requests vary +/- 50%)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant-mode", default="bf16")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--attn-block-q", type=int, default=0,
                    help="flash-attention Q tile rows for prefill (0=auto)")
    ap.add_argument("--attn-block-k", type=int, default=0,
                    help="flash-attention KV tile rows, prefill + the "
                         "decode ring-cache kernel (0 = auto)")
    ap.add_argument("--cache-mode", default="ring",
                    choices=("ring", "paged"),
                    help="paged = block-pool KV cache + radix prefix "
                         "cache + block-table decode kernel (DESIGN §10)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per physical KV block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged: pool size; 0 = auto (ring-equivalent "
                         "capacity max_batch*ceil(max_len/block_size))")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged: disable parking finished requests' "
                         "blocks for shared-prefix reuse")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="paged: per-step token budget for chunked "
                         "prefill; long prompts prefill in slices that "
                         "share the step with decodes (0 = monolithic)")
    ap.add_argument("--preemption", default="off",
                    choices=("off", "recompute"),
                    help="paged: when the block pool runs dry mid-decode, "
                         "park the newest request's blocks to the prefix "
                         "cache and requeue it (recompute-on-resume)")
    ap.add_argument("--spec-mode", default="off", choices=("off", "ngram"),
                    help="paged + greedy: n-gram speculative decoding — "
                         "draft from the request's own history, verify "
                         "all drafts in one paged-prefill pass, roll "
                         "back rejected tail blocks (DESIGN §12)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="spec: max drafted tokens per slot per step")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="spec: longest history n-gram to match")
    ap.add_argument("--spec-min-ngram", type=int, default=2,
                    help="spec: shortest n-gram worth a verify pass")
    ap.add_argument("--mesh", default="auto",
                    choices=("auto", "test"))
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host CPU devices (read pre-jax-import)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write flight-recorder JSONL events here (read "
                         "with python -m repro.telemetry.report); only the "
                         "measured generate() call is recorded, not warmup")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="wrap engine waves A..B (inclusive) in a "
                         "jax.profiler trace")
    ap.add_argument("--profile-dir", default="/tmp/repro-profile")
    args = ap.parse_args()

    from repro.launch.mesh import make_cli_mesh
    enable_compile_cache()
    cfg = get_config(args.arch)
    mesh = make_cli_mesh(args.mesh)
    scfg = ServeConfig(max_batch=args.max_batch, max_len=args.max_len,
                       temperature=args.temperature,
                       quant_mode=args.quant_mode,
                       kernel_backend=args.kernel_backend,
                       attn_block_q=args.attn_block_q,
                       attn_block_k=args.attn_block_k,
                       cache_mode=args.cache_mode,
                       block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       prefix_cache=not args.no_prefix_cache,
                       prefill_chunk_tokens=args.prefill_chunk_tokens,
                       preemption=args.preemption,
                       spec_mode=args.spec_mode,
                       spec_k=args.spec_k,
                       spec_ngram=args.spec_ngram,
                       spec_min_ngram=args.spec_min_ngram,
                       seed=args.seed)
    try:
        engine = make_serve_engine(build(cfg), scfg, mesh)
    except NotImplementedError as e:
        # ssm/hybrid archs have no batched-prefill engine path (DESIGN §8);
        # they still serve through the one-token decode_step loop
        return decode_step_fallback(cfg, args, reason=str(e))
    params = engine.init_params(args.seed)
    cache_desc = (f"{engine.num_blocks}x{scfg.block_size} paged blocks"
                  if scfg.cache_mode == "paged"
                  else f"{scfg.max_batch}x{scfg.max_len} ring cache")
    print(f"[serve] {args.arch} mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"{args.quant_mode}/{args.kernel_backend} — {cache_desc}")

    rng = np.random.default_rng(args.seed)
    lens = rng.integers(max(args.prompt_len // 2, 1),
                        args.prompt_len + args.prompt_len // 2 + 1,
                        size=args.n_requests)
    lens = np.minimum(lens, args.max_len)    # scheduler rejects > max_len
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]

    # warmup on the full request list compiles every prefill bucket the
    # timed run will hit (a single-prompt warmup would leave the other
    # buckets compiling inside the measured window) + the decode step
    engine.generate(params, prompts, max_new_tokens=2)
    tele = Telemetry(args.telemetry,
                     profile_steps=parse_profile_steps(args.profile_steps),
                     profile_dir=args.profile_dir, program="serve",
                     meta={"arch": args.arch, "quant_mode": args.quant_mode,
                           "cache_mode": args.cache_mode,
                           "spec_mode": args.spec_mode,
                           "n_requests": args.n_requests})
    engine.telemetry = tele
    try:
        gens, stats = engine.generate(params, prompts,
                                      max_new_tokens=args.new_tokens)
    finally:
        tele.close()
    print(f"[serve] {stats['new_tokens']} new tokens "
          f"({stats['prefill_tokens']} prefilled) in "
          f"{stats['wall_s']:.2f}s — {stats['tokens_per_s']:.0f} tok/s, "
          f"{stats['decode_steps']} decode steps, "
          f"{stats['prefill_calls']} prefill calls; "
          f"ttft p50 {stats['ttft_p50_s']*1e3:.1f}ms, "
          f"itl p50 {stats['itl_p50_s']*1e3:.2f}ms (decode-only; "
          f"wall p95 {stats['itl_wall_p95_s']*1e3:.2f}ms, "
          f"prefill-stall p95 {stats['prefill_stall_p95_s']*1e3:.2f}ms)")
    if scfg.cache_mode == "paged":
        print(f"[serve] paged: {stats['prefix_hits']}/"
              f"{stats['prefix_lookups']} prefix hits, "
              f"{stats['prefill_tokens_saved']} prefill tokens saved, "
              f"peak {stats['peak_blocks_in_use']} blocks "
              f"({stats['peak_cache_bytes']/1e6:.2f} MB vs "
              f"{stats['ring_equiv_cache_bytes']/1e6:.2f} MB ring)")
        if scfg.prefill_chunk_tokens or scfg.preemption != "off":
            print(f"[serve] slo: {stats['prefill_chunks']} prefill chunks "
                  f"over {stats['prefill_calls']} calls, "
                  f"{stats['sched_preempted']} preemptions")
        if scfg.spec_mode != "off":
            print(f"[serve] spec: {stats['spec_accepted']}/"
                  f"{stats['spec_drafted']} drafts accepted "
                  f"({stats['spec_acceptance_rate']:.2f}) over "
                  f"{stats['spec_verify_calls']} verify calls — "
                  f"{stats['tokens_per_model_pass']:.2f} tokens per "
                  f"model pass")
    print("sample:", gens[0][:12])
    if args.telemetry:
        print(f"[telemetry] events written to {args.telemetry} — summarize "
              f"with: python -m repro.telemetry.report {args.telemetry}")


if __name__ == "__main__":
    main()
