"""Config dataclasses for models, parallelism, training and shapes."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int                   # experts the router scores
    top_k: int
    every_n_layers: int = 1          # MoE MLP every N layers (1 = all)
    dense_residual: bool = False     # arctic: dense FFN in parallel w/ MoE
    dense_residual_ff: int = 0       # width of the parallel dense FFN
    expert_ff: int = 0               # routed expert width; 0 => d_model's d_ff
    scoring: str = "softmax"         # softmax | sigmoid (DeepSeek-V3: the
    # top-k is chosen on score + a selection-only bias, gates are the raw
    # chosen scores)
    norm_topk: bool = True           # gates renormalised to sum 1
    routed_scale: float = 1.0        # gates times this (V3 routed_scaling_factor)
    n_shared: int = 0                # shared experts, run as one SwiGLU of
    # width n_shared * expert_ff through quant_linear
    balance: str = "switch"          # switch (top-1 density x mean prob) |
    # seq (DeepSeek-V3 sequence-wise: per sequence f_i * P_i over experts)
    balance_alpha: float = 0.01      # weight of the balance loss in the loss


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) without a query LoRA:
    q straight from x; k and v up-projected from one RMS-normed latent of
    ``kv_lora_rank``; a ``qk_rope_dim`` key part shared by all heads."""
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                 # 0 => ceil(d_model/16)


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64             # LoRA rank for data-dependent decay
    mix_lora: int = 32               # LoRA rank for token-shift mixes


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    n_encoder_layers: int = 24
    encoder_is_causal: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|encdec|vlm|audio|clip
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 => d_model // n_heads
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    first_k_dense: int = 0           # leading dense layers (d_ff wide) ahead
    # of the scanned layer groups (DeepSeek-V3 first_k_dense_replace)
    held_experts: Optional[Tuple[int, int]] = None   # (first id, count) of
    # the routed experts this chip holds under expert parallelism; None =
    # all of them. The router still scores moe.n_experts.
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    encdec: Optional[EncDecConfig] = None
    attn_layer_period: int = 0       # jamba: 1 attn layer per this many (rest mamba)
    attn_layer_offset: int = 4       # which layer in the period is attention
    frontend: Optional[str] = None   # "vision_stub" | "audio_stub"
    frontend_tokens: int = 256       # patches / frames prepended by the stub
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    act: str = "swiglu"              # "swiglu" | "gelu"
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    tie_embeddings: bool = False
    layer_scale_init: Optional[float] = None   # None = off; 0.0 = paper's zero-init
    logit_softcap: float = 0.0

    def __post_init__(self):
        if self.held_experts is not None:       # a JSON list -> a tuple
            object.__setattr__(self, "held_experts",
                               tuple(int(v) for v in self.held_experts))

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def held(self) -> Tuple[int, int]:
        """(first id, count) of the routed experts held here."""
        return self.held_experts or (0, self.moe.n_experts)

    @property
    def is_attention_free(self) -> bool:
        return self.rwkv is not None

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing => long_500k shape applies."""
        return self.family in ("ssm", "hybrid")

    def layer_kind(self, i: int) -> str:
        """'attn' | 'mamba' | 'rwkv' — sequence-mixer type of layer i."""
        if self.rwkv is not None:
            return "rwkv"
        if self.attn_layer_period:
            return ("attn" if i % self.attn_layer_period == self.attn_layer_offset
                    else "mamba")
        return "attn"

    def layer_is_moe(self, i: int) -> bool:
        if self.moe is None:
            return False
        return i % self.moe.every_n_layers == (self.moe.every_n_layers - 1)


@dataclasses.dataclass(frozen=True)
class CLIPConfig:
    """Two-tower CLIP (the paper's own model)."""
    name: str
    image_size: int = 224
    patch_size: int = 14
    vision_layers: int = 32
    vision_width: int = 1280
    vision_heads: int = 16
    vision_ff: int = 5120
    text_layers: int = 24
    text_width: int = 1024
    text_heads: int = 16
    text_ff: int = 4096
    text_vocab: int = 49408
    text_ctx: int = 77
    embed_dim: int = 1024
    patch_dropout: float = 0.5       # paper §2.2.2
    layer_scale_init: Optional[float] = None
    post_embed_norm: bool = True     # paper §3.2: LN after patch embedding
    logit_scale_init: float = 2.659  # ln(1/0.07)
    logit_scale_max: float = 4.6052  # ln(100), clipped per §3.2
    family: str = "clip"

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    mesh_shape: Tuple[int, ...] = (16, 16)
    mesh_axes: Tuple[str, ...] = ("data", "model")
    fsdp: bool = False               # shard weights over data too (ZeRO-3)
    fsdp_gather_weights: bool = False  # explicit bf16 weight all-gather at
    # use (ZeRO-3 semantics) instead of GSPMD activation partial-sums
    gather_wire: str = "bf16"        # bf16|int8 — int8 ships weights over
    # the wire tensor-wise-quantized; free under SwitchBack (§Perf it. 2)
    pure_dp: bool = False            # fold the model axis into data
    # parallelism (models too small to need TP, e.g. 1B CLIP on 256 chips)
    shard_kv_heads: bool = True      # False: replicate K/V projections —
    # when n_kv_heads < model-axis size, sharding the flat KV dim splits
    # heads across devices and GSPMD regathers at the head reshape
    # (§Perf qwen iteration 5); decode keeps True (shards the KV cache)
    scan_layers: bool = True
    remat: str = "block"             # none|block|full
    sequence_parallel: bool = False  # shard seq over data when batch too small
    grad_compression: str = "none"   # none|int8_rowwise
    attn_impl: str = "flash_scan"    # flash_scan | dense — "dense" forces
    # the materialized-scores oracle on EVERY backend (kernels included)
    attn_block_q: int = 0            # flash-attention kernel Q-tile rows;
    # 0 = auto (sized to the sequence — kernels/flash_attention/ops.py)
    attn_block_k: int = 0            # KV-tile rows (fwd/bwd and the serve
    # decode ring-cache kernel); 0 = auto

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axes that jointly form the batch/data dimension (pod folds in)."""
        return tuple(a for a in self.mesh_axes if a in ("pod", "data"))


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "stable_adamw"
    learning_rate: float = 2e-3
    warmup_steps: int = 5000
    total_steps: int = 20000
    weight_decay: float = 0.2
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip_norm: float = 0.0      # 0 = off (paper default: no grad clip)
    loss_scaler: str = "none"        # none|fixed_tensor|dynamic
    quant_mode: str = "bf16"         # precision policy for all linears:
    # bf16 | int8[_*] | fp8 | fp8_mixed | fp8_sim... (core/precision.MODES)
    kernel_backend: str = "xla"      # xla|pallas|pallas_interpret —
    # quantized-matmul implementation (QuantPolicy.backend)
    fp8_block_rows: int = 128        # fp8_mixed: blockwise-quant tile rows
    fp8_block_cols: int = 128        # fp8_mixed: blockwise-quant tile cols
    fp8_fallback_ratio: float = 8.0  # fp8_mixed: tile absmax > ratio ×
    # median(tile absmaxes) routes that matmul tile through bf16
    seed: int = 0
    global_batch: int = 256
    seq_len: int = 4096
    microbatch_steps: int = 1        # gradient accumulation
    checkpoint_every: int = 1000
    keep_checkpoints: int = 3
    quant_health_metrics: bool = True  # quantized modes only: per-group
    # device-side health scalars (fp8 fallback-block fraction, int8 clip
    # fraction, weight absmax — telemetry/health.py) ride the existing
    # metrics dict; fetched only at flush boundaries, never a per-step
    # sync. Off = the jitted step is bit-identical to pre-telemetry.


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Knobs for the self-healing TrainSupervisor (repro.train.supervisor).

    Detection runs at the trainer's flush granularity on the metrics it
    already fetches; recovery is the paper-era mitigation: restore the
    last good checkpoint and deterministically skip past the offending
    data window (the pipeline is a pure function of step, so skip =
    advance the data cursor).  Repeat failures escalate — rewind →
    rewind one checkpoint earlier + skip wider → abort — under bounded
    retries.
    """
    checkpoint_every: int = 10       # supervisor requires checkpoints
    keep_checkpoints: int = 4        # escalation rewinds need depth > 1
    max_retries: int = 3             # rewinds per incident before abort
    max_total_rewinds: int = 12      # global bound across all incidents
    skip_margin: int = 1             # data steps skipped past the fault
    skip_widen: int = 8              # extra skip per escalation attempt
    grad_norm_ratio: float = 20.0    # grad_norm > ratio × running EMA
    grad_norm_abs: float = float("inf")  # absolute grad-norm ceiling
    loss_jump_ratio: float = 3.0     # loss > ratio × running EMA
    detect_warmup: int = 10          # steps of EMA before ratio checks
    spike_min_history: int = 20      # LossSpikeDetector.min_history
    spike_z: float = 3.2             # LossSpikeDetector.z_threshold
    log_every: int = 10


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs for the continuously-batched inference engine (repro.serve).

    ``max_batch`` × ``max_len`` fixes the preallocated ring KV cache; the
    scheduler admits queued requests into free slots and evicts finished
    ones, so throughput comes from keeping the decode batch full rather
    than from growing shapes. ``quant_mode``/``kernel_backend`` mirror
    TrainConfig: int8 modes route every linear through the same
    kernels/switchback ops inference-side (wgrad-free — only Eq. 3/4
    forwards run).
    """
    max_batch: int = 8               # decode-batch slots (ring cache rows)
    max_len: int = 256               # cache cells per slot (ring capacity)
    prefill_bucket: int = 8          # prompts pad to pow2 buckets >= this
    temperature: float = 0.0         # 0 = greedy argmax
    cache_dtype: str = "bfloat16"    # KV cache storage dtype
    rollover: bool = False           # keep decoding past max_len (sliding
    # window via the ring cache) instead of evicting at the cache edge
    quant_mode: str = "bf16"         # precision policy for all linears
    kernel_backend: str = "xla"      # xla|pallas|pallas_interpret
    attn_block_q: int = 0            # flash-attention tile sizes for the
    attn_block_k: int = 0            # engine's ParallelConfig; 0 = auto
    cache_mode: str = "ring"         # ring|paged — "paged" swaps the dense
    # per-slot ring cache for the block-pool + block-table + radix
    # prefix-cache subsystem (serve/paged, kernels/paged_attention,
    # DESIGN.md §10); the ring path stays the parity oracle
    block_size: int = 16             # paged: tokens per physical KV block
    num_blocks: int = 0              # paged: pool size; 0 = auto (the ring
    # capacity max_batch * ceil(max_len/block_size) — size DOWN for the
    # memory win once the live-token ceiling is known)
    prefix_cache: bool = True        # paged: park finished requests' full
    # blocks in the radix cache so shared prompt prefixes skip prefill
    prefill_chunk_tokens: int = 0    # paged: per-step token budget mixing
    # live decode tokens with a bounded prefill slice — a long prompt
    # prefills as fixed-size chunks across engine steps instead of one
    # monolithic call that stalls every decoding slot's ITL; 0 = off
    # (monolithic admission prefill, the pre-SLO behaviour)
    preemption: str = "off"          # paged: "off" reserves worst-case
    # generation blocks at admission; "recompute" admits optimistically
    # and, when decode growth finds the pool empty, parks the newest
    # request's blocks back to the radix cache and requeues it (prefix
    # adoption makes its re-prefill nearly free)
    spec_mode: str = "off"           # off|ngram — "ngram" drafts up to
    # spec_k tokens per slot from the request's own prompt+generated
    # history (prompt lookup, no draft model) and verifies them all in
    # one k-query paged_prefill call; greedy acceptance keeps output
    # token-for-token identical to "off" (paged cache only, greedy
    # temperature==0 steps only — sampling steps fall back to plain
    # one-token decode). DESIGN.md §12.
    spec_k: int = 4                  # spec: max drafted tokens per slot
    spec_ngram: int = 3              # spec: longest history n-gram matched
    spec_min_ngram: int = 2          # spec: shortest n-gram accepted as a
    # match — 1 drafts on any repeated token (max acceptance on loopy
    # text), 2+ avoids paying padded verify calls for accidental
    # single-token matches on non-repetitive traffic
    seed: int = 0                    # engine PRNG seed: temperature>0
    # sampling folds (seed, request uid, generation step) into the key,
    # so sampled generations are reproducible across batching/scheduling


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Flight-recorder knobs (repro.telemetry, DESIGN.md §15).

    ``path=None`` disables the JSONL sink entirely; a disabled Telemetry
    is a no-op object the train/serve loops thread unconditionally.
    ``profile_steps`` is an inclusive (start, stop) step window wrapped
    in ``jax.profiler`` start/stop (the ``--profile-steps A:B`` CLI
    flag); traces land in ``profile_dir``.
    """
    path: Optional[str] = None       # JSONL event file (None = off)
    profile_steps: Optional[Tuple[int, int]] = None
    profile_dir: str = "/tmp/repro-profile"


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell of the assignment."""
    name: str                        # train_4k / prefill_32k / decode_32k / long_500k
    kind: str                        # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524288, 1),
}
