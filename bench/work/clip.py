"""Shapes of the work in one training step of two-tower CLIP."""
from __future__ import annotations

from bench.work.common import Attention, Linear


def train_step(c, mix):
    v, t, E, B = c["vision_cfg"], c["text_cfg"], c["embed_dim"], mix["batch"]
    n_patches = (v["image_size"] // v["patch_size"]) ** 2
    Sv = int(n_patches * (1 - c["patch_dropout"])) + 1
    St = t["context_length"]
    linears = [Linear(B * n_patches, 3 * v["patch_size"] ** 2, v["width"],
                      quantized=True, dgrad=False)]     # images need no grad
    attn = []
    for S, W, heads, L, ratio in (
            (Sv, v["width"], v["width"] // v["head_width"], v["layers"],
             v["mlp_ratio"]),
            (St, t["width"], t["heads"], t["layers"], t["mlp_ratio"])):
        F = W * ratio
        for n, m in ((W, W), (W, W), (W, W), (W, W), (W, F), (F, W)):
            linears.append(Linear(B * S, n, m, quantized=True, dgrad=True,
                                  count=L))
        linears.append(Linear(B, W, E, quantized=False, dgrad=True))  # proj
        attn.append(Attention(B, S, S, heads, heads, W // heads,
                              causal=S == St, count=L))
    return linears, attn
