"""Shapes of the work in one training step of a Llama-style decoder."""
from __future__ import annotations

from bench.work.common import Attention, Linear


def train_step(c, mix):
    D, H = c["hidden_size"], c["num_attention_heads"]
    KV, hd = c["num_key_value_heads"], c["hidden_size"] // H
    F, L, V = c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]
    B, S = mix["batch"], mix["seq"]
    T = B * S
    per_layer = [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
                 (D, F), (D, F), (F, D)]
    linears = [Linear(T, n, m, quantized=True, dgrad=True, count=L)
               for n, m in per_layer]
    linears.append(Linear(T, D, V, quantized=False, dgrad=True))  # tied head
    return linears, [Attention(B, S, S, H, KV, hd, causal=True, count=L)]
