"""Work of the requests a serving window completed, from their lengths."""
from __future__ import annotations

from typing import Dict, List, Tuple


def lm_serve(c, requests: List[Tuple[int, int]]) -> Dict:
    """``requests``: (prompt tokens, served tokens). Prefill runs every
    prompt token through the layers and the head at the last one; decode
    feeds back every served token but the last and samples after each.
    ``attention`` lists (operations, bytes) per request and phase: the
    (query, key) pairs of the causal mask, and the KV cache read once per
    prefill and once per decode step at bf16."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    KV, hd = c["num_key_value_heads"], D // c["num_attention_heads"]
    F, L, V = c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]
    per_tok = 2 * L * (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F)
    head = 2 * D * V
    kv_tok = L * 2 * KV * hd * 2                      # K and V, bf16
    attn_pair = L * 4 * H * hd                        # QK^T and PV
    ops, attention = 0.0, []
    for p, n in requests:
        fed = p + max(n - 1, 0)
        ops += per_tok * fed + head * n
        pre_pairs = p * (p + 1) / 2
        dec_pairs = sum(range(p + 1, p + n))          # contexts p+1..p+n-1
        dec_bytes = kv_tok * dec_pairs
        attention.append((attn_pair * pre_pairs, kv_tok * p))
        attention.append((attn_pair * dec_pairs, dec_bytes))
        ops += attn_pair * (pre_pairs + dec_pairs)
    return {"ops": ops, "attention": attention}
