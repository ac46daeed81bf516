"""Shapes of the work in one training step of a DeepSeek-V3-style decoder
on one chip's share (Moonlight-16B-A3B): latent attention counts its
scores at the query/key width and its weighted sum at the value width;
the routed experts are their own kind, at the expected number of slots
held here (tokens x experts per token x held / routed); every other
matmul (latent projections, dense and shared experts, router, head) is a
``Linear``."""

import dataclasses

from bench.work.common import Attention, Linear


@dataclasses.dataclass(frozen=True)
class LatentAttention(Attention):
    """Attention whose values (and output) are ``v_dim`` wide while queries
    and keys are ``head_dim`` wide."""
    v_dim: int = 0

    def fwd_ops(self) -> float:
        return 2.0 * self.batch * self.heads * self.pairs * (
            self.head_dim + self.v_dim)

    def _rows(self, heads: int, s: int) -> float:
        return self.batch * s * heads * (self.head_dim + self.v_dim) * 2.0

    def fwd_bytes(self) -> float:
        """q and o, k and v in bf16, the f32 logsumexp out."""
        return (self._rows(self.heads, self.sq)
                + self._rows(self.kv_heads, self.sk)
                + self.batch * self.heads * self.sq * 4.0)

    def bwd_bytes(self) -> float:
        """q, o, do in and dq out; k, v in and dk, dv out; lse and di."""
        return 2 * self.fwd_bytes()


@dataclasses.dataclass(frozen=True)
class RoutedExperts:
    """One grouped matmul over the slots routed to the experts held here:
    ``slots`` rows of ``n`` features times each expert's (n, m) weight.
    Forward and input gradient in int8, weight gradient in bf16."""
    slots: float
    n: int
    m: int
    experts: int
    quantized: bool = True
    dgrad: bool = True
    count: int = 1

    @property
    def matmul_ops(self) -> float:
        return 2.0 * self.slots * self.n * self.m

    def fwd_bytes(self) -> float:
        """bf16 rows in and out, every held expert's int8 weight."""
        return 2.0 * self.slots * (self.n + self.m) + 1.0 * (
            self.experts * self.n * self.m)

    def dgrad_bytes(self) -> float:
        return self.fwd_bytes()

    def wgrad_bytes(self) -> float:
        """bf16 rows and output gradients in, f32 weight gradients out."""
        return 2.0 * self.slots * (self.n + self.m) + 4.0 * (
            self.experts * self.n * self.m)


def train_step(c, mix):
    D, H = c["hidden_size"], c["num_attention_heads"]
    r, dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
    dr, dv = c["qk_rope_head_dim"], c["v_head_dim"]
    K0 = c["first_k_dense_replace"]
    Lm = c["num_hidden_layers"] - K0
    E, Eh = c["share"]["router_experts"], c["n_routed_experts"]
    K, F = c["num_experts_per_tok"], c["moe_intermediate_size"]
    Fd, Fs = c["intermediate_size"], c["n_shared_experts"] * F
    B, S, V = mix["batch"], mix["seq"], c["vocab_size"]
    T = B * S
    mla = [(D, H * (dn + dr)), (D, r + dr), (r, H * (dn + dv)), (H * dv, D)]
    swiglu = lambda f: [(D, f), (D, f), (f, D)]
    linears = [Linear(T, n, m, quantized=True, dgrad=True, count=K0 + Lm)
               for n, m in mla]
    linears += [Linear(T, n, m, quantized=True, dgrad=True, count=K0)
                for n, m in swiglu(Fd)]
    linears += [Linear(T, n, m, quantized=True, dgrad=True, count=Lm)
                for n, m in swiglu(Fs)]
    linears.append(Linear(T, D, E, quantized=False, dgrad=True, count=Lm))
    slots = T * K * Eh / E
    linears += [RoutedExperts(slots, n, m, Eh, count=Lm)
                for n, m in swiglu(F)]
    linears.append(Linear(T, D, V, quantized=False, dgrad=True))  # head
    return linears, [LatentAttention(B, S, S, H, H, dn + dr, causal=True,
                                     count=K0 + Lm, v_dim=dv)]
