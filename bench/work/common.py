"""Operations and bytes of a training step's matmuls and attention calls,
from their shapes. Operations count multiply and add; attention counts
only the (query, key) pairs the mask leaves, and its backward pass the
four matmuls it needs (the recomputed scores are not required work)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Linear:
    tokens: int
    n: int                     # input features
    m: int                     # output features
    quantized: bool            # forward and input-gradient matmuls in int8
    dgrad: bool                # the input needs a gradient
    count: int = 1             # identical calls per step (layers)

    @property
    def matmul_ops(self) -> float:
        return 2.0 * self.tokens * self.n * self.m

    def fwd_bytes(self) -> float:
        """bf16 input and output, the float32 master weight read once."""
        return 2.0 * self.tokens * (self.n + self.m) + 4.0 * self.n * self.m

    def dgrad_bytes(self) -> float:
        """bf16 output-gradient in and input-gradient out, int8 weight."""
        return 2.0 * self.tokens * (self.n + self.m) + 1.0 * self.n * self.m


@dataclasses.dataclass(frozen=True)
class Attention:
    batch: int
    sq: int
    sk: int
    heads: int
    kv_heads: int
    head_dim: int
    causal: bool
    count: int = 1

    @property
    def pairs(self) -> float:
        if self.causal:
            return self.sq * (self.sq + 1) / 2.0
        return float(self.sq * self.sk)

    def fwd_ops(self) -> float:
        return 2 * 2.0 * self.batch * self.heads * self.pairs * self.head_dim

    def bwd_ops(self) -> float:
        return 2 * self.fwd_ops()

    def fwd_bytes(self) -> float:
        q = self.batch * self.sq * self.heads * self.head_dim * 2.0
        kv = self.batch * self.sk * self.kv_heads * self.head_dim * 2.0
        return 2 * q + 2 * kv + self.batch * self.heads * self.sq * 4.0

    def bwd_bytes(self) -> float:
        q = self.batch * self.sq * self.heads * self.head_dim * 2.0
        kv = self.batch * self.sk * self.kv_heads * self.head_dim * 2.0
        return 4 * q + 4 * kv + 2 * self.batch * self.heads * self.sq * 4.0


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  bw: float) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(ops / peak_ops, nbytes / bw)


def mfu_seconds(linears, attns, peaks) -> float:
    """Least time of the step's required operations at peak: forward and
    input-gradient matmuls of quantized linears at the int8 peak, all else
    at the bf16 peak."""
    i8, bf = peaks["int8_ops"], peaks["bf16_flops"]
    t = 0.0
    for ln in linears:
        fast = i8 if ln.quantized else bf
        t += ln.count * ln.matmul_ops * ((1 + ln.dgrad) / fast + 1 / bf)
    for a in attns:
        t += a.count * (a.fwd_ops() + a.bwd_ops()) / bf
    return t
