"""Operations and bytes of the work a step asks for, from shapes alone.

The same logical work is counted whatever lowering runs it:

* a ternary GEMM (M, K) x (K, N) is ``2*M*K*N`` operations; it reads its
  weights as 2-bit codes (``K*N/4`` bytes) plus a float32 scale per output
  channel, and bf16 activations in and out;
* the fused MLP is the gate and up GEMMs (K = d, N = ff) and the down GEMM
  (K = ff, N = d) with the hidden activation kept on chip: bf16 in and out
  once, weights once;
* paged attention is ``4 * len * heads * head_dim`` operations for each
  query row (scores and the weighted sum over the ``len`` tokens it
  attends). It reads the K and V of each slot row's keys once
  (``2 * keys * kv_heads * head_dim`` cache elements): a decode row
  attends its own keys, and the query rows of a chunk window share their
  slot's pages, so a window's row of S tokens after a prefix of p reads
  p + S keys, not one copy of them for each of its S queries. Each query
  row's input and output are read and written once.

A step's least time on the chip is the larger of its operations over the
peak rate and its bytes over the memory bandwidth (``least_time``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

BF16 = 2


@dataclasses.dataclass
class Work:
    ops: float = 0.0
    bytes: float = 0.0
    least_s: float = 0.0

    def add(self, ops: float, nbytes: float, peaks: Dict[str, float],
            times: float = 1.0) -> None:
        self.ops += ops * times
        self.bytes += nbytes * times
        self.least_s += least_time(ops, nbytes, peaks) * times


def least_time(ops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def gemm(m: int, k: int, n: int):
    ops = 2.0 * m * k * n
    nbytes = k * n / 4 + 4 * n + BF16 * m * k + BF16 * m * n
    return ops, nbytes


def fused_mlp(m: int, d: int, ff: int):
    ops = 2.0 * m * d * ff * 3
    nbytes = 3 * d * ff / 4 + 4 * (2 * ff + d) + BF16 * m * d * 2
    return ops, nbytes


def paged_attention(rows: int, attended: int, heads: int, kv_heads: int,
                    head_dim: int, kv_bytes: int = BF16,
                    keys: Optional[int] = None):
    """``rows`` query rows attending ``attended`` tokens in all, reading
    ``keys`` cached tokens (``attended`` when each row has its own)."""
    keys = attended if keys is None else keys
    ops = 4.0 * attended * heads * head_dim
    nbytes = (2 * keys * kv_heads * head_dim * kv_bytes
              + 2 * rows * heads * head_dim * BF16)
    return ops, nbytes


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The per-layer widths of a dense GQA decoder."""
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int

    @classmethod
    def from_config(cls, c: Dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   ff=c["intermediate_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   vocab=c["vocab_size"])

    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through: projections, MLP, head."""
        q = self.d * self.heads * self.head_dim
        kv = 2 * self.d * self.kv_heads * self.head_dim
        o = self.heads * self.head_dim * self.d
        mlp = 3 * self.d * self.ff
        return self.layers * (q + kv + o + mlp) + self.d * self.vocab


class StepWork:
    """Sums the kernel work and the useful model operations of the steps
    of a window. A forward over ``m`` token rows runs every projection and
    the head at M = m, the fused MLP at M = m, and paged attention over
    ``m`` query rows that read ``keys`` cached tokens (``attended`` when
    every row is its own slot's)."""

    def __init__(self, shapes: Shapes, peaks: Dict[str, float]):
        self.s = shapes
        self.peaks = peaks
        self.gemm = Work()
        self.attn = Work()
        self.useful_ops = 0.0

    def forward(self, m: int, attended: int,
                keys: Optional[int] = None) -> None:
        s, p = self.s, self.peaks
        hq = s.heads * s.head_dim
        hkv = s.kv_heads * s.head_dim
        for k, n in ((s.d, hq), (s.d, hkv), (s.d, hkv), (hq, s.d)):
            self.gemm.add(*gemm(m, k, n), p, times=s.layers)
        self.gemm.add(*fused_mlp(m, s.d, s.ff), p, times=s.layers)
        self.gemm.add(*gemm(m, s.d, s.vocab), p)
        self.attn.add(*paged_attention(m, attended, s.heads, s.kv_heads,
                                       s.head_dim, keys=keys), p,
                      times=s.layers)

    def useful(self, tokens: int, attended: int) -> None:
        """Real tokens (pad rows excluded) and the context they attend."""
        s = self.s
        self.useful_ops += (2.0 * s.matmul_params * tokens
                            + 4.0 * s.layers * s.heads * s.head_dim
                            * attended)

    def counters(self, deltas: Dict[str, int]) -> None:
        """The engine's counters over one step, by name. The shapes and
        row counts above are all a dense step needs."""
