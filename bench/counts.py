"""Operations and bytes the work needs, computed from shapes.

The yardstick of the utilisation metrics: the attention term of a model's
FLOPs per trained token (each architecture counts the rest in its module
under bench/arch/), and a fused GaLore kernel call's matrix FLOPs and HBM
bytes. They are kept with the benchmark so that a change to the program
cannot move them.
"""
from __future__ import annotations

F32 = 4
QBLOCK = 128  # block of the int8 moments' and int4 projectors' scales


def attention_flops_per_token(layers: int, heads: int, head_dim: int,
                              seq_len: int) -> int:
    """PaLM's count (Chowdhery et al. 2022, appendix B) for causal softmax
    attention: 12 · layers · heads · head size · sequence length, forward
    and backward."""
    return 12 * layers * heads * head_dim * seq_len


def galore_kernel(side: str, layers: int, m: int, n: int, rank: int,
                  g_bytes: int = 2) -> tuple:
    """(matrix FLOPs, HBM bytes) of one fused 8-bit GaLore-Adam call over a
    stacked leaf of `layers` (m, n) weights: R = PᵀG (or G P) and the
    back-projection P N̂, 2·m·n·r FLOPs each; it reads G, the packed int4
    projector with its scales and the int8 moments with theirs, and writes
    the f32 update and the moments again."""
    short, long_ = (m, n) if side == "left" else (n, m)
    flops = 4 * m * n * rank
    projector = (0.5 + F32 / QBLOCK) * short * rank
    moments = 2 * 2 * rank * long_ * (1 + F32 / QBLOCK)  # m and v, read + write
    bytes_ = g_bytes * m * n + F32 * m * n + projector + moments
    return layers * flops, layers * bytes_
