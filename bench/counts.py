"""Operations and bytes the work needs, computed from shapes.

The yardstick of the utilisation metrics: a model's FLOPs per trained
token, and a fused GaLore kernel call's matrix FLOPs and HBM bytes. They are
kept with the benchmark so that a change to the program cannot move them.
"""
from __future__ import annotations

F32 = 4
QBLOCK = 128  # block of the int8 moments' and int4 projectors' scales


def matmul_params(config: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and the tied output head over the vocabulary slice (the
    embedding lookup is a gather and is not counted)."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    hd = config.get("head_dim") or d // H
    f = config["intermediate_size"]
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * f
    return config["num_hidden_layers"] * per_layer + config["vocab_size"] * d


def attention_flops_per_token(config: dict, seq_len: int) -> int:
    """PaLM's count (Chowdhery et al. 2022, appendix B): 12 · layers ·
    heads · head size · sequence length, forward and backward."""
    H = config["num_attention_heads"]
    hd = config.get("head_dim") or config["hidden_size"] // H
    return 12 * config["num_hidden_layers"] * H * hd * seq_len


def model_flops_per_token(config: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per trained token: 6 per matmul weight
    plus attention, with no recomputation counted."""
    return 6 * matmul_params(config) + attention_flops_per_token(config, seq_len)


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
