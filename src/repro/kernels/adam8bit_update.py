"""Fused 8-bit Adam update on flat (nb, 256) blocks: a shim over the
epilogue's fold.

Flat blocks of 256 are the axis layout with n = qblock = BLOCK: one
quantization block per row. `galore_fused.adam8_axis_update` runs them
through the quantized GaLore epilogue with ``project=False`` (R = G),
TILE_BLOCKS rows per grid step, so one kernel body serves every quantized
variant. This shim keeps the historical signature (including the codebook
args — the epilogue owns its codebooks, which are the same
`dynamic_codebook` tables every caller ever passed) and the historical
shapes, bitwise.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.galore_fused import adam8_axis_update
from repro.optim.quant8 import BLOCK

TILE_BLOCKS = 16  # rows of 256 elements per grid step

__all__ = ["BLOCK", "TILE_BLOCKS", "adam8bit_update"]


def adam8bit_update(
    g_blocks, m_codes, m_scale, v_codes, v_scale, count,
    book_signed, book_unsigned,
    *, b1=0.9, b2=0.999, eps=1e-8, interpret: bool = False,
):
    """Inputs: g (nb, BLOCK) f32; codes (nb, BLOCK) u8; scales (nb,) f32;
    count scalar int32; codebooks (256,) f32 (accepted for signature
    compatibility — the fused epilogue uses the canonical dynamic
    codebooks, which are what every caller passes). Returns
    (update, m_codes', m_scale', v_codes', v_scale')."""
    del book_signed, book_unsigned
    # moments are not aliased: the eager adam8bit_step callers reuse them
    upd, mq, ms, vq, vs = adam8_axis_update(
        g_blocks.astype(jnp.float32), m_codes, m_scale[:, None], v_codes,
        v_scale[:, None], count, b1=b1, b2=b2, eps=eps, qblock=BLOCK,
        rows_per_step=TILE_BLOCKS, alias_moments=False, interpret=interpret)
    return upd, mq, ms[:, 0], vq, vs[:, 0]
