"""Pure-jnp oracles for every Pallas kernel (the `ref.py` layer).

These are the numerical ground truth for the kernel sweep tests AND the
implementations the 512-device dry-run lowers (custom calls neither partition
on the CPU backend nor contribute FLOPs to cost_analysis — DESIGN.md §3.5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.optim import quant8


def galore_project(P: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """R = Pᵀ G.  P (..., m, r), G (..., m, n) -> (..., r, n) f32."""
    return jnp.einsum("...mr,...mn->...rn", P.astype(jnp.float32), G.astype(jnp.float32))


def galore_project_back(P: jnp.ndarray, N: jnp.ndarray, alpha: float) -> jnp.ndarray:
    """G̃ = α · P N.  P (..., m, r), N (..., r, n) -> (..., m, n) f32."""
    return alpha * jnp.einsum(
        "...mr,...rn->...mn", P.astype(jnp.float32), N.astype(jnp.float32)
    )


def lowrank_adam_update(R, M, V, count, b1=0.9, b2=0.999, eps=1e-8):
    """Fused Adam moment update + normalized step in the compact space.

    R, M, V: (r, n) f32. Returns (N_t, M_t, V_t)."""
    R = R.astype(jnp.float32)
    M_t = b1 * M + (1 - b1) * R
    V_t = b2 * V + (1 - b2) * jnp.square(R)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    N_t = (M_t / c1) / (jnp.sqrt(V_t / c2) + eps)
    return N_t, M_t, V_t


def galore_fused_adam_step(P, G, M, V, count, b1=0.9, b2=0.999, eps=1e-8, alpha=1.0):
    """Oracle for the fused leaf update: R = PᵀG → Adam → G̃ = α P N̂.

    P (..., m, r), G (..., m, n), M/V (..., r, n) f32.
    Returns (G̃ f32, M_t, V_t) — the exact composition of galore_project,
    lowrank_adam_update and galore_project_back."""
    R = galore_project(P, G)
    N_t, M_t, V_t = lowrank_adam_update(R, M, V, count, b1, b2, eps)
    return galore_project_back(P, N_t, alpha), M_t, V_t


def galore_project_right(P: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """R = G P.  P (..., n, r), G (..., m, n) -> (..., m, r) f32."""
    return jnp.einsum("...mn,...nr->...mr", G.astype(jnp.float32), P.astype(jnp.float32))


def galore_project_back_right(P: jnp.ndarray, N: jnp.ndarray, alpha: float) -> jnp.ndarray:
    """G̃ = α · N Pᵀ.  P (..., n, r), N (..., m, r) -> (..., m, n) f32."""
    return alpha * jnp.einsum(
        "...mr,...nr->...mn", N.astype(jnp.float32), P.astype(jnp.float32)
    )


def galore_fused_adam_step_right(P, G, M, V, count, b1=0.9, b2=0.999, eps=1e-8,
                                 alpha=1.0):
    """Right-side oracle: R = G P → Adam → G̃ = α N̂ Pᵀ.

    P (..., n, r), G (..., m, n), M/V (..., m, r) f32. Exactly the transpose
    of the left-side composition — the dedicated right-side kernel must match
    this without materializing any swapped views."""
    R = galore_project_right(P, G)
    N_t, M_t, V_t = lowrank_adam_update(R, M, V, count, b1, b2, eps)
    return galore_project_back_right(P, N_t, alpha), M_t, V_t


def adam8_axis_update(R, Mq, Ms, Vq, Vs, count, b1=0.9, b2=0.999, eps=1e-8,
                      stochastic=False, axis=-1):
    """Dequant → Adam → requant of axis-blocked INT8 moments (quant/codec.py:
    blocks of QBLOCK along `axis`), the moment shape being R's. Returns
    (N_t f32, Mq', Ms', Vq', Vs'). With `stochastic` the requant uses the
    counter-hash stochastic rounding the kernels share bitwise."""
    from repro.quant import codec

    m = codec.dequantize_axis(Mq, Ms, axis=axis, signed=True)
    v = codec.dequantize_axis(Vq, Vs, axis=axis, signed=False)
    N_t, M_t, V_t = lowrank_adam_update(R, m, v, count, b1, b2, eps)
    mq, ms = codec.quantize_axis(M_t, axis=axis, signed=True,
                                 stochastic=stochastic, count=count,
                                 salt=codec.SR_SALT_M)
    vq, vs = codec.quantize_axis(V_t, axis=axis, signed=False,
                                 stochastic=stochastic, count=count,
                                 salt=codec.SR_SALT_V)
    return N_t, mq, ms, vq, vs


def adam8_full_step(G, Mq, Ms, Vq, Vs, count, b1=0.9, b2=0.999, eps=1e-8,
                    stochastic=False):
    """Oracle for the full-shape INT8 fold (no projection, blocks along the
    last axis). Returns (update in G's dtype, Mq', Ms', Vq', Vs')."""
    out = adam8_axis_update(G, Mq, Ms, Vq, Vs, count, b1, b2, eps, stochastic)
    return (out[0].astype(G.dtype),) + out[1:]


def galore_fused_adam8_step(P, G, Mq, Ms, Vq, Vs, count, b1=0.9, b2=0.999,
                            eps=1e-8, alpha=1.0, stochastic=False):
    """Oracle for the INT8-moment fused epilogue (left side).

    M/V arrive as axis-blocked codes + scales (quant/codec.py: blocks of
    QBLOCK along n). Exactly the composition project → dequant → Adam →
    requant → back-project the kernel performs in one VMEM pass; code-level
    agreement is within 1 ulp of the codebook (searchsorted vs the kernel's
    midpoint-count rule differ only on exact midpoint hits). With
    `stochastic` the requant uses the counter-hash stochastic rounding the
    kernel shares bitwise (codec.quantize_axis(stochastic=True))."""
    N_t, *moments = adam8_axis_update(galore_project(P, G), Mq, Ms, Vq, Vs,
                                      count, b1, b2, eps, stochastic)
    return (galore_project_back(P, N_t, alpha), *moments)


def galore_fused_adam8_step_right(P, G, Mq, Ms, Vq, Vs, count, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0,
                                  stochastic=False):
    """Right-side INT8-moment oracle: blocks run along the swept m axis."""
    N_t, *moments = adam8_axis_update(galore_project_right(P, G), Mq, Ms, Vq,
                                      Vs, count, b1, b2, eps, stochastic,
                                      axis=-2)
    return (galore_project_back_right(P, N_t, alpha), *moments)


def _apply_weight(W, gt, eta, wd):
    w32 = W.astype(jnp.float32)
    return (w32 + eta * (gt + wd * w32)).astype(W.dtype)


def galore_fused_adam_apply_step(P, G, W, M, V, count, b1=0.9, b2=0.999,
                                 eps=1e-8, alpha=1.0, eta=-1e-3, wd=0.0):
    """Weight-apply oracle: the emit-path composition followed by the chain's
    decay/lr application, W' = W + eta·(α P N̂ + wd·W)."""
    gt, M_t, V_t = galore_fused_adam_step(P, G, M, V, count, b1, b2, eps, alpha)
    return _apply_weight(W, gt, eta, wd), M_t, V_t


def galore_fused_adam_apply_step_right(P, G, W, M, V, count, b1=0.9, b2=0.999,
                                       eps=1e-8, alpha=1.0, eta=-1e-3, wd=0.0):
    gt, M_t, V_t = galore_fused_adam_step_right(P, G, M, V, count, b1, b2, eps,
                                                alpha)
    return _apply_weight(W, gt, eta, wd), M_t, V_t


def galore_fused_adam8_apply_step(P, G, W, Mq, Ms, Vq, Vs, count, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0, eta=-1e-3,
                                  wd=0.0, stochastic=False):
    out = galore_fused_adam8_step(P, G, Mq, Ms, Vq, Vs, count, b1, b2, eps,
                                  alpha, stochastic=stochastic)
    return (_apply_weight(W, out[0], eta, wd),) + out[1:]


def galore_fused_adam8_apply_step_right(P, G, W, Mq, Ms, Vq, Vs, count, b1=0.9,
                                        b2=0.999, eps=1e-8, alpha=1.0,
                                        eta=-1e-3, wd=0.0, stochastic=False):
    out = galore_fused_adam8_step_right(P, G, Mq, Ms, Vq, Vs, count, b1, b2,
                                        eps, alpha, stochastic=stochastic)
    return (_apply_weight(W, out[0], eta, wd),) + out[1:]


def quantize_blocks(x_blocks: jnp.ndarray, book: jnp.ndarray):
    """x (nb, BLOCK) f32 -> (codes u8, absmax f32 (nb,)). book sorted (256,)."""
    absmax = jnp.max(jnp.abs(x_blocks), axis=1) + 1e-12
    normed = x_blocks / absmax[:, None]
    mids = (book[:-1] + book[1:]) / 2.0
    codes = jnp.searchsorted(mids, normed).astype(jnp.uint8)
    return codes, absmax


def dequantize_blocks(codes: jnp.ndarray, absmax: jnp.ndarray, book: jnp.ndarray):
    return book[codes.astype(jnp.int32)] * absmax[:, None]


def adam8bit_update(g_blocks, m_codes, m_scale, v_codes, v_scale, count,
                    book_signed, book_unsigned, b1=0.9, b2=0.999, eps=1e-8):
    """One fused 8-bit Adam step on (nb, BLOCK) blocks.

    dequant m,v -> adam math in f32 -> requant m,v; returns
    (update_blocks, m_codes', m_scale', v_codes', v_scale')."""
    m = dequantize_blocks(m_codes, m_scale, book_signed)
    v = dequantize_blocks(v_codes, v_scale, book_unsigned)
    g = g_blocks.astype(jnp.float32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
    m_codes, m_scale = quantize_blocks(m, book_signed)
    v_codes, v_scale = quantize_blocks(v, book_unsigned)
    return upd, m_codes, m_scale, v_codes, v_scale


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)
