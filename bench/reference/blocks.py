"""Float32 building blocks of the plain references (bench/arch/).

Written from the published descriptions of the models the benchmark runs:
RMSNorm, rotary embeddings on half rotation, grouped-query causal attention
with optional q/k/v bias, a SwiGLU MLP, and the output head tied to the
embedding over the vocabulary slice (the program's departure, see the
configuration's `departures`). Nothing here imports the program.

Matrix products run at "highest" precision, or, for the control, with both
operands rounded to float8 (e4m3, one scale per tensor) in the forward and
the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(F32) * s


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    g8 = _fp8(g)
    return (jnp.matmul(g8, _fp8(b.T), precision=HIGHEST),
            jnp.matmul(_fp8(a.T), g8, precision=HIGHEST))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def matmul(a, b, precision: str = "f32"):
    """a (..., k) @ b (k, n) in float32, or rounded to float8 for the control."""
    if precision == "fp8":
        lead = a.shape[:-1]
        return _mm8(a.reshape(-1, a.shape[-1]), b).reshape(lead + (b.shape[-1],))
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (B, S, heads, hd): the first and second halves of each head are the
    two coordinates rotated by position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(c: dict, w: dict, x, precision):
    B, S, _ = x.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // H

    def proj(name, heads):
        y = matmul(x, w["w" + name], precision)
        if "b" + name in w:
            y = y + w["b" + name]
        return y.reshape(B, S, heads, hd)

    q = rotary(proj("q", H), c["rope_theta"])
    k = rotary(proj("k", KV), c["rope_theta"])
    v = proj("v", KV)
    q = q.reshape(B, S, KV, H // KV, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v, precision=HIGHEST)
    return matmul(out.reshape(B, S, H * hd), w["wo"], precision)


def mlp(w: dict, x, precision):
    h = jax.nn.silu(matmul(x, w["gate"], precision)) * matmul(x, w["up"], precision)
    return matmul(h, w["down"], precision)


def head_loss(c: dict, final_scale, emb, x, batch, precision: str = "f32"):
    """Mean next-token cross entropy over the loss mask, with the logits of
    the tied head over the vocabulary slice (pad rows masked out)."""
    h = rms_norm(x, final_scale, c["rms_norm_eps"])
    logits = matmul(h, emb.T, precision)
    valid = jnp.arange(emb.shape[0]) < c["vocab_size"]
    logits = jnp.where(valid, logits, -jnp.inf)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch["loss_mask"]
    return jnp.sum((logz - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
