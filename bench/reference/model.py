"""Plain float32 reference of a dense decoder: forward, loss and gradients.

Written from the published description of the Llama-style decoders the
benchmark runs (InternLM2, Qwen2): RMSNorm, rotary embeddings on half
rotation, grouped-query causal attention with optional q/k/v bias, a SwiGLU
MLP, and the output head tied to the embedding over the vocabulary slice
(the program's departure, see the configuration's `departures`). Nothing
here imports the program.

Matrix products run at "highest" precision, or, for the control, with both
operands rounded to float8 (e4m3, one scale per tensor) in the forward and
the backward pass. One layer is traced at a time, so that a whole stage
fits beside its state: the caller keeps the layer inputs and walks the
layers back.
"""
from __future__ import annotations

import functools

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


def layer(c: dict, w: dict, x, precision: str = "f32"):
    """One decoder layer; w holds the layer's leaves by short name (wq, wk,
    wv, wo, [bq, bk, bv], ln1, ln2, gate, up, down)."""
    eps = c["rms_norm_eps"]
    x = x + attention(c, w, rms_norm(x, w["ln1"], eps), precision)
    return x + mlp(w, rms_norm(x, w["ln2"], eps), precision)


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


def _key(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "vocab_size")
    return tuple((k, c[k]) for k in keys if k in c)


@functools.lru_cache(maxsize=None)
def _programs(c_key: tuple, precision: str):
    c = dict(c_key)

    @jax.jit
    def fwd(w, x):
        return layer(c, w, x, precision)

    @jax.jit
    def bwd(w, x, dy):
        _, vjp = jax.vjp(lambda w_, x_: layer(c, w_, x_, precision), w, x)
        return vjp(dy)

    @jax.jit
    def head(final_scale, emb, x, batch):
        return jax.value_and_grad(
            lambda s, e, x_: head_loss(c, s, e, x_, batch, precision),
            argnums=(0, 1, 2))(final_scale, emb, x)

    @jax.jit
    def embed_grad(d_emb, tokens, dx0):
        return d_emb.at[tokens.reshape(-1)].add(dx0.reshape(-1, dx0.shape[-1]))

    return fwd, bwd, head, embed_grad


def loss_and_grads(c: dict, weights, batch, on_layer, precision: str = "f32"):
    """Loss and gradients of a stage, one layer at a time.

    `weights.embed()`, `weights.final_scale()` and `weights.layer(l)` give
    float32 arrays; `on_layer(l, dw)` takes each layer's gradients as they
    come, last layer first, so that no more than one layer's are alive.
    Returns (loss, d_final_scale, d_embed)."""
    fwd, bwd, head, embed_grad = _programs(_key(c), precision)
    emb = weights.embed()
    x = jnp.take(emb, batch["tokens"], axis=0)
    xs = []
    for l in range(c["num_hidden_layers"]):
        xs.append(x)
        x = fwd(weights.layer(l), x)
    loss, (d_scale, d_emb, dx) = head(weights.final_scale(), emb, x, batch)
    del emb
    for l in reversed(range(len(xs))):
        dw, dx = bwd(weights.layer(l), xs.pop(), dx)
        on_layer(l, dw)
    d_emb = embed_grad(d_emb, batch["tokens"], dx)
    return loss, d_scale, d_emb
