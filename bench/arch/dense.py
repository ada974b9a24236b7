"""The dense decoder of the Llama lineage (Qwen2, InternLM2, Llama).

The program's tree is `{"embed", "final_norm", "blocks": {"attn", "ln1",
"ln2", "ffn"}}`, each leaf of `blocks` stacked over the layers. The
reference is RMSNorm, rotary embeddings, grouped-query causal attention with
optional q/k/v bias and a SwiGLU MLP in each layer, and the output head
tied to the embedding over the vocabulary slice (bench/reference/blocks.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import counts
from bench.reference.blocks import attention, head_loss, mlp, rms_norm
from bench.weights import Leaf, padded_vocab

# published config.json keys -> ModelConfig fields of the program
MODEL_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "head_dim": "head_dim",
}

# leaf of `blocks` -> the name the layer reads it by
SHORT = {
    "blocks/attn/wq": "wq", "blocks/attn/wk": "wk", "blocks/attn/wv": "wv",
    "blocks/attn/wo": "wo", "blocks/attn/bq": "bq", "blocks/attn/bk": "bk",
    "blocks/attn/bv": "bv", "blocks/ln1/scale": "ln1",
    "blocks/ln2/scale": "ln2", "blocks/ffn/gate": "gate",
    "blocks/ffn/up": "up", "blocks/ffn/down": "down",
}
PATH = {v: k for k, v in SHORT.items()}


def head_dim(config: dict) -> int:
    return int(config.get("head_dim")
               or config["hidden_size"] // config["num_attention_heads"])


def model_fields(config: dict) -> dict:
    kw = {field: config[key] for key, field in MODEL_KEYS.items() if key in config}
    kw.setdefault("head_dim", head_dim(config))
    return kw


def leaves(config: dict) -> dict:
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    hd = head_dim(config)
    f = config["intermediate_size"]
    L = config["num_hidden_layers"]
    out = {
        "embed/embedding": Leaf((padded_vocab(config["vocab_size"]), d), d, "normal"),
        "final_norm/scale": Leaf((d,), None, "ones"),
        "blocks/attn/wq": Leaf((d, H * hd), d, "normal", L),
        "blocks/attn/wk": Leaf((d, KV * hd), d, "normal", L),
        "blocks/attn/wv": Leaf((d, KV * hd), d, "normal", L),
        "blocks/attn/wo": Leaf((H * hd, d), H * hd, "normal", L),
        "blocks/ln1/scale": Leaf((d,), None, "ones", L),
        "blocks/ln2/scale": Leaf((d,), None, "ones", L),
        "blocks/ffn/gate": Leaf((d, f), d, "normal", L),
        "blocks/ffn/up": Leaf((d, f), d, "normal", L),
        "blocks/ffn/down": Leaf((f, d), f, "normal", L),
    }
    if config["assumed"]["model"].get("qkv_bias"):
        out["blocks/attn/bq"] = Leaf((H * hd,), None, "zeros", L)
        out["blocks/attn/bk"] = Leaf((KV * hd,), None, "zeros", L)
        out["blocks/attn/bv"] = Leaf((KV * hd,), None, "zeros", L)
    return out


def layer_kinds(config: dict) -> list:
    """The kind of each layer, by index: every one a decoder layer here."""
    return ["decoder"] * config["num_hidden_layers"]


def layer(c: dict, w: dict, x, precision: str = "f32"):
    """One decoder layer; w holds the layer's leaves by short name (wq, wk,
    wv, wo, [bq, bk, bv], ln1, ln2, gate, up, down)."""
    eps = c["rms_norm_eps"]
    x = x + attention(c, w, rms_norm(x, w["ln1"], eps), precision)
    return x + mlp(w, rms_norm(x, w["ln2"], eps), precision)


def _key(c: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "vocab_size")
    return tuple((k, c[k]) for k in keys if k in c)


@functools.lru_cache(maxsize=None)
def _programs(c_key: tuple, precision: str):
    """{kind: (forward, backward)} of one layer, the head's loss and
    gradients, and the embedding's gradient, jitted once per configuration."""
    c = dict(c_key)

    @jax.jit
    def fwd(w, x):
        return layer(c, w, x, precision)

    @jax.jit
    def bwd(w, x, dy):
        _, vjp = jax.vjp(lambda w_, x_: layer(c, w_, x_, precision), w, x)
        return vjp(dy)

    if precision == "fp8":
        # the float8 casts take a third more scratch than float32: at 64 rows
        # of 256, more than the chip has free beside the reference's state
        bwd = _in_row_blocks(bwd, 2)

    @jax.jit
    def head(final_scale, emb, x, batch):
        return jax.value_and_grad(
            lambda s, e, x_: head_loss(c, s, e, x_, batch, precision),
            argnums=(0, 1, 2))(final_scale, emb, x)

    @jax.jit
    def embed_grad(d_emb, tokens, dx0):
        return d_emb.at[tokens.reshape(-1)].add(dx0.reshape(-1, dx0.shape[-1]))

    return {"decoder": (fwd, bwd)}, head, embed_grad


_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
               donate_argnums=0)


def _in_row_blocks(bwd, n: int):
    """`bwd(w, x, dy)` over n blocks of rows, the weights' gradients summed."""
    def blocked(w, x, dy):
        cut = [x.shape[0] * i // n for i in range(n + 1)]
        dw, dxs = None, []
        for i, j in zip(cut, cut[1:]):
            g, d = bwd(w, x[i:j], dy[i:j])
            dw = g if dw is None else _add(dw, g)
            dxs.append(d)
        return dw, jnp.concatenate(dxs)

    return blocked


def loss_and_grads(c: dict, weight, batch, on_grad, precision: str = "f32"):
    """Loss and gradients of a stage, one layer at a time: the caller keeps
    the layer inputs and walks the layers back, so that a whole stage fits
    beside its state and no more than one layer's gradients are alive."""
    layers, head, embed_grad = _programs(_key(c), precision)
    names = [p for p in SHORT if p in leaves(c)]
    kinds = layer_kinds(c)

    def layer_weights(l):
        return {SHORT[p]: weight(p, l) for p in names}

    emb = weight("embed/embedding", 0)
    x = jnp.take(emb, batch["tokens"], axis=0)
    xs = []
    for l, kind in enumerate(kinds):
        xs.append(x)
        x = layers[kind][0](layer_weights(l), x)
    loss, (d_scale, d_emb, dx) = head(weight("final_norm/scale", 0), emb, x, batch)
    del emb
    for l in reversed(range(len(xs))):
        dw, dx = layers[kinds[l]][1](layer_weights(l), xs.pop(), dx)
        for s, g in dw.items():
            on_grad(PATH[s], l, g)
    on_grad("final_norm/scale", 0, d_scale)
    on_grad("embed/embedding", 0, embed_grad(d_emb, batch["tokens"], dx))
    return loss


def matmul_params(config: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and the tied output head over the vocabulary slice (the
    embedding lookup is a gather and is not counted)."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    hd = head_dim(config)
    f = config["intermediate_size"]
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * f
    return config["num_hidden_layers"] * per_layer + config["vocab_size"] * d


def flops_per_token(config: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per trained token: 6 per matmul weight
    plus attention, with no recomputation counted."""
    return 6 * matmul_params(config) + counts.attention_flops_per_token(
        config["num_hidden_layers"], config["num_attention_heads"],
        head_dim(config), seq_len)
