"""Weights of a dense decoder, made on the device from the seed.

The tree is the one the program trains (`{"embed", "final_norm",
"blocks": {"attn", "ln1", "ln2", "ffn"}}`, layers stacked on a leading
axis). Every matrix of every layer is drawn from its own key, folded from
the seed, the leaf's name and the layer, so the reference can make one
layer of one leaf again, bit for bit, without the whole tree.
"""
from __future__ import annotations

import functools
import json
import zlib

import jax
import jax.numpy as jnp

from bench.data import seed_key


def padded_vocab(vocab: int) -> int:
    """Rows of the program's embedding: the vocabulary padded to 256."""
    return -(-int(vocab) // 256) * 256


def leaf_shapes(config: dict) -> dict:
    """{path: (shape of one layer, fan_in or None, kind, stacked)}; kind is
    "normal", "ones" or "zeros"."""
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    hd = config.get("head_dim") or d // H
    f = config["intermediate_size"]
    out = {
        "embed/embedding": ((padded_vocab(config["vocab_size"]), d), d, "normal", False),
        "final_norm/scale": ((d,), None, "ones", False),
        "blocks/attn/wq": ((d, H * hd), d, "normal", True),
        "blocks/attn/wk": ((d, KV * hd), d, "normal", True),
        "blocks/attn/wv": ((d, KV * hd), d, "normal", True),
        "blocks/attn/wo": ((H * hd, d), H * hd, "normal", True),
        "blocks/ln1/scale": ((d,), None, "ones", True),
        "blocks/ln2/scale": ((d,), None, "ones", True),
        "blocks/ffn/gate": ((d, f), d, "normal", True),
        "blocks/ffn/up": ((d, f), d, "normal", True),
        "blocks/ffn/down": ((f, d), f, "normal", True),
    }
    if config["assumed"]["model"].get("qkv_bias"):
        out["blocks/attn/bq"] = ((H * hd,), None, "zeros", True)
        out["blocks/attn/bk"] = ((KV * hd,), None, "zeros", True)
        out["blocks/attn/bv"] = ((KV * hd,), None, "zeros", True)
    return out


def dtype_of(config: dict):
    return jnp.dtype(config["assumed"]["model"].get("dtype", "bfloat16"))


def seed_leaf_key(seed: int):
    """The key of the whole tree; each leaf and layer folds its own from it."""
    return seed_key(seed)


def layer_leaf(config: dict, key, path: str, layer, dtype=None):
    """One layer of one leaf (layer 0 for unstacked leaves), from the tree's
    key; traceable in `key` and `layer`."""
    shape, fan_in, kind, _ = leaf_shapes(config)[path]
    dtype = dtype or dtype_of(config)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    key = jax.random.fold_in(key, zlib.crc32(path.encode()))
    key = jax.random.fold_in(key, layer)
    return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)


@functools.lru_cache(maxsize=None)
def leaf_fn(config_json: str, path: str):
    """jit of layer_leaf for one configuration and leaf: (key, layer) -> W0."""
    config = json.loads(config_json)
    return jax.jit(lambda key, layer: layer_leaf(config, key, path, layer))


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def init_params(config: dict, seed: int):
    """The whole tree, traceable (the program jits it onto its shardings)."""
    L = config["num_hidden_layers"]
    key = seed_leaf_key(seed)
    flat = {}
    for path, (_, _, _, stacked) in leaf_shapes(config).items():
        if stacked:
            flat[path] = jax.vmap(
                lambda l, p=path: layer_leaf(config, key, p, l))(jnp.arange(L))
        else:
            flat[path] = layer_leaf(config, key, path, 0)
    return _nest(flat)


def flat_paths(tree, prefix: str = "") -> dict:
    """{"a/b/c": leaf} of a nested dict tree."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_paths(v, p + "/"))
        else:
            out[p] = v
    return out
