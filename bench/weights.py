"""Weights of the program's parameter tree, made on the device from the seed.

The architecture's module (bench/arch/) lists the tree's leaves: for each
path, the shape of one instance, its fan-in, how it is initialised and how
many instances are stacked on a leading axis. Paths run through dict keys
and through the indices of tuple nodes ("blocks/5/mix/wq"). Every instance
of every leaf is drawn from its own key, folded from the seed, the leaf's
path and the instance's index, so the reference can make one instance of
one leaf again, bit for bit, without the whole tree.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import zlib

import jax
import jax.numpy as jnp

from bench import arch
from bench.data import seed_key


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One leaf of the tree. `shape` is one instance's (a layer's weight,
    with any leading axes of its own, such as experts); `init` is "normal"
    (scaled by fan_in ** -0.5), "ones" or "zeros"; `stack` instances lie on
    a leading axis, or the leaf is one instance with no such axis (0);
    `dtype` None takes the configuration's."""
    shape: tuple
    fan_in: int | None
    init: str
    stack: int = 0
    dtype: str | None = None

    @property
    def instances(self) -> int:
        return self.stack or 1


def padded_vocab(vocab: int) -> int:
    """Rows of the program's embedding: the vocabulary padded to 256."""
    return -(-int(vocab) // 256) * 256


def leaves(config: dict) -> dict:
    """{path: Leaf} of the configuration's architecture."""
    return arch.of(config).leaves(config)


def dtype_of(config: dict):
    return jnp.dtype(config["assumed"]["model"].get("dtype", "bfloat16"))


def seed_leaf_key(seed: int):
    """The key of the whole tree; each leaf and instance folds its own from it."""
    return seed_key(seed)


def layer_leaf(config: dict, key, path: str, layer, dtype=None):
    """One instance of one leaf (instance 0 of an unstacked leaf), from the
    tree's key; traceable in `key` and `layer`."""
    leaf = leaves(config)[path]
    dtype = dtype or leaf.dtype or dtype_of(config)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    key = jax.random.fold_in(key, zlib.crc32(path.encode()))
    key = jax.random.fold_in(key, layer)
    return (jax.random.normal(key, leaf.shape, jnp.float32)
            * leaf.fan_in ** -0.5).astype(dtype)


@functools.lru_cache(maxsize=None)
def leaf_fn(config_json: str, path: str):
    """jit of layer_leaf for one configuration and leaf: (key, layer) -> W0."""
    config = json.loads(config_json)
    return jax.jit(lambda key, layer: layer_leaf(config, key, path, layer))


def _nest(flat: dict):
    """The tree of {"a/b/c": leaf}; a node whose keys are 0..n-1 is a tuple."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v

    def build(node):
        if not isinstance(node, dict):
            return node
        if node and sorted(node) == sorted(str(i) for i in range(len(node))):
            return tuple(build(node[str(i)]) for i in range(len(node)))
        return {k: build(v) for k, v in node.items()}

    return build(tree)


def init_params(config: dict, seed: int):
    """The whole tree, traceable (the program jits it onto its shardings)."""
    key = seed_leaf_key(seed)
    flat = {}
    for path, leaf in leaves(config).items():
        if leaf.stack:
            flat[path] = jax.vmap(
                lambda l, p=path: layer_leaf(config, key, p, l))(jnp.arange(leaf.stack))
        else:
            flat[path] = layer_leaf(config, key, path, 0)
    return _nest(flat)


def flat_paths(tree, prefix: str = "", is_leaf=None) -> dict:
    """{"a/0/c": leaf} of a tree of dicts, tuples and lists; a node for
    which `is_leaf` holds is kept whole."""
    if is_leaf is not None and is_leaf(tree):
        return {prefix.rstrip("/"): tree}
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (tuple, list)):
        items = enumerate(tree)
    else:
        return {prefix.rstrip("/"): tree}
    out = {}
    for k, v in items:
        out.update(flat_paths(v, f"{prefix}{k}/", is_leaf))
    return out
