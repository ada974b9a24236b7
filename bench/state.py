"""Readings of the program's own training state, for the comparison with
the reference: the first gradient as the optimizer holds it, and how far
each leaf has moved from the weights the benchmark made.

Norms are taken per leaf and layer ("blocks/ffn/up", 3) on the device and
only the numbers come back to the host.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench import weights as bw
from bench.reference.galore import side


def galore_state(opt_state):
    """The GaLore transform's state inside the optimizer chain's tuple."""
    for s in opt_state:
        if isinstance(s, dict) and {"proj", "inner"} <= set(s):
            return s
    raise ValueError("no GaLore state in the optimizer state")


def _layer_norms(x, stacked: bool):
    """Norm of each layer of a stacked leaf, or of an unstacked one."""
    x = x.astype(jnp.float32)
    if stacked:
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(jnp.square(x)))[None]


def first_grad_norms(config: dict, opt_state, b1: float) -> dict:
    """{(path, layer): ‖g‖} of the first step's clipped gradient as the
    optimizer got it (projected for GaLore leaves), from its first moment
    after one step: m_1 = (1 − b1) g. Int8 moments are decoded with the
    program's own codec, which is part of what runs; one jitted program
    reads them all."""
    m_tree = bw.flat_paths(_unquant_paths(galore_state(opt_state)["inner"]["m"]))
    norms = jax.device_get(_moment_norms(json.dumps(config, sort_keys=True))(m_tree))
    out = {}
    for path, per_layer in norms.items():
        for layer, n in enumerate(per_layer):
            out[(path, layer)] = float(n) / (1.0 - b1)
    return out


@functools.lru_cache(maxsize=None)
def _moment_norms(config_json: str):
    from repro.quant import codec

    config = json.loads(config_json)
    rank = config["assumed"]["galore"]["rank"]
    shapes = bw.leaf_shapes(config)

    @jax.jit
    def fn(m_tree):
        out = {}
        for path, m in m_tree.items():
            per_layer_shape, _, _, stacked = shapes[path]
            axis = -2 if side(path, per_layer_shape, rank) == "right" else -1
            if isinstance(m, tuple):
                m = codec.dequant_axis_state({"q": m[0], "scale": m[1]},
                                             axis=axis, signed=True)
            out[path] = _layer_norms(m, stacked)
        return out

    return fn


def _unquant_paths(tree):
    """The moment tree with each int8 {"q", "scale"} node as a (q, scale)
    leaf, so that paths end at the weight's name."""
    if isinstance(tree, dict):
        if set(tree) == {"q", "scale"}:
            return (tree["q"], tree["scale"])
        return {k: _unquant_paths(v) for k, v in tree.items()}
    return tree


@functools.lru_cache(maxsize=None)
def _diff_norm(config_json: str, path: str):
    config = json.loads(config_json)
    stacked = bw.leaf_shapes(config)[path][3]

    @jax.jit
    def fn(leaf, key, layer):
        w0 = bw.layer_leaf(config, key, path, layer)
        w = leaf[layer] if stacked else leaf
        return jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32)
                                           - w0.astype(jnp.float32))))

    return fn


def change_norms(config: dict, seed: int, params) -> dict:
    """{(path, layer): ‖W − W0‖} of the program's weights against the ones
    the benchmark made, one layer at a time."""
    cj = json.dumps(config, sort_keys=True)
    key = bw.seed_leaf_key(seed)
    L = config["num_hidden_layers"]
    out = {}
    for path, leaf in bw.flat_paths(params).items():
        stacked = bw.leaf_shapes(config)[path][3]
        fn = _diff_norm(cj, path)
        for layer in range(L if stacked else 1):
            out[(path, layer)] = fn(leaf, key, jnp.int32(layer))
    return {k: float(v) for k, v in jax.device_get(out).items()}
