"""Readings of the program's own training state, for the comparison with
the reference: the first gradient as the optimizer holds it, and how far
each leaf has moved from the weights the benchmark made.

Norms are taken per leaf and instance, (path, index), on the device
and only the numbers come back to the host. The first moment of the leaves
that take full-shape Adam is also kept whole, on the host, so that the
gradient can be compared element by element once the reference has run.
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


def _instance_norms(x, stacked: bool):
    """Norm of each instance of a stacked leaf, or of an unstacked one."""
    x = x.astype(jnp.float32)
    if stacked:
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(jnp.square(x)))[None]


def _is_int8(node) -> bool:
    return isinstance(node, dict) and set(node) == {"q", "scale"}


def first_grad_norms(config: dict, opt_state, b1: float) -> dict:
    """{(path, instance): ‖g‖} of the first step's clipped gradient as the
    optimizer got it (projected for GaLore leaves), from its first moment
    after one step: m_1 = (1 − b1) g. Int8 moments are decoded with the
    program's own codec, which is part of what runs; one jitted program
    reads them all."""
    m_tree = bw.flat_paths(galore_state(opt_state)["inner"]["m"], is_leaf=_is_int8)
    norms = jax.device_get(_moment_norms(json.dumps(config, sort_keys=True))(m_tree))
    out = {}
    for path, per_instance in norms.items():
        for i, n in enumerate(per_instance):
            out[(path, i)] = float(n) / (1.0 - b1)
    return out


def first_moments(config: dict, opt_state) -> dict:
    """{path: first moment} of every leaf that takes full-shape Adam, as the
    optimizer stores it (int8 codes and scales, or floats), copied to the
    host: the device keeps nothing of it."""
    rank = config["assumed"]["galore"]["rank"]
    leaves = bw.leaves(config)
    m_tree = bw.flat_paths(galore_state(opt_state)["inner"]["m"], is_leaf=_is_int8)
    return jax.device_get({p: m for p, m in m_tree.items()
                           if side(p, leaves[p].shape, rank) is None})


class FirstGrads:
    """{(path, instance): g} of the first step's gradient of each leaf that
    takes full-shape Adam, decoded on the device from the moments that
    `first_moments` copied, one leaf at a time, when it is looked up."""

    def __init__(self, config: dict, moments: dict, b1: float):
        self.config_json = json.dumps(config, sort_keys=True)
        self.stacked = {p: bool(l.stack) for p, l in bw.leaves(config).items()}
        self.moments = moments
        self.b1 = b1
        self._last = (None, None)

    def __getitem__(self, key):
        path, instance = key
        if self._last[0] != path:
            self._last = (path, None)  # free the leaf decoded before
            fn = _decode(self.config_json, path)
            self._last = (path, fn(self.moments[path]) / (1.0 - self.b1))
        g = self._last[1]
        return g[instance] if self.stacked[path] else g


@functools.lru_cache(maxsize=None)
def _decode(config_json: str, path: str):
    """jit of one full-shape leaf's first moment, decoded to float32 with the
    program's own codec."""
    from repro.quant import codec

    @jax.jit
    def fn(m):
        if _is_int8(m):
            m = codec.dequant_axis_state(m, axis=-1, signed=True)
        return m.astype(jnp.float32)

    return fn


@functools.lru_cache(maxsize=None)
def _moment_norms(config_json: str):
    from repro.quant import codec

    config = json.loads(config_json)
    rank = config["assumed"]["galore"]["rank"]
    leaves = bw.leaves(config)

    @jax.jit
    def fn(m_tree):
        out = {}
        for path, m in m_tree.items():
            leaf = leaves[path]
            axis = -2 if side(path, leaf.shape, rank) == "right" else -1
            if _is_int8(m):
                m = codec.dequant_axis_state(m, axis=axis, signed=True)
            out[path] = _instance_norms(m, bool(leaf.stack))
        return out

    return fn


@functools.lru_cache(maxsize=None)
def _diff_norm(config_json: str, path: str):
    config = json.loads(config_json)
    stacked = bool(bw.leaves(config)[path].stack)

    @jax.jit
    def fn(leaf, key, layer):
        w0 = bw.layer_leaf(config, key, path, layer)
        w = leaf[layer] if stacked else leaf
        return jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32)
                                           - w0.astype(jnp.float32))))

    return fn


def change_norms(config: dict, seed: int, params) -> dict:
    """{(path, instance): ‖W − W0‖} of the program's weights against the
    ones the benchmark made, one instance at a time."""
    cj = json.dumps(config, sort_keys=True)
    key = bw.seed_leaf_key(seed)
    leaves = bw.leaves(config)
    out = {}
    for path, leaf in bw.flat_paths(params).items():
        fn = _diff_norm(cj, path)
        for i in range(leaves[path].instances):
            out[(path, i)] = fn(leaf, key, jnp.int32(i))
    return {k: float(v) for k, v in jax.device_get(out).items()}
