"""Plain float32 GaLore-Adam training of a stage, for the first few steps.

What one optimizer step of the configuration does, written from the GaLore
paper (Zhao et al. 2024, Algorithm 2) and its stated settings, with none of
the program's storage tricks:

- the gradient of every leaf, then one global-norm clip;
- at the first step, for every matrix whose shorter side exceeds the rank
  (embeddings excluded), P = its top-`rank` singular vectors of the shorter
  side, found by subspace iteration with an oversampled sketch and a
  Rayleigh-Ritz step; R = PᵀG (or G P), Adam on R in float32, and the update
  scale · P N back at full shape. The matrix is an instance's last two axes:
  an instance with leading axes of its own (experts) has a P for each;
- every other leaf: Adam at full shape in float32;
- the learning rate of the configuration's warm-up / cosine schedule.

A GaLore weight moves only inside span(P) while P holds, so it is kept as
W0 + P·C with C of the compact shape: W0 is made again from the seed, one
instance at a time, and the compact state (P and the clipped projected
gradient of each step) is all that is stored. That is the same arithmetic
as a full-shape float32 weight, and it lets a 6144-wide stage run its
reference on the chip that trained it. The model itself, its forward pass,
loss and gradients, is the architecture's (bench/arch/).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch
from bench import weights as bw

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
SUBSPACE_ITERS = 4  # power iterations of the reference's subspace search


def side(path: str, shape: tuple, rank: int):
    """"left" (P spans the columns, m <= n), "right", or None for a leaf
    that takes full-shape Adam; `shape` is one instance's, whose last two
    axes (m, n) are the matrix."""
    if "embed" in path or len(shape) < 2 or min(shape[-2:]) <= rank:
        return None
    return "left" if shape[-2] <= shape[-1] else "right"


def schedule(train: dict, count: int) -> float:
    """Linear warm-up to `lr`, then cosine decay to a tenth at `total_steps`."""
    lr, warm, total = train["lr"], train["warmup_steps"], train["total_steps"]
    if count < warm:
        return lr * count / max(1.0, float(warm))
    progress = min(max((count - warm) / max(1.0, float(total - warm)), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * progress)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _sketch(A, k: int, iters: int, key):
    """Orthonormal Q (s, k) spanning the top of A's column space, and the
    Gram (QᵀA)(QᵀA)ᵀ for the Rayleigh-Ritz step."""
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    Q = jnp.linalg.qr(dot(A, jax.random.normal(key, (A.shape[1], k), F32)))[0]
    for _ in range(iters):
        Q = jnp.linalg.qr(dot(A, dot(A.T, Q)))[0]
    B = dot(Q.T, A)
    return Q, dot(B, B.T)


def top_subspace(A, rank: int, key):
    """Top-`rank` left singular vectors of A (s, l), s <= l."""
    k = min(A.shape[0], rank + max(64, rank // 8))
    Q, gram = _sketch(A, k, SUBSPACE_ITERS, key)
    _, U = np.linalg.eigh(np.asarray(gram, np.float64))
    top = jnp.asarray(U[:, ::-1][:, :rank].copy(), F32)
    return jnp.matmul(Q, top, precision=HIGHEST)


def projector(G, left: bool, rank: int, key):
    """P of an instance's gradient G (..., m, n): one top subspace of the
    shorter side for each matrix of the leading axes, each from its own key."""
    A = G if left else jnp.swapaxes(G, -1, -2)
    lead = A.shape[:-2]
    if not lead:
        return top_subspace(A, rank, key)
    flat = A.reshape((-1,) + A.shape[-2:])
    P = jnp.stack([top_subspace(a, rank, jax.random.fold_in(key, j))
                   for j, a in enumerate(flat)])
    return P.reshape(lead + P.shape[-2:])


@jax.jit
def _sq(x):
    return jnp.sum(jnp.square(x.astype(F32)))


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@functools.partial(jax.jit, static_argnums=(2,))
def _project(P, G, left: bool):
    return (jnp.matmul(_t(P), G, precision=HIGHEST) if left
            else jnp.matmul(G, P, precision=HIGHEST))


@functools.partial(jax.jit, static_argnums=(3,))
def _expand(W0, P, C, left: bool):
    return W0.astype(F32) + (jnp.matmul(P, C, precision=HIGHEST) if left
                             else jnp.matmul(C, _t(P), precision=HIGHEST))


@jax.jit
def _half_ulp_rms(w):
    """Root mean square of half the spacing of w's own floating-point type at
    each element: the least change a weight stored in that type can hold."""
    _, e = jnp.frexp(jnp.abs(w.astype(F32)))
    half = jnp.where(w == 0, 0.0, jnp.ldexp(jnp.ones((), F32),
                                            e - jnp.finfo(w.dtype).nmant - 2))
    return jnp.sqrt(jnp.mean(jnp.square(half)))


@jax.jit
def _adam_direction(Rs, b1, b2, eps):
    """Adam's bias-corrected direction N_t after gradients Rs[0..t-1]."""
    m = jnp.zeros_like(Rs[0])
    v = jnp.zeros_like(Rs[0])
    for R in Rs:
        m = b1 * m + (1 - b1) * R
        v = b2 * v + (1 - b2) * R * R
    t = len(Rs)
    return (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)


class GaloreReference:
    """The first steps of a stage's training in float32.

    `precision="fp8"` rounds the model's matrix products to float8 (the
    control); `rows` keeps only the first rows of every batch (a planted
    fault: half the batch left out). `fault` plants a fault in the update
    of the leaves that take full-shape Adam: "embed_update_skipped" leaves
    the embedding where it was, "v_scale_x4" reads the second moment four
    times too large."""

    FAULTS = (None, "embed_update_skipped", "v_scale_x4")

    def __init__(self, config: dict, seed: int, precision: str = "f32",
                 rows: int | None = None, fault: str | None = None):
        if fault not in self.FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.c = config
        self.seed = seed
        self.precision = precision
        self.rows = rows
        self.fault = fault
        a = config["assumed"]
        self.rank = a["galore"]["rank"]
        self.scale = a["galore"]["scale"]
        self.train = a["train"]
        self.arch = arch.of(config)
        self.leaves = bw.leaves(config)
        self.t = 0
        self.lrs: list = []
        self.P: dict = {}    # (path, instance) -> P
        self.R: dict = {}    # (path, instance) -> [clipped R of each step]
        self.full: dict = {}  # (path, instance) -> {"W", "m", "v"} float32
        self.losses: list = []
        self.grad_norms: dict = {}  # first step, clipped, as the optimizer gets it
        self.first_grads: dict = {}  # the same gradient of each full-shape leaf
        self.first_step_rms: dict = {}  # rms of each leaf's first update
        self.w0_half_ulp_rms: dict = {}
        cj = json.dumps(config, sort_keys=True)
        key = bw.seed_leaf_key(seed)
        self._w0 = {p: functools.partial(bw.leaf_fn(cj, p), key)
                    for p in self.leaves}

    # --- weights as the model reads them --------------------------------
    def side_of(self, path):
        return side(path, self.leaves[path].shape, self.rank)

    def weight(self, path, instance):
        key = (path, instance)
        if key in self.full:
            return self.full[key]["W"]
        w0 = self._w0[path](instance)
        if key not in self.P:
            return w0.astype(F32)
        return _expand(w0, self.P[key], self.compact_change(key),
                       self.side_of(path) == "left")

    def compact_change(self, key, upto=None):
        """C with W = W0 + P·C (left) or W0 + C·Pᵀ (right) after `upto` steps."""
        Rs = self.R[key][:upto]
        tr = self.train
        C = 0.0
        for t in range(1, len(Rs) + 1):
            N = _adam_direction(Rs[:t], tr["b1"], tr["b2"], tr["eps"])
            C = C - self.lrs[t - 1] * self.scale * N
        return C

    # --- one optimizer step ---------------------------------------------
    def step(self, batch):
        if self.rows is not None:
            batch = {k: v[:self.rows] for k, v in batch.items()}
        first = self.t == 0
        pending: dict = {}
        sq = []

        def take(path, instance, g):
            key = (path, instance)
            sq.append(_sq(g))
            sd = self.side_of(path)
            if sd is None:
                pending[key] = g
                return
            if first:
                k = jax.random.fold_in(jax.random.PRNGKey(self.seed & 0xFFFFFFFF),
                                       len(self.P))
                self.P[key] = projector(g, sd == "left", self.rank, k)
            pending[key] = _project(self.P[key], g, sd == "left")

        loss = self.arch.loss_and_grads(self.c, self.weight, batch, take,
                                        self.precision)
        if first:
            for key in pending:
                if key not in self.P:
                    self.full[key] = None
        gnorm = float(jnp.sqrt(sum(sq)))
        clip = min(1.0, self.train["grad_clip"] / (gnorm + 1e-9))
        self.t += 1
        self.lrs.append(schedule(self.train, self.t))
        tr = self.train
        v_read = 4.0 if self.fault == "v_scale_x4" else 1.0
        for key, g in pending.items():
            g = g * clip
            if first:
                self.grad_norms[key] = float(jnp.sqrt(_sq(g)))
                w0 = self._w0[key[0]](key[1])
                self.w0_half_ulp_rms[key] = float(_half_ulp_rms(w0))
            if key in self.P:
                self.R.setdefault(key, []).append(g)
                if first:
                    self.first_step_rms[key] = float(jnp.sqrt(
                        _sq(self.compact_change(key)) / w0.size))
                continue
            if first:
                self.first_grads[key] = g
            st = self.full[key]
            if st is None:
                w0 = self._w0[key[0]](key[1]).astype(F32)
                st = {"W": w0, "m": jnp.zeros_like(w0), "v": jnp.zeros_like(w0)}
            st["m"] = tr["b1"] * st["m"] + (1 - tr["b1"]) * g
            st["v"] = tr["b2"] * st["v"] + (1 - tr["b2"]) * g * g
            n = ((st["m"] / (1 - tr["b1"] ** self.t))
                 / (jnp.sqrt(v_read * st["v"] / (1 - tr["b2"] ** self.t))
                    + tr["eps"]))
            if not (self.fault == "embed_update_skipped" and "embed" in key[0]):
                st["W"] = st["W"] - self.lrs[-1] * n
            if first:
                self.first_step_rms[key] = float(jnp.sqrt(
                    _sq(st["W"] - w0) / w0.size))
            self.full[key] = st
        self.losses.append(float(loss))

    def change_norms(self) -> dict:
        """‖W_t − W_0‖ of every leaf and instance after the steps taken."""
        out = {}
        for key in self.P:
            out[key] = float(jnp.sqrt(_sq(self.compact_change(key))))
        for key, st in self.full.items():
            w0 = self._w0[key[0]](key[1]).astype(F32)
            out[key] = float(jnp.sqrt(_sq(st["W"] - w0)))
        return out


def run(config: dict, seed: int, batches, precision: str = "f32",
        rows: int | None = None, fault: str | None = None) -> dict:
    """Readings of the first len(batches) steps: per-step losses, the first
    step's gradient as the optimizer gets it (its norm for every leaf and
    instance, the whole of it for those that take full-shape Adam) and the
    change of every leaf and instance; with, for each, the root mean square
    of its first update and of half a spacing of its initial weights' type,
    and the leaves that move in a GaLore subspace."""
    ref = GaloreReference(config, seed, precision, rows, fault)
    for b in batches:
        ref.step(b)
    return {"losses": ref.losses, "grad_norms": ref.grad_norms,
            "first_grads": ref.first_grads,
            "change_norms": ref.change_norms(),
            "first_step_rms": ref.first_step_rms,
            "w0_half_ulp_rms": ref.w0_half_ulp_rms,
            "galore": sorted(ref.P)}
