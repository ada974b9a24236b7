"""The one token generator of the benchmark, driven by a traffic file.

A C4-like stream with learnable structure: Zipf unigrams, a hidden bigram
map and a share of structured successors (after the program's
`repro.data.pipeline.SyntheticC4`, copied here so that the yardstick does
not move with the program). The program's map, x -> (x * mult + 7) mod V,
is not a permutation when mult shares a factor with V: chains of
successors fall into short cycles or a fixed point, and a row repeats one
token for most of its length. Here each bigram map is one
cycle through the whole vocabulary, drawn from the seed, so no token is its
own successor and no chain closes before it has visited every token. Rows
are a pure function of (seed, step): the same seed gives the same batches,
every step's rows differ, and every seed gives the same shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that uses all the bits of a seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def bigram_successor(key, vocab: int):
    """successor[x] for every token x: one cycle through all `vocab` tokens
    in an order drawn from `key` (successor(order[j]) = order[j + 1])."""
    order = jax.random.permutation(key, vocab).astype(jnp.int32)
    return jnp.zeros((vocab,), jnp.int32).at[order].set(jnp.roll(order, -1))


class TokenStream:
    """batch(step) -> {"tokens", "targets", "loss_mask"} on the device, for
    `rows` sequences of `traffic["seq_len"]` tokens over `vocab` ids."""

    def __init__(self, traffic: dict, vocab: int, rows: int, seed: int):
        if traffic.get("generator", "synthetic_c4") != "synthetic_c4":
            raise ValueError(f"unknown generator {traffic['generator']!r}")
        self.traffic = traffic
        self.vocab = int(vocab)
        self.rows = int(rows)
        self.seq = int(traffic["seq_len"])
        self.key = jax.random.fold_in(seed_key(seed), 0xDA7A)
        self._fn = jax.jit(self._make)

    def _make(self, step):
        t = self.traffic
        V, B, S = self.vocab, self.rows, self.seq
        k_uni, k_map, k_step = (jax.random.fold_in(self.key, i) for i in (1, 2, 3))
        ranks = jnp.arange(1, V + 1, dtype=jnp.float32)
        unigram = (-float(t["zipf_exponent"]) * jnp.log(ranks)
                   + float(t["zipf_jitter"]) * jax.random.normal(k_uni, (V,)))
        successor = bigram_successor(
            jax.random.fold_in(k_map, step % int(t["bigram_maps"])), V)
        k1, k2, k3 = jax.random.split(jax.random.fold_in(k_step, step), 3)
        first = jax.random.categorical(k1, unigram, shape=(B, 1))
        noise = jax.random.categorical(k2, unigram, shape=(B, S)).astype(jnp.int32)
        structured = jax.random.bernoulli(k3, float(t["structured_share"]), (B, S))

        def scan_fn(prev, inp):
            noise_t, struct_t = inp
            nxt = jnp.where(struct_t, successor[prev], noise_t)
            return nxt, nxt

        _, rest = jax.lax.scan(scan_fn, first[:, 0].astype(jnp.int32),
                               (noise.T[:-1], structured.T[:-1]))
        tokens = jnp.concatenate([first, rest.T], axis=1).astype(jnp.int32)
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
        mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
        return {"tokens": tokens, "targets": targets, "loss_mask": mask}

    def batch(self, step: int):
        return self._fn(jnp.int32(step))
