"""Weights from the seed: the draw every architecture family shares.

A family lists one layer's leaves and lays them out as the program wants
them (``bench/families/<family>.py``); this module draws them.  Every leaf
is drawn from its own key, and every layer of a stacked leaf from
``fold_in(leaf key, layer)``, so the reference can draw one layer's
weights again without holding the rest (``layer_weights``).  The values
are drawn in float32 and rounded once to the served type: the program
gets that type, the reference the same rounded numbers in float32.

Scales: projections are normal with standard deviation fan_in**-0.5, the
embedding table 0.02, norm scales 1 + 0.1 * normal rounded to bfloat16
(not all ones, so a norm applied with the wrong layer's scale shows in
the comparison).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any non-negative whole number, 64 bits and more."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return key


def _draw(key, shape, scale):
    """One rounding step each, so every compiled program that draws a
    leaf gets the same bits: a product, or 1 plus a bfloat16 number
    (exact in float32)."""
    # the barrier keeps the compiler from folding ``scale`` into the
    # sampler's own constants, which would round differently
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    if scale is None:
        return 1.0 + (0.1 * x).astype(jnp.bfloat16).astype(jnp.float32)
    return x * scale


def _leaf_key(key, i: int):
    return jax.random.fold_in(key, 1000 + i)


def layer_weights(c: dict, leaves, key, layer, dtype) -> dict:
    """Layer ``layer``'s weights (traceable in ``layer``), rounded to the
    served type and returned in ``dtype``.  ``leaves`` lists the layer's
    (group, name, shape, scale), scale None marking a norm scale; leaf i
    is drawn from ``_leaf_key(key, i)``."""
    served = jnp.dtype(c["torch_dtype"])
    out: dict = {}
    for i, (group, name, shape, scale) in enumerate(leaves):
        k = jax.random.fold_in(_leaf_key(key, i), layer)
        w = _draw(k, shape, scale).astype(served).astype(dtype)
        out.setdefault(group, {})[name] = w
    return out


def embed_weights(c: dict, key, dtype) -> dict:
    served = jnp.dtype(c["torch_dtype"])
    d, v = c["hidden_size"], c["vocab_size"]
    out = {"table": (jax.random.normal(jax.random.fold_in(key, 1), (v, d),
                                       jnp.float32) * 0.02)}
    if not c["tie_word_embeddings"]:
        out["head"] = _draw(jax.random.fold_in(key, 2), (d, v), d ** -0.5)
    final = _draw(jax.random.fold_in(key, 3), (d,), None)
    rounded = {k: w.astype(served).astype(dtype) for k, w in out.items()}
    return rounded, final.astype(served).astype(dtype)


def freeze(c):
    """A hashable view of ``c`` (static jit argument): its items sorted by
    key, lists as tuples in their order and dicts as tuples of sorted
    items, so ``dict(freeze(c))`` gives every key, nested ones whole."""
    if isinstance(c, dict):
        return tuple(sorted((k, freeze(v)) for k, v in c.items()))
    if isinstance(c, (list, tuple)):
        return tuple(freeze(v) for v in c)
    return c
