"""Weights from the seed, in the program's parameter layout.

Every leaf is drawn from its own key, and every layer of a stacked leaf
from ``fold_in(leaf key, layer)``, so the reference can draw one layer's
weights again without holding the rest (``layer_weights``).  The values
are drawn in float32 and rounded once to the served type: the program
gets that type, the reference the same rounded numbers in float32.

Scales: projections are normal with standard deviation fan_in**-0.5, the
embedding table 0.02, norm scales 1 + 0.1 * normal rounded to bfloat16
(not all ones, so a norm applied with the wrong layer's scale shows in
the comparison).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from config import head_dim


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


def layer_leaves(c: dict):
    """(group, name, shape, scale) of one decoder layer; scale None marks
    a norm scale."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  head_dim(c))
    return [
        ("ln1", "scale", (d,), None),
        ("attn", "wq", (d, h, hd), d ** -0.5),
        ("attn", "wk", (d, hkv, hd), d ** -0.5),
        ("attn", "wv", (d, hkv, hd), d ** -0.5),
        ("attn", "wo", (h, hd, d), (h * hd) ** -0.5),
        ("ln2", "scale", (d,), None),
        ("ffn", "w_in", (d, f), d ** -0.5),
        ("ffn", "w_gate", (d, f), d ** -0.5),
        ("ffn", "w_out", (f, d), f ** -0.5),
    ]


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


def layer_weights(c: dict, key, layer, dtype) -> dict:
    """Layer ``layer``'s weights (traceable in ``layer``), rounded to the
    served type and returned in ``dtype``."""
    served = jnp.dtype(c["torch_dtype"])
    out: dict = {}
    for i, (group, name, shape, scale) in enumerate(layer_leaves(c)):
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


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(frozen, key):
    c = dict(frozen)
    served = jnp.dtype(c["torch_dtype"])
    layers = jax.vmap(lambda i: layer_weights(c, key, i, served))(
        jnp.arange(c["num_hidden_layers"]))
    emb, final = embed_weights(c, key, served)
    return {"embed": emb, "final_norm": {"scale": final},
            "stages": {"stage_0": layers}}


def freeze(c: dict):
    """A hashable view of the numbers and flags of ``c`` (static jit
    argument)."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def program_params(c: dict, seed: int):
    """The whole parameter tree, made on the device in one jitted call."""
    return _program_params(freeze(c), base_key(seed))
