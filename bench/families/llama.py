"""The ``llama`` family: a dense decoder-only transformer.

Per layer RMSNorm, grouped-query attention with rotary positions
(half-split rotation, base ``rope_theta``, scores times
``attention_multiplier`` or head_dim**-0.5), a causal softmax over the
whole prefix, the output projection, a second RMSNorm and a SwiGLU MLP,
each branch added times ``residual_multiplier``; token embedding (times
``embedding_multiplier``) before the first layer, a final RMSNorm and the
unembedding (tied or a separate head), divided by ``logits_scaling``,
after the last.  All layers are one stacked stage of the program
(``stages.stage_0``).

A configuration file without a ``"family"`` key is of this family.  The
module's interface is its four public functions (``config.FAMILY_API``);
the rest is its own.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from reference import _ein, _rmsnorm
from weights import base_key, embed_weights, freeze, layer_weights
from work import Work

def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program_config(c: dict):
    """The program's ``ModelConfig`` with every size taken from ``c``."""
    from repro.configs import get_config
    base = get_config(c["repo_config"])
    return dataclasses.replace(
        base,
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=_head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], dtype=c["torch_dtype"],
        act=c["hidden_act"])


# -- weights -----------------------------------------------------------------

def _layer_leaves(c: dict):
    """(group, name, shape, scale) of one decoder layer; scale None marks
    a norm scale."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  _head_dim(c))
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


def _layer_weights(c: dict, key, layer, dtype) -> dict:
    return layer_weights(c, _layer_leaves(c), key, layer, dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(frozen, key):
    c = dict(frozen)
    served = jnp.dtype(c["torch_dtype"])
    layers = jax.vmap(lambda i: _layer_weights(c, key, i, served))(
        jnp.arange(c["num_hidden_layers"]))
    emb, final = embed_weights(c, key, served)
    return {"embed": emb, "final_norm": {"scale": final},
            "stages": {"stage_0": layers}}


def program_params(c: dict, seed: int):
    """The whole parameter tree, made on the device in one jitted call."""
    return _program_params(freeze(c), base_key(seed))


# -- the float32 reference ---------------------------------------------------

def _rope(x, pos, theta):
    """x (S, H, D); rotate the two halves of D by pos * theta**(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv      # (S, 1, D/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c, w, x, mode):
    s = x.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 _head_dim(c))
    eps = c["rms_norm_eps"]
    res = c.get("residual_multiplier", 1.0)
    pos = jnp.arange(s)
    a = _rmsnorm(x, w["ln1"]["scale"], eps)
    q = _rope(_ein("sd,dhk->shk", a, w["attn"]["wq"], mode), pos,
              c["rope_theta"])
    k = _rope(_ein("sd,dhk->shk", a, w["attn"]["wk"], mode), pos,
              c["rope_theta"])
    v = _ein("sd,dhk->shk", a, w["attn"]["wv"], mode)
    q = q.reshape(s, hkv, h // hkv, d)
    scores = _ein("sjgk,tjk->jgst", q, k, mode) * c.get(
        "attention_multiplier", d ** -0.5)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _ein("jgst,tjk->sjgk", p, v, mode).reshape(s, h, d)
    x = x + res * _ein("shk,hkd->sd", o, w["attn"]["wo"], mode,
                       a_axis=(-2, -1))
    b = _rmsnorm(x, w["ln2"]["scale"], eps)
    up = _ein("sd,df->sf", b, w["ffn"]["w_in"], mode)
    gate = _ein("sd,df->sf", b, w["ffn"]["w_gate"], mode)
    return x + res * _ein("sf,fd->sd", jax.nn.silu(gate) * up,
                          w["ffn"]["w_out"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(frozen, key, tokens, mode):
    c = dict(frozen)
    emb, final = embed_weights(c, key, jnp.float32)
    x = emb["table"][tokens] * c.get("embedding_multiplier", 1.0)

    def body(i, x):
        return _layer(c, _layer_weights(c, key, i, jnp.float32), x, mode)

    x = jax.lax.fori_loop(0, c["num_hidden_layers"], body, x)
    x = _rmsnorm(x, final, c["rms_norm_eps"])
    if "head" in emb:
        out = _ein("sd,dv->sv", x, emb["head"], mode)
    else:
        out = _ein("sd,vd->sv", x, emb["table"], mode)
    return out / c.get("logits_scaling", 1.0)


def logits(c: dict, key, tokens, mode: str):
    """Float32 logits of every position of ``tokens`` (one sequence);
    ``mode="fp8"`` is the control."""
    return _logits(freeze(c), key, tokens, mode)


# -- work counts -------------------------------------------------------------

class _Shape(NamedTuple):
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    page: int
    kv_bytes: int = 2              # bfloat16 pages
    act_bytes: int = 2             # bfloat16 q and out


def _shape_of(c: dict, page: int) -> _Shape:
    return _Shape(c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], _head_dim(c), c["vocab_size"],
                  page)


def _layer_matmul_params(s: _Shape) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the
    three SwiGLU matrices."""
    attn = s.d_model * s.head_dim * (2 * s.heads + 2 * s.kv_heads)
    return attn + 3 * s.d_model * s.d_ff


def _attn_flops(s: _Shape, ctxs) -> int:
    """QK^T and PV over each row's live context, all layers."""
    per_row = 4 * s.heads * s.head_dim
    return s.layers * per_row * sum(int(c) + 1 for c in ctxs)


def _attn_bytes(s: _Shape, ctxs, owners) -> int:
    """Each request's live pages of K and V read once per layer, plus q
    read and out written for every row."""
    live: dict = {}
    for c, o in zip(ctxs, owners):
        live[o] = max(live.get(o, 0), int(c) + 1)
    pages = sum(-(-n // s.page) for n in live.values())
    kv = pages * s.page * s.kv_heads * s.head_dim * s.kv_bytes * 2
    qo = len(ctxs) * s.heads * s.head_dim * s.act_bytes * 2
    return s.layers * (kv + qo)


def _step_flops(s: _Shape, ctxs, n_logits: int) -> int:
    """Useful model operations of one step: 2 per weight per real row,
    attention over each row's live context, and the unembedding of the
    rows whose logits are used."""
    dense = 2 * s.layers * _layer_matmul_params(s) * len(ctxs)
    return dense + _attn_flops(s, ctxs) + 2 * s.d_model * s.vocab * n_logits


def _counts(s: _Shape) -> Work:
    return Work(functools.partial(_step_flops, s),
                functools.partial(_attn_flops, s),
                functools.partial(_attn_bytes, s), ())


def work(c: dict, page: int) -> Work:
    """The counts of one step of ``c`` with pages of ``page`` tokens."""
    return _counts(_shape_of(c, page))
