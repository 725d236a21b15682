"""Plain float32 reference of the served model, and its fp8 control.

A decoder-only transformer as the configuration file states it: token
embedding (times ``embedding_multiplier``), then per layer RMSNorm,
grouped-query attention with rotary positions (half-split rotation, base
``rope_theta``, scores times ``attention_multiplier`` or head_dim**-0.5),
a causal softmax over the whole prefix, the output projection, a second
RMSNorm and a SwiGLU MLP, each branch added times ``residual_multiplier``;
a final RMSNorm and the unembedding (tied or a separate head), divided by
``logits_scaling``.  It imports nothing of the program: the weights come
from ``weights.py``, one layer at a time inside the layer loop, so only
one layer's float32 weights are on the device at once.

Every matmul runs at ``Precision.HIGHEST``.  ``mode="fp8"`` is the
control: the same computation with every matmul's two inputs rounded to
float8 e4m3 (a scale per weight tensor, per activation row), the
precision one step below the configuration's bfloat16.

One sequence per call, padded to a fixed length so that one program
serves every request: the attention is causal, so padding at the end
changes no earlier position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from config import head_dim
from weights import base_key, embed_weights, freeze, layer_weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8 e4m3fn


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale that maps the largest |x| over
    ``axis`` to the format's largest finite value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, mode, a_axis=-1):
    if mode == "fp8":
        a = _fp8(a, a_axis)
        b = _fp8(b, None)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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
                 head_dim(c))
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
        return _layer(c, layer_weights(c, key, i, jnp.float32), x, mode)

    x = jax.lax.fori_loop(0, c["num_hidden_layers"], body, x)
    x = _rmsnorm(x, final, c["rms_norm_eps"])
    if "head" in emb:
        out = _ein("sd,dv->sv", x, emb["head"], mode)
    else:
        out = _ein("sd,vd->sv", x, emb["table"], mode)
    return out / c.get("logits_scaling", 1.0)


@jax.jit
def _gaps(ref, nxt, other):
    """Per position: the reference's best logit less its logit of the
    served next token, and less its logit of ``other``'s first choice."""
    best = jnp.max(ref, -1)
    served = jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    pick = jnp.argmax(other, -1)
    chosen = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return best - served, best - chosen


def served_gaps(c: dict, seed: int, seq, n_prompt: int, length: int,
                control: bool = False):
    """Gaps of one request's served tokens against the reference.

    ``seq`` is the prompt followed by the served tokens; the token at
    position p + 1 was served from the logits of position p.  Returns the
    gap of each served token, and with ``control`` also the gap of the
    token the fp8 control puts first at each of those positions."""
    import numpy as np
    seq = np.asarray(seq, np.int32)
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} tokens > {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    nxt = np.zeros(length, np.int32)
    nxt[:len(seq) - 1] = seq[1:]
    frozen, key = freeze(c), base_key(seed)
    with jax.default_matmul_precision("highest"):
        ref = _logits(frozen, key, tokens, "f32")
        other = _logits(frozen, key, tokens, "fp8") if control else ref
        g_served, g_ctrl = _gaps(ref, nxt, other)
    span = slice(n_prompt - 1, len(seq) - 1)
    return (np.asarray(g_served)[span],
            np.asarray(g_ctrl)[span] if control else None)
