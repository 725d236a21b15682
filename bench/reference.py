"""The comparison that decides ``correct``, and what every family's float32
reference shares.

Each architecture family's ``logits`` (``bench/families/<family>.py``) is
a plain float32 forward pass of the served model as the configuration
file states it.  It imports nothing of the program: its weights come from
``weights.py``, one layer at a time inside the layer loop, so only one
layer's float32 weights are on the device at once.

Every matmul runs at ``Precision.HIGHEST`` (``_ein``).  ``mode="fp8"`` is
the control: the same computation with every matmul's two inputs rounded
to float8 e4m3 (a scale per weight tensor, per activation row), the
precision one step below the configuration's bfloat16.

One sequence per call, padded to a fixed length so that one program
serves every request: the attention is causal, so padding at the end
changes no earlier position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from config import family
from weights import base_key

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
    logits, key = family(c).logits, base_key(seed)
    with jax.default_matmul_precision("highest"):
        ref = logits(c, key, tokens, "f32")
        other = logits(c, key, tokens, "fp8") if control else ref
        g_served, g_ctrl = _gaps(ref, nxt, other)
    span = slice(n_prompt - 1, len(seq) - 1)
    return (np.asarray(g_served)[span],
            np.asarray(g_ctrl)[span] if control else None)
