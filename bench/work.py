"""Operations and bytes that one fused step needs, from its shapes.

The counts are of the work the step has to do, whatever kernel does it:
padding rows, pages past a request's context and logits nobody reads
count nothing.  So a kernel that skips pages or a step that drops unused
logits raises its share of the roofline instead of making the count
stale.

A step is described by its real rows: for each, its context position
``ctx`` (the row attends ``ctx + 1`` tokens), the request it belongs to,
and whether its logits are used (the last row of a chunk that completes
a prompt, and every decode row).
"""
from __future__ import annotations

from dataclasses import dataclass

from config import head_dim


@dataclass(frozen=True)
class Shape:
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


def shape_of(c: dict, page: int, kv_bytes: int = 2) -> Shape:
    return Shape(c["num_hidden_layers"], c["hidden_size"],
                 c["intermediate_size"], c["num_attention_heads"],
                 c["num_key_value_heads"], head_dim(c), c["vocab_size"],
                 page, kv_bytes)


def layer_matmul_params(s: Shape) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the
    three SwiGLU matrices."""
    attn = s.d_model * s.head_dim * (2 * s.heads + 2 * s.kv_heads)
    return attn + 3 * s.d_model * s.d_ff


def attn_flops(s: Shape, ctxs) -> int:
    """QK^T and PV over each row's live context, all layers."""
    per_row = 4 * s.heads * s.head_dim
    return s.layers * per_row * sum(int(c) + 1 for c in ctxs)


def attn_bytes(s: Shape, ctxs, owners) -> int:
    """Each request's live pages of K and V read once per layer, plus q
    read and out written for every row."""
    live: dict = {}
    for c, o in zip(ctxs, owners):
        live[o] = max(live.get(o, 0), int(c) + 1)
    pages = sum(-(-n // s.page) for n in live.values())
    kv = pages * s.page * s.kv_heads * s.head_dim * s.kv_bytes * 2
    qo = len(ctxs) * s.heads * s.head_dim * s.act_bytes * 2
    return s.layers * (kv + qo)


def step_flops(s: Shape, ctxs, n_logits: int) -> int:
    """Useful model operations of one step: 2 per weight per real row,
    attention over each row's live context, and the unembedding of the
    rows whose logits are used."""
    dense = 2 * s.layers * layer_matmul_params(s) * len(ctxs)
    return dense + attn_flops(s, ctxs) + 2 * s.d_model * s.vocab * n_logits


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float):
    """(seconds, bound): the larger of compute time and memory time at
    the chip's peaks, and which of the two it is."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
