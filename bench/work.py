"""Operations and bytes that one fused step needs, from its shapes.

Each architecture family counts its own step (``work`` in
``bench/families/<family>.py``) by the rules below.

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

from typing import Callable, NamedTuple


class Work(NamedTuple):
    """What an architecture family's ``work(c, page)`` returns: the counts
    of one step of that configuration, which the readers use."""
    step_flops: Callable        # (ctxs, n_logits) -> useful operations
    attn_flops: Callable        # (ctxs) -> attention operations
    attn_bytes: Callable        # (ctxs, owners) -> attention bytes
    kernel_names: tuple         # the family's own kernels' device ops,
    #                             beyond readers.KERNEL_NAMES


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float):
    """(seconds, bound): the larger of compute time and memory time at
    the chip's peaks, and which of the two it is."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
