"""Faults planted under the timed path, to show that ``correct`` catches
them: each breaks the program where it produces its answer, and a run
that serves through it has to come out not correct.

- ``token_altered``: every sampled token is the next id after the
  greedy choice;
- ``state_unchanged``: the fused step returns the K/V pools it was given,
  so no iteration's K/V reaches later ones;
- ``half_left_out``: the fused step serves the second half of its rows
  as token 0 (the first half as given).

``planted(name)`` patches the program's module for the duration of a
``with`` block; the bench's own tools and tests plant them, a cell's
run never does.
"""
from __future__ import annotations

import contextlib

import numpy as np


def _step_fault(fault):
    def plant():
        from repro.serving import engine as mod
        real = mod._paged_decode_step

        def broken(params, tokens, ctx, tables, rmap, *rest):
            return fault(real, params, tokens, ctx, tables, rmap, *rest)
        broken._cache_size = real._cache_size
        return mod, "_paged_decode_step", broken
    return plant


def _state_unchanged(real, params, tokens, ctx, tables, rmap, kp, vp, *a):
    out = real(params, tokens, ctx, tables, rmap, kp, vp, *a)
    return (out[0], kp, vp) + tuple(out[3:])


def _half_left_out(real, params, tokens, ctx, tables, rmap, *a):
    tokens = np.array(tokens)
    tokens[len(tokens) // 2:] = 0
    return real(params, tokens, ctx, tables, rmap, *a)


def _token_altered():
    from repro.serving.engine import ServingEngine
    real = ServingEngine._sample

    def altered(self, row):
        return (real(self, row) + 1) % self.cfg.vocab_size
    return ServingEngine, "_sample", altered


FAULTS = {
    "token_altered": _token_altered,
    "state_unchanged": _step_fault(_state_unchanged),
    "half_left_out": _step_fault(_half_left_out),
}


@contextlib.contextmanager
def planted(name: str):
    owner, attr, broken = FAULTS[name]()
    real = getattr(owner, attr)
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, real)
