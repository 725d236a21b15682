"""The program's own step tracer and flight recorder, read for the
benchmark.

``repro.serving.telemetry.StepTracer`` times the parts of each engine
iteration on the host (``PROGRAM_SPANS``) as profiler annotations and
keeps one record per iteration (``span_ns`` and ``counts``);
``FlightRecorder`` stamps each request event with the same wall clock
(``tw``).  This module reduces them:

- ``split_program`` separates the program's spans from the harness's in
  a trace loaded by ``tracefile.load``;
- ``gap_split`` divides an idle gap among the program spans, each
  instant to the innermost span there, and ``name_gap`` names the gap by
  the span that holds most of it, falling back to the harness's spans;
- the per-iteration readings (``sched_span_ms``, ``fetch_ms``,
  ``logits_used_share``, ``pad_row_share``) and ``queue_waits`` take the
  drained records and the recorder's events.

Every function returns None (or nothing) where the program it reads has
no tracer: nothing here raises on a program without one.
"""
from __future__ import annotations

import math

import tracefile

PROGRAM_SPANS = ("engine.step", "engine.submit", "core.admit",
                 "core.prepare", "core.plan", "core.lifecycle",
                 "step.pack", "step.dispatch", "step.wait", "step.fetch",
                 "step.widen")
CORE_SPANS = ("core.admit", "core.prepare", "core.plan", "core.lifecycle")
HOST_SPANS = ("step.fetch", "step.widen")


def split_program(spans):
    """(program spans, other spans) of ``(name, start, end)`` lists."""
    prog = [s for s in spans if s[0] in PROGRAM_SPANS]
    return prog, [s for s in spans if s[0] not in PROGRAM_SPANS]


def gap_split(gap, program) -> dict:
    """Seconds of ``gap`` under each program span's name, each instant
    given to the innermost (shortest) span that covers it."""
    lo, hi = gap
    cuts = sorted({lo, hi} | {t for s in program for t in s[1:]
                              if lo < t < hi})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [s for s in program if s[1] <= a and b <= s[2]]
        if cover:
            name = min(cover, key=lambda s: s[2] - s[1])[0]
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def name_gap(gap, program, spans) -> str:
    """The program span that holds most of ``gap`` innermost; where none
    covers any of it, the harness span that overlaps it most
    (``tracefile.name_gap``)."""
    split = gap_split(gap, program)
    if not split:
        return tracefile.name_gap(gap, spans)
    return max(split, key=split.get)


def top_gaps(ops, program, spans, t0, t1, n=10):
    """``tracefile.top_gaps`` with each gap named by ``name_gap``, and
    its split among the program's spans."""
    gaps = sorted(tracefile.idle_gaps(ops, t0, t1),
                  key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(g, program, spans), (g[1] - g[0]) * 1e-9,
             gap_split(g, program)] for g in gaps]


def covered(program, outer) -> float:
    """The share of the ``outer`` span ``(name, start, end)`` that the
    ``step.*`` spans inside it cover."""
    inner = [(max(s[1], outer[1]), min(s[2], outer[2])) for s in program
             if s[0].startswith("step.") and s[2] > outer[1]
             and s[1] < outer[2]]
    span = outer[2] - outer[1]
    return sum(e - s for s, e in tracefile.union(inner)) / span \
        if span > 0 else math.nan


def _launched(records):
    return [r for r in records if r["counts"].get("rows_launched")]


def sched_span_ms(records):
    """Host ms per iteration inside the four ``core.*`` calls."""
    if not records:
        return None
    return 1e-6 * sum(r["span_ns"].get(s, 0) for r in records
                      for s in CORE_SPANS) / len(records)


def fetch_ms(records):
    """Host ms per iteration bringing the logits to the host
    (``step.fetch``) and widening them (``step.widen``)."""
    if not records:
        return None
    return 1e-6 * sum(r["span_ns"].get(s, 0) for r in records
                      for s in HOST_SPANS) / len(records)


def logits_used_share(records):
    """Percent of the logits rows brought to the host that the engine
    reads (final chunk rows and decode rows)."""
    rs = _launched(records)
    if not rs:
        return None
    return 100.0 * sum(r["counts"]["logits_rows_used"] for r in rs) / sum(
        r["counts"]["logits_rows_fetched"] for r in rs)


def pad_row_share(records):
    """Percent of the fused launches' rows that are padding."""
    rs = _launched(records)
    if not rs:
        return None
    return 100.0 * (1.0 - sum(r["counts"]["rows_real"] for r in rs) / sum(
        r["counts"]["rows_launched"] for r in rs))


def queue_waits(events, rids) -> dict:
    """{rid: (queue wait, admitted to first token)} in seconds on the
    recorder's wall clock for each of ``rids`` that arrived: from its
    arrival to its first admission, and on to its first token.  A request
    not yet admitted, or without a first token, counts to the last
    event's stamp."""
    arrived, admitted, first = {}, {}, {}
    end = None
    for ev in events:
        tw = ev.get("tw")
        if tw is None:
            return {}
        end = tw
        rid = ev.get("rid")
        if ev["type"] == "arrival":
            arrived[rid] = tw
        elif ev["type"] == "admit":
            admitted.setdefault(rid, tw)
        elif ev["type"] == "first_token":
            first.setdefault(rid, tw)
    out = {}
    for rid in rids:
        if rid in arrived:
            a = admitted.get(rid, end)
            out[rid] = (a - arrived[rid], first.get(rid, end) - a)
    return out
