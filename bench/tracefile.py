"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps named by what the host was doing.

A trace is read into plain lists (``Trace``) so that the reduction can be
checked on a small recorded trace without the profiler (``from_json``):

- ``ops``: device operations, ``(name, start_ns, end_ns, label)``, one
  list per device; ``label`` is the op's framework name where the trace
  gives one (``tf_op``), which carries the JAX name stack;
- ``modules``: executions of compiled programs on the devices,
  ``(name, start_ns, end_ns)``;
- ``spans``: the harness's host spans, ``(name, start_ns, end_ns)``.

All times are on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    ops: list = field(default_factory=list)        # per device
    modules: list = field(default_factory=list)
    spans: list = field(default_factory=list)


def from_json(d: dict) -> Trace:
    return Trace([[tuple(e) for e in dev] for dev in d["ops"]],
                 [tuple(e) for e in d["modules"]],
                 [tuple(e) for e in d["spans"]])


# a control-flow op's event spans the ops of its body: it is not work of
# its own, and counting it would hide the idle time between those ops
CONTROL_FLOW = re.compile(r"\s(while|conditional|call)\(")
SHAPE = re.compile(r"\w+\[[\d,]*\]")


def _label(ev) -> str:
    """The op's framework name where the trace gives one, else its
    instruction text."""
    v = dict(ev.stats).get("tf_op")
    return v if isinstance(v, str) and v else ev.name


def is_control_flow(op) -> bool:
    return bool(CONTROL_FLOW.search(op[0]))


def work_ops(ops):
    return [o for o in ops if not is_control_flow(o)]


def load(logdir: str, span_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            tr.ops.append([(ev.name, ev.start_ns, ev.end_ns, _label(ev))
                           for ev in lines[OPS_LINE].events])
            if MODULES_LINE in lines:
                tr.modules += [(ev.name, ev.start_ns, ev.end_ns)
                               for ev in lines[MODULES_LINE].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                tr.spans += [(ev.name, ev.start_ns, ev.end_ns)
                             for ev in ln.events if ev.name in span_names]
    return tr


def union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy_ns(ops, t0, t1) -> float:
    """Time in [t0, t1) in which some operation ran on this device."""
    return float(sum(e - s for s, e in clip(
        union((o[1], o[2]) for o in work_ops(ops)), t0, t1)))


def matches(op, patterns) -> bool:
    return any(p in op[0] or p in op[3] for p in patterns)


def kernel_ns(ops, patterns, t0, t1) -> float:
    """Device time of the operations whose name or label contains one of
    ``patterns``, counted once where they overlap."""
    return float(sum(e - s for s, e in clip(
        union((o[1], o[2]) for o in work_ops(ops) if matches(o, patterns)),
        t0, t1)))


def module_ns(modules, pattern, t0, t1) -> float:
    return float(sum(e - s for s, e in clip(
        [(m[1], m[2]) for m in modules if pattern in m[0]], t0, t1)))


def idle_gaps(ops, t0, t1):
    """The intervals of [t0, t1) in which no operation ran."""
    gaps, cur = [], t0
    for s, e in clip(union((o[1], o[2]) for o in work_ops(ops)), t0, t1):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def name_gap(gap, spans) -> str:
    """The host span that overlaps ``gap`` the most, or ``"none"``."""
    best, name = 0, "none"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def top_ops(ops, t0, t1, n=10):
    """The ``n`` operations with the most device time, in seconds, by
    ``short`` name."""
    tot: dict = {}
    for name, s, e, label in work_ops(ops):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            key = short(label)
            tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def short(label: str) -> str:
    """An HLO instruction's name and first result shape
    (``fusion.79 bf16[256,49155]``); a name stack's last two parts."""
    if " = " in label:
        name, rest = label.split(" = ", 1)
        shape = SHAPE.search(rest)
        return name.lstrip("%") + (" " + shape.group(0) if shape else "")
    parts = [p for p in label.split("/") if p]
    return "/".join(parts[-2:]) if parts else label


def top_gaps(ops, spans, t0, t1, n=10):
    gaps = sorted(idle_gaps(ops, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(g, spans), (g[1] - g[0]) * 1e-9] for g in gaps]

