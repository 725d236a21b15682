"""The trace reduction on a small recorded trace (times in ns)."""
import json
from types import SimpleNamespace

import pytest

import readers
import tracefile
from conftest import DATA
from harness import Iteration
from work import Shape, attn_bytes, attn_flops, least_time, step_flops

T0, T1 = 0, 1200


@pytest.fixture
def tr():
    with open(DATA / "small_trace.json") as f:
        return tracefile.from_json(json.load(f))


def test_busy_is_the_union_of_op_intervals(tr):
    assert tracefile.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20),
                                                           (30, 40)]
    assert tracefile.busy_ns(tr.ops[0], T0, T1) == 450 + 100 + 100
    assert tracefile.busy_ns(tr.ops[0], 300, 750) == 150 + 50


def test_kernel_time_by_name_counts_overlap_once(tr):
    # the Pallas kernel [100, 400] and its combine [350, 450]
    assert tracefile.kernel_ns(tr.ops[0], readers.KERNEL_NAMES, T0,
                               T1) == 350
    assert tracefile.module_ns(tr.modules, readers.STEP_MODULE, T0,
                               T1) == 900


def test_gaps_are_named_by_the_host_span_they_fall_in(tr):
    assert tracefile.idle_gaps(tr.ops[0], T0, T1) == [(450, 700),
                                                     (800, 1000),
                                                     (1100, 1200)]
    gaps = tracefile.top_gaps(tr.ops[0], tr.spans, T0, T1)
    assert [g[0] for g in gaps] == ["mixed_step", "lifecycle",
                                    "mixed_step"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9,
                                                  100e-9])


def test_top_ops_name_instructions_and_skip_control_flow(tr):
    top = tracefile.top_ops(tr.ops[0], T0, T1)
    assert top[0] == ["paged_attention.4 f32[256,8,32,4,64]",
                      pytest.approx(300e-9)]
    assert len(top) == 5 and not any("while" in k for k, _ in top)
    assert tracefile.short("a/b/c/d") == "c/d"


def test_readers_on_the_recorded_trace(tr):
    shape = Shape(layers=2, d_model=8, d_ff=16, heads=4, kv_heads=2,
                  head_dim=2, vocab=10, page=4)
    it = Iteration(t0=0.0, t1=1.0, ctxs=[0, 1, 2, 7], owners=[1, 1, 1, 2],
                   n_logits=2, spans={"admit": 0.001, "plan": 0.002,
                                      "mixed_step": 0.5})
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    run = SimpleNamespace(shape=shape, peaks=peaks, trace=tr, t0=T0, t1=T1,
                          window_iters=[it, it], traced_iters=[it])
    assert readers.device_idle_share(run) == pytest.approx(
        100 * (1 - 650 / 1200))
    assert readers.sched_ms_per_iter(run) == pytest.approx(3.0)
    assert readers.step_mfu(run) == pytest.approx(
        100 * step_flops(shape, it.ctxs, 2) / (900e-9 * 1e12))
    need = least_time(attn_flops(shape, it.ctxs),
                      attn_bytes(shape, it.ctxs, it.owners), 1e12, 1e9)[0]
    assert readers.paged_attn_roofline(run) == pytest.approx(
        100 * need / 350e-9)


def test_readers_return_nothing_without_a_trace(tr):
    run = SimpleNamespace(trace=tracefile.Trace(), t0=0, t1=0,
                          window_iters=[], traced_iters=[])
    assert readers.device_idle_share(run) is None
    assert readers.sched_ms_per_iter(run) is None
    assert readers.step_mfu(run) is None
    assert readers.paged_attn_roofline(run) is None


@pytest.fixture
def recorded():
    with open(DATA / "recorded_trace.json") as f:
        d = json.load(f)
    return tracefile.from_json(d), d["window"]


def test_reduction_on_a_recorded_chip_trace(recorded):
    """An excerpt of a traced granite2b.tenants run on a v5e: the end of
    one fused step, the idle gap, the first layer of the next."""
    tr, (t0, t1) = recorded
    ops = tr.ops[0]
    work = tracefile.work_ops(ops)
    # busy by an independent count: a 1 us timeline
    import numpy as np
    line = np.zeros(int(t1 - t0) // 1000 + 1, bool)
    for _, s, e, _ in work:
        line[int(s - t0) // 1000:int(e - t0 + 999) // 1000] = True
    busy = tracefile.busy_ns(ops, t0, t1)
    assert busy == pytest.approx(line.sum() * 1000, rel=1e-2)
    gaps = tracefile.idle_gaps(ops, t0, t1)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(t1 - t0)
    # the paged kernel: the Pallas call and the combine fusions that read
    # its outputs, which do not overlap one another
    hits = [o for o in work if tracefile.matches(o, readers.KERNEL_NAMES)]
    assert any(o[0].startswith("%paged_attention") for o in hits)
    assert any(o[0].startswith("%multiply_reduce_fusion") for o in hits)
    assert tracefile.kernel_ns(ops, readers.KERNEL_NAMES, t0, t1) == \
        pytest.approx(sum(min(o[2], t1) - max(o[1], t0) for o in hits
                          if o[2] > t0 and o[1] < t1))
    # the longest idle gap lies inside the host's fused-step call (the
    # logits' copy to the host and the next step's inputs)
    name, secs = tracefile.top_gaps(ops, tr.spans, t0, t1, 1)[0]
    assert name == "mixed_step" and secs == pytest.approx(0.056947413)
    top = tracefile.top_ops(ops, t0, t1, 1)[0]
    assert top[0] == "paged_attention.4 f32[256,8,32,4,64]"
