"""Event-driven macro-stepping pinned bit-identical to the per-iteration
loop (DESIGN.md §15).

``SimConfig(macro_step=True)`` must be a pure *speed* knob: every request
timestamp, scheduler counter, KV count, timeline sample and telemetry
event has to come out byte-for-byte equal to the legacy loop, across the
policy matrix, with the prefix cache on or off, under both SLO budget
modes, with and without a flight recorder, and inside a cluster.  The
suite also pins the two building blocks the macro path's exactness rests
on: ``CostModel.decode_macro_times`` (closed-form per-iteration times ==
sequential cost-model calls) and ``SchedulerBase.on_tokens`` (bulk
billing == the sequential ``on_token`` fold), the latter as a property
over every registered policy.
"""
import copy

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.core import HFParams
from repro.core.request import Request
from repro.core.schedulers import DLPM, FCFS, RPM, VTC, Equinox, \
    make_scheduler
from repro.core.simulator import SimConfig, Simulator
from repro.predictor.mope import BasePredictor
from repro.serving.batch_core import BatchCore
from repro.serving.cluster import make_sim_cluster
from repro.serving.costmodel import A100_80G, CostModel
from repro.serving.telemetry import FlightRecorder, replay_counters, \
    scheduler_counters
from repro.workloads import stochastic
from repro.workloads.synthetic import tag_slo_classes


@pytest.fixture(scope="module")
def cm():
    return CostModel(get_config("llama2-7b"), A100_80G)


class _ConstPredictor(BasePredictor):
    """Deterministic stub so Equinox runs without training."""

    def __init__(self, const=100.0):
        super().__init__(CostModel(get_config("llama2-7b")), calibrate=False)
        self.const = const

    def predict_tokens(self, req):
        return self.const


def _sched(name):
    pred = _ConstPredictor() if name == "equinox" else None
    return make_scheduler(name, predictor=pred)


def _run(cm, sched_name, wl, *, macro, cache=False, slo=False,
         recorder=False):
    sched = _sched(sched_name)
    obs = FlightRecorder() if recorder else None
    cfg = SimConfig(max_batch=16, macro_step=macro, prefix_cache=cache,
                    slo_budget="auto" if slo else "static")
    sim = Simulator(cm, sched, cfg, observer=obs)
    reqs = [copy.deepcopy(r) for r in wl]
    if slo:
        tag_slo_classes(reqs)
    res = sim.run(reqs)
    return res, sched, obs


def _request_fingerprint(res):
    return {r.rid: (r.first_token_time, r.finish_time, r.generated,
                    r.state, r.prefill_done, r.cached_prefix)
            for r in res.requests}


def _assert_equivalent(r0, s0, r1, s1):
    """Exact (==, not approx) equality of everything macro may touch."""
    assert _request_fingerprint(r0) == _request_fingerprint(r1)
    assert r0.sim_time == r1.sim_time
    assert dict(s0.service) == dict(s1.service)
    for attr in ("counter", "ufc", "rfc", "deficit"):
        if hasattr(s0, attr):
            assert dict(getattr(s0, attr)) == dict(getattr(s1, attr)), attr
    # timeline: identical iteration structure and timestamps; the
    # service column is delta-encoded and may coalesce inside a bulk
    # macro step, but must fold to the same final table
    t0, t1 = r0.timeline, r1.timeline
    assert t0.t == t1.t
    assert t0.util == t1.util
    assert t0.batch == t1.batch
    assert t0.tokens == t1.tokens
    assert t0.budget == t1.budget
    assert t0.final_service() == t1.final_service()


@pytest.mark.parametrize("sched_name", ["fcfs", "vtc", "dlpm", "equinox"])
@pytest.mark.parametrize("cache", [False, True], ids=["nocache", "cache"])
@pytest.mark.parametrize("slo", [False, True], ids=["static", "slo_auto"])
def test_macro_bit_identical_matrix(cm, sched_name, cache, slo):
    wl = stochastic(duration=5.0)
    r0, s0, _ = _run(cm, sched_name, wl, macro=False, cache=cache, slo=slo)
    r1, s1, _ = _run(cm, sched_name, wl, macro=True, cache=cache, slo=slo)
    _assert_equivalent(r0, s0, r1, s1)


@pytest.mark.parametrize("sched_name", ["vtc", "equinox"])
def test_macro_flight_recorder_identical(cm, sched_name):
    """The interleaved macro path fires every telemetry hook in the
    legacy order: the recorded event stream is equal event-for-event
    (all but ``tw``, the host's wall clock when each hook fired), and
    the counter-replay audit still reconstructs the live scheduler's
    tables from the macro-mode trace."""
    wl = stochastic(duration=5.0)
    r0, s0, o0 = _run(cm, sched_name, wl, macro=False, recorder=True)
    r1, s1, o1 = _run(cm, sched_name, wl, macro=True, recorder=True)
    _assert_equivalent(r0, s0, r1, s1)
    assert len(o0.events) == len(o1.events)

    def modeled(events):
        return [{k: v for k, v in e.items() if k != "tw"} for e in events]

    assert modeled(o0.events) == modeled(o1.events)
    assert replay_counters(o1.trace()) == scheduler_counters(s1)


def _distinct_account_trace(n=12, out_len=64):
    """One request per client, all present at t=0: every running batch
    has pairwise-distinct accounts, which (with no observer and no
    cache) steers ``execute_macro_step`` onto the bulk path."""
    return [Request(rid=i, client=f"tenant{i:03d}", arrival=0.001 * i,
                    prompt_len=32, output_len=out_len, keywords=("chat",))
            for i in range(n)]


def test_bulk_path_engages_and_is_identical(cm, monkeypatch):
    wl = _distinct_account_trace()
    r0, s0, _ = _run(cm, "vtc", wl, macro=False)
    bulk_calls = []
    orig = VTC.on_tokens
    monkeypatch.setattr(VTC, "on_tokens",
                        lambda self, req, ts: (bulk_calls.append(len(ts)),
                                               orig(self, req, ts))[1])
    r1, s1, _ = _run(cm, "vtc", wl, macro=True)
    assert bulk_calls and max(bulk_calls) >= 2   # bulk billing really ran
    _assert_equivalent(r0, s0, r1, s1)


def test_macro_timeline_coalesces_bulk_deltas(cm):
    """Inside a bulk macro step the per-iteration service deltas
    coalesce to the boundary sample (DESIGN.md §15): intermediate
    samples are empty dicts, yet the fold still matches legacy."""
    wl = _distinct_account_trace()
    r1, _, _ = _run(cm, "vtc", wl, macro=True)
    assert any(not d for d in r1.timeline.service)
    r0, _, _ = _run(cm, "vtc", wl, macro=False)
    assert all(d for d in r0.timeline.service)
    assert r0.timeline.final_service() == r1.timeline.final_service()


def test_macro_in_cluster_identical(cm):
    """Macro bursts inside the cluster event loop stop at arrivals and
    busy-peer clocks, so shared fairness counters are charged in the
    legacy replica interleaving — routing and results pin exactly."""
    wl = stochastic(duration=6.0)

    def run(macro):
        cl = make_sim_cluster(3, cm, scheduler="vtc",
                              sim_cfg=SimConfig(max_batch=8,
                                                macro_step=macro),
                              policy="least_kv")
        return cl.run([copy.deepcopy(r) for r in wl], max_time=60.0)

    r0, r1 = run(False), run(True)
    assert r0.routed_to == r1.routed_to
    assert {r.rid: (r.first_token_time, r.finish_time, r.state)
            for r in r0.requests} \
        == {r.rid: (r.first_token_time, r.finish_time, r.state)
            for r in r1.requests}
    assert dict(r0.scheduler.service) == dict(r1.scheduler.service)
    assert dict(r0.scheduler.counter) == dict(r1.scheduler.counter)
    assert r0.sim_time == r1.sim_time


def test_stable_horizon_zero_cases(cm):
    """Each exhaustive condition in ``stable_horizon`` (DESIGN.md §15)
    individually forces the per-iteration fallback."""
    core = BatchCore(_sched("fcfs"), cm, SimConfig(max_batch=8))
    assert core.stable_horizon() == 0            # empty batch

    def decoding_req(rid, left=10):
        r = Request(rid=rid, client=f"c{rid}", arrival=0.0, prompt_len=16,
                    output_len=4 + left, keywords=("chat",))
        r.state = "decoding"
        r.generated = 4
        r.prefill_done = 16
        return r

    r0 = decoding_req(0)
    core.running.append(r0)
    core.reserved[r0.rid] = core._round_kv(core.footprint(r0) + 64)
    assert core.stable_horizon() == 10           # completion bound (3)

    r0.generated = r0.output_len                 # nothing left to decode
    assert core.stable_horizon() == 0
    r0.generated = 4

    r0.state = "prefilling"                      # condition (1)
    assert core.stable_horizon() == 0
    r0.state = "decoding"

    core.sched.on_arrival(decoding_req(99), 0.0)  # condition (2)
    assert core.stable_horizon() == 0


def test_kv_stable_iters_matches_sequential_reconcile(cm):
    """Condition (4): the closed-form KV bound equals the last iteration
    a sequential reconcile loop would admit before headroom runs out."""
    cfg = SimConfig(max_batch=8, kv_budget_tokens=3000)
    core = BatchCore(_sched("fcfs"), cm, cfg)
    for rid in range(4):
        r = Request(rid=rid, client=f"c{rid}", arrival=0.0, prompt_len=100,
                    output_len=5000, keywords=("chat",))
        r.state = "decoding"
        r.generated = 1
        r.prefill_done = 100
        core.running.append(r)
        need = core._round_kv(core.footprint(r))
        core.reserved[r.rid] = need
        core.kv_used += need
    k = core.stable_horizon()
    assert 0 < k < 4999                          # the KV bound binds
    headroom = core.kv_headroom()

    def used_after(m):
        u = core.kv_used
        for r in core.running:
            need = core._round_kv(core.footprint(r) + m - 1)
            u += max(0, need - core.reserved[r.rid])
        return u

    assert used_after(k) <= headroom
    assert used_after(k + 1) > headroom


# -- on_tokens == sequential on_token fold (every policy) ---------------------
_POLICIES = {
    "fcfs": lambda: FCFS(),
    "vtc": lambda: VTC(),
    "dlpm": lambda: DLPM(),
    "rpm": lambda: RPM(),
    "equinox": lambda: Equinox(_ConstPredictor(),
                               params=HFParams(charging="incremental")),
}


def _fold_check(name, weight, n_tokens, pre_tokens):
    """Two fresh schedulers, same request: one billed token-by-token,
    one via a single bulk ``on_tokens`` — every counter table and the
    per-request charge mirrors must be *exactly* equal."""
    tables = ("service", "counter", "ufc", "rfc", "deficit")
    mirrors = ("_service_charged", "_vtc_charged", "_ufc_charged")
    out = []
    for bulk in (False, True):
        s = _POLICIES[name]()
        r = Request(rid=0, client="acct", arrival=0.0, prompt_len=64,
                    output_len=n_tokens + pre_tokens + 1, weight=weight,
                    keywords=("chat",))
        s.on_arrival(r, 0.0)
        s.on_admit(s.pop_next(0.0), 0.0)
        for i in range(pre_tokens):              # an uneven float base
            s.on_token(r, 0.1 * (i + 1), 1)
        stamps = [1.0 + 0.37 * i for i in range(n_tokens)]
        if bulk:
            s.on_tokens(r, stamps)
        else:
            for t in stamps:
                s.on_token(r, t, 1)
        state = {a: dict(getattr(s, a)) for a in tables if hasattr(s, a)}
        state.update({m: getattr(r, m, None) for m in mirrors})
        out.append(state)
    assert out[0] == out[1], name


@pytest.mark.parametrize("name", sorted(_POLICIES))
def test_on_tokens_equals_fold_seeded(name):
    rng = np.random.default_rng(7)
    for _ in range(25):
        _fold_check(name,
                    weight=float(rng.uniform(0.1, 3.0)),
                    n_tokens=int(rng.integers(1, 40)),
                    pre_tokens=int(rng.integers(0, 7)))


@settings(max_examples=40, deadline=None)
@given(name=st.sampled_from(sorted(_POLICIES)),
       weight=st.floats(min_value=0.01, max_value=10.0,
                        allow_nan=False, allow_infinity=False),
       n_tokens=st.integers(min_value=0, max_value=100),
       pre_tokens=st.integers(min_value=0, max_value=10))
def test_on_tokens_equals_fold_hypothesis(name, weight, n_tokens,
                                          pre_tokens):
    _fold_check(name, weight, n_tokens, pre_tokens)


# -- decode_macro_times == sequential cost-model calls ------------------------
def test_decode_macro_times_exact(cm):
    rng = np.random.default_rng(3)
    for _ in range(20):
        b = int(rng.integers(1, 24))
        k = int(rng.integers(1, 50))
        ctxs = [int(rng.integers(1, 8192)) for _ in range(b)]
        got = cm.decode_macro_times(ctxs, k)
        want = [cm.mixed_step_time([], [c + i for c in ctxs])
                for i in range(k)]
        assert got.tolist() == want              # bitwise, not approx

    assert cm.decode_macro_times([128], 0).tolist() == []
    assert cm.decode_macro_times([], 3).tolist() == [0.0, 0.0, 0.0]


def test_decode_macro_times_respects_attention_windows():
    """The closed-form path must honour per-layer KV windows (sliding-
    window attention caps the effective context), exactly like the
    sequential cost model."""
    cfg = get_config("recurrentgemma-2b")        # local-window preset
    cm = CostModel(cfg, A100_80G)
    ctxs = [1000, 6000]                          # straddles the window
    got = cm.decode_macro_times(ctxs, 12)
    want = [cm.mixed_step_time([], [c + i for c in ctxs])
            for i in range(12)]
    assert got.tolist() == want


# -- macro_bulk_ok: when same-account batch-mates commute ---------------------
def _req_pair(weight_b=1.0, tilt_b=None):
    a = Request(rid=0, client="acct0", arrival=0.0, prompt_len=8,
                output_len=32, keywords=("chat",))
    b = Request(rid=1, client="acct0", arrival=0.0, prompt_len=8,
                output_len=32, keywords=("chat",), weight=weight_b)
    if tilt_b is not None:
        a._tilt = 1.0
        b._tilt = tilt_b
    return [a, b]


def test_macro_bulk_ok_same_account_equal_increment():
    """Equal-weight same-account requests DO commute (the accumulator
    sees the same count of identical additions either way), so the
    relaxed bulk gate admits the Zipf-trace batches where one popular
    account holds several slots."""
    assert VTC().macro_bulk_ok(_req_pair())
    assert not VTC().macro_bulk_ok(_req_pair(weight_b=2.0))


def test_macro_bulk_ok_equinox_tilt_sensitive():
    """Equinox's incremental UFC divides by the per-request admission
    tilt — same-account folds only commute at equal tilt."""
    eq = Equinox(_ConstPredictor())
    assert eq.macro_bulk_ok(_req_pair(tilt_b=1.0))
    assert not eq.macro_bulk_ok(_req_pair(tilt_b=1.25))


def test_macro_duplicate_account_batches_bit_identical(cm):
    """End-to-end pin of the relaxed gate: a 2-client trace whose
    batches always hold many same-account requests must still be
    bit-identical under macro — the case the first-cut distinct-accounts
    precondition excluded entirely."""
    wl = stochastic(5.0)
    for name in ("vtc", "fcfs"):
        r0, s0, _ = _run(cm, name, wl, macro=False)
        r1, s1, _ = _run(cm, name, wl, macro=True)
        _assert_equivalent(r0, s0, r1, s1)
