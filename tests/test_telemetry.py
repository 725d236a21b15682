"""Flight recorder (DESIGN.md §14).

- ``Observer`` contract: misspelled hook overrides fail at class
  definition (the failure mode the old ``hasattr`` duck typing silently
  swallowed), and ``BatchCore`` rejects non-``Observer`` observers.
- ``MultiObserver`` fan-out: every overridden hook forwarded, base
  no-ops skipped, ``None`` members dropped.
- Recording: a saturated run with admission control, preemption and
  closed-loop interactions produces every event type in
  ``EVENT_TYPES``; JSON round-trip preserves the trace.
- Consumers: Chrome-trace export is structurally valid (matched async
  begin/end, metadata, counter tracks), the windowed fairness audit
  returns sane bounds, prediction accuracy surfaces the injected
  misprediction.
- The headline property: **counter replay** — re-deriving the live
  scheduler's accounting tables purely from the event log — matches
  the live tables exactly for every policy, under preemption and
  admission control.
- Telemetry-off parity: attaching a recorder must not perturb any
  modeled metric or scheduler counter.
- Wall stamps: every event's ``tw`` never decreases along the log, and
  replay reads only the modeled ``t``.
"""
import json

import pytest

from repro.configs import get_config
from repro.core import SimConfig, Simulator, make_scheduler, summarize
from repro.core.metrics import HFObserver
from repro.predictor.mope import Oracle, ScaledOracle
from repro.serving.admission import AdmissionConfig
from repro.serving.costmodel import A100_80G, CostModel
from repro.serving.telemetry import (EVENT_TYPES, FlightRecorder,
                                     MultiObserver, Observer, load_trace,
                                     merge_traces, prediction_accuracy,
                                     replay_counters, save_trace,
                                     scheduler_counters, to_chrome_trace,
                                     windowed_fairness)
from repro.workloads import balanced, multiturn_interactions


@pytest.fixture(scope="module")
def cm():
    return CostModel(get_config("llama2-7b"), A100_80G)


def _stress_run(cm, policy, *, factor=0.2, sample_every=16,
                max_time=150.0):
    """Saturated closed-loop run: admission control on, output lengths
    under-predicted 5x so preemption fires, multiturn interactions so
    turn releases fire."""
    pred = None if policy == "fcfs" else ScaledOracle(cm, factor=factor)
    sched = make_scheduler(policy, predictor=pred)
    rec = FlightRecorder(sample_every=sample_every)
    sim = Simulator(cm, sched,
                    SimConfig(max_batch=8, kv_budget_tokens=6_000,
                              default_reserve=64, max_time=max_time),
                    observer=MultiObserver(HFObserver(), rec),
                    admission=AdmissionConfig(window_s=30.0, user_rate=3.0,
                                              app_rate=12.0, kv_thresh=0.7,
                                              queue_thresh=0.3))
    res = sim.run(interactions=multiturn_interactions(
        n_users=8, n_apps=2, sessions_per_user=(2, 10), session_gap=0.5,
        think_time=0.5, seed=7))
    return res, sim, sched, rec


# -- Observer contract --------------------------------------------------------

def test_misspelled_hook_override_raises_at_class_definition():
    with pytest.raises(TypeError, match="on_arival"):
        class Bad(Observer):                       # noqa: F811
            def on_arival(self, req, now):         # missing double-r
                pass


def test_unknown_on_hook_raises():
    with pytest.raises(TypeError):
        class Bad(Observer):
            def on_token(self, req, now):  # scheduler hook, not observer
                pass


def test_valid_subclass_with_helpers_is_fine():
    class Fine(Observer):
        def on_admit(self, req, now):
            self.note(req)

        def note(self, req):               # non-hook helpers untouched
            pass
    Fine()


def test_batch_core_rejects_duck_typed_observer(cm):
    class Duck:                            # not an Observer subclass
        def on_admit(self, req, now):
            pass
    with pytest.raises(TypeError, match="Observer"):
        Simulator(cm, make_scheduler("vtc"), SimConfig(max_batch=4),
                  observer=Duck())


# -- MultiObserver fan-out ----------------------------------------------------

def test_multi_observer_forwards_to_all_overriders(cm):
    calls = []

    class SpyA(Observer):
        def on_admit(self, req, now):
            calls.append(("a", req.rid))

    class SpyB(Observer):
        def on_admit(self, req, now):
            calls.append(("b", req.rid))

        def on_complete(self, req, now, *, latency, tps, util):
            calls.append(("b-done", req.rid))

    sim = Simulator(cm, make_scheduler("vtc"), SimConfig(max_batch=4),
                    observer=MultiObserver(SpyA(), None, SpyB()))
    sim.run(balanced(duration=1.0))
    rids_a = {r for tag, r in calls if tag == "a"}
    rids_b = {r for tag, r in calls if tag == "b"}
    assert rids_a and rids_a == rids_b     # both spies saw every admit
    assert any(tag == "b-done" for tag, _ in calls)


def test_multi_observer_skips_non_overridden_hooks():
    class AdmitOnly(Observer):
        def on_admit(self, req, now):
            pass
    m = MultiObserver(AdmitOnly(), HFObserver())
    # precomputed target lists only contain actual overriders
    assert len(m._on_admit) == 2
    assert len(m._on_requeue) == 0         # nobody overrides it
    assert len(m._on_complete) == 1        # HFObserver only


# -- recording ----------------------------------------------------------------

def test_stress_run_records_every_event_type(cm):
    res, sim, sched, rec = _stress_run(cm, "vtc")
    assert sim.n_preemptions > 0 and res.n_throttled > 0
    seen = {e["type"] for e in rec.events}
    assert seen == set(EVENT_TYPES)
    # per-iteration samples always carry replay/timeline essentials;
    # table snapshots appear every sample_every iterations
    samples = rec.samples()
    snaps = rec.samples(full=True)
    assert len(samples) > len(snaps) > 0
    assert all("produced" in s and "t_iter" in s for s in samples)
    assert all("counters" in s and "active" in s for s in snaps)


def test_sample_every_one_snapshots_every_iteration(cm):
    rec = FlightRecorder(sample_every=1)
    sim = Simulator(cm, make_scheduler("vtc"), SimConfig(max_batch=8),
                    observer=rec)
    sim.run(balanced(duration=1.0))
    assert len(rec.samples()) == len(rec.samples(full=True)) > 0


def test_trace_json_round_trip(cm, tmp_path):
    _, _, sched, rec = _stress_run(cm, "vtc", max_time=60.0)
    path = save_trace(rec.trace(), str(tmp_path / "t.json"))
    loaded = load_trace(path)
    assert loaded["meta"]["policy"] == "vtc"
    assert replay_counters(loaded) == scheduler_counters(sched)


# -- counter replay (the headline property) -----------------------------------

@pytest.mark.parametrize("policy", ["vtc", "dlpm", "equinox", "fcfs"])
def test_replay_reproduces_live_counters_under_stress(cm, policy):
    res, sim, sched, rec = _stress_run(cm, policy)
    assert sim.n_preemptions > 0, "stress config must exercise preemption"
    assert res.n_throttled > 0, "stress config must exercise admission"
    assert replay_counters(rec.trace()) == scheduler_counters(sched)


def test_replay_with_accurate_predictor(cm):
    sched = make_scheduler("equinox", predictor=Oracle(cm))
    rec = FlightRecorder()
    sim = Simulator(cm, sched, SimConfig(max_batch=16),
                    observer=rec)
    sim.run(balanced(duration=3.0))
    assert replay_counters(rec.trace()) == scheduler_counters(sched)


# -- Chrome trace export ------------------------------------------------------

def test_chrome_trace_structurally_valid(cm):
    _, _, _, rec = _stress_run(cm, "vtc", max_time=60.0)
    chrome = to_chrome_trace(rec.trace())
    evs = chrome["traceEvents"]
    assert evs and chrome["displayTimeUnit"] == "ms"
    assert all("ph" in e and "ts" in e and "pid" in e for e in evs)
    opens = {}
    for e in evs:
        if e["ph"] == "b":
            opens[e["id"]] = opens.get(e["id"], 0) + 1
        elif e["ph"] == "e":
            opens[e["id"]] = opens.get(e["id"], 0) - 1
            assert opens[e["id"]] >= 0, "end before begin"
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "kv" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "service" for e in evs)
    json.dumps(chrome)                     # serializable as-is


def test_merge_traces_keeps_replica_processes(cm):
    recs = []
    for i in range(2):
        _, _, _, rec = _stress_run(cm, "vtc", max_time=40.0)
        rec.set_replica(i)
        recs.append(rec)
    merged = merge_traces([r.trace() for r in recs])
    ts = [e["t"] for e in merged["events"]]
    assert ts == sorted(ts)
    chrome = to_chrome_trace(merged)
    assert {e["pid"] for e in chrome["traceEvents"]} == {0, 1}


# -- windowed fairness audit --------------------------------------------------

def test_windowed_fairness_bounds(cm):
    _, _, _, rec = _stress_run(cm, "vtc", sample_every=4)
    wf = windowed_fairness(rec.trace())
    assert wf["n_windows"] > 0
    assert wf["max_discrepancy"] >= 0.0
    assert wf["worst_pair"] is not None
    a, b = wf["worst_pair"]
    assert a != b
    t0, t1 = wf["worst_window"]
    assert t0 <= t1
    assert all(0.0 <= j <= 1.0 + 1e-9 for j in wf["rolling_jain"])
    assert 0.0 <= wf["min_jain"] <= 1.0 + 1e-9


def test_prediction_accuracy_surfaces_misprediction(cm):
    _, _, _, rec = _stress_run(cm, "equinox", factor=0.2)
    acc = prediction_accuracy(rec.trace())
    assert acc
    total = sum(v["n"] for v in acc.values())
    assert total > 0
    # ScaledOracle(0.2) under-predicts 5x -> |0.2x - x|/x = 0.8
    rel = max(v["rel_err"] for v in acc.values())
    assert rel == pytest.approx(0.8, abs=0.05)


def test_wall_stamps_are_monotone_and_replay_ignores_them(cm):
    _, _, sched, rec = _stress_run(cm, "equinox")
    trace = rec.trace()
    tws = [e["tw"] for e in trace["events"]]
    assert all(isinstance(t, float) for t in tws)
    assert tws == sorted(tws)
    modeled = dict(trace, events=[{k: v for k, v in e.items() if k != "tw"}
                                  for e in trace["events"]])
    assert replay_counters(modeled) == replay_counters(trace) == \
        scheduler_counters(sched)


# -- telemetry-off parity -----------------------------------------------------

def test_recorder_does_not_perturb_modeled_results(cm):
    def go(with_recorder):
        pred = ScaledOracle(cm, factor=0.2)
        sched = make_scheduler("vtc", predictor=pred)
        obs = HFObserver()
        observer = MultiObserver(obs, FlightRecorder()) \
            if with_recorder else obs
        sim = Simulator(cm, sched,
                        SimConfig(max_batch=8, kv_budget_tokens=6_000,
                                  default_reserve=64, max_time=80.0),
                        observer=observer,
                        admission=AdmissionConfig(window_s=30.0,
                                                  user_rate=3.0,
                                                  app_rate=12.0,
                                                  kv_thresh=0.7,
                                                  queue_thresh=0.3))
        res = sim.run(interactions=multiturn_interactions(
            n_users=6, n_apps=2, sessions_per_user=(2, 8),
            session_gap=0.5, think_time=0.5, seed=3))
        return summarize(res), scheduler_counters(sched), obs.hf()

    assert go(False) == go(True)


@pytest.mark.slow
def test_bench_payload_identical_with_telemetry_on(tmp_path, monkeypatch):
    """ISSUE 8 acceptance: telemetry disabled -> BENCH payloads
    unchanged.  Run a trace-emitting benchmark with REPRO_TRACE off and
    on; every CSV row must match after blanking the wall-time column,
    and the enabled run must leave a Perfetto-loadable TRACE file."""
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.overload_admission import run as bench_run

    def rows(trace_on, out_dir):
        monkeypatch.setenv("REPRO_TRACE", "1" if trace_on else "0")
        monkeypatch.setenv("BENCH_OUT", str(out_dir))
        lines = bench_run(quick=True)
        return [",".join(p if i != 1 else "_"
                         for i, p in enumerate(line.split(",", 2)))
                for line in lines]

    off = rows(False, tmp_path / "off")
    on = rows(True, tmp_path / "on")
    assert off == on
    trace_path = tmp_path / "on" / "TRACE_overload_admission.json"
    assert trace_path.exists()
    chrome = json.loads(trace_path.read_text())
    assert chrome["traceEvents"]
    assert not (tmp_path / "off" / "TRACE_overload_admission.json").exists()
