"""The harness loop on a tiny configuration on the CPU, the program's
own serving path underneath (Pallas kernels interpreted), and the
comparison that decides ``correct``: sound runs pass it; the fp8 control
and each fault of ``bench/faults.py`` fail it, each judged by
``cell.run_cell``'s own verdict.

The configuration is float32, so a sound run's served tokens are the
reference's first choices and the limit can be tight.
"""
import jax
import pytest

import cell as cell_mod
import config
import harness
from config import load_json
from conftest import DATA
from faults import planted
from traffic import generate
from weights import program_params

SEED = 2**32 + 17
SECONDS = 3.0
CELL = {"config": "tiny", "traffic": "tiny_mix", "chips": 1}


@pytest.fixture(scope="module")
def session():
    c = load_json(DATA / "tiny.json")
    return c, harness.Session(c)


def run_tiny(session, monkeypatch, control=False):
    """``cell.run_cell`` as ``bench/run.py`` calls it, on the tiny
    configuration and mix, past the look for a chip."""
    c, sess = session
    monkeypatch.setattr(cell_mod, "load_config", lambda name: c)
    monkeypatch.setattr(cell_mod, "load_mix",
                        lambda name: load_json(DATA / "tiny_mix.json"))
    return cell_mod.run_cell("granite2b.tenants", CELL, SEED, SECONDS, False,
                             0.0, jax.devices(), {}, control, sess)


def test_window_compiles_nothing_and_reads_every_metric(session):
    c, sess = session
    eng = sess.engine_for(program_params(c, SEED), SEED)
    sess.warm_up(eng)
    specs = generate(load_json(DATA / "tiny_mix.json"), SEED, SECONDS,
                     c["vocab_size"])
    out = harness.serve(eng, specs, SECONDS, harness.CompileCount())
    harness.free(eng)
    e2e = harness.end_to_end(out)
    assert out.compiles_in_window == 0 and out.step_traces_in_window == 0
    assert e2e["_n"]["ttft"] > 0 and e2e["_n"]["gaps"] > 0
    assert 0 < e2e["service_jain"] <= 1 and e2e["tokens_per_s"] > 0
    assert harness.sampleable_tokens(out) > 0


def test_sound_run_is_correct_and_the_control_is_not(session, monkeypatch):
    res = run_tiny(session, monkeypatch, control=True)
    limit = session[0]["correct"]["max_gap"]
    assert res["program"]["correct"] is True
    assert res["program"]["checks"]["max_gap"]["value"] <= limit
    assert res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > limit
    assert set(res["metrics"]) == {
        m["name"] for m in config.cell_metrics("granite2b.tenants", False)}


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_left_out"])
def test_planted_faults_are_not_correct(session, fault, monkeypatch):
    with planted(fault):
        res = run_tiny(session, monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > \
        session[0]["correct"]["max_gap"]


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_gate_refuses_cpu_too_few_chips_and_unknown_kinds():
    table = {"TPU v5 lite": {}}
    assert config.gate([_Dev("tpu", "TPU v5 lite")], 1, table) is None
    assert "TPU" in config.gate([_Dev("cpu", "cpu")], 1, table)
    assert "4 chips" in config.gate([_Dev("tpu", "TPU v5 lite")], 4,
                                     table)
    assert "no peaks" in config.gate([_Dev("tpu", "TPU v9")], 1, table)


def test_command_refuses_the_cpu_and_prints_no_result():
    import subprocess
    import sys
    from conftest import ROOT
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "granite2b.tenants", "--seed", "1", "--seconds",
                        "1"], cwd=ROOT, capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": str(ROOT / "_local")}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
