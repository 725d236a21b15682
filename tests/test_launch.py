"""Launch machinery on the host mesh: input specs, step building, and a
real 1-device lower+compile through the exact dry-run code path."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import INPUT_SHAPES, SMOKE_FACTORIES, get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import build_step, config_for, input_specs


def test_input_specs_shapes():
    cfg = get_config("deepseek-7b")
    batch, _ = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert batch["tokens"].shape == (256, 4096)
    assert batch["labels"].dtype == jnp.int32
    tok, _ = input_specs(cfg, INPUT_SHAPES["decode_32k"])
    assert tok.shape == (128,)


def test_input_specs_frontends():
    wh = get_config("whisper-large-v3")
    batch, _ = input_specs(wh, INPUT_SHAPES["train_4k"])
    assert batch["frames"].shape == (256, 1500, 1280)
    vl = get_config("internvl2-76b")
    batch, _ = input_specs(vl, INPUT_SHAPES["prefill_32k"])
    assert batch["patch_embeds"].shape[1] == 256
    assert batch["tokens"].shape[1] == 32768 - 256   # patches + text = S


def test_config_for_long_context():
    cfg = get_config("deepseek-7b")
    lc = config_for(cfg, INPUT_SHAPES["long_500k"])
    assert lc.window == 4096
    assert config_for(cfg, INPUT_SHAPES["train_4k"]) is cfg


@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_build_step_lowers_on_host_mesh(shape_name, monkeypatch):
    """The dry-run path end to end on the real 1-device mesh, with a
    reduced config standing in (same code, CPU-sized)."""
    import dataclasses
    full = get_config("llama2-7b")
    small = SMOKE_FACTORIES["llama2-7b"]()
    cfg = dataclasses.replace(
        small, name=full.name, dtype="bfloat16")
    shape = dataclasses.replace(INPUT_SHAPES[shape_name], seq_len=32,
                                global_batch=2)
    mesh = make_host_mesh()
    fn, args, in_sh, donate = build_step(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh,
                           donate_argnums=donate).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0


# -- serving launcher ---------------------------------------------------------

def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is JAX's own to read: the helper
    reports it and sets no other path."""
    from repro.launch import serve
    calls = []
    monkeypatch.setattr(serve.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    from repro.launch import serve
    calls = []
    monkeypatch.setattr(serve.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve.enable_compile_cache()
    assert path == str(serve.REPO_ROOT / ".jax_cache")
    assert (serve.REPO_ROOT / "pyproject.toml").exists()
    assert calls == [("jax_compilation_cache_dir", path)]


@pytest.mark.parametrize("arch,backend", [("granite-3-2b", "paged"),
                                          ("mamba2-2.7b", "slots")])
def test_build_engine_picks_paged_where_chunkable(arch, backend):
    from repro.core import make_scheduler
    from repro.launch.serve import build_engine
    from repro.serving.costmodel import CostModel
    cfg = SMOKE_FACTORIES[arch]()
    eng = build_engine(cfg, make_scheduler("fcfs"), CostModel(cfg),
                       max_slots=2, max_len=64)
    assert eng.backend == backend


def test_engine_device_pins_params_pools_and_step():
    """``device=`` commits params and pools to that device, and the
    fused step keeps them there."""
    from repro.core import Request, make_scheduler
    from repro.serving.engine import ServingEngine
    dev = jax.devices()[-1]
    cfg = SMOKE_FACTORIES["granite-3-2b"]()
    eng = ServingEngine(cfg, make_scheduler("fcfs"), max_slots=2,
                        max_len=64, backend="paged", device=dev)
    reqs = [Request(rid=i, client="c", arrival=0.0, prompt_len=12,
                    output_len=3, keywords=("chat",)) for i in range(2)]
    assert len(eng.run(reqs)) == 2
    held = {d for a in (*jax.tree.leaves(eng.params), eng.k_pools,
                        eng.v_pools) for d in a.devices()}
    assert held == {dev}
    assert all(a.committed for a in (eng.k_pools, eng.v_pools))
