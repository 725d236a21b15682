"""Configuration files of the benchmark and the architecture families.

``bench/configs/<name>.json`` holds one model configuration as it is run,
under the published ``config.json`` key names, plus the engine settings of
its cells.  Its ``"family"`` key (``llama`` where absent) names the module
``bench/families/<family>.py`` that maps those numbers onto the program
(``program_config``, ``program_params``), holds the float32 reference
(``logits``) and counts a step's work (``work``), so the file, not the
program's registry, decides what is served, and a new architecture is a
new family file.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def setup(cache: bool = True):
    """What each entry script under ``bench/`` does before it touches JAX:
    the program's sources on the path, the TPU runtime's log kept off its
    fixed path under /tmp, and, with ``cache``, JAX's compilation cache in
    ``.jax_cache`` at the root of the checkout, holding every program.
    Returns the ``jax`` module."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    if cache:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    return jax


def gate(devices, chips: int, table: dict):
    """Why a run on ``devices`` cannot measure, or None: it needs
    ``chips`` TPU chips of a kind that the peaks table lists."""
    if devices[0].platform != "tpu":
        return f"needs a TPU; JAX found {devices[0].platform!r}"
    if len(devices) < chips:
        return f"needs {chips} chips; JAX found {len(devices)}"
    if devices[0].device_kind not in table:
        return (f"no peaks for device kind {devices[0].device_kind!r} in "
                f"bench/peaks.json")
    return None


def peaks_table() -> dict:
    """``bench/peaks.json``'s peaks by device kind."""
    return load_json(BENCH / "peaks.json")["devices"]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def load_cell(workload: str) -> dict:
    """The ``workloads`` entry of ``BENCHMARK.json`` named ``workload``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(workload: str, trace: bool) -> list:
    """The metrics this cell reports: its ``end_to_end`` entries with
    ``--trace 0``, its ``per_layer`` entries with ``--trace 1``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec[key]
            if "workloads" not in m or workload in m["workloads"]]


FAMILIES = BENCH / "families"
FAMILY_API = ("program_config", "program_params", "logits", "work")


def family(c: dict):
    """The architecture family module that configuration ``c`` names."""
    return _load_family(FAMILIES / f"{c.get('family', 'llama')}.py")


@functools.cache
def _load_family(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no architecture family module {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{path} lacks {', '.join(missing)}")
    return mod
