import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) pair, lower + compile the step
function on the production mesh (16×16 single-pod and 2×16×16 multi-pod)
with ShapeDtypeStruct inputs (no allocation), then record:

- memory_analysis(): per-device argument/output/temp bytes (proves fit);
- cost_analysis(): FLOPs / bytes for §Roofline;
- collective bytes parsed from the optimized HLO (all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute).

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
benchmarks/roofline.py.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k --mesh single           # one pair
    PYTHONPATH=src python -m repro.launch.dryrun --all  # every pair
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind (output-shape proxy)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            token = f" {kind}("
            start_token = f" {kind}-start("
            if token not in line and start_token not in line:
                continue
            m = _SHAPE_RE.search(line)
            if not m:
                continue
            dt, dims = m.group(1), m.group(2)
            nbytes = _DTYPE_BYTES.get(dt, 4)
            for d in dims.split(","):
                if d.strip():
                    nbytes *= int(d)
            out[kind] += nbytes
            counts[kind] += 1
            break
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def run_pair(arch: str, shape_name: str, mesh_kind: str, verbose=True,
             fsdp=None, seq_parallel=None, remat_group=None):
    """None options resolve to the production policy: training shapes use
    TP weights + batch over (data×model) + ZeRO-1 optimizer sharding
    (16 GiB/chip residency); inference shapes use plain TP+DP.  FSDP /
    sequence-parallel remain explicit flags for §Perf exploration."""
    import dataclasses as _dc
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    over = {}
    over["fsdp"] = bool(fsdp) if fsdp is not None else False
    over["seq_parallel"] = bool(seq_parallel) if seq_parallel is not None \
        else False
    if remat_group is not None:
        over["remat_group"] = remat_group
    cfg = _dc.replace(cfg, **over)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    fn, args, in_sh, donate = build_step(cfg, shape, mesh)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": mesh.size,
        "options": {"fsdp": cfg.fsdp, "seq_parallel": cfg.seq_parallel,
                    "remat_group": cfg.remat_group},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes",
                                      0)),
        },
        "cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        },
        "collectives": coll,
    }
    if verbose:
        m = result["memory"]
        print(f"{arch:18s} {shape_name:12s} {mesh_kind:6s} "
              f"args={m['argument_bytes']/2**30:7.2f}GiB "
              f"temp={m['temp_bytes']/2**30:7.2f}GiB "
              f"flops={result['cost']['flops']:.3e} "
              f"coll={coll['total_bytes']/2**20:9.1f}MiB "
              f"compile={t_compile:5.1f}s", flush=True)
    return result


def save_result(res: dict):
    os.makedirs(OUT_DIR, exist_ok=True)
    name = f"{res['arch']}__{res['shape']}__{res['mesh']}.json"
    with open(os.path.join(OUT_DIR, name), "w") as f:
        json.dump(res, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--fsdp", type=int, default=None, choices=[0, 1])
    ap.add_argument("--seq-parallel", type=int, default=None, choices=[0, 1])
    ap.add_argument("--remat-group", type=int, default=None)
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                fname = os.path.join(OUT_DIR,
                                     f"{arch}__{shape}__{mesh_kind}.json")
                if args.skip_existing and os.path.exists(fname):
                    print(f"skip {arch} {shape} {mesh_kind}", flush=True)
                    continue
                try:
                    res = run_pair(
                        arch, shape, mesh_kind,
                        fsdp=None if args.fsdp is None else bool(args.fsdp),
                        seq_parallel=(None if args.seq_parallel is None
                                      else bool(args.seq_parallel)),
                        remat_group=args.remat_group)
                    if args.tag:
                        res["tag"] = args.tag
                        res["shape"] = f"{shape}@{args.tag}"
                    save_result(res)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    failures.append((arch, shape, mesh_kind, repr(e)))
                    print(f"FAIL {arch} {shape} {mesh_kind}: {e}",
                          flush=True)
                    traceback.print_exc()
    print(f"\n{len(failures)} failures")
    for f in failures:
        print("  ", *f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
