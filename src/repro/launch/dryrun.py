import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

_DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 TPU v5e pods.
``.lower().compile()`` must succeed for every applicable cell;
``memory_analysis()`` proves per-chip fit; ``cost_analysis()`` +
collective parsing feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only] [--out report.json]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro import optim  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.models import model_zoo as zoo  # noqa: E402

from . import sharding as shd  # noqa: E402
from . import steps  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

_LINE_RE = re.compile(
    r"=\s+(?P<rtype>\([^)]*\)|\S+)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}


def parse_collectives(hlo_text: str) -> dict:
    """Sum collective *output* bytes per device, by op kind, from the
    optimized (post-SPMD) HLO. Result-type shapes (tuple or single) are
    the per-participant output buffers."""
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        size = 0
        for dt, dims in _SHAPE_RE.findall(m.group("rtype")):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            size += n * _DTYPE_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0.0) + size
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": out, "counts": counts,
            "total_bytes": sum(out.values())}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               remat: str | None = None):
    """Lower + compile one cell; return the report dict."""
    cfg = configs.get_config(arch)
    if remat:
        cfg = cfg.replace(remat=remat)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    steps.install_act_rules(mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        ins = zoo.input_specs(cfg, shape)
        if shape.kind == "train":
            jit_for, p_sh, o_sh = steps.jit_train_step(cfg, mesh)
            batch = ins["batch"]
            pspecs = zoo.param_specs(cfg)
            ospecs = jax.eval_shape(optim.init, pspecs)
            jitted = jit_for(batch)
            lowered = jitted.lower(pspecs, ospecs, batch)
        elif shape.kind == "prefill":
            jit_for, p_sh = steps.jit_prefill_step(cfg, mesh)
            batch = ins["batch"]
            pspecs = zoo.param_specs(cfg)
            jitted = jit_for(batch)
            lowered = jitted.lower(pspecs, batch)
        else:
            jit_for, p_sh = steps.jit_serve_step(cfg, mesh)
            pspecs = zoo.param_specs(cfg)
            jitted = jit_for(ins["cache"], ins["tokens"])
            lowered = jitted.lower(pspecs, ins["cache"], ins["tokens"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    n_dev = mesh.devices.size

    def _get(obj, attr):
        try:
            return float(getattr(obj, attr))
        except Exception:
            return None

    mem_report = {}
    if mem is not None:
        for a in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_report[a] = _get(mem, a)

    flops = None
    bytes_accessed = None
    if cost:
        c = cost if isinstance(cost, dict) else cost[0]
        flops = c.get("flops")
        bytes_accessed = c.get("bytes accessed")

    report = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_report,
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": coll,
        "params_total": zoo.count_params(zoo.param_specs(cfg)),
        "params_active": zoo.active_params(
            cfg, zoo.count_params(zoo.param_specs(cfg))),
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--remat", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a, s, applicable in configs.cells():
            if applicable:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    meshes = [False, True]
    if args.multi_pod:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]

    reports = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
            try:
                r = lower_cell(arch, shape, multi_pod=mp, remat=args.remat)
                mem_gb = (r["memory"].get("temp_size_in_bytes") or 0) / 2**30
                print(f"[OK]   {tag}: compile={r['compile_s']}s "
                      f"temp/dev={mem_gb:.2f}GiB "
                      f"flops/dev={r['flops_per_device'] and r['flops_per_device']:.3g} "
                      f"coll={r['collectives']['total_bytes']/2**20:.1f}MiB",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                r = {"arch": arch, "shape": shape,
                     "mesh": "2x16x16" if mp else "16x16",
                     "ok": False, "error": f"{type(e).__name__}: {e}",
                     "trace": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:200]}",
                      flush=True)
            reports.append(r)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(reports, f, indent=1, default=str)
        print(f"wrote {args.out}")
    n_ok = sum(1 for r in reports if r.get("ok"))
    print(f"{n_ok}/{len(reports)} cells OK")
    return 0 if n_ok == len(reports) else 1


if __name__ == "__main__":
    raise SystemExit(main())
