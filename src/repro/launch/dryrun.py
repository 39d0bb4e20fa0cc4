import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
against placeholder devices, prove the sharding config is coherent, and
extract memory / cost / collective analyses for the roofline tables.

MUST be imported before anything that initializes jax (the device count is
locked at first init) — hence the XLA_FLAGS lines above everything.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  python -m repro.launch.dryrun --all --multi-pod
"""
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import roofline as rl                     # noqa: E402
from repro.configs import ARCH_IDS, get_config       # noqa: E402
from repro.launch import shapes as shp               # noqa: E402
from repro.launch.mesh import make_production_mesh   # noqa: E402
from repro.launch.steps import (abstract_opt_state, make_prefill_step,  # noqa: E402
                                make_serve_step, make_train_step)
from repro.models.transformer import Model           # noqa: E402
from repro.optim import adamw                        # noqa: E402


def lower_cell(arch: str, shape_name: str, mesh, *, opt_overrides=None):
    """Returns (lowered, cfg, model, shape). Raises on sharding bugs."""
    cfg = get_config(arch)
    if opt_overrides:
        cfg = cfg.replace(**opt_overrides)
    shape = shp.SHAPES[shape_name]
    model = Model(cfg, mesh=mesh)
    params = model.init(abstract=True)
    batch = shp.input_specs(cfg, shape, mesh)
    if shape.kind == "train":
        step = make_train_step(model, adamw.AdamWConfig())
        opt_state = abstract_opt_state(params, mesh)
        lowered = jax.jit(step).lower(params, opt_state, batch)
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        lowered = jax.jit(step).lower(params, batch)
    else:  # decode
        step = make_serve_step(model)
        cache, _specs = shp.abstract_cache(model, shape)
        idx = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jax.jit(step).lower(params, cache, batch, idx)
    return lowered, cfg, model, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_overrides=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": rl.mesh_name(mesh), "chips": int(mesh.devices.size),
           "status": "ok"}
    cfg = get_config(arch)
    skip = shp.runnable(cfg, shp.SHAPES[shape_name])
    if skip:
        rec.update(status="skip", reason=skip)
        return rec
    try:
        lowered, cfg, model, shape = lower_cell(arch, shape_name, mesh,
                                                opt_overrides=opt_overrides)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

        chips = int(mesh.devices.size)
        cost = compiled.cost_analysis() or {}
        # cost_analysis is per-partition under SPMD (calibrated; see
        # roofline.py docstring) -> scale to global.
        flops = float(cost.get("flops", 0.0)) * chips
        bytes_acc = float(cost.get("bytes accessed", 0.0)) * chips
        try:
            mem = compiled.memory_analysis()
            mem_stats = {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or
                                  getattr(mem, "temp_size_in_bytes", 0)),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_stats = {"error": str(e)}
        coll = {k: v * chips for k, v in
                rl.parse_collective_bytes(compiled.as_text()).items()}
        mf = rl.model_flops(cfg, shape, shape.kind)
        roof = rl.Roofline(arch, shape_name, rl.mesh_name(mesh),
                           chips, flops, bytes_acc,
                           float(sum(coll.values())), mf)
        rec.update(
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            hlo_flops=flops, hlo_bytes=bytes_acc,
            collective_bytes=coll, collective_total=float(sum(coll.values())),
            model_flops=mf, memory=mem_stats,
            t_compute=roof.t_compute, t_memory=roof.t_memory,
            t_collective=roof.t_collective, dominant=roof.dominant,
            useful_ratio=roof.useful_ratio,
            roofline_fraction=roof.roofline_fraction,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def _layer_unit(cfg) -> int:
    if cfg.cross_attn_every:
        return cfg.cross_attn_every
    if cfg.block_pattern == "zamba2":
        return cfg.shared_attn_every
    return 1


def _cell_costs(arch: str, shape_name: str, mesh, layers: int,
                extra_overrides=None) -> dict:
    """Compile one reduced-depth, UNROLLED variant and return raw costs."""
    ov = {"scan_layers": False, "num_layers": layers}
    ov.update(extra_overrides or {})
    lowered, cfg, model, shape = lower_cell(arch, shape_name, mesh,
                                            opt_overrides=ov)
    compiled = lowered.compile()
    chips = int(mesh.devices.size)
    cost = compiled.cost_analysis() or {}
    coll = rl.parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)) * chips,
        "bytes": float(cost.get("bytes accessed", 0.0)) * chips,
        "coll": {k: v * chips for k, v in coll.items()},
    }


def run_roofline_cell(arch: str, shape_name: str, multi_pod: bool = False) -> dict:
    """Exact-accounting roofline: XLA counts while-loop bodies once, so the
    full scanned compile undercounts layer costs. We compile 1-unit and
    2-unit *unrolled* variants at full width and extrapolate linearly — exact
    for the homogeneous layer stacks used here."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": rl.mesh_name(mesh),
           "chips": int(mesh.devices.size), "status": "ok", "kind": "roofline"}
    skip = shp.runnable(cfg, shp.SHAPES[shape_name])
    if skip:
        rec.update(status="skip", reason=skip)
        return rec
    try:
        t0 = time.monotonic()
        unit = _layer_unit(cfg)
        L1 = cfg.first_dense + unit
        L2 = L1 + unit
        n_units = (cfg.num_layers - cfg.first_dense) // unit
        c1 = _cell_costs(arch, shape_name, mesh, L1)
        c2 = _cell_costs(arch, shape_name, mesh, L2)

        def extrap(a, b):
            return a + (n_units - 1) * (b - a)

        flops = extrap(c1["flops"], c2["flops"])
        bytes_acc = extrap(c1["bytes"], c2["bytes"])
        coll = {k: extrap(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}
        shape = shp.SHAPES[shape_name]
        mf = rl.model_flops(cfg, shape, shape.kind)
        est = rl.estimate_hbm_bytes(cfg, shape, shape.kind)
        roof = rl.Roofline(arch, shape_name, rl.mesh_name(mesh),
                           int(mesh.devices.size), flops, bytes_acc,
                           float(sum(coll.values())), mf, est_hbm_bytes=est)
        rec.update(
            compile_s=round(time.monotonic() - t0, 1),
            hlo_flops=flops, hlo_bytes=bytes_acc,
            collective_bytes=coll, collective_total=float(sum(coll.values())),
            model_flops=mf, est_hbm_bytes=est,
            t_compute=roof.t_compute, t_memory=roof.t_memory,
            t_memory_est=roof.t_memory_est,
            t_collective=roof.t_collective, dominant=roof.dominant,
            dominant_est=roof.dominant_est,
            useful_ratio=roof.useful_ratio,
            roofline_fraction=roof.roofline_fraction,
            roofline_fraction_est=roof.roofline_fraction_est,
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(shp.SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mode", default="compile", choices=["compile", "roofline"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(shp.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    out_f = open(args.out, "a") if args.out else None
    n_bad = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                if args.mode == "roofline":
                    rec = run_roofline_cell(arch, shape_name, multi_pod)
                else:
                    rec = run_cell(arch, shape_name, multi_pod)
                line = json.dumps(rec)
                if out_f:
                    out_f.write(line + "\n")
                    out_f.flush()
                status = rec["status"]
                msg = (f"[{rec['mesh']}] {arch} x {shape_name}: {status}")
                if status == "ok":
                    msg += (f"  compile={rec['compile_s']}s"
                            f" dominant={rec['dominant']}"
                            f" roofline={rec['roofline_fraction']*100:.1f}%")
                elif status == "error":
                    n_bad += 1
                    msg += "  " + rec["error"][:200]
                print(msg, flush=True)
                if status == "ok" and len(archs) == 1 and len(shapes) == 1:
                    print("memory_analysis:", json.dumps(rec.get("memory", {})))
                    print("cost_analysis: flops=%.4g bytes=%.4g (global; "
                          "per-partition x chips)" % (rec.get("hlo_flops", 0),
                                                      rec.get("hlo_bytes", 0)))
                    print("collectives:", json.dumps(rec.get("collective_bytes", {})))
    if out_f:
        out_f.close()
    raise SystemExit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
