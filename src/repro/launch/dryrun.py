"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

:func:`main` forces 512 host devices through ``XLA_FLAGS`` before it
imports JAX (JAX fixes the device count when it first initialises), so
importing this module changes nothing in the caller's process.

For each cell the dry-run:
  1. builds the production mesh (16×16 single-pod, or 2×16×16 multi-pod),
  2. builds the cell's step bundle (train_step / prefill / serve_step)
     with mesh-resolved in/out shardings,
  3. ``jax.jit(...).lower(*input_specs).compile()`` — ShapeDtypeStructs
     only, no allocation,
  4. records memory_analysis / cost_analysis / the HLO-parsed roofline
     terms (analysis/roofline.py) into a JSON artifact.

Run one cell:   python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
Run everything: python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

from repro.analysis.roofline import analyze_hlo, roofline_terms
from repro.configs import SHAPES, get_config, list_configs

DEFAULT_OUT = "results/dryrun"


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                                    # noqa: BLE001
        return {"error": repr(e)}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: str = DEFAULT_OUT, save_hlo: bool = False,
             overrides: dict | None = None, tag: str = "") -> dict:
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.train import make_step_bundle

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")

    ok, why = cfg.supports_shape(shape)
    if not ok:
        result = {"cell": cell_id, "arch": arch, "shape": shape_name,
                  "mesh": mesh_name, "status": "SKIP", "reason": why}
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"[dryrun] {cell_id}: SKIP ({why})")
        return result

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    try:
        with mesh:
            bundle = make_step_bundle(cfg, shape, mesh)
            jitted = jax.jit(bundle.step_fn,
                             in_shardings=bundle.in_shardings,
                             out_shardings=bundle.out_shardings)
            lowered = jitted.lower(*bundle.in_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception:                                         # noqa: BLE001
        result = {"cell": cell_id, "arch": arch, "shape": shape_name,
                  "mesh": mesh_name, "status": "FAIL",
                  "error": traceback.format_exc()}
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"[dryrun] {cell_id}: FAIL")
        print(result["error"].splitlines()[-1])
        return result

    mem = _memory_analysis_dict(compiled)
    try:
        cost = dict(compiled.cost_analysis() or {})
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float))}
    except Exception as e:                                    # noqa: BLE001
        cost = {"error": repr(e)}

    hlo = compiled.as_text()
    analysis = analyze_hlo(hlo, chips_per_pod=256)
    kind = "train" if shape.kind == "train" else "serve"
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    model_flops = cfg.model_flops_per_token(
        "train" if kind == "train" else "serve") * tokens
    rl = roofline_terms(analysis, model_flops_total=model_flops,
                        n_chips=n_chips)

    result = {
        "cell": cell_id, "arch": arch, "shape": shape_name,
        "mesh": mesh_name, "status": "OK",
        "kind": shape.kind,
        "n_chips": n_chips,
        "tokens_per_step": tokens,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "hlo_analysis": {
            "flops_per_device": analysis.flops,
            "hbm_bytes_per_device": analysis.hbm_bytes,
            "ici_bytes_per_device": analysis.ici_bytes,
            "dcn_bytes_per_device": analysis.dcn_bytes,
            "collective_operand_bytes": analysis.collective_operand_bytes,
            "top_collectives": [dataclasses.asdict(c)
                                for c in analysis.collectives[:12]],
            "top_dots": analysis.dots[:12],
        },
        "model_flops_total": model_flops,
        "roofline": rl.to_json(),
    }
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    if save_hlo:
        import gzip
        with gzip.open(os.path.join(out_dir, cell_id + ".hlo.gz"),
                       "wt") as f:
            f.write(hlo)
    tps = result["roofline"]
    print(f"[dryrun] {cell_id}: OK  compile={t_compile:.0f}s  "
          f"bottleneck={tps['bottleneck']}  "
          f"t_comp={tps['t_compute']:.4f}s t_mem={tps['t_memory']:.4f}s "
          f"t_ici={tps['t_ici']:.4f}s t_dcn={tps['t_dcn']:.4f}s  "
          f"frac={tps['roofline_fraction']:.3f}")
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    from repro import chip
    chip.init_jax()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true",
                   help="every (arch x shape) for the chosen mesh")
    p.add_argument("--out", default=DEFAULT_OUT)
    p.add_argument("--save-hlo", action="store_true")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    archs = [args.arch] if args.arch else list_configs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    if not args.all and args.arch is None and args.shape is None:
        p.error("pass --arch/--shape or --all")

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            mesh_name = "2x16x16" if args.multi_pod else "16x16"
            path = os.path.join(
                args.out, f"{arch}__{shape}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") in ("OK", "SKIP"):
                    print(f"[dryrun] {prev['cell']}: cached "
                          f"({prev['status']})")
                    continue
            r = run_cell(arch, shape, multi_pod=args.multi_pod,
                         out_dir=args.out, save_hlo=args.save_hlo)
            n_fail += r["status"] == "FAIL"
    print(f"[dryrun] done, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
