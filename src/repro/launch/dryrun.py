import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

The two lines above MUST run before any other import — jax locks the device
count at first init. 512 placeholder host devices back the production meshes
(16,16) single-pod and (2,16,16) multi-pod.

Per (architecture x input-shape x mesh) cell:
  1. build the model, abstract inputs (ShapeDtypeStruct — no allocation),
  2. jit the step (train_step / prefill / decode) with in/out shardings from
     the named strategy, donating the train state / caches,
  3. ``.lower()`` + ``.compile()`` — sharding mismatches, unsupported
     collectives and compile-time OOMs surface here as hard failures,
  4. print ``memory_analysis()`` (proves it fits) and ``cost_analysis()``,
  5. derive the three roofline terms (launch/roofline.py) and write the JSON
     artifact + the portable StableHLO feature vector (the predictor's
     dataset — the paper's pipeline applied to our own framework).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from dataclasses import asdict
from pathlib import Path

import jax

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             strategy: str = "2d", verbose: bool = True,
             save: bool = True, extract_features: bool = True) -> dict:
    from ..configs import SHAPES, get_config, supports_shape
    from ..launch.mesh import make_production_mesh, mesh_devices
    from ..launch.roofline import analyze_cell
    from ..models.registry import build_model

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape.name}__{mesh_name}__{strategy}"

    if not supports_shape(cfg, shape):
        rec = {"tag": tag, "status": "skipped",
               "reason": "full-attention arch: long_500k requires "
                         "sub-quadratic decode (DESIGN.md §4)"}
        if save:
            _save_json(rec, ARTIFACTS / f"{tag}.json")
        if verbose:
            print(f"SKIP {tag}: {rec['reason']}")
        return rec

    from ..sharding.context import activation_sharding

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh_devices(mesh)
    model = build_model(cfg)
    t0 = time.perf_counter()
    from .cells import cell_fns
    fn, args, in_sh, out_sh, donate = cell_fns(model, shape, strategy, mesh)
    with mesh, activation_sharding(mesh, strategy):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    rep = analyze_cell(compiled, arch=arch, shape=shape, mesh_name=mesh_name,
                       n_devices=n_dev, strategy=strategy, cfg=cfg)
    mem = compiled.memory_analysis()
    if verbose:
        print(f"CELL {tag}")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        ca = compiled.cost_analysis() or {}
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: {rep.row()}")

    rec = {"tag": tag, "status": "ok", "lower_s": t_lower,
           "compile_s": t_compile, "report": asdict(rep)}

    if extract_features:
        # portable features (paper §3.1): recorded once per cell, reusable
        # for every target device — the predictor's framework-level dataset.
        from ..core.features import LaunchConfig, extract_from_text
        fv = extract_from_text(
            lowered.as_text(),
            LaunchConfig(work_items=float(shape.tokens), n_shards=n_dev))
        rec["features"] = fv.as_dict()
        rec["feature_aux"] = {k: float(v) for k, v in fv.aux.items()}

    if save:
        _save_json(rec, ARTIFACTS / f"{tag}.json")
    return rec


def _save_json(obj, path: Path):
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    tmp.replace(path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--strategy", default="2d")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from ..configs import ARCHS, SHAPES

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                tag = f"{arch}__{shape}__{mesh_name}__{args.strategy}"
                path = ARTIFACTS / f"{tag}.json"
                if args.skip_existing and path.exists():
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"EXISTS {tag}")
                            continue
                try:
                    run_cell(arch, shape, multi_pod=mp,
                             strategy=args.strategy)
                except Exception as e:
                    traceback.print_exc()
                    failures.append(tag)
                    _save_json({"tag": tag, "status": "error",
                                "error": f"{type(e).__name__}: {e}"}, path)
    if failures:
        print(f"\nFAILURES ({len(failures)}):")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nDRY-RUN COMPLETE")


if __name__ == "__main__":
    main()
