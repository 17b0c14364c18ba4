import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# placeholder devices on the host CPU, never an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any jax import (jax locks device count on first init).

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, on the single-pod 16x16
mesh and the two-pod 2x16x16 mesh:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...)
                  .lower(**input_specs)
    compiled = lowered.compile()
    memory_analysis / cost_analysis / HLO collective-bytes

No arrays are ever allocated at the full sizes — inputs are
``ShapeDtypeStruct``s and the 512 "devices" are XLA host-platform
placeholders.  Results land in ``results/dryrun/<cell>.json``; the
roofline table (EXPERIMENTS.md section Roofline) is generated from
those files by ``launch/roofline.py``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm_135m
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
    PYTHONPATH=src python -m repro.launch.dryrun --arch X --shape train_4k \
        --mesh multi  [--variant optimized]
"""
import argparse
import json
from ..obs import clock
import traceback
from pathlib import Path

import jax

from .. import compat
from ..configs import ARCHS, SHAPES, applicable, get_config, shape_by_name
from ..optim import AdamWConfig
from .hlo_cost import analyze as hlo_analyze, xla_cost_analysis
from .mesh import make_production_mesh
from .roofline import roofline_terms
from . import specs as S

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# the device kind whose published peaks turn counts into modeled seconds
DEVICE_KIND = "TPU v5 lite"


def _get_cfg(arch: str, shape, variant: str):
    """Arch config for one cell (long-context flavor where supported)."""
    import importlib
    mod = importlib.import_module(
        f"repro.configs.{arch.replace('-', '_')}")
    kwargs = {}
    if shape.name == "long_500k" and "long_context" in \
            mod.config.__code__.co_varnames:
        kwargs["long_context"] = True
    return mod.config(**kwargs).validate()


def lower_cell(arch: str, shape_name: str, mesh, *, n_micro: int = 1,
               variant: str = "baseline"):
    """Lower+compile one (arch, shape) cell on ``mesh``. Returns record."""
    from .serve import make_sharded_prefill_step, make_sharded_serve_step
    from .train import make_sharded_train_step
    shape = shape_by_name(shape_name)
    cfg = _get_cfg(arch, shape, variant)
    skip = applicable(cfg, shape)
    if skip is not None:
        return {"arch": arch, "shape": shape_name, "skipped": skip}

    t0 = clock.perf_counter()
    with compat.use_mesh(mesh):
        if shape.kind == "train":
            nm = max(n_micro, _default_micro(arch))
            step, (ps, os_, bsh) = make_sharded_train_step(
                cfg, AdamWConfig(), mesh, shape, n_micro=nm,
                variant=variant)
            params_abs, opt_abs = S.abstract_train_state(cfg)
            lowered = step.lower(params_abs, opt_abs,
                                 S.batch_specs(cfg, shape))
        elif shape.kind == "prefill":
            step, (ps, bsh) = make_sharded_prefill_step(
                cfg, mesh, shape, variant=variant)
            params_abs, _ = S.abstract_train_state(cfg)
            lowered = step.lower(params_abs, S.batch_specs(cfg, shape))
        else:  # decode
            step, (ps, cs, tok) = make_sharded_serve_step(
                cfg, mesh, shape, variant=variant)
            params_abs, _ = S.abstract_train_state(cfg)
            caches_abs = S.abstract_serve_cache(cfg, shape)
            lowered = step.lower(params_abs, caches_abs,
                                 S.serve_token_spec(cfg, shape))
        t_lower = clock.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = clock.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = xla_cost_analysis(compiled)      # list-vs-dict normalized
    # trip-count-aware analysis (XLA's cost_analysis counts while/scan
    # bodies once — see hlo_cost.py); XLA numbers kept for cross-check
    hc = hlo_analyze(compiled.as_text())
    n_chips = mesh.devices.size
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "kind": shape.kind,
        "variant": variant,
        "n_chips": int(n_chips),
        "flops": hc["flops"],
        "bytes_accessed": hc["bytes_accessed"],
        "bytes_hbm": (hc["bytes_materialized"]
                      + int(mem.argument_size_in_bytes)
                      + int(mem.output_size_in_bytes)),
        "collective_bytes": hc["collective_bytes"],
        "xla_flops_noscan": float(cost.get("flops", 0.0)),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     + mem.output_size_in_bytes),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    rec.update(roofline_terms(rec, cfg, shape, DEVICE_KIND))
    return rec


def _default_micro(arch: str) -> int:
    """Microbatch counts so train_4k activations fit 16 GB HBM."""
    return {
        "jamba_v01_52b": 16, "grok_1_314b": 32, "qwen25_32b": 16,
        "deepseek_v2_lite_16b": 8, "yi_6b": 8, "qwen3_4b": 8,
        "internvl2_2b": 4, "whisper_medium": 4,
    }.get(arch, 2)


# per-arch best-known perf flags (EXPERIMENTS.md §Perf); selected with
# ``--variant best``.  Preconditions (batch divisibility, expert
# divisibility) are enforced downstream by effective_variant/spec_for.
BEST_VARIANT = {
    "smollm_135m": "dponly,flashvjp",
    "mamba2_130m": "dponly",
    "whisper_medium": "dponly,flashvjp",
    "deepseek_v2_lite_16b": "ep,micro2",
    "internvl2_2b": "dponly,flashvjp",
    "qwen3_4b": "flashvjp",
    "yi_6b": "flashvjp",
    "qwen25_32b": "flashvjp",
    "jamba_v01_52b": "flashvjp",
    "grok_1_314b": "flashvjp",
}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             variant: str = "baseline", save: bool = True):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        rec = lower_cell(arch, shape_name, mesh, variant=variant)
    except Exception as e:  # noqa: BLE001 — record the failure
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "variant": variant, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()}
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        tag = "" if variant == "baseline" else f".{variant}"
        out = RESULTS / f"{arch}.{shape_name}.{mesh_kind}{tag}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS + ["all"], default="all")
    ap.add_argument("--shape", default="all",
                    choices=[s.name for s in SHAPES] + ["all"])
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" \
        else [args.shape]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                variant = BEST_VARIANT.get(arch, "baseline") \
                    if args.variant == "best" else args.variant
                rec = run_cell(arch, shape, mk, variant=variant)
                if "error" in rec:
                    n_fail += 1
                    status = "FAIL " + rec["error"][:90]
                elif "skipped" in rec:
                    n_skip += 1
                    status = "skip: " + rec["skipped"][:60]
                else:
                    n_ok += 1
                    status = (f"ok   {rec['flops']:.2e} fl "
                              f"{rec['peak_bytes_per_device']/2**30:.2f} "
                              f"GiB/dev  comp {rec['compile_s']}s "
                              f"dom={rec['dominant']}")
                print(f"{arch:22s} {shape:12s} {mk:6s} {status}",
                      flush=True)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
