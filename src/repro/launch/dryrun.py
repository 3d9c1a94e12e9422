import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (dev override for fast iteration; production dry-run keeps 512)
if os.environ.get("DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={os.environ['DRYRUN_DEVICES']}"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, prove memory/sharding coherence, and extract roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --shape long_500k --multi-pod
"""
import argparse
import json

import jax
import numpy as np

from repro.configs import get_arch
from repro.obs.clock import now, since
from repro.configs.common import SHAPES, lm_batch_specs, decode_specs, params_specs
from repro.launch import hlo_cost
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models import api
from repro.models.partitioning import batch_pspecs, cache_pspecs, param_pspecs, to_named
from repro.models.sharding import use_mesh_rules


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False, fsdp: bool = True,
               out_dir: str | None = None, print_hlo_stats: bool = True) -> dict:
    mod = get_arch(arch)
    cfg = mod.config()
    shape = SHAPES[shape_name]
    mesh_tag = "pod2" if multi_pod else "pod1"
    result = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_tag,
        "kind": shape.kind, "devices": 512 if multi_pod else 256,
    }

    skip = getattr(mod, "SKIP_SHAPES", {}).get(shape_name)
    if skip:
        result["skipped"] = skip
        _write(result, out_dir)
        print(f"SKIP {arch} {shape_name}: {skip}")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    # monotonic: compile timing must not jump when NTP steps the wall clock
    t0 = now()

    rules = None
    if getattr(cfg, "pure_dp", False):
        rules = {
            "batch": ("pod", "data", "model"), "heads": None, "kv_heads": None,
            "ff": None, "experts": None, "vocab": None, "moe_d": None,
        }
    # FSDP re-gathers weights every step — amortized over thousands of tokens
    # in training/prefill, but a pure per-token tax at decode (measured 6.3 GB
    # of weight all-gathers per token on gemma3 long_500k). Decode keeps
    # weights model-sharded only; they fit (<= params/16 per chip).
    if shape.kind == "decode":
        fsdp = False
    with use_mesh_rules(mesh, rules):
        params = params_specs(cfg)
        pp = to_named(param_pspecs(cfg, params, mesh, fsdp=fsdp), mesh)
        if shape.kind == "train":
            opt = jax.eval_shape(api.adamw_init, params)
            op = to_named(param_pspecs(cfg, opt, mesh, fsdp=fsdp), mesh)
            batch = lm_batch_specs(cfg, shape)
            bp = to_named(batch_pspecs(cfg, batch, mesh), mesh)
            step = api.make_train_step(cfg)
            lowered = jax.jit(step, in_shardings=(pp, op, bp)).lower(params, opt, batch)
        elif shape.kind == "prefill":
            batch = lm_batch_specs(cfg, shape)
            bp = to_named(batch_pspecs(cfg, batch, mesh), mesh)
            step = api.make_prefill_step(cfg)
            lowered = jax.jit(step, in_shardings=(pp, bp)).lower(params, batch)
        else:  # decode
            specs = decode_specs(cfg, shape)
            cp = to_named(cache_pspecs(cfg, specs["cache"], mesh), mesh)
            tp = to_named(batch_pspecs(cfg, {"t": specs["tokens"]}, mesh)["t"], mesh)
            step = api.make_serve_step(cfg)
            lowered = jax.jit(step, in_shardings=(pp, cp, tp, None)).lower(
                params, specs["cache"], specs["tokens"], specs["pos"]
            )
        compiled = lowered.compile()
    t_compile = since(t0)

    mem = compiled.memory_analysis()
    print(mem)
    xla_cost = compiled.cost_analysis()
    print({k: xla_cost.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    cost = hlo_cost.analyze(hlo)

    # ---- roofline terms (per chip, seconds)
    flops = cost["flops"]
    bytes_hbm = cost["bytes"]
    bytes_coll = cost["coll_total_moved_bytes"]
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_hbm / HBM_BW
    collective_s = bytes_coll / ICI_BW

    # analytic model flops (global), then per chip
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else (shape.seq_len if shape.kind == "prefill" else 1))
    if shape.kind == "train":
        model_flops = 6.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * tokens
    model_flops_chip = model_flops / n_chips

    dominant = max(("compute", compute_s), ("memory", memory_s), ("collective", collective_s), key=lambda kv: kv[1])[0]
    result.update(
        {
            "compile_s": round(t_compile, 1),
            "memory_analysis": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
                "fits_16gb": (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 16e9,
            },
            "xla_cost_raw": {k: xla_cost.get(k) for k in ("flops", "bytes accessed", "transcendentals")},
            "hlo_flops": flops,
            "hlo_bytes": bytes_hbm,
            "collective_moved_bytes": bytes_coll,
            "collectives": cost["coll"],
            "top_collectives": cost.get("top_collectives", []),
            "top_bytes": cost.get("top_bytes", []),
            "roofline": {
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": collective_s,
                "dominant": dominant,
            },
            "model_flops_per_chip": model_flops_chip,
            "useful_flop_ratio": model_flops_chip / flops if flops else None,
            "params_total": cfg.param_count(),
            "params_active": n_active,
        }
    )
    _write(result, out_dir)
    print(
        f"{arch} {shape_name} {mesh_tag}: compile {t_compile:.0f}s  "
        f"compute {compute_s*1e3:.2f}ms  memory {memory_s*1e3:.2f}ms  "
        f"collective {collective_s*1e3:.2f}ms  dominant={dominant}  "
        f"useful={result['useful_flop_ratio'] and round(result['useful_flop_ratio'],3)}"
    )
    return result


def _write(result, out_dir):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{result['arch']}_{result['shape']}_{result['mesh']}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    enable_compile_cache()
    run_dryrun(args.arch, args.shape, multi_pod=args.multi_pod, fsdp=not args.no_fsdp, out_dir=args.out)


if __name__ == "__main__":
    main()
