import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks device count on first init.
# A CPU-only tool: it never claims an accelerator.

import argparse
import json
import time
import traceback

import jax

from repro.configs import get_config, get_shape, cell_is_runnable
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim import OptConfig
from repro.parallel import sharding
from repro.roofline import hlo as hlo_lib
from repro.roofline import model as roof


def opt_config_for(cfg) -> OptConfig:
    # >=50B params: bf16 moments + bf16 stored params with fp32 master
    # (DESIGN.md §Memory budget)
    big = cfg.param_count() > 5e10
    return OptConfig(moment_dtype="bfloat16" if big else "float32",
                     master_weights=big)


def model_config_for(arch: str):
    import dataclasses
    cfg = get_config(arch)
    if cfg.param_count() > 5e10:
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    # perf-iteration knobs (EXPERIMENTS.md §Perf): override via env
    if os.environ.get("REPRO_REMAT"):
        cfg = dataclasses.replace(cfg, remat=os.environ["REPRO_REMAT"])
    if os.environ.get("REPRO_KV_CHUNK"):
        cfg = dataclasses.replace(
            cfg, attn_kv_chunk=int(os.environ["REPRO_KV_CHUNK"]))
    return cfg


# Gradient-accumulation depth per arch for the train_4k cell: chosen so the
# activation working set fits 16GB v5e HBM (EXPERIMENTS.md §Dry-run).
MICROBATCHES = {
    "jamba-1.5-large-398b": 16,
    "llama-3.2-vision-90b": 8,
    "mixtral-8x22b": 8,
    "qwen3-moe-30b-a3b": 4,
    "qwen2.5-14b": 2,
    "stablelm-12b": 2,
    "llama3-8b": 2,
}


def build_lowerable(cfg, shape, mesh):
    """Returns (fn, arg_specs, arg_shardings, donate_argnums)."""
    ns = lambda tree: sharding.named(tree, mesh)
    if shape.kind == "train":
        opt_cfg = opt_config_for(cfg)
        fn = api.make_train_step(cfg, opt_cfg,
                                 MICROBATCHES.get(cfg.name, 1))
        specs = (api.abstract_params(cfg),
                 api.abstract_opt_state(cfg, opt_cfg),
                 api.batch_specs(cfg, shape))
        shardings = (ns(api.param_pspecs(cfg, mesh)),
                     ns(api.opt_pspecs(cfg, opt_cfg, mesh)),
                     ns(api.batch_pspecs(cfg, shape, mesh)))
        return fn, specs, shardings, (0, 1)
    if shape.kind == "prefill":
        fn = api.make_prefill_step(cfg)
        specs = (api.abstract_params(cfg),
                 api.batch_specs(cfg, shape, with_labels=False))
        shardings = (ns(api.param_pspecs(cfg, mesh)),
                     ns(api.batch_pspecs(cfg, shape, mesh,
                                         with_labels=False)))
        return fn, specs, shardings, ()
    # decode
    fn = api.make_serve_step(cfg)
    cache, tok, pos = api.decode_specs(cfg, shape)
    cache_ps, tok_ps, pos_ps = api.decode_pspecs(cfg, shape, mesh)
    specs = (api.abstract_params(cfg), cache, tok, pos)
    shardings = (ns(api.param_pspecs(cfg, mesh)), ns(cache_ps),
                 ns(tok_ps), ns(pos_ps))
    return fn, specs, shardings, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: str = "") -> dict:
    cfg = model_config_for(arch)
    shape = get_shape(shape_name)
    ok, why = cell_is_runnable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    fn, specs, shardings, donate = build_lowerable(cfg, shape, mesh)
    act_rules = sharding.activation_rules(mesh, shape.global_batch, cfg,
                                          kind=shape.kind)
    with mesh, sharding.use_activation_rules(act_rules):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*specs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):      # jax >= 0.4.3x: one dict per program
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(txt)
    hl = hlo_lib.analyze(txt)
    mf = roof.model_flops(cfg, shape)
    terms = roof.terms_from_analysis(hl)
    per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "n_devices": n_dev,
        "mesh": list(mesh.shape.values()), "axis_names": list(mesh.axis_names),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "per_device_gib": round(per_dev_bytes / 2**30, 3),
            "fits_16g_hbm": bool(per_dev_bytes < 16 * 2**30),
        },
        "cost_analysis": {"flops": ca.get("flops", 0.0),
                          "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "hlo": hl,
        "model_flops": mf,
        "roofline": {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s, "dominant": terms.dominant,
            "bound_s": terms.bound_s,
            "roofline_fraction": terms.roofline_fraction,
            "useful_flops_ratio": (
                mf["model_flops"] /
                max(hl["flops_per_device"] * n_dev, 1.0)),
            "useful_flops_ratio_with_attn": (
                mf["model_flops_with_attn"] /
                max(hl["flops_per_device"] * n_dev, 1.0)),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--save-hlo", default="")
    args = ap.parse_args()
    try:
        res = run_cell(args.arch, args.shape, args.multi_pod, args.save_hlo)
    except Exception as e:  # noqa: BLE001 — report failures as data
        res = {"arch": args.arch, "shape": args.shape,
               "multi_pod": args.multi_pod, "status": "error",
               "error": repr(e), "traceback": traceback.format_exc()}
    js = json.dumps(res, indent=1)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)
    if res["status"] == "error":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
