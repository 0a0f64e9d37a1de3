import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede every other import: jax locks the
# device count at first initialization.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step for train
shapes, serve_step/prefill for inference shapes) with production
shardings, compiles it (SPMD, 256 or 512 partitions), and records:

  memory_analysis()      - bytes per device (proves it fits)
  cost_analysis()        - XLA's flop/byte counts (scan body once)
  hlo_analysis           - honest whole-program dot FLOPs + collective
                           bytes with while-trip multipliers
  roofline terms         - compute / memory / collective seconds on
                           TPU v5e constants, + MODEL_FLOPS = 6ND

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
Results land in results/dryrun/<cell>.json (one process per cell is
recommended; see scripts/run_dryrun_all.py).
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.launch import specs as SP
from repro.launch.hlo_analysis import analyze_text
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.sharding.rules import make_rules, rules_context
from repro.train.step import make_train_step

# --- TPU v5e constants ------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (per chip, per direction)

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = batch tokens."""
    n = cfg.active_param_count()
    _, dec_len = SP.split_lens(cfg, shape.seq_len)
    if shape.kind == "train":
        tokens = shape.global_batch * dec_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * dec_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # one token per sequence


def build_cell(arch: str, shape_name: str, multi_pod: bool, *,
               profile: str = "tp", accum: int = 1,
               donate_cache: bool = False, kv_dtype: str = None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = applicable(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = SP.tune_for_mesh(cfg, mesh)
    if kv_dtype:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, kv_cache_dtype=kv_dtype)
    rules = make_rules(cfg, mesh, batch_size=shape.global_batch,
                       profile=profile)
    t0 = time.time()

    with rules_context(mesh, rules), jax.set_mesh(mesh):
        if shape.kind == "train":
            state_shape = SP.abstract_train_state(cfg)
            st_sh = SP.train_state_shardings(state_shape, cfg, mesh, rules)
            batch = SP.input_specs(cfg, shape)
            b_sh = SP.batch_shardings(batch, mesh, rules)
            opt_cfg = AdamWConfig()
            step = make_train_step(cfg, opt_cfg, accum_steps=accum)
            fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None))
            lowered = fn.lower(state_shape, batch)
        elif shape.kind == "prefill":
            params_shape = SP.abstract_params(cfg)
            from repro.sharding.rules import param_shardings
            psh = param_shardings(params_shape, mesh, rules)
            batch = SP.input_specs(cfg, shape)
            b_sh = SP.batch_shardings(batch, mesh, rules)

            def prefill(params, b):
                logits, _ = T.forward(params, cfg, b["tokens"],
                                      enc_frames=b.get("enc_frames"))
                return logits

            fn = jax.jit(prefill, in_shardings=(psh, b_sh),
                         out_shardings=None)
            lowered = fn.lower(params_shape, batch)
        else:  # decode
            params_shape = SP.abstract_params(cfg)
            from repro.sharding.rules import param_shardings
            psh = param_shardings(params_shape, mesh, rules)
            inputs = SP.input_specs(cfg, shape, abstract_params=params_shape)
            c_sh = SP.cache_shardings(inputs["cache"], mesh, rules)
            from repro.sharding.rules import logical_to_spec
            tok_spec = logical_to_spec(("batch", None), rules)
            tok_sh = NamedSharding(mesh, tok_spec)

            def serve_step(params, tokens, cache):
                return T.decode_step(params, cfg, tokens, cache)

            fn = jax.jit(serve_step,
                         in_shardings=(psh, tok_sh, c_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(2,) if donate_cache else ())
            lowered = fn.lower(params_shape,
                               jax.ShapeDtypeStruct((shape.global_batch, 1),
                                                    jnp.int32),
                               inputs["cache"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    hlo = analyze_text(hlo_text)

    chips = mesh.size
    mf = model_flops(cfg, shape)
    flops_dev = hlo["dot_flops"]                   # per-device program
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = hlo["dot_bytes"] / HBM_BW
    coll_s = hlo["collective_total"] / ICI_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", coll_s)), key=lambda kv: kv[1])[0]

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "chips": chips,
        "kv_repeat": cfg.kv_repeat,
        "variant": {"profile": profile, "accum": accum,
                    "donate_cache": donate_cache, "kv_dtype": kv_dtype},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_estimate_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "xla_cost": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "transcendentals")
                     if k in cost},
        "hlo": {
            "dot_flops_per_device": flops_dev,
            "dot_bytes_per_device": hlo["dot_bytes"],
            "collective_bytes_per_device": hlo["collective_bytes"],
            "collective_total_per_device": hlo["collective_total"],
        },
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": dominant,
            "model_flops_global": mf,
            "useful_flops_ratio": (mf / (flops_dev * chips)
                                   if flops_dev else None),
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--profile", default="tp")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--donate-cache", action="store_true")
    ap.add_argument("--kv-dtype", default=None)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            tag = f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}"
            out = pathlib.Path(args.out) if args.out \
                else RESULTS_DIR / f"{tag}.json"
            try:
                res = build_cell(arch, shape, args.multi_pod,
                                 profile=args.profile, accum=args.accum,
                                 donate_cache=args.donate_cache,
                                 kv_dtype=args.kv_dtype)
            except Exception as e:          # noqa: BLE001
                res = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if args.multi_pod else "16x16",
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]}
            out.write_text(json.dumps(res, indent=1, default=str))
            if res.get("status") == "ok" and "hlo_text" in dir():
                pass
            status = res["status"]
            extra = ""
            if status == "ok":
                r = res["roofline"]
                extra = (f" dominant={r['dominant']} "
                         f"compute={r['compute_s']:.3f}s "
                         f"mem={r['memory_s']:.3f}s "
                         f"coll={r['collective_s']:.3f}s "
                         f"peak/dev={res['memory']['peak_estimate_gb']}GB")
            elif status == "error":
                extra = " " + res["error"][:200]
            print(f"[dryrun] {tag}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
