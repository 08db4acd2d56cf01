import os
# an ahead-of-time tool: it compiles for 512 placeholder host devices, so it
# pins the CPU platform (on a TPU host the production mesh would meet the
# local chips) and appends its device count to flags set from outside
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    f for f in (os.environ.get("XLA_FLAGS"),
                "--xla_force_host_platform_device_count=512") if f)

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production mesh, proving the distribution config is coherent, and record
memory_analysis / cost_analysis / collective traffic for §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]

The environment lines above MUST stay the first statements: jax locks the
platform and device count on first init, and the dry-run needs 512
placeholder host devices. Tests and benchmarks import repro.* directly and
see their own platform.
"""
import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, shape_supported
from repro.distributed.sharding import (DEFAULT_RULES, INFERENCE_RULES,
                                        SEQ_PARALLEL_RULES, global_mesh)
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.layers import ModelOptions
from repro.roofline import collective_bytes, model_flops_for
from repro.roofline.analytic import analytic_cell
from repro.training import (AdamWConfig, TrainConfig, init_train_state,
                            make_train_step)


def build_step(cfg, shape, opts: ModelOptions, tcfg: TrainConfig):
    """Returns (fn, arg_specs, arg_shardings, donate) for the cell."""

    if shape.kind == "train":
        step = make_train_step(cfg, opts, tcfg)
        return step, ("params", "opt_state", "batch"), (0, 1)
    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return M.prefill(cfg, opts, params, batch, shape.seq_len,
                             cache_dtype=SP.CACHE_DTYPE)
        return prefill_step, ("params", "batch"), ()
    def serve_step(params, token, caches, index):
        return M.decode_step(cfg, opts, params, token, caches, index)
    return serve_step, ("params", "token", "caches", "index"), (2,)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: Optional[str] = None, opts: Optional[ModelOptions] = None,
             microbatches: int = 1, moment_dtype: str = "float32",
             infer_rules: bool = False, seq_parallel: bool = False,
             pad_experts: int = 0, tag: str = "", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if pad_experts:
        cfg = dataclasses.replace(cfg, num_experts_padded=pad_experts)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    opts = opts or ModelOptions()
    tcfg = TrainConfig(opt=AdamWConfig(moment_dtype=getattr(jnp, moment_dtype)),
                       microbatches=microbatches)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi_pod" if multi_pod else "single_pod"
    rules = dict(DEFAULT_RULES)
    if infer_rules:
        rules.update(INFERENCE_RULES)
    if seq_parallel:
        rules.update({k: v for k, v in SEQ_PARALLEL_RULES.items()
                      if k == "act_seq"})

    with global_mesh(mesh, rules=rules):
        params_sds, params_sh = SP.model_specs_and_shardings(cfg, mesh)
        in_sds = SP.input_specs(cfg, shape, opts)
        in_sh = SP.input_shardings(cfg, shape, mesh, opts)
        fn, order, donate = build_step(cfg, shape, opts, tcfg)

        args, shardings = [], []
        for name in order:
            if name == "params":
                args.append(params_sds)
                shardings.append(params_sh)
            elif name == "opt_state":
                opt_sds = jax.eval_shape(
                    lambda p: init_train_state(cfg, tcfg, p), params_sds)
                repl = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())
                opt_sh = {"inner": {"mu": params_sh, "nu": params_sh,
                                    "count": repl}}
                if tcfg.compress_grads:
                    opt_sh["error"] = params_sh
                args.append(opt_sds)
                shardings.append(opt_sh)
            else:
                args.append(in_sds[name])
                shardings.append(in_sh[name])

        t0 = time.time()
        jitted = jax.jit(fn, in_shardings=tuple(shardings),
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if not isinstance(cost, dict):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    mem_d = {k: float(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")} if mem else {}
    ac = analytic_cell(cfg, shape, multi_pod=multi_pod,
                       causal_pairs=opts.causal_pairs,
                       window_cache=opts.window_cache, remat=opts.remat,
                       microbatches=microbatches, infer_rules=infer_rules,
                       seq_parallel=seq_parallel)
    row = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "kind": shape.kind,
        "cost": {k: float(v) for k, v in cost.items()
                 if k in ("flops", "bytes accessed", "transcendentals")},
        "memory": mem_d,
        "collectives": coll,
        "analytic": {"flops_per_dev": ac.flops_per_dev,
                     "hbm_bytes_per_dev": ac.hbm_bytes_per_dev,
                     "coll_bytes_per_dev": ac.coll_bytes_per_dev,
                     "breakdown": ac.breakdown},
        "model_flops": model_flops_for(cfg, shape),
        "params_total": cfg.param_counts()["total"],
        "params_active": cfg.param_counts()["active"],
        "t_lower_s": t_lower, "t_compile_s": t_compile,
        "hlo_bytes": len(hlo),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"flops/dev={row['cost'].get('flops', 0):.3e} "
              f"bytes/dev={row['cost'].get('bytes accessed', 0):.3e} "
              f"coll/dev={coll.get('total', 0):.3e} "
              f"temp/dev={mem_d.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        print("memory_analysis:", mem)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"-{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(row, f, indent=1)
    return row


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", required=True, choices=list(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--tag", default="")
    p.add_argument("--causal-pairs", action="store_true")
    p.add_argument("--window-cache", action="store_true")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--unroll", action="store_true",
                   help="unroll the layer scan (exact XLA cost analysis)")
    p.add_argument("--infer-rules", action="store_true",
                   help="inference sharding rules (no FSDP; see §Perf)")
    p.add_argument("--seq-parallel", action="store_true",
                   help="sequence-parallel TP residual sharding (see §Perf)")
    p.add_argument("--moe-per-seq", action="store_true",
                   help="per-sequence-local MoE dispatch (see §Perf)")
    p.add_argument("--pad-experts", type=int, default=0,
                   help="pad expert dim to divide the TP axis (see §Perf)")
    p.add_argument("--moe-gather", action="store_true",
                   help="tiny-batch decode: gather top-k expert weights")
    p.add_argument("--remat-sublayers", action="store_true",
                   help="nested per-sublayer remat (see §Perf)")
    args = p.parse_args()
    opts = ModelOptions(causal_pairs=args.causal_pairs,
                        window_cache=args.window_cache,
                        remat=not args.no_remat,
                        moe_per_seq_dispatch=args.moe_per_seq,
                        moe_gather_decode=args.moe_gather,
                        remat_sublayers=args.remat_sublayers,
                        unroll_layers=args.unroll)
    row = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_dir=args.out, opts=opts,
                   microbatches=args.microbatches, tag=args.tag,
                   infer_rules=args.infer_rules,
                   seq_parallel=args.seq_parallel,
                   pad_experts=args.pad_experts)
    if "skipped" in row:
        print(f"[dryrun] SKIP {args.arch} x {args.shape}: {row['skipped']}")


if __name__ == "__main__":
    main()
