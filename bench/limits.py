"""Readings that a cell's output limits are set from, in one process.

    python3 bench/limits.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 8

For each seed: draw the weights and the fleet, serve the cell's own load
for a short window through the same engine and front end as ``run.py``,
and judge the window's served answers with the plain reference, exactly as
a benchmark run does. On the control seeds the control is judged instead:
the reference in float8 weights, whose first token at each of the same
positions takes the served token's place in the same checks, so that its
``correct`` has to come out false at the cell's own limit. One JSON line
per seed, with the program's gap and ``correct`` and, on a control seed,
the control's; the lower reading is the largest program gap, the upper the
smallest control gap. Not run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}

    bm, wl, c, mix, lim = run.prepare(args.workload, args.rehearse)
    try:
        devices, compiles = run.setup_jax(wl["chips"], args.rehearse)
    except run.NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 2
    from harness.fleet import Fleet
    pcfg, c = run.program_config(c, args.rehearse)
    mesh = run.serving_mesh(c)
    prompt = c["vision"]["num_tokens"] + int(mix["instruction_tokens"])
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        W = run.make_weights(pcfg, c, seed, mesh)
        fleet = Fleet(mix, (c["vision"]["num_tokens"],
                            c["vision"]["embed_dim"]), c["vocab_size"], seed)
        eng = run.build_engine(pcfg, c, W, fleet.robots, mesh)
        run.warm_shapes(eng, prompt, c["d_model"], fleet.pool[0])
        run.warm_engine(eng, fleet)
        w = run.serve_window(eng, fleet, args.seconds, False, compiles, None)
        del eng
        gc.collect()
        checks, attempted, failed, rd = run.judge(
            W, c, fleet, w, lim, seed, control=seed in ctrl)
        gap = float(rd["gap"].max())
        served = dict(checks, max_logit_gap=dict(checks["max_logit_gap"],
                                                 value=gap))
        row = {"seed": seed, "gap": gap, "correct": run.decide(served),
               "control_gap": None, "control_correct": None,
               "steps": len(fleet.counted()), "attempted": attempted,
               "failed": failed, "served_tokens": rd["served_tokens"],
               "reference_s": rd["reference_s"],
               "limit": checks["max_logit_gap"]["limit"],
               "seconds": time.perf_counter() - t0}
        if seed in ctrl:
            row.update(control_gap=float(rd["control_gap"].max()),
                       control_correct=run.decide(checks))
        out.append(row)
        print(json.dumps(row), flush=True)
        del W, fleet, w
        gc.collect()
    gaps = [r["gap"] for r in out]
    cg = [r["control_gap"] for r in out if r["control_gap"] is not None]
    print(json.dumps({"lower": max(gaps), "upper": min(cg) if cg else None,
                      "seeds": len(out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
