"""Benchmark harness — one module per paper table/figure plus the roofline
report and measured microbenchmarks. Prints ``name,us_per_call,derived``.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig3,...]
    PYTHONPATH=src python -m benchmarks.run --list

Every module carries a ``DESCRIPTION`` (one line: what it measures and what
it gates) surfaced by ``--list`` — the same text docs/benchmarks.md expands
on, so the tool and the docs can't drift apart silently.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (claims_check, decode_microbench, engine_bench,
                        fig2_phase_latency, fig3_control_frequency,
                        frontend_bench, kv_cache_bench, perf_compare,
                        roofline_report, scheduler_bench, sharded_bench,
                        spec_decode_bench, table1_hardware)
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "claims": claims_check,
    "fig2": fig2_phase_latency,
    "table1": table1_hardware,
    "fig3": fig3_control_frequency,
    "roofline": roofline_report,
    "perf": perf_compare,
    "micro": decode_microbench,
    "engine": engine_bench,
    "kv_cache": kv_cache_bench,
    "scheduler": scheduler_bench,
    "frontend": frontend_bench,
    "spec_decode": spec_decode_bench,
    "sharded": sharded_bench,
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="")
    p.add_argument("--list", action="store_true",
                   help="print each benchmark's name and DESCRIPTION, "
                        "then exit")
    args = p.parse_args()
    if args.list:
        width = max(len(k) for k in MODULES)
        for key, mod in MODULES.items():
            desc = getattr(mod, "DESCRIPTION", None) or next(
                iter((mod.__doc__ or "").strip().splitlines()), "")
            print(f"{key:<{width}}  {desc}")
        return
    selected = args.only.split(",") if args.only else list(MODULES)
    enable_compile_cache()

    rows = []

    def emit(name: str, us_per_call: float, derived: str = ""):
        rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.3f},{derived}", flush=True)

    print("name,us_per_call,derived")
    failed = []
    for key in selected:
        try:
            MODULES[key].run(emit)
        except Exception:
            failed.append(key)
            traceback.print_exc()
    if failed:
        print(f"# FAILED modules: {failed}", file=sys.stderr)
        sys.exit(1)
    print(f"# {len(rows)} rows from {len(selected)} modules")


if __name__ == "__main__":
    main()
