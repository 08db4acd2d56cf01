"""The benchmark's data, found by name.

``BENCHMARK.json`` at the checkout's root names the cells; everything that
belongs to one configuration, traffic mix, cell's limits or per-layer metric
is a file of its own under ``bench/``:

    bench/configs/<config>.json    sizes, engine flags, source, cuts
    bench/traffic/<traffic>.json   parameters of the closed-loop fleet
    bench/limits/<workload>.json   the limits of the output comparison
    bench/metrics/<metric>.py      ``read(run) -> float | None``

Adding a cell, a mix or a metric adds files and entries; no code changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{workload_name}.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def _applies(entry: dict, wl: dict, end_to_end: dict) -> bool:
    if "workloads" in entry:
        return wl["name"] in entry["workloads"]
    moved = end_to_end.get(entry.get("moves"))
    return moved is None or "workloads" not in moved \
        or wl["name"] in moved["workloads"]


def end_to_end_metrics(bm: dict, wl: dict) -> list:
    return [m for m in bm["end_to_end"] if _applies(m, wl, {})]


def per_layer_metrics(bm: dict, wl: dict) -> list:
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    return [m for m in bm["per_layer"] if _applies(m, wl, e2e)]


def metric_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
