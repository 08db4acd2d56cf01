"""Every entry of ``BENCHMARK.json`` finds its files by name, and the file
keeps to the benchmark's contract on names, units and keys."""
import re

from harness import spec

BM = spec.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


def test_configs_found_by_name():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"])
        f = spec.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert f["source"] == c["source"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")) and key in f


def test_workloads_found_by_name():
    configs = {c["name"] for c in BM["configs"]}
    pairs = set()
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = spec.traffic(w["traffic"])
        assert mix["name"] == w["traffic"] and mix["loop"] == "closed"
        lim = spec.limits(w["name"])
        assert lim["sample_steps"] >= 1 and lim["max_logit_gap"] > 0


def test_metrics_found_by_name():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m["workloads"] if "workloads" in m else cells) <= cells
    for m in BM["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert callable(spec.metric_reader(m["name"]))
        for w in m.get("workloads", []):
            moved = e2e[m["moves"]]
            assert w in cells and w in moved.get("workloads", cells)
    for w in BM["workloads"]:
        assert spec.end_to_end_metrics(BM, w)
        assert spec.per_layer_metrics(BM, w)
