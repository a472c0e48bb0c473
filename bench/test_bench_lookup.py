"""Cells, configurations, mixes, limits and metrics are found by name, and
BENCHMARK.json keeps the benchmark's rules (CPU)."""
import json
import re

import pytest

from bench import harness

SPEC = harness.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_finds_its_files():
    for cell in SPEC["workloads"]:
        assert harness.find_cell(SPEC, cell["name"]) is cell
        cfg = harness.read_json(harness.BENCH / "configs" / f"{cell['config']}.json")
        mix = harness.read_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
        limits = harness.read_json(harness.BENCH / "limits" / f"{cell['name']}.json")
        assert cfg["served_dtype"] and limits
        assert (harness.BENCH / "drivers" / f"{mix['driver']}.py").exists()
    with pytest.raises(KeyError):
        harness.find_cell(SPEC, "no-such-cell")


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        mod = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_metrics_of_a_cell():
    serve = harness.metrics_for(SPEC, "serve-qwen3-1.7b-longprompt", "end_to_end")
    assert [m["name"] for m in serve] == ["serve_tokens_per_s", "setup_s"]
    relayout = harness.metrics_for(SPEC, "relayout-qwen3-1.7b-kv", "per_layer")
    assert "kernels.relayout_roofline" in [m["name"] for m in relayout]


def test_names_units_and_references():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {c["name"]: c for c in SPEC["workloads"]}
    configs = {c["name"]: c for c in SPEC["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (harness.CHECKOUT / c["file"]).exists()
    for c in cells.values():
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert NAME.match(c["traffic"]) and 0 < len(c["why"]) <= 200
        reported = [m["name"] for m in harness.metrics_for(SPEC, c["name"], "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_for(SPEC, c["name"], "per_layer")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_unknown_device_has_no_peaks():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
