"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
has its file: configuration, traffic, limits and per-layer reader."""
import json
import re

import pytest

from bench import generator, harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(SPEC) == KEYS
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and _text_ok(conf["source"])
    assert _text_ok(conf["why"]) and conf["file"].startswith("bench/")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert data["reduced"] == conf["reduced"]
    generator_file = f"{data['system']['generator']}.py"
    assert (ROOT / "bench" / "reference" / "systems"
            / generator_file).exists()
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and _text_ok(cell["why"])
    c = harness.load_cell(cell["name"])
    assert c.traffic["kind"] in generator.KINDS
    assert c.limits and all(v > 0 for v in c.limits.values())
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer


def test_cells_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in SPEC["end_to_end"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert _text_ok(metric["layer"])
        moves = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
        for w in metric["workloads"]:
            assert w in moves.get("workloads", cells)
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").exists()


def test_peaks_name_the_v5e():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 1.97e14
    assert peaks["bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
