"""BENCHMARK.json against the benchmark's naming rules, and every name it
uses against the files that the harness finds by it."""
import json
import re

import pytest

from bench import harness

B = harness.load_benchmark()
BENCH = harness.BENCH
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def names():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in B[sec]:
            yield sec, e["name"]
    for w in B["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in B["configs"]:
        for k in c["reduced"]:
            yield "reduced", k


@pytest.mark.parametrize("sec,name", list(names()))
def test_name_characters(sec, name):
    assert NAME.fullmatch(name), (sec, name)


@pytest.mark.parametrize("m", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(m):
    assert UNIT.fullmatch(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    cells = {w["name"] for w in B["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_unique_names():
    for sec in ("configs", "workloads"):
        ns = [e["name"] for e in B[sec]]
        assert len(ns) == len(set(ns))
    ns = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(ns) == len(set(ns))
    assert "setup_s" in ns


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    cfg = next(c for c in B["configs"] if c["name"] == w["config"])
    assert (harness.ROOT / cfg["file"]).is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    cfg = json.loads((harness.ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"]
    assert cfg["source"] == c["source"] and len(c["source"]) <= 200
    assert cfg["reduced"] == c["reduced"]
    for k in c["reduced"]:
        assert k in cfg and k in cfg["published"]


def test_command_and_paths():
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024
