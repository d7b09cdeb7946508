"""BENCHMARK.json against the rules of its contract that a file can break."""

import json
import re

import pytest
from conftest import BENCH, ROOT

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and M["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    cells = len(M["workloads"])
    # a full check with the full 24 cells must fit 43200 s
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, cells // 4)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_name_files_of_their_own_with_the_reduced_keys():
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]) and LINE.match(c["why"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("benchmark/") and conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head|expert", key)
        assert conf["chips"] in (1, 4) and conf["kv"] and conf["reference"]["tolerance"] > 0
        assert any(w["config"] == c["name"] for w in M["workloads"])


def test_cells_name_a_config_and_a_mix_once():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and LINE.match(w["why"])
        conf = next(c for c in M["configs"] if c["name"] == w["config"])
        assert json.loads((ROOT / conf["file"]).read_text())["chips"] == w["chips"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()


def _cells_of(metric):
    return set(metric.get("workloads", [w["name"] for w in M["workloads"]]))


def test_metrics_have_their_files_bounds_and_cells():
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
        assert (BENCH / "e2e_metrics" / f"{m['name']}.json").is_file()
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
        assert (spec["layer"], spec["unit"], spec["moves"]) == (m["layer"], m["unit"], m["moves"])
        # the metric it should move is reported in every cell where this one is
        assert _cells_of(m) <= _cells_of(e2e[m["moves"]])
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for w in M["workloads"]:
        have_e2e = [m for m in M["end_to_end"] if w["name"] in _cells_of(m)]
        assert len(have_e2e) >= 2 and any(w["name"] in _cells_of(m) for m in M["per_layer"])


@pytest.mark.parametrize("path", sorted(p for p in BENCH.rglob("*") if p.is_file()
                                        and "__pycache__" not in p.parts))
def test_file_names_use_the_characters_of_a_name(path):
    assert re.fullmatch(r"[A-Za-z0-9_./-]+", str(path.relative_to(ROOT)))
