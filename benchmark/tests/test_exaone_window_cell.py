"""PR 55's one addition to the K-EXAONE cell: the per-layer metric
``exaone.spec.window_steps_mean`` (the verify steps of a verify WINDOW, histogram
``engine.window_steps``, the reader the benchmark had: ``prom_delta_mean``). Its
manifest entry and data file, the reader on a fixture and on a server that prints
no such histogram (the PARENT's in this cell: None, never a raise), and the cell at
tiny size on the CPU: the traced line holds EVERY per-layer metric that lists the
cell, the new name among them. A file of its own: this PR edits no file the
benchmark had (``test_exaone_cell.py``'s own list of the cell's nine names stays as
PR 54 wrote it and no longer equals the manifest's: a ``benchmark`` PR's to extend)."""

import json
import shutil
import sys

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

CELL = "exaone-decode-wide-closed"
NAME = "exaone.spec.window_steps_mean"
HIST = "bee2bee_engine_window_steps"


def test_the_manifest_lists_the_metric_for_the_exaone_cell_alone():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = M["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "steps", "better": "higher",
                     "source": "program_counter", "layer": "speculation", "moves": "tok_s",
                     "workloads": [CELL]}
    spec = json.loads((BENCH / "layer_metrics" / f"{NAME}.json").read_text())
    assert spec == {"layer": "speculation", "unit": "steps", "moves": "tok_s",
                    "reader": "prom_delta_mean", "params": {"metric": HIST}}
    # the scheduler's own list is as the parent's: the cell is not on it
    steps = next(m for m in M["per_layer"] if m["name"] == "sched.window_steps_mean")
    assert CELL not in steps["workloads"]
    assert {m["layer"] for m in M["per_layer"] if m["name"].startswith("exaone.spec.")} == {
        "speculation"}


def test_the_reader_reads_the_histograms_growth_and_none_from_the_parents_server():
    sys.path[:0] = [str(BENCH)]
    import run as bench_run

    ctx = {"m0": {f"{HIST}_sum": 320.0, f"{HIST}_count": 10.0},
           "m1": {f"{HIST}_sum": 320.0 + 60 * 30.5, f"{HIST}_count": 70.0}}
    assert bench_run.read_metric("layer_metrics", NAME, ctx) == pytest.approx(30.5)
    # the parent's server in this cell: every step a serialized verify, no window
    assert bench_run.read_metric("layer_metrics", NAME, {"m0": {}, "m1": {}}) is None
    flat = {"m0": ctx["m0"], "m1": ctx["m0"]}
    assert bench_run.read_metric("layer_metrics", NAME, flat) is None


@pytest.fixture
def exaone_tree(tree):
    shutil.copy(FIXTURES / "tiny-exaone.json", tree / "benchmark/configs/tiny-exaone.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-exaone", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-exaone.json",
                                "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-exaone-cell", "config": "tiny-exaone",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-exaone-cell")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def test_the_rehearsals_traced_line_holds_every_listed_metric_with_the_new_name_in(
        exaone_tree, tmp_path, monkeypatch):
    # a compile cache of its own: a second boot of one model on one source would load
    # the first's stored CPU executables, which this machine's XLA does not always run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    rc, line, lines, err = run_cell(exaone_tree, "--workload", "tiny-exaone-cell", "--seed",
                                    "3000000054", "--seconds", "3", "--trace", "1",
                                    "--rehearse-on-cpu", timeout=900.0)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    M = json.loads((exaone_tree / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in M["per_layer"] if "tiny-exaone-cell" in m.get("workloads", ())
              and m["source"] != "device_trace"}
    assert NAME in listed
    # (the CPU's dense reader copies no pages: the run-page counters stay at 0)
    assert listed - set(got) == {"pool.run_page_share"}
    # every decode step of the cell is a verify step of a verify window: at least a
    # step, at most the server's decode_chunk; no decode window, so the scheduler's
    # own reading of the same histogram is off this cell's lists and its line
    assert 1.0 <= got[NAME]["value"] <= 32.0 and "sched.window_steps_mean" not in got
    assert got["exaone.spec.tokens_per_step"]["value"] >= 1.0
    # a window's wall time over its steps = ms a verify step
    assert got["sched.step_mean_ms"]["value"] > 0
