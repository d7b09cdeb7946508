"""The falcon-h1 cell's own files at tiny size on the CPU: run.py boots
``tiny-falcon-h1``, the forking reference (``reference_falcon_h1.py``) decides
``correct``, the counter readers read the mixer's counters, and the
reference's perturbations come out NOT correct against the same served text."""

import json
import shutil
import subprocess
import sys

import pytest
from conftest import FIXTURES, run_cell

COUNTER_METRICS = {"ssm.scan_pad_share"}  # state.bytes_share needs a device's memory


@pytest.fixture
def h1_tree(tree):
    shutil.copy(FIXTURES / "tiny-falcon-h1.json", tree / "benchmark/configs/tiny-falcon-h1.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-falcon-h1", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-falcon-h1.json", "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-h1", "config": "tiny-falcon-h1",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if "h1-decode-wide-closed" in entry.get("workloads", []):
            entry["workloads"].append("tiny-h1")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(h1_tree):
    rc, line, lines, err = run_cell(h1_tree, "--workload", "tiny-h1", "--seed", "3000000011",
                                    "--seconds", "3", "--trace", "1", "--rehearse-on-cpu")
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert COUNTER_METRICS <= set(got)
    assert 0.0 < got["ssm.scan_pad_share"] < 100.0 and "state.bytes_share" not in got
    # the same served text against a reference that drops a multiplier: not correct
    job_path = h1_tree / ".bench_home/tiny-h1/reference_job.json"
    job = json.loads(job_path.read_text())
    job["perturb"] = {"drop_multiplier": "ssm_out_multiplier"}
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_falcon_h1.py", str(job_path)],
                          cwd=h1_tree, capture_output=True, text=True, timeout=300,
                          env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 1 and res["ok"] is False and res["worst_margin"] > res["tolerance"]
