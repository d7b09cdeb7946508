"""CPU rehearsal of the benchmark's own files (python -m pytest benchmark/tests -q)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(BENCH), str(BENCH / "readers")]


@pytest.fixture
def tree(tmp_path):
    """A temp copy of BENCHMARK.json + benchmark/ beside the real package,
    extended WITH NEW FILES ONLY: a configuration, two mixes, three cells and
    a per-layer metric with an existing reader. No copied file is edited but
    the manifest, which a PR extends by adding entries."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bee2bee_tpu").symlink_to(ROOT / "bee2bee_tpu")
    for name in ("tiny-llama.json", "tiny-mistral-tp4.json"):
        shutil.copy(FIXTURES / name, root / "benchmark" / "configs" / name)
    for name in ("tiny-closed.json", "tiny-open.json"):
        shutil.copy(FIXTURES / name, root / "benchmark" / "traffic" / name)
    shutil.copy(FIXTURES / "engine.e2e_mean_ms.json",
                root / "benchmark" / "layer_metrics" / "engine.e2e_mean_ms.json")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = ["tiny-closed", "tiny-open", "tiny-tp4"]
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in entry and not entry["name"].startswith("long."):
            entry["workloads"] += cells
    manifest["configs"] += [
        {"name": n, "source": "test preset", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "CPU rehearsal"} for n in ("tiny-llama", "tiny-mistral-tp4")]
    manifest["workloads"] += [
        {"name": "tiny-closed", "config": "tiny-llama", "traffic": "tiny-closed", "chips": 1, "why": "t"},
        {"name": "tiny-open", "config": "tiny-llama", "traffic": "tiny-open", "chips": 1, "why": "t"},
        {"name": "tiny-tp4", "config": "tiny-mistral-tp4", "traffic": "tiny-closed", "chips": 4, "why": "t"},
    ]
    manifest["per_layer"].append(
        {"name": "engine.e2e_mean_ms", "unit": "ms", "better": "lower", "source": "program_counter",
         "layer": "engine", "moves": "ttft_p50_ms", "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return root


def run_cell(root: Path, *args: str, timeout: float = 300.0):
    """(return code, parsed last stdout line or None, all stdout lines)."""
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    proc = subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            pass
    return proc.returncode, last, lines, proc.stderr
