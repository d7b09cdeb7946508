"""run.py end to end on the CPU backend, at a tiny preset, from a temp copy
that adds a configuration, mixes, cells and a per-layer metric as new files."""

import json

import pytest
from conftest import run_cell

E2E = {"ttft_p50_ms", "ttft_p90_ms", "tok_s", "request_p50_ms", "setup_s"}
COUNTERS = {"gateway.overhead_mean_ms", "engine.queue_wait_mean_ms", "engine.prefill_mean_ms",
            "engine.compiles_in_window", "sched.step_mean_ms", "sched.batch_fill_mean",
            "pool.used_peak_share", "gap_p90_ms", "engine.e2e_mean_ms"}


@pytest.mark.parametrize("cell,trace", [("tiny-closed", 0), ("tiny-open", 0), ("tiny-closed", 1)])
def test_rehearsal_runs_end_to_end(tree, cell, trace):
    rc, line, lines, err = run_cell(tree, "--workload", cell, "--seed", "3000000001",
                                    "--seconds", "3", "--trace", str(trace), "--rehearse-on-cpu")
    assert rc == 0, err[-2000:]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        # the new per-layer metric came from a new file and an existing reader;
        # a CPU run prints no metric whose source is the device trace
        assert set(line["metrics"]) == COUNTERS
        assert "busy_s" not in line["device"] and "breakdown" not in line
    else:
        assert set(line["metrics"]) == E2E
        assert all(m["value"] > 0 for m in line["metrics"].values())
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert phases["correctness"]["ok"] is True  # reference vs served, byte classes
    assert phases["correctness"]["decode_checked"] >= 4
    if cell == "tiny-open":
        assert phases["window"]["generator_late_ms"]["max"] < 250.0
    else:
        assert line["correct"] is True


def test_four_virtual_chips_run_the_tp4_server_flags(tree):
    """The mistral-7b-tp4 file's server flags (--mesh-shape model:4, attention
    auto) at the tiny-mistral size on four virtual CPU devices: a wrong mesh
    flag or sharding rule costs no chip time. The reference child gathers the
    sharded weights one layer at a time and must agree with the served path."""
    real = json.loads((tree / "benchmark/configs/mistral-7b-tp4.json").read_text())
    tiny = json.loads((tree / "benchmark/configs/tiny-mistral-tp4.json").read_text())
    assert tiny["server"]["flags"] == real["server"]["flags"]
    assert tiny["server"]["mesh_shape"] == real["server"]["mesh_shape"]
    rc, line, lines, err = run_cell(tree, "--workload", "tiny-tp4", "--seed", "5",
                                    "--seconds", "3", "--trace", "0", "--rehearse-on-cpu")
    assert rc == 0, err[-2000:]
    assert line["device"]["count"] == 4 and line["correct"] is True


def test_without_the_rehearsal_flag_a_cpu_run_fails_its_device_check(tree):
    rc, line, lines, err = run_cell(tree, "--workload", "tiny-closed", "--seed", "1",
                                    "--seconds", "1", "--trace", "0")
    assert rc != 0 and line is None
    assert "platform" in err


def test_a_directory_without_the_program_gives_no_result(tree):
    (tree / "bee2bee_tpu").unlink()
    rc, line, lines, err = run_cell(tree, "--workload", "tiny-closed", "--seed", "1",
                                    "--seconds", "1", "--trace", "0", "--rehearse-on-cpu")
    assert rc != 0 and not lines
