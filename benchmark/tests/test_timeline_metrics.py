"""The per-layer metrics that read the program's request timeline and the
scheduler's phase counter: the ``prom_delta_rate`` reader alone, and every
new ``program_counter`` metric on the line of a traced CPU rehearsal."""

import importlib

import pytest
from conftest import run_cell

TIMELINE = {"gateway.ttft_mean_ms", "gateway.admission_wait_mean_ms", "gateway.dispatch_mean_ms",
            "sched.first_text_mean_ms", "service.holdback_mean_ms", "gateway.write_mean_ms"}
PHASES = {"sched.admit_time_share", "sched.fetch_wait_share"}
SEGMENTS = (TIMELINE - {"gateway.ttft_mean_ms"}) | {"engine.queue_wait_mean_ms",
                                                    "engine.prefill_mean_ms"}


def test_prom_delta_rate_takes_one_series_by_its_full_key():
    read = importlib.import_module("prom_delta_rate").read
    fetch = 'bee2bee_engine_phase_seconds_total{phase="fetch"}'
    admit = 'bee2bee_engine_phase_seconds_total{phase="admit"}'
    ctx = {"t0": 100.0, "t1": 150.0,
           "m0": {fetch: 10.0, admit: 1.0}, "m1": {fetch: 50.0, admit: 6.0}}
    assert read(ctx, {"metric": fetch, "scale": 100}) == pytest.approx(80.0)
    assert read(ctx, {"metric": admit, "scale": 100}) == pytest.approx(10.0)
    # a bare name sums its label sets; no scale gives seconds a second
    assert read(ctx, {"metric": "bee2bee_engine_phase_seconds_total"}) == pytest.approx(0.9)
    # a series first seen at the window's end counts from zero
    assert read({**ctx, "m0": {}}, {"metric": admit}) == pytest.approx(0.12)
    # a program without the counter (the parent commit): nothing to read
    assert read({**ctx, "m1": {"bee2bee_other_total": 1.0}}, {"metric": fetch}) is None


def test_traced_rehearsal_reports_the_timeline_and_the_phase_shares(tree):
    rc, line, lines, err = run_cell(tree, "--workload", "tiny-closed", "--seed", "3000000007",
                                    "--seconds", "3", "--trace", "1", "--rehearse-on-cpu")
    assert rc == 0, err[-2000:]
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(got) >= TIMELINE | PHASES
    assert all(m["unit"] == "ms" for n, m in line["metrics"].items() if n in TIMELINE)
    assert all(got[name] >= 0.0 for name in TIMELINE)
    assert 0.0 < got["sched.admit_time_share"] + got["sched.fetch_wait_share"] <= 100.0
    # the same requests' segments make up their time to the first byte; the two
    # the scheduler observes at admission may differ by the window's edges
    assert sum(got[name] for name in SEGMENTS) == pytest.approx(
        got["gateway.ttft_mean_ms"], rel=0.25)
