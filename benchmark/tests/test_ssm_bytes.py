"""ssm_bytes.py against hand-worked cases at the published Falcon-H1-34B sizes."""

import json

import pytest
from conftest import BENCH
from kv_bytes import min_seconds
from ssm_bytes import decode_step, prefill_scan, state_bytes_per_row

STATE = json.loads((BENCH / "configs" / "falcon-h1-34b-6l.json").read_text())["state"]
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_a_rows_state_is_25_megabytes():
    # 6 layers x 32 heads x 128 x 256 x 4 B = 25,165,824; conv 6 x 3 x 5120 x 2 B = 184,320
    assert state_bytes_per_row(STATE) == 25_165_824 + 184_320
    assert STATE["ssm_bytes_per_row"] == 25_165_824 and STATE["conv_bytes_per_row"] == 184_320


def test_a_decode_step_reads_and_writes_the_state_once():
    nbytes, flops = decode_step(STATE)
    assert nbytes == 2 * 25_350_144
    assert flops == 5 * 6 * 32 * 128 * 256
    # 64 rows a step: 3.24 GB, 3.96 ms at 819 GB/s, memory-bound by far
    secs, bound = min_seconds([(nbytes, flops)] * 64, PEAK)
    assert bound == "memory" and secs == pytest.approx(64 * 2 * 25_350_144 / 819e9)
    assert secs == pytest.approx(3.96e-3, rel=0.01)


def test_a_prefill_scan_counts_its_tokens_and_the_state_once():
    nbytes, flops = prefill_scan(256, STATE)
    per_token = (2 * 4096 + 2 * 512 + 32) * 4  # x and y, B and C, dt, float32
    assert nbytes == 6 * 256 * per_token + 2 * 25_350_144
    # per token and layer: 2 Q (G N + H P) + 4 H P N with Q = 128
    assert flops == 6 * 256 * (2 * 128 * (512 + 4096) + 4 * 32 * 128 * 256)
    # a prompt shorter than the chunk scans one chunk of its own length
    assert prefill_scan(32, STATE)[1] == 6 * 32 * (2 * 32 * 4608 + 4 * 1_048_576)
