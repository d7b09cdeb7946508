"""trace_reduce.py: the interval arithmetic on hand-made events, and the whole
reduction on a small capture recorded on the TPU v5e (fixtures/)."""

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
import trace_op_share
import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny_tpu.xplane.pb.gz"


def ev(start, end, name):
    return (float(start), float(end), name)


def test_busy_time_is_the_union_and_gaps_lie_between():
    events = sorted([ev(0, 10, "a"), ev(5, 12, "b"), ev(20, 30, "c"), ev(22, 25, "d")],
                    key=lambda e: (e[0], -e[1]))
    busy, gaps = trace_reduce.union_seconds(events)
    assert busy == pytest.approx(22e-9) and gaps == [(12.0, 20.0)]
    assert trace_reduce.union_seconds([]) == (0.0, [])


def test_an_enclosing_op_keeps_only_what_its_children_leave():
    events = sorted([ev(0, 100, "while"), ev(10, 40, "fusion"), ev(50, 70, "custom-call"),
                     ev(55, 60, "inner"), ev(200, 210, "fusion")], key=lambda e: (e[0], -e[1]))
    ops = trace_reduce.self_seconds(events)
    assert ops["while"] == pytest.approx(50e-9)       # 100 - 30 - 20
    assert ops["custom-call"] == pytest.approx(15e-9)  # 20 - 5
    assert ops["fusion"] == pytest.approx(40e-9) and ops["inner"] == pytest.approx(5e-9)
    assert sum(ops.values()) == pytest.approx(110e-9)  # = the union: nothing counted twice


def test_host_label_takes_the_innermost_frame_that_is_not_parked():
    lines = [[ev(0, 100, "$threading.py:1 run"), ev(10, 50, "$scheduler.py:9 _admit"),
              ev(20, 30, "$time sleep")]]
    assert trace_reduce.host_label(lines, 25.0) == "$scheduler.py:9 _admit"
    assert trace_reduce.host_label(lines, 500.0) == "unattributed"


def test_hlo_lines_become_short_names_and_op_share_matches_them():
    hlo = ('%closed_call.25 = bf16[4,2,8,16]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[4,8]{1,0} '
           '%get-tuple-element.1444, bf16[2,73,16,16]{3,2,1,0} %copy.67), custom_call_target="tpu_custom_call"')
    assert trace_reduce.short_name(hlo) == "closed_call.25 custom-call:tpu_custom_call"
    assert trace_reduce.short_name("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x)") == "all-reduce.7 all-reduce"
    assert trace_reduce.short_name("jit__prefill_fn(318)") == "jit__prefill_fn(318)"
    trace = {"busy_s": 2.0, "ops": {"closed_call.25 custom-call:tpu_custom_call": 0.5,
                                    "custom-call.14 custom-call:AllocateBuffer": 0.25,
                                    "all-reduce.7 all-reduce": 0.25, "copy.2 copy": 1.0}}
    assert trace_op_share.read({"trace": trace}, {"pattern": "tpu_custom_call"}) == pytest.approx(25.0)
    assert trace_op_share.read({"trace": trace}, {"pattern": "all-reduce|all-gather"}) == pytest.approx(12.5)
    assert trace_op_share.read({"trace": None}, {"pattern": "x"}) is None


def test_recorded_tpu_capture_reduces_to_sane_numbers(tmp_path):
    raw = tmp_path / "tiny_tpu.xplane.pb"
    raw.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, trace_reduce.__file__, str(raw)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    red = json.loads(proc.stdout.splitlines()[-1])
    assert red["chips"] == 1 and 0.0 < red["busy_s"] <= red["window_s"] < 1.0
    kernel = trace_op_share.matched_seconds(red, "tpu_custom_call")
    assert 0.0 < kernel < red["busy_s"]  # the ragged kernel's events are found by their target
    # self times partition the busy time: no op is counted twice
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert len(red["device_ops"]) == 10 and red["device_ops"][0][1] >= red["device_ops"][9][1]
    assert 1 <= len(red["idle_gaps"]) <= 10 and all(g[1] > 0 for g in red["idle_gaps"])
