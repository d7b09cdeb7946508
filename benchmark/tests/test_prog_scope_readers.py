"""The readers of the program roots' device time (``prog_scopes.py``,
``prog_scope_share.py``): on a small synthetic capture whose ops carry the
``op_name`` paths the program writes, on the recorded TPU fixture (a program
WITHOUT root scopes: the parent commit), and on no capture at all."""

import gzip
import importlib
import json
import re
import shutil

import pytest
from conftest import BENCH, FIXTURES, ROOT

# (op_name as the compiler composes it, start ns, duration ns) of one chip's op line
OPS = [
    ("jit(_prefill_fn)/prog.prefill/while/body/closed_call/mla.read/pallas_call", 0, 300),
    ("jit(_prefill_fn)/prog.prefill/while/body/closed_call/moe.experts/pallas_call", 300, 500),
    ("jit(sample_batched)/prog.sample/reduce_max", 800, 50),
    ("jit(_copy_slot)/prog.pool/dynamic_update_slice", 900, 100),
    # a decode window: the `while` encloses its body's ops and keeps what they leave
    ("jit(_decode_fn)/prog.decode/while", 1000, 3000),
    ("jit(_decode_fn)/prog.decode/while/body/closed_call/ssm.step/pallas_call", 1100, 1000),
    ("jit(_decode_fn)/prog.decode/while/body/prog.sample/reduce_max", 2200, 200),
    ("cond/branch_1_fun/reduce_sum", 2500, 100),  # a private function: no root in its path
]


def write_capture(path, ops):
    """An XSpace with one TPU plane whose `XLA Ops` events carry ``ops``' paths
    as the `tf_op` stat of their metadata, as a `/debug/profile` capture does."""
    try:
        from tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for k, (op_name, start_ns, dur_ns) in enumerate(ops, start=1):
        meta = plane.event_metadata[k]
        meta.id, meta.name = k, f"%fusion.{k} = f32[8] fusion()"
        meta.stats.add(metadata_id=1, str_value=op_name)
        line.events.add(metadata_id=k, offset_ps=start_ns * 1000, duration_ps=dur_ns * 1000)
    path.write_bytes(space.SerializeToString())


@pytest.fixture
def home():
    """A cell's directory under .bench_home, where run.py leaves a capture."""
    cell = "test-prog-scope-readers"
    path = ROOT / ".bench_home" / cell
    path.mkdir(parents=True, exist_ok=True)
    yield cell, path
    shutil.rmtree(path)


def test_the_roots_pattern_books_an_op_under_the_first_root_of_its_path():
    pattern = re.compile(importlib.import_module("prog_scopes").PATTERN)
    assert [m.group(1) if (m := pattern.search(op)) else None for op, _, _ in OPS] == [
        "prog.prefill", "prog.prefill", "prog.sample", "prog.pool", "prog.decode",
        "prog.decode", "prog.decode", None]
    # and the older readers' patterns still find their scopes behind a root
    assert re.search(importlib.import_module("scope_common").PATTERN, OPS[5][0]).group(1) == "ssm.step"
    joyai = re.compile(importlib.import_module("joyai_scopes").PATTERN)
    assert [joyai.search(op).group(1) for op, _, _ in OPS[:2]] == ["mla.read", "moe.experts"]
    assert not any(p.search("prog.decode") for p in (
        joyai, re.compile(importlib.import_module("scope_common").PATTERN)))


def test_prog_scope_share_reads_a_capture_with_roots(home):
    pytest.importorskip("tensorflow")
    cell, path = home
    write_capture(path / "profile.xplane.pb", OPS)
    read = importlib.import_module("prog_scope_share").read
    ctx = {"cell": {"name": cell}, "trace": {"busy_s": 1.0}}
    share = read(ctx, {"pattern": r"^prog\.(prefill|sample)$"})
    # busy: [0, 850) + [900, 4000) ns; prefill 800 + the admission's sample 50
    assert share == pytest.approx(100.0 * 850 / 3950)
    red = ctx["_scope_reduce_prog"]  # reduced once, kept for the next reader
    assert red["busy_s"] == pytest.approx(3950e-9)
    assert red["scopes"] == pytest.approx({
        "prog.prefill": 800e-9, "prog.sample": 50e-9, "prog.pool": 100e-9,
        "prog.decode": 2900e-9})  # 3000 - the 100 ns of the op without a root
    assert red["events"] == 8 and red["matched_events"] == 7
    (path / "profile.xplane.pb").unlink()
    assert read(ctx, {"pattern": r"^prog\.decode$"}) == pytest.approx(100.0 * 2900 / 3950)
    assert read(ctx, {"pattern": r"^prog\.verify$"}) is None  # no such program ran


def test_a_program_without_root_scopes_reads_nothing_and_nothing_raises(home):
    """The parent commit under this PR's benchmark files: its capture has no
    `prog.` path, and the reader returns None (the line leaves the metric out)."""
    pytest.importorskip("tensorflow")
    cell, path = home
    read = importlib.import_module("prog_scope_share").read
    params = json.loads((BENCH / "layer_metrics" / "device.prefill_time_share.json").read_text())["params"]
    (path / "profile.xplane.pb").write_bytes(
        gzip.decompress((FIXTURES / "tiny_tpu.xplane.pb.gz").read_bytes()))
    ctx = {"cell": {"name": cell}, "trace": {"busy_s": 1.0}}
    assert read(ctx, params) is None and ctx["_scope_reduce_prog"]["scopes"] == {}
    # no capture on disk, no trace, a capture that cannot be parsed
    assert read({"cell": {"name": "no-such-cell"}, "trace": {"busy_s": 1.0}}, params) is None
    assert read({"cell": {"name": cell}, "trace": None}, params) is None
    (path / "profile.xplane.pb").write_bytes(b"not a capture")
    assert read({"cell": {"name": cell}, "trace": {"busy_s": 1.0}}, params) is None


def test_the_two_prefill_time_share_metrics_name_the_reader_and_both_scopes():
    for name in ("device.prefill_time_share", "long.device.prefill_time_share"):
        spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert spec["reader"] == "prog_scope_share" and spec["layer"] == "device"
        rx = re.compile(spec["params"]["pattern"])
        assert [s for s in ("prog.prefill", "prog.sample", "prog.decode", "prog.pool",
                            "prog.verify") if rx.search(s)] == ["prog.prefill", "prog.sample"]
