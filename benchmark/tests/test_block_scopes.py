"""The readers of the block's parts (PR 56): the regex that books an op under
a part of the program's table, the dense stack's bytes from a configuration's
published keys, and the three readers on a context with and without a capture."""

import gzip
import importlib
import json
import re
import shutil

import pytest
from conftest import BENCH, FIXTURES

block = importlib.import_module("block_scopes")
dense_bytes = importlib.import_module("dense_bytes")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("op_name, part", [
    ("jit(_decode_fn)/jit(main)/prog.decode/while/body/attn.qkv/dot_general", "attn.qkv"),
    ("jit(f)/prog.verify/while/body/spec.verify/while/body/moe.experts/gmm", "moe.experts"),
    ("jit(f)/prog.verify/mtp.block/attn.read/attn.read", "attn.read"),  # a wrapper books inside
    ("jit(f)/prog.decode/moe.shared/mlp.down/dot_general", "moe.shared"),  # the FIRST part
    ("jit(f)/prog.prefill/attn.write/kv.write/kv.write", "attn.write"),
    ("jit(f)/prog.decode/while/body/sample.draw/argmax", "sample.draw"),
    ("jit(f)/prog.decode/while/body/dynamic_slice", ""),  # no part: under nothing
    ("jit(f)/prog.decode/xattn.qkv/attn.qkvs/add", ""),  # a part is a whole path step
    ("", ""),
])
def test_an_op_is_booked_under_the_first_part_of_its_path(op_name, part):
    assert block.part_of(op_name) == part
    # scope_reduce's own booking with the file's regex: the part, or for an op
    # without one its whole path (what the line lists as unnamed)
    m = re.compile(block.PATTERN).search(op_name)
    label = (m.group(1) if m.groups() else m.group(0)) if m else ""
    assert label == (part or op_name)
    got = block.split({"busy_s": 1.0, "scopes": {label: 0.25} if label else {}})
    assert got["parts"] == ({part: 0.25} if part else {})
    assert got["unnamed"] == ({op_name: 0.25} if op_name and not part else {})


def test_the_readers_parts_are_the_programs_table():
    tracing = pytest.importorskip("bee2bee_tpu.tracing")
    table = getattr(tracing, "DEVICE_PARTS", None)
    if table is None:
        pytest.skip("a program from before the table")
    assert set(block.PARTS) == set(table)
    assert not set(tracing.DEVICE_WRAPPERS) & set(block.PARTS)


def test_dense_bytes_from_the_published_keys():
    phi3, h1 = _config("phi-3-mini-4k"), _config("falcon-h1-34b-6l")
    assert dense_bytes.layer_bytes(phi3) == (4 * 3072 ** 2 + 3 * 3072 * 8192) * 2 == 226_492_416
    assert dense_bytes.head_bytes(phi3) == 32_064 * 3_072 * 2 == 197_001_216
    assert dense_bytes.head_bytes(h1) == 261_120 * 5_120 * 2 == 2_673_868_800
    # falcon-h1: q and o 20 x 128 wide, k and v 4 x 128 (GQA), no mixer matrix
    assert dense_bytes.attention_bytes(h1) == 5120 * 2 * (20 * 128 + 4 * 128) * 2
    assert dense_bytes.loop_of(h1) == {
        "passes": 1, "layers": 6, "layer_bytes": 723_517_440,
        "head_bytes": 2_673_868_800, "dtype_bytes": 2}
    assert dense_bytes.loop_of(phi3, head=False)["head_bytes"] == 0
    assert dense_bytes.loop_of(phi3)["layers"] == 32


def _ctx(tmp_cell="no-such-cell"):
    return {"cell": {"name": tmp_cell, "chips": 1}, "trace": None, "profile": None,
            "config": _config("phi-3-mini-4k"), "records": [], "m0": {}, "m1": {},
            "t0": 0.0, "t1": 10.0, "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((BENCH / "peaks.json").read_text())}


@pytest.mark.parametrize("reader, params", [
    ("block_scopes", {}),
    ("block_scopes", {"pattern": r"^head\."}),
    ("dense_weight_roofline", {"pattern": r"^mlp\.", "passes": "bee2bee_engine_loop_passes_total",
                               "prefill_tokens": 'bee2bee_engine_prefill_tokens_total{kind="real"}'}),
    ("attn_read_roofline", {"pattern": r"^attn\.read$"}),
])
def test_a_reader_finds_nothing_without_a_capture_and_does_not_raise(reader, params):
    read = importlib.import_module(reader).read
    assert read(_ctx(), params) is None
    # ... nor with a reduction that holds no part (the parent's program)
    empty = dict(_ctx(), **{block.KEY: {"busy_s": 2.0, "parts": {}, "unnamed": {"a/b": 1.0}}})
    assert read(empty, params) is None
    # ... nor with parts but no counters, records or profile header to place them
    some = dict(_ctx(), **{block.KEY: {"busy_s": 2.0, "unnamed": {}, "parts": {
        "mlp.down": 0.5, "attn.read": 0.25}}}, trace={"window_s": 4.0, "busy_s": 2.0, "ops": {}})
    if reader != "block_scopes":
        assert read(some, params) is None


def test_shares_and_the_weights_roofline_read_the_cached_reduction(capsys):
    got = {"busy_s": 4.0, "unnamed": {"jit(f)/prog.decode/add": 0.1}, "parts": {
        "attn.qkv": 0.4, "attn.out": 0.2, "mlp.gate_up": 0.8, "mlp.down": 0.4,
        "head.logits": 0.1, "attn.read": 0.5, "norm.block": 0.3, "sample.draw": 0.1}}
    ctx = dict(_ctx(), **{block.KEY: got})
    assert block.read(ctx, {}) == pytest.approx(100.0 * 2.8 / 4.0)  # the named share
    weights = r"^(attn\.(qkv|out)|mlp\.|head\.)"
    assert block.read(ctx, {"pattern": weights}) == pytest.approx(100.0 * 1.9 / 4.0)
    assert block.read(ctx, {"pattern": r"^mtp\."}) is None
    # 100 decode steps of 16 rows and 10 prefill calls over 1,280 positions in
    # the traced interval (here the whole window)
    import time
    now = time.time()
    dec, pre = ('bee2bee_engine_loop_passes_total{kind="decode"}',
                'bee2bee_engine_loop_passes_total{kind="prefill"}')
    tok = 'bee2bee_engine_prefill_tokens_total{kind="real"}'
    ctx.update(trace={"window_s": 4.0, "busy_s": 4.0, "ops": {}},
               profile={"header": {"ts": now, "duration_s": 4.0}}, t0=0.0, t1=4.0,
               m0={dec: 0.0, pre: 0.0, tok: 0.0}, m1={dec: 100.0, pre: 10.0, tok: 1280.0})
    params = {"pattern": weights, "passes": "bee2bee_engine_loop_passes_total",
              "prefill_tokens": tok}
    value = importlib.import_module("dense_weight_roofline").read(ctx, params)
    peak = ctx["peaks"]["TPU v5 lite"]
    step = 32 * 226_492_416 + 197_001_216  # a call's bytes: memory-bound at these rows
    assert value == pytest.approx(100.0 * 110 * step / peak["hbm_bytes_per_s"] / 1.9, rel=1e-6)
    line = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if '"dense_weight_roofline"' in ln][-1]
    assert line["bound_by"] == "memory" and line["layers"] == 32


def test_attn_read_roofline_is_kv_rooflines_least_time_over_the_part(monkeypatch):
    kv = importlib.import_module("kv_roofline")
    seen = {}

    def fake(ctx, params):
        seen.update(ops=ctx["trace"]["ops"], window=ctx["trace"]["window_s"], params=params)
        return 41.0

    monkeypatch.setattr(kv, "read", fake)
    got = {"busy_s": 4.0, "unnamed": {}, "parts": {"attn.read": 0.5, "attn.write": 0.1}}
    ctx = dict(_ctx(), **{block.KEY: got}, trace={"window_s": 4.0, "ops": {"x": 1.0}},
               profile={"header": {}})
    read = importlib.import_module("attn_read_roofline").read
    assert read(ctx, {"pattern": r"^attn\.read$"}) == 41.0
    assert seen == {"ops": got["parts"], "window": 4.0, "params": {"pattern": r"^attn\.read$"}}
    assert ctx["trace"]["ops"] == {"x": 1.0}  # the run's own trace is left as it was


def test_one_reduction_a_run_on_a_recorded_capture(tmp_path, monkeypatch, capsys):
    """On the recorded TPU fixture (a program with no part at all): ONE child
    run, kept in the context; everything lands under ``unnamed`` and the
    ``block_scopes`` line says so with the seconds the reduction took."""
    pytest.importorskip("tensorflow")
    home = tmp_path / ".bench_home" / "a-cell"
    home.mkdir(parents=True)
    (home / "profile.xplane.pb").write_bytes(
        gzip.decompress((FIXTURES / "tiny_tpu.xplane.pb.gz").read_bytes()))
    bench = tmp_path / "benchmark"
    bench.mkdir()
    for f in ("scope_reduce.py", "trace_reduce.py"):
        shutil.copy(BENCH / f, bench / f)
    monkeypatch.setattr(block, "BENCH", bench)
    ctx = dict(_ctx("a-cell"), trace={"window_s": 1.0, "busy_s": 1.0})
    got = block.scopes(ctx)
    assert got["parts"] == {} and got["unnamed"] and got["busy_s"] > 0
    assert block.scopes(ctx) is got and block.read(ctx, {}) is None
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if '"block_scopes"' in ln]
    assert len(lines) == 1 and lines[0]["named_s"] == 0 and lines[0]["reduce_s"] > 0
    assert len(lines[0]["unnamed"]) <= block.LISTED


def test_a_traced_rehearsal_reads_the_delivery_share_and_no_device_metric(tree):
    """``run.py`` end to end on the CPU with this PR's entries in the manifest:
    ``sched.delivery_hidden_share`` comes from the counter the program has had
    since PR 40 (a delivery with a window or a burst in flight is hidden), and a
    CPU run prints none of the new device-trace metrics."""
    from conftest import run_cell

    rc, line, lines, err = run_cell(tree, "--workload", "tiny-closed", "--seed", "3000000056",
                                    "--seconds", "3", "--trace", "1", "--rehearse-on-cpu")
    assert rc == 0, err[-2000:]
    assert 0.0 < line["metrics"]["sched.delivery_hidden_share"]["value"] <= 100.0
    new = {"device.named_time_share", "phi3.weights.time_share", "phi3.weights.stream_roofline",
           "phi3.attn.read_roofline", "h1.weights.time_share", "h1.head.time_share"}
    assert not new & set(line["metrics"])
    assert not [ln for ln in lines if '"error"' in ln and "block_scopes" in ln]
