"""The readers this configuration brought: scope time from a capture's raw
XSpace (on the recorded TPU fixture), and the counter / gauge shares."""

import gzip
import importlib
import json

import pytest
from conftest import BENCH, FIXTURES


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "tiny_tpu.xplane.pb"
    path.write_bytes(gzip.decompress((FIXTURES / "tiny_tpu.xplane.pb.gz").read_bytes()))
    return path


def test_scope_reduce_books_self_time_by_op_name(xplane):
    pytest.importorskip("tensorflow")
    scope_reduce = importlib.import_module("scope_reduce")
    trace_reduce = importlib.import_module("trace_reduce")
    red = scope_reduce.reduce(str(xplane), r"(jit\(_take\)|while)")
    # the same union of op intervals as trace_reduce's, from the raw message
    assert red["busy_s"] == pytest.approx(trace_reduce.reduce(str(xplane))["busy_s"], rel=1e-3)
    assert red["events"] == 8242 and 0 < red["matched_events"] < red["events"]
    assert set(red["scopes"]) == {"jit(_take)", "while"}
    assert sum(red["scopes"].values()) <= red["busy_s"] * 1.001  # self times never overlap
    # a program without the scopes (the parent commit): nothing booked, no error
    none = scope_reduce.reduce(str(xplane), r"(ssm\.[a-z_]+)")
    assert none["scopes"] == {} and none["matched_events"] == 0


def test_trace_scope_share_and_roofline_read_the_cached_reduction():
    share = importlib.import_module("trace_scope_share").read
    ctx = {"_scope_reduce": {"busy_s": 2.0, "scopes": {"ssm.step": 0.5, "ssm.in_proj": 0.3}}}
    assert share(ctx, {"pattern": r"^ssm\."}) == pytest.approx(40.0)
    assert share(ctx, {"pattern": r"^ssm\.(step|scan)$"}) == pytest.approx(25.0)
    assert share({"_scope_reduce": {"busy_s": 2.0, "scopes": {}}}, {"pattern": r"^ssm\."}) is None
    assert share({"_scope_reduce": None}, {"pattern": r"^ssm\."}) is None
    # no capture on disk and no trace: both readers find nothing and do not raise
    roofline = importlib.import_module("ssm_roofline").read
    bare = {"cell": {"name": "no-such-cell"}, "trace": None, "profile": None, "config": {}}
    assert share(dict(bare), {"pattern": r"^ssm\."}) is None
    assert roofline(dict(bare), {"pattern": r"^ssm\."}) is None


def test_prom_delta_share_and_gauge_share():
    share = importlib.import_module("prom_delta_share").read
    pad, real = ('bee2bee_engine_ssm_scan_tokens_total{kind="pad"}',
                 'bee2bee_engine_ssm_scan_tokens_total{kind="real"}')
    params = {"part": pad, "whole": "bee2bee_engine_ssm_scan_tokens_total"}
    ctx = {"m0": {pad: 100.0, real: 300.0}, "m1": {pad: 150.0, real: 450.0}}
    assert share(ctx, params) == pytest.approx(25.0)
    assert share({"m0": {}, "m1": {"bee2bee_other_total": 1.0}}, params) is None  # the parent
    hbm = importlib.import_module("gauge_poll_max_of_hbm").read
    peaks = json.loads((BENCH / "peaks.json").read_text())
    ctx = {"polls": [(0.0, {"bee2bee_engine_state_bytes": 0.8e9}),
                     (1.0, {"bee2bee_engine_state_bytes": 1.6e9})],
           "peaks": peaks, "device": {"kind": "TPU v5 lite"}}
    assert hbm(ctx, {"gauge": "bee2bee_engine_state_bytes"}) == pytest.approx(10.0)
    assert hbm({**ctx, "polls": [(0.0, {"x": 1.0})]}, {"gauge": "bee2bee_engine_state_bytes"}) is None
    assert hbm({**ctx, "device": {"kind": "cpu", "platform": "cpu"}},
               {"gauge": "bee2bee_engine_state_bytes"}) is None  # a CPU rehearsal
    with pytest.raises(KeyError):
        hbm({**ctx, "device": {"kind": "TPU v9"}}, {"gauge": "bee2bee_engine_state_bytes"})
