"""The granite cell's own files: the count file of an expert SHARE by hand, every
new reader on a fixture and on an empty context (None, never an exception), the
manifest's new entries, the configuration file against the catalog's row, and
the cell at tiny size on the CPU: run.py boots ``tiny-granite`` (m m a m m, 4 of
8 experts held), ``reference_granite.py`` decides ``correct``, each
ONE-thing-wrong reference comes out NOT correct against the same served text,
and a server that does not know the model (the PARENT's tree) fails fast and
leaves the other cells' lines untouched. Written so that entries a later PR
appends do not break it."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import moe_bytes
import moe_share_bytes
import ssm_bytes

CELL = "granite-decode-wide-closed"
NAME = "granite-4.0-h-small-10l-e36"
NEW = ["granite.ssm.time_share", "granite.ssm.state_roofline", "granite.state.bytes_share",
       "granite.moe.time_share", "granite.moe.experts_roofline", "granite.moe.experts_hit_share",
       "granite.moe.load_max", "granite.moe.here_share", "granite.attn.time_share"]
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
PERTURBED = [{"drop": "residual_multiplier"}, {"drop": "shared_expert"}, {"expert_first": 0},
             {"attention_at": 1}, {"activation_dtype": "float8_e4m3fn"}]
HIT = "bee2bee_engine_moe_experts_hit_total"
CALLS = "bee2bee_engine_moe_layer_calls_total"
HERE = 'bee2bee_engine_moe_assignments_total{kind="live"}'
AWAY = 'bee2bee_engine_moe_assignments_total{kind="elsewhere"}'


def test_share_work_by_hand():
    moe = CONF["moe"]
    assert moe_share_bytes.expert_bytes(moe) == 3 * 4096 * 768 * 2 == 18_874_368 == moe["expert_bytes"]
    assert moe_share_bytes.shared_bytes(moe) == 3 * 4096 * 1536 * 2 == 37_748_736 == moe["shared_bytes"]
    # ONE decode step of 64 live rows: 10 layer calls, every one of 10 x 36 held
    # experts hit, 6,400 assignments of which half lie here
    nbytes, flops = moe_share_bytes.share_work(360, 10, 3200, 3200, moe)
    assert nbytes == 360 * 18_874_368 + 10 * 37_748_736 == 7_172_259_840
    assert nbytes / 819e9 == pytest.approx(0.008757, rel=1e-3)  # 8.8 ms: memory-bound
    # 3,200 products of 9.44 M weights here + the shared expert on 640 token-layers
    assert flops == 2 * (3200 * 9_437_184 + 640 * 18_874_368) == 84_557_168_640
    assert flops / 197e12 < nbytes / 819e9
    # moe_bytes.py on the same counts would take 320 tokens for 640 and a shared
    # expert half as wide: 18 % fewer bytes for the shared part, the flops short
    old = moe_bytes.expert_work(360, 10, 3200, moe)
    assert old[0] == (360 + 10) * 18_874_368 < nbytes and old[1] < flops
    assert moe_share_bytes.here_share(3200, 3200) == 50.0
    assert moe_share_bytes.here_share(0, 0) is None
    assert moe_share_bytes.shared_bytes(dict(moe, n_shared_experts=0)) == 0.0


def test_the_state_section_counts_nine_layers():
    st = CONF["state"]
    row = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert ssm_bytes.state_bytes_per_row(st) == row == 38_204_928
    assert st["ssm_bytes_per_row"] + st["conv_bytes_per_row"] == row
    nbytes, flops = ssm_bytes.decode_step(st)
    assert nbytes == 2 * row and flops == 5 * 9 * 128 * 64 * 128
    assert 64 * row == pytest.approx(2.445e9, rel=1e-3)  # 2.45 GB at 64 rows
    assert CONF["kv"]["n_layers"] == 1 and CONF["kv"]["bytes_per_token"] == 2 * 8 * 128 * 2 == 4096


def test_the_configuration_file_keeps_the_catalog_row_and_the_issues_letter():
    row = CONF
    assert (row["hidden_size"], row["num_hidden_layers"], row["num_attention_heads"],
            row["num_key_value_heads"], row["intermediate_size"], row["shared_intermediate_size"],
            row["vocab_size"], row["num_local_experts"], row["num_experts_per_tok"],
            row["mamba_n_heads"], row["mamba_d_head"], row["mamba_d_state"], row["mamba_n_groups"],
            row["mamba_d_conv"], row["mamba_expand"], row["mamba_chunk_size"]) == (
                4096, 40, 32, 8, 768, 1536, 100352, 72, 10, 128, 64, 128, 1, 4, 2, 256)
    assert (row["attention_multiplier"], row["embedding_multiplier"], row["logits_scaling"],
            row["residual_multiplier"], row["rms_norm_eps"]) == (0.0078125, 12, 16, 0.22, 1e-05)
    assert row["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert row["model_type"] == "granitemoehybrid" and row["position_embedding_type"] == "nope"
    assert row["tie_word_embeddings"] is True
    assert row["reduced"] == ["layers", "num_local_experts", "max_position_embeddings"]
    assert (row["layers"], row["layers_published"], row["num_local_experts_held"],
            row["max_position_embeddings"]) == (10, 40, 36, 2048)
    srv = row["server"]["config_json"]
    assert (srv["max_seq_len"], srv["max_batch_size"], srv["kv_block_size"],
            srv["kv_pool_blocks"]) == (2048, 64, 16, 3200)
    mix = json.loads((BENCH / "traffic" / "decode-wide-closed.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] + 32
    assert srv["kv_pool_blocks"] >= 64 * -(-longest // 16) + 1
    assert row["server"]["env"]["BEE2BEE_ADMISSION"]["max_concurrent"] == mix["callers"] == 96
    assert (row["state"]["n_layers"], row["kv"]["n_layers"], row["moe"]["n_expert_layers"]) == (9, 1, 10)
    assert (row["moe"]["n_experts"], row["moe"]["n_experts_held"], row["moe"]["experts_per_token"],
            row["moe"]["d_ff"], row["moe"]["d_ff_shared"]) == (72, 36, 10, 768, 1536)
    ref = row["reference"]
    assert ref["module"] == "reference_granite" and 0 < ref["mean_margin_limit"] < ref["tolerance"]
    assert set(row["assumed"]) >= {"weights", "router", "router_precision", "state_dtype",
                                   "tokenizer", "intermediate_size"}


def test_the_program_s_preset_says_what_the_file_says():
    sys.path[:0] = [str(ROOT)]
    import reference_granite as plain
    from bee2bee_tpu.models.config import get_config

    want = plain.dims_of_preset(get_config(NAME))
    assert {k: CONF[k] for k in want} == want
    assert list(get_config(NAME).layer_types) == CONF["layer_types"][:CONF["layers"]]
    assert plain.layer_plan(CONF)[4:7] == [("mamba", 4), ("attention", 0), ("mamba", 5)]
    assert plain.layer_plan(CONF, {"attention_at": 4})[4:7] == [
        ("attention", 0), ("mamba", 4), ("mamba", 5)]


def _empty_ctx(config):
    from loadgen import percentile

    return {"cell": {"name": "no-such-cell", "chips": 1}, "config": config, "mix": {},
            "client": {"ttft_ms": [], "gap_ms": [], "tokens": 0.0, "attempted": 0, "failed": 0,
                       "errors": [], "request_ms": []},
            "records": [], "t0": 0.0, "t1": 1.0, "setup_s": 0.0, "m0": {}, "m1": {},
            "polls": [], "profile": None, "trace": None,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
            "peaks": json.loads((BENCH / "peaks.json").read_text()), "percentile": percentile}


def _read(spec, ctx):
    sys.path[:0] = [str(BENCH)]
    import run as bench_run

    return bench_run.read_metric("layer_metrics", spec, ctx)


def _bare(config):
    return {k: v for k, v in config.items() if k not in ("moe", "state")}


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_none_from_an_empty_run(spec):
    """No scrape, no poll, no trace, a configuration without ``moe`` / ``state``:
    what a program without the scopes and the counters gives. None, never a raise."""
    assert _read(spec, _empty_ctx(_bare(CONF))) is None
    assert _read(spec, _empty_ctx(CONF)) is None


SCOPES = {"ssm.in_proj": 0.5, "ssm.conv": 0.05, "ssm.step": 0.7, "ssm.state_write": 0.02,
          "ssm.out_proj": 0.25, "attn.qkv": 0.02, "attn.write": 0.01, "attn.read": 0.02,
          "attn.out": 0.02, "moe.router": 0.05, "moe.dispatch": 0.1, "moe.experts": 1.3,
          "moe.combine": 0.1, "moe.shared": 0.2, "head.logits": 0.15}


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold the counters the new readers ask for, whose
    capture reduced to ``scopes`` and whose client saw one stream decode through
    the traced interval; a TPU's device record."""
    from loadgen import Record, Spec

    ctx = _empty_ctx(config)
    # a 51 s window: 1,800 decode steps of 64 rows, every held expert hit
    m1 = {HIT: 1800 * 360.0, CALLS: 1800 * 10.0, HERE: 1800 * 3100.0, AWAY: 1800 * 3300.0,
          "bee2bee_engine_state_bytes": 2.445e9,
          "bee2bee_engine_moe_expert_load_max": 2.1,
          'bee2bee_engine_hbm_bytes{component="state"}': 2.445e9,
          'bee2bee_engine_hbm_bytes{component="params"}': 9.93e9}
    now = time.monotonic()
    rec = Record(Spec(64, 128, "x", "mix"), now - 30.0, now - 30.0,
                 events=[(now - 20.0 + 0.5 * i, "x" * 32) for i in range(40)],
                 t_end=now, tokens=40 * 32)
    ctx.update(m0={}, m1=m1, t0=now - 40.0, t1=now + 11.0, polls=[(now - 10.0, m1)],
               records=[rec], device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0},
               profile={"header": {"ts": time.time() - 12.0, "duration_s": 4.0}},
               _scope_reduce={"busy_s": 3.9, "scopes": scopes},
               _scope_reduce_moe_mla={"busy_s": 3.9, "scopes": scopes},
               _scope_reduce_attn_moe={"busy_s": 3.9, "scopes": scopes})
    return ctx


def test_the_fixture_names_the_scope_readers_own_keys():
    import joyai_scopes
    import scope_common
    import st_scopes

    assert joyai_scopes.KEY == "_scope_reduce_moe_mla" and st_scopes.KEY == "_scope_reduce_attn_moe"
    ctx = _counted_ctx(CONF, SCOPES)
    assert scope_common.scopes(ctx)["scopes"] is SCOPES


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    full = _read(spec, _counted_ctx(CONF, SCOPES))
    assert full is not None and 0 < full <= 100.0, (spec, full)
    if spec.endswith(("time_share", "roofline")):
        other = _counted_ctx(CONF, {"mla.read": 1.0, "mlp.down": 0.2, "kv.write": 1.0})
        assert _read(spec, other) is None  # another model's capture: none of these scopes
    no_counters = _counted_ctx(CONF, SCOPES)
    no_counters.update(m0={}, m1={}, polls=[], records=[])
    assert _read(spec, no_counters) is None or spec.endswith("time_share")
    # the PARENT's server prints no kind="elsewhere": the share's readers read nothing
    parent = _counted_ctx(CONF, SCOPES)
    parent["m1"] = {k: v for k, v in parent["m1"].items() if k != AWAY}
    if spec in ("granite.moe.experts_roofline", "granite.moe.here_share"):
        assert _read(spec, parent) is None
    needs_section = spec.endswith(("roofline", "hit_share", "here_share"))
    assert (_read(spec, _counted_ctx(_bare(CONF), SCOPES)) is None) == needs_section


def test_the_share_roofline_is_the_counters_work_over_the_scopes_time():
    ctx = _counted_ctx(CONF, SCOPES)
    got = _read("granite.moe.experts_roofline", ctx)
    share = 4.0 / 51.0  # the traced interval's share of the window
    nbytes, flops = moe_share_bytes.share_work(
        1800 * 360 * share, 1800 * 10 * share, 1800 * 3100 * share, 1800 * 3300 * share,
        CONF["moe"])
    least = max(nbytes / PEAK["hbm_bytes_per_s"], flops / PEAK["bf16_flops_per_s"])
    assert got == pytest.approx(100.0 * least / (1.3 + 0.2), rel=1e-6)
    assert _read("granite.moe.experts_hit_share", ctx) == pytest.approx(100.0)
    assert _read("granite.moe.here_share", ctx) == pytest.approx(100.0 * 3100 / 6400)
    assert _read("granite.moe.time_share", ctx) == pytest.approx(100.0 * 1.75 / 3.9)
    assert _read("granite.ssm.time_share", ctx) == pytest.approx(100.0 * 1.52 / 3.9)
    assert _read("granite.attn.time_share", ctx) == pytest.approx(100.0 * 0.07 / 3.9)
    assert _read("granite.moe.load_max", ctx) == pytest.approx(2.1)


def test_the_manifest_gains_one_configuration_one_cell_and_nine_metrics():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in M["configs"] if c["name"] == NAME)
    assert conf["reduced"] == ["layers", "num_local_experts", "max_position_embeddings"]
    assert conf["file"] == f"benchmark/configs/{NAME}.json"
    assert conf["source"] == CONF["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json")
    cells = [w for w in M["workloads"] if w["config"] == conf["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "decode-wide-closed", 1)]
    assert all(len(w["why"]) <= 200 for w in M["workloads"]) and len(conf["why"]) <= 200
    assert not any(w["chips"] == 4 for w in M["workloads"])
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "tok_s"
        assert (BENCH / "layer_metrics" / f"{name}.json").is_file()
    names = [m["name"] for m in M["per_layer"]]
    assert [n for n in names if n.startswith("granite.")] == NEW  # in this order
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    assert {"tok_s", "sched.step_mean_ms", "device.idle_share", "sched.window_steps_mean"} <= mine
    # ``ttft_p50_ms`` is NOT reported here (its twelve first seeds spread by 5.8 %
    # against half its bound, 4 %: PERF.md section 6), so no metric that moves it is
    h1 = {m["name"] for m in M["per_layer"] if "h1-decode-wide-closed" in m.get("workloads", ())
          and m["moves"] == "tok_s" and not m["name"].startswith(("ssm.", "h1.", "state."))}
    assert h1 <= mine  # every shared list the other cell of this traffic is on
    assert "ttft_p50_ms" not in mine and not {
        m["name"] for m in M["per_layer"] if m["name"] in mine and m["moves"] != "tok_s"}
    assert not {n for n in mine if "kernel.ragged" in n or n.startswith(
        ("joyai.", "st.", "long.", "ssm.", "h1.", "ouro.", "state."))}
    assert "request_p50_ms" not in mine and "ttft_p90_ms" not in mine
    assert all("setup_s" == m["name"] or "workloads" in m for m in M["end_to_end"])


@pytest.fixture
def granite_tree(tree):
    shutil.copy(FIXTURES / "tiny-granite.json", tree / "benchmark/configs/tiny-granite.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-granite", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-granite.json",
                                "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-granite-cell", "config": "tiny-granite",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-granite-cell")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_granite.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=900,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(granite_tree):
    rc, line, lines, err = run_cell(granite_tree, "--workload", "tiny-granite-cell", "--seed",
                                    "3000000051", "--seconds", "3", "--trace", "1",
                                    "--rehearse-on-cpu", timeout=900.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert phases["boot"]["kv"]["cache_layers"] == 1
    assert phases["boot"]["kv"]["bytes_per_token"] == 2 * 2 * 16 * 2  # a bf16 pool, ONE layer
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["mean_margin"] <= ref["mean_margin_limit"]
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    # the counters' readers read; a CPU run prints no device-trace metric
    assert {"sched.step_mean_ms", "engine.prefill_calls_per_s", "granite.moe.experts_hit_share",
            "granite.moe.here_share", "granite.moe.load_max"} <= got
    assert 0 < line["metrics"]["granite.moe.here_share"]["value"] < 100
    assert 0 < line["metrics"]["granite.moe.experts_hit_share"]["value"] <= 100
    assert not {n for n in NEW if n.endswith(("time_share", "roofline"))} & got
    job_path = granite_tree / ".bench_home/tiny-granite-cell/reference_job.json"
    for perturb in PERTURBED:
        rc, res = _reference(granite_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False, (perturb, res)


def test_a_server_that_does_not_know_the_model_fails_fast_and_alone(granite_tree):
    """The PARENT's tree on the new cell: ``serve-tpu --model <unknown>`` exits
    at once, run.py reports it (``Server.check_alive("booting")``), prints no
    result line, leaves no process, and another cell of the same manifest runs
    as before."""
    conf_path = granite_tree / "benchmark/configs/tiny-granite.json"
    conf = json.loads(conf_path.read_text())
    conf["server"]["model"] = "no-such-hybrid-model"
    conf_path.write_text(json.dumps(conf))
    t = time.monotonic()
    rc, line, lines, err = run_cell(granite_tree, "--workload", "tiny-granite-cell", "--seed", "1",
                                    "--seconds", "1", "--trace", "0", "--rehearse-on-cpu")
    assert rc != 0 and line is None and time.monotonic() - t < 60.0
    assert "server child exited" in err and "no model config matches" in err
    left = subprocess.run(["pgrep", "-af", "no-such-hybrid-model"], capture_output=True, text=True)
    assert not [ln for ln in left.stdout.splitlines() if "pgrep" not in ln]
    rc, line, lines, err = run_cell(granite_tree, "--workload", "tiny-closed", "--seed",
                                    "3000000002", "--seconds", "3", "--trace", "0",
                                    "--rehearse-on-cpu")
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err[-2000:]
