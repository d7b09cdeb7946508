"""The K-EXAONE cell's own files: the byte files on its shapes by hand, every new
reader on a fixture and on an empty context (None, never an exception), the
manifest's new entries, the configuration file against the catalog's row, and the
cell at tiny size on the CPU: run.py boots ``tiny-exaone`` (L(dense) L L G L + an
MTP layer, 4 of 16 experts held, a sliced vocabulary) with its own drafter on,
``reference_exaone.py`` decides ``correct`` and compares the MTP layer's draft
logits, each ONE-thing-wrong reference comes out NOT correct against the same
served text, and a server that does not know the model (the PARENT's tree) fails
fast. Written so that entries a later PR appends do not break it."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import kv_mixed_bytes
import moe_share_bytes

CELL = "exaone-decode-wide-closed"
NAME = "k-exaone-236b-a23b-5l-e16"
NEW = ["exaone.mtp.time_share", "exaone.mtp.accept_share", "exaone.spec.tokens_per_step",
       "exaone.moe.time_share", "exaone.moe.experts_roofline", "exaone.moe.here_share",
       "exaone.attn.time_share", "exaone.attn.read_roofline",
       "exaone.pool.behind_window_share"]
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
PERTURBED = [{"window_off": 1}, {"window_off": 4}, {"mtp_window": 4}, {"mtp_token": "current"},
             {"rope_global": True}, {"drop": "qk_norm"},
             {"drop": "routed_scaling_factor"}, {"drop": "norm_topk_prob"},
             {"drop": "shared_expert"}, {"expert_first": 8}, {"dense_as_sparse": True},
             {"activation_dtype": "float8_e4m3fn"}]
HIT = "bee2bee_engine_moe_experts_hit_total"
CALLS = "bee2bee_engine_moe_layer_calls_total"
HERE = 'bee2bee_engine_moe_assignments_total{kind="live"}'
AWAY = 'bee2bee_engine_moe_assignments_total{kind="elsewhere"}'
DRAFTED = 'bee2bee_engine_spec_drafted_total{tier="mtp"}'
ACCEPTED = 'bee2bee_engine_spec_accepted_total{tier="mtp"}'
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_a_verify_step_s_weights_by_hand():
    moe = CONF["moe"]
    assert moe_share_bytes.expert_bytes(moe) == 3 * 6144 * 2048 * 2 == 75_497_472 == moe["expert_bytes"]
    assert moe_share_bytes.shared_bytes(moe) == 75_497_472 == moe["shared_bytes"]
    # ONE verify step of 64 live rows: 5 expert-layer calls (4 trunk, the MTP
    # block's), every one of 5 x 16 held experts hit, 64 x 2 x 8 x 5 assignments
    # of which an eighth lie here
    nbytes, flops = moe_share_bytes.share_work(80, 5, 640, 4480, moe)
    assert nbytes == (80 + 5) * 75_497_472 == 6_417_285_120
    assert nbytes / 819e9 == pytest.approx(0.007836, rel=1e-3)  # 7.8 ms: memory-bound
    assert flops / 197e12 < nbytes / 819e9
    assert moe_share_bytes.here_share(640, 4480) == 12.5
    # the rest of a step's weights: six attention blocks, the dense MLP, W_eh, the head
    attn = (6144 * 8192 * 2 + 2 * 6144 * 1024) * 2
    rest = 6 * attn + 3 * 6144 * 18432 * 2 + CONF["mtp"]["eh_proj_bytes"] + 6144 * 19200 * 2
    assert (nbytes + rest) / 1e9 == pytest.approx(8.85, abs=0.08)  # ISSUE 54's 8.85 GB a step


def test_the_kv_section_counts_six_cache_layers_of_two_kinds():
    kv = CONF["kv"]
    assert kv_mixed_bytes.kinds_of(kv) == [(4, 128), (2, None)]
    assert kv["bytes_per_token"] == 6 * 2 * 8 * 128 * 2 == 24576
    nbytes, _ = kv_mixed_bytes.decode_token(256, kv)
    assert nbytes == (4 * 128 + 2 * 256) * 2 * 8 * 128 * 2
    held, behind = kv_mixed_bytes.held_behind_window(256, kv)
    assert (held, behind) == (6 * 256, 4 * 129)  # a third of a 256-token row is dead
    assert 3200 * 16 * kv["bytes_per_token"] == pytest.approx(1.258e9, rel=1e-3)


def test_the_configuration_file_keeps_the_catalog_row_and_the_issues_letter():
    row = CONF
    assert (row["hidden_size"], row["num_hidden_layers"], row["num_attention_heads"],
            row["num_key_value_heads"], row["head_dim"], row["intermediate_size"],
            row["moe_intermediate_size"], row["vocab_size"], row["num_experts"],
            row["num_experts_per_tok"], row["num_shared_experts"], row["sliding_window"],
            row["first_k_dense_replace"], row["num_nextn_predict_layers"]) == (
                6144, 48, 64, 8, 128, 18432, 2048, 153600, 128, 8, 1, 128, 1, 1)
    assert (row["routed_scaling_factor"], row["scoring_func"], row["norm_topk_prob"],
            row["n_group"], row["topk_group"], row["rms_norm_eps"]) == (
                2.5, "sigmoid", True, 1, 1, 1e-05)
    assert row["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 12
    assert row["sliding_windows"] == [128, 128, 128, 0] * 12
    assert row["model_type"] == "exaone_moe" and row["tie_word_embeddings"] is False
    assert row["reduced"] == ["layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert set(row["reduced_why"]) == set(row["reduced"])
    assert (row["layers"], row["layers_published"], row["num_experts_held"], row["expert_first"],
            row["vocab_size_held"], row["max_position_embeddings"]) == (5, 48, 16, 0, 19200, 2048)
    if CATALOG.is_file():  # every number of the catalog's config under its own key
        cat = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if json.loads(ln)["name"] == "K-EXAONE-236B-A23B")
        assert row["source"] == cat["source_url"]
        differs = {k for k, v in cat["config"].items() if row.get(k) != v}
        assert differs == {"max_position_embeddings"}
    srv = row["server"]["config_json"]
    assert (srv["max_seq_len"], srv["max_batch_size"], srv["kv_block_size"],
            srv["kv_pool_blocks"], srv["spec_tokens"], srv["spec_min_accept"]) == (
                2048, 64, 16, 3200, 1, 0)
    mix = json.loads((BENCH / "traffic" / "decode-wide-closed.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] + 32
    assert srv["kv_pool_blocks"] >= 64 * -(-longest // 16) + 1
    assert row["server"]["env"]["BEE2BEE_ADMISSION"]["max_concurrent"] == mix["callers"] == 96
    assert (row["kv"]["n_layers"], row["moe"]["n_expert_layers"], row["mtp"]["layers"]) == (6, 5, 1)
    ref = row["reference"]
    assert ref["module"] == "reference_exaone" and 0 < ref["mean_margin_limit"] < ref["tolerance"]
    assert 0 < ref["long_margin_limit"] < ref["tolerance"]
    assert 0 < ref["mtp_margin_limit"] < ref["tolerance"] and 0 < ref["window_read_limit"] < 1
    assert set(row["assumed"]) >= {"qk_norm", "nope_global", "norms", "router_bias", "mtp",
                                   "weights", "acceptance", "router_precision", "tokenizer"}
    assert "modeling_exaone4.py" in row["assumed"]["qk_norm"]  # the family's own code
    assert "same file" in row["assumed"]["nope_global"] and "same file" in row["assumed"]["norms"]
    assert "96" in row["deployment"] and "eight" in row["deployment"].lower()


def test_the_program_s_preset_says_what_the_file_says():
    sys.path[:0] = [str(ROOT)]
    import reference_exaone as plain
    from bee2bee_tpu.models.config import get_config

    want = plain.dims_of_preset(get_config(NAME))
    have = plain.dims_of_file(CONF)
    assert {k: have[k] for k in want} == want
    plan = plain.layer_plan(CONF)
    assert [(p["group"], p["index"], p["window"], p["rope"]) for p in plan] == [
        ("dense_layers", 0, 128, True), ("layers", 0, 128, True), ("layers", 1, 128, True),
        ("layers", 2, 0, False), ("layers", 3, 128, True)]
    assert plain.layer_plan(CONF, {"window_off": 1})[1]["window"] == 0
    assert all(p["rope"] for p in plain.layer_plan(CONF, {"rope_global": True}))
    tiny = json.loads((FIXTURES / "tiny-exaone.json").read_text())
    want = plain.dims_of_preset(get_config("tiny-exaone"))
    assert {k: plain.dims_of_file(tiny)[k] for k in want} == want


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
    return {k: v for k, v in config.items() if k not in ("moe", "kv")}


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_none_from_an_empty_run(spec):
    """No scrape, no poll, no trace, a configuration without ``moe`` / ``kv``: what
    a program without the scopes and the counters (the PARENT's) gives. None,
    never a raise."""
    assert _read(spec, _empty_ctx(_bare(CONF))) is None
    assert _read(spec, _empty_ctx(CONF)) is None


SCOPES = {"attn.qkv": 0.30, "attn.rope": 0.01, "attn.write": 0.02, "attn.read": 0.12,
          "attn.out": 0.15, "moe.router": 0.04, "moe.dispatch": 0.08, "moe.experts": 1.6,
          "moe.combine": 0.08, "moe.shared": 0.1, "mtp.proj": 0.06, "mtp.block/attn.qkv": 0.06,
          "mtp.block/attn.write": 0.004, "mtp.block/attn.read": 0.03, "mtp.block/attn.out": 0.03,
          "mtp.block/moe.router": 0.01, "mtp.block/moe.dispatch": 0.02, "mtp.block/moe.experts": 0.4,
          "mtp.block/moe.combine": 0.02, "mtp.block/moe.shared": 0.025, "mtp.head": 0.05}


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold the counters the new readers ask for, whose
    capture reduced to ``scopes`` and whose client saw one stream decode through
    the traced interval; a TPU's device record."""
    from loadgen import Record, Spec

    ctx = _empty_ctx(config)
    # a 51 s window: 3,000 verify steps of 64 rows, every held expert hit, one
    # draft in sixteen accepted
    m1 = {HIT: 3000 * 80.0, CALLS: 3000 * 5.0, HERE: 3000 * 640.0, AWAY: 3000 * 4480.0,
          DRAFTED: 3000 * 64.0, ACCEPTED: 3000 * 4.0,
          "bee2bee_engine_kv_tokens_held": 6 * 64 * 300.0,
          "bee2bee_engine_kv_tokens_behind_window": 4 * 64 * 173.0}
    now = time.monotonic()
    rec = Record(Spec(64, 128, "x", "mix"), now - 30.0, now - 30.0,
                 events=[(now - 20.0 + 0.5 * i, "x" * 32) for i in range(40)],
                 t_end=now, tokens=40 * 32)
    ctx.update(m0={}, m1=m1, t0=now - 40.0, t1=now + 11.0, polls=[(now - 10.0, m1)],
               records=[rec], device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0},
               profile={"header": {"ts": time.time() - 12.0, "duration_s": 4.0}},
               _scope_reduce_exaone={"busy_s": 3.9, "scopes": scopes})
    return ctx


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    full = _read(spec, _counted_ctx(CONF, SCOPES))
    assert full is not None and 0 < full <= 100.0, (spec, full)
    if spec.endswith(("time_share", "roofline")):
        # another model's capture (no mtp.* scope), or this model with its drafter off
        other = _counted_ctx(CONF, {"attn.read": 1.0, "moe.experts": 1.0, "kv.write": 1.0})
        assert _read(spec, other) is None
    no_counters = _counted_ctx(CONF, SCOPES)
    no_counters.update(m0={}, m1={}, polls=[], records=[])
    assert _read(spec, no_counters) is None or spec.endswith("time_share")
    # the PARENT's server prints no tier="mtp" and no kind="elsewhere"
    parent = _counted_ctx(CONF, SCOPES)
    parent["m1"] = {k: v for k, v in parent["m1"].items() if k not in (AWAY, DRAFTED, ACCEPTED)}
    if not spec.endswith(("time_share", "behind_window_share")):
        assert _read(spec, parent) is None
    needs_section = spec in ("exaone.moe.experts_roofline", "exaone.moe.here_share",
                             "exaone.attn.read_roofline")
    assert (_read(spec, _counted_ctx(_bare(CONF), SCOPES)) is None) == needs_section


def test_the_readers_read_what_the_counters_and_the_scopes_imply():
    import exaone_scopes

    assert exaone_scopes.KEY == "_scope_reduce_exaone"
    ctx = _counted_ctx(CONF, SCOPES)
    assert _read("exaone.mtp.accept_share", ctx) == pytest.approx(100.0 * 4 / 64)
    assert _read("exaone.spec.tokens_per_step", ctx) == pytest.approx(1.0 + 4 / 64)
    assert _read("exaone.moe.here_share", ctx) == pytest.approx(12.5)
    mtp = sum(v for k, v in SCOPES.items() if k.startswith("mtp."))
    assert _read("exaone.mtp.time_share", ctx) == pytest.approx(100.0 * mtp / 3.9)
    moe = sum(v for k, v in SCOPES.items() if "moe." in k)
    assert _read("exaone.moe.time_share", ctx) == pytest.approx(100.0 * moe / 3.9)
    attn = sum(v for k, v in SCOPES.items() if "attn." in k)
    assert _read("exaone.attn.time_share", ctx) == pytest.approx(100.0 * attn / 3.9)
    share = 4.0 / 51.0  # the traced interval's share of the window
    nbytes, flops = moe_share_bytes.share_work(
        3000 * 80 * share, 3000 * 5 * share, 3000 * 640 * share, 3000 * 4480 * share, CONF["moe"])
    least = max(nbytes / PEAK["hbm_bytes_per_s"], flops / PEAK["bf16_flops_per_s"])
    assert _read("exaone.moe.experts_roofline", ctx) == pytest.approx(
        100.0 * least / (1.6 + 0.1 + 0.4 + 0.025), rel=1e-6)
    assert _read("exaone.pool.behind_window_share", ctx) == pytest.approx(
        100.0 * 4 * 173 / (6 * 300))
    # a verify step's reads count once for its tokens: more acceptance, fewer reads
    low = _read("exaone.attn.read_roofline", ctx)
    ctx2 = _counted_ctx(CONF, SCOPES)
    ctx2["m1"][ACCEPTED] = 3000 * 64.0
    assert _read("exaone.attn.read_roofline", ctx2) < low
    # the regex books an op under the LONGER name where it has one
    import re

    rx = re.compile(exaone_scopes.PATTERN)
    for text, want in (
            ("jit(f)/prog.verify/spec.verify/while/body/attn.read/pallas_call", "attn.read"),
            ("jit(f)/prog.verify/mtp.block/moe.experts/jit(gmm)/pallas_call", "mtp.block/moe.experts"),
            ("jit(f)/prog.verify/mtp.block/attn.qkv/dot_general", "mtp.block/attn.qkv"),
            ("jit(f)/prog.prefill/mtp.proj/dot_general", "mtp.proj"),
            ("jit(f)/prog.verify/spec.verify/closed_call/moe.shared/dot_general", "moe.shared")):
        assert rx.search(text).group(1) == want


def test_the_manifest_gains_one_configuration_one_cell_and_nine_metrics():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in M["configs"] if c["name"] == NAME)
    assert conf["reduced"] == CONF["reduced"]
    assert conf["file"] == f"benchmark/configs/{NAME}.json"
    assert conf["source"] == CONF["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")
    cells = [w for w in M["workloads"] if w["config"] == conf["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "decode-wide-closed", 1)]
    assert all(len(w["why"]) <= 200 for w in M["workloads"]) and len(conf["why"]) <= 200
    assert not any(w["chips"] == 4 for w in M["workloads"])
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] or CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "tok_s"
        assert (BENCH / "layer_metrics" / f"{name}.json").is_file()
    names = [m["name"] for m in M["per_layer"]]
    assert [n for n in names if n.startswith("exaone.")] == NEW  # in this order
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    assert {"tok_s", "sched.step_mean_ms", "device.idle_share", "gap_p90_ms",
            "pool.run_page_share"} <= mine
    granite = {m["name"] for m in M["end_to_end"] + M["per_layer"]
               if "granite-decode-wide-closed" in m.get("workloads", ())
               and not m["name"].startswith("granite.")}
    # every shared list the other cells of this traffic are on, but the one that reads
    # engine.window_steps: every step here is a verify, no decode window is dispatched,
    # so its reader finds nothing and the line would lack a listed metric
    assert granite - mine == {"sched.window_steps_mean"}
    # ... and, with ttft_p50_ms reported, the lists joyai's cell of this traffic is on for it
    ttft = {m["name"] for m in M["per_layer"] if m["name"] in mine and m["moves"] != "tok_s"}
    assert "ttft_p50_ms" in mine and "pool.used_peak_share" in mine
    assert ttft == {"engine.queue_wait_mean_ms", "engine.prefill_mean_ms",
                    "engine.compiles_in_window", "gateway.dispatch_mean_ms"}
    assert all(m["moves"] == "ttft_p50_ms" for m in M["per_layer"] if m["name"] in ttft)
    assert not {n for n in mine if "kernel.ragged" in n or n.startswith(
        ("joyai.", "st.", "long.", "ssm.", "h1.", "ouro.", "state.", "granite."))}
    assert "request_p50_ms" not in mine and "ttft_p90_ms" not in mine
    assert all("setup_s" == m["name"] or "workloads" in m for m in M["end_to_end"])


@pytest.fixture
def exaone_tree(tree):
    shutil.copy(FIXTURES / "tiny-exaone.json", tree / "benchmark/configs/tiny-exaone.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-exaone", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-exaone.json",
                                "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-exaone-cell", "config": "tiny-exaone",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-exaone-cell")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_exaone.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=900,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(exaone_tree):
    rc, line, lines, err = run_cell(exaone_tree, "--workload", "tiny-exaone-cell", "--seed",
                                    "3000000054", "--seconds", "3", "--trace", "1",
                                    "--rehearse-on-cpu", timeout=900.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert phases["boot"]["kv"]["cache_layers"] == 6
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["mean_margin"] <= ref["mean_margin_limit"]
    # the ENGINE's own prefill and verify programs on contexts past the window: the
    # trunk's tokens and the MTP layer's drafts at three positions a row, both deciding
    rows = 4  # the fixture's max_batch_size
    assert ref["long"]["ok"] and ref["long"]["rows"] == rows and ref["long"]["tokens"][0] > 16
    assert ref["long"]["positions"] == 3 * rows - ref["long"]["own_rejected"]
    assert ref["long"]["own_rejected"] == 0  # a step takes the program's own verdict
    assert ref["long"]["mean_margin"] <= ref["long"]["limit"] == 0.0005
    assert ref["long"]["max_abs_diff"] < 0.1  # (a node's pool is bf16 beside float32 weights)
    assert ref["mtp"]["ok"] and ref["mtp"]["positions"] >= 2 * rows and ref["mtp"]["agrees"] > 0.9
    assert ref["mtp"]["mean_margin"] <= ref["mtp"]["limit"] == 0.0005
    # the ragged read against a dense mask, six cache layers with their own windows
    assert ref["window_read_ok"] and ref["window_read_layers"] == [8, 8, 8, 0, 8, 0]
    assert ref["window_read_err"] <= ref["window_read_limit"] == 0.01
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    # the counters' readers read; a CPU run prints no device-trace metric
    assert {"sched.step_mean_ms", "engine.prefill_calls_per_s", "exaone.mtp.accept_share",
            "exaone.spec.tokens_per_step", "exaone.moe.here_share"} <= got
    assert 0 < line["metrics"]["exaone.moe.here_share"]["value"] < 100
    assert 1.0 <= line["metrics"]["exaone.spec.tokens_per_step"]["value"] < 1.2
    assert not {n for n in NEW if n.endswith(("time_share", "roofline"))} & got
    # ... and every other per-layer metric that lists the cell IS in the line
    M = json.loads((exaone_tree / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in M["per_layer"] if "tiny-exaone-cell" in m.get("workloads", ())
              and m["source"] != "device_trace"}
    # (the CPU's dense reader copies no pages: the run-page counters stay at 0)
    assert listed - got == {"pool.run_page_share"} and "sched.window_steps_mean" not in got
    job_path = exaone_tree / ".bench_home/tiny-exaone-cell/reference_job.json"
    for perturb in PERTURBED:
        rc, res = _reference(exaone_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False, (perturb, res)


def test_a_server_that_does_not_know_the_model_fails_fast_and_alone(exaone_tree):
    """The PARENT's tree on the new cell: ``serve-tpu --model <unknown>`` exits at
    once, run.py reports it, prints no result line and leaves no process."""
    conf_path = exaone_tree / "benchmark/configs/tiny-exaone.json"
    conf = json.loads(conf_path.read_text())
    conf["server"]["model"] = "no-such-exaone-model"
    conf_path.write_text(json.dumps(conf))
    t = time.monotonic()
    rc, line, lines, err = run_cell(exaone_tree, "--workload", "tiny-exaone-cell", "--seed", "1",
                                    "--seconds", "1", "--trace", "0", "--rehearse-on-cpu")
    assert rc != 0 and line is None and time.monotonic() - t < 60.0
    assert "server child exited" in err and "no model config matches" in err
    left = subprocess.run(["pgrep", "-af", "no-such-exaone-model"], capture_output=True, text=True)
    assert not [ln for ln in left.stdout.splitlines() if "pgrep" not in ln]
