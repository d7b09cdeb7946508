"""The JoyAI-LLM-Flash cell's own files: the byte counts by hand, every reader
of every per-layer metric on an empty context (None, never an exception), the
manifest's new entries, and the cell at tiny size on the CPU: run.py boots
``tiny-joyai``, ``reference_joyai.py`` decides ``correct``, the counter readers
read the expert layer's counters, and each ONE-thing-wrong reference comes out
NOT correct against the same served text."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import latent_bytes
import moe_bytes

CELL = "joyai-decode-wide-closed"
NEW = ["joyai.moe.time_share", "joyai.mla.time_share", "joyai.moe.experts_roofline",
       "joyai.mla.read_roofline", "joyai.moe.experts_hit_share", "joyai.moe.load_max",
       "joyai.latent.bytes_share"]
CONF = json.loads((BENCH / "configs" / "joyai-llm-flash-5l.json").read_text())


def test_moe_bytes_by_hand():
    moe = CONF["moe"]
    # gate, up [2048, 768] and down [768, 2048] at 2 B: 3 x 1,572,864 x 2
    assert moe_bytes.expert_bytes(moe) == 9_437_184 == moe["expert_bytes"]
    assert moe_bytes.assignment_flops(moe) == 2 * 3 * 2048 * 768 == 9_437_184
    # a 64-row decode step: 4 expert-layer calls that hit 890 routed experts in all,
    # each call's shared expert read once and run on its 64 live tokens
    nbytes, flops = moe_bytes.expert_work(890, 4, 64 * 8 * 4, moe)
    assert nbytes == (890 + 4) * 9_437_184 and flops == (2048 + 256) * 9_437_184
    assert nbytes / 819e9 == pytest.approx(0.010301, rel=1e-3)  # memory-bound: 10.3 ms
    assert flops / 197e12 < nbytes / 819e9 / 90


def test_latent_bytes_by_hand():
    lat = CONF["latent"]
    assert latent_bytes.row_bytes(lat) == 576 * 2 == 1152  # as published, not the 640 stored
    assert latent_bytes.token_bytes(lat) == 5 * 1152 == 5760
    # one decode step of a row 200 tokens deep: 200 rows in each of 5 layers
    nbytes, flops = latent_bytes.read_work(200 * 5, lat)
    assert nbytes == 200 * 5760
    assert flops == 200 * 5 * 2 * 32 * (576 + 512)
    assert flops / nbytes == pytest.approx(60.4, abs=0.1)  # under 240 flop/B: memory-bound


def test_the_configuration_file_keeps_the_published_keys():
    row = CONF
    assert row["num_hidden_layers"] == 40 and row["layers"] == 5
    assert row["reduced"] == ["layers", "max_position_embeddings"]
    assert (row["hidden_size"], row["num_attention_heads"], row["q_lora_rank"],
            row["kv_lora_rank"], row["qk_nope_head_dim"], row["qk_rope_head_dim"],
            row["v_head_dim"], row["intermediate_size"], row["moe_intermediate_size"],
            row["n_routed_experts"], row["num_experts_per_tok"], row["n_shared_experts"],
            row["vocab_size"]) == (2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 1, 129280)
    assert row["num_nextn_predict_layers"] == 1 and "num_nextn_predict_layers" in row["not_built"]
    assert row["latent"]["row_width"] == row["kv_lora_rank"] + row["qk_rope_head_dim"]
    ref = row["reference"]
    assert ref["module"] == "reference_joyai" and 0 < ref["near_tie"] < 0.1
    assert 0 < ref["mean_margin_limit"] < ref["tolerance"]  # the mean decides `correct`


def _empty_ctx(config):
    from loadgen import percentile

    return {"cell": {"name": "no-such-cell", "chips": 1}, "config": config, "mix": {},
            "client": {"ttft_ms": [], "gap_ms": [], "tokens": 0.0, "attempted": 0, "failed": 0,
                       "errors": [], "request_ms": []},
            "records": [], "t0": 0.0, "t1": 1.0, "setup_s": 0.0, "m0": {}, "m1": {},
            "polls": [], "profile": None, "trace": None,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
            "peaks": json.loads((BENCH / "peaks.json").read_text()), "percentile": percentile}


@pytest.mark.parametrize("spec", sorted(p.stem for p in (BENCH / "layer_metrics").glob("*.json")))
def test_every_layer_metric_reads_none_from_an_empty_run(spec):
    """No scrape, no poll, no trace and a configuration without ``state`` /
    ``latent`` / ``moe``: every reader says None and none raises (what the
    PARENT's program gives a metric this PR adds)."""
    sys.path[:0] = [str(BENCH)]
    import run as bench_run

    bare = {k: v for k, v in CONF.items() if k not in ("state", "latent", "moe")}
    assert bench_run.read_metric("layer_metrics", spec, _empty_ctx(bare)) is None


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold every counter and gauge the new readers
    ask for and whose capture reduced to ``scopes``; a TPU's device record."""
    import time

    ctx = _empty_ctx(config)
    m1 = {"bee2bee_engine_moe_experts_hit_total": 9.0e5, "bee2bee_engine_moe_layer_calls_total": 4.8e3,
          'bee2bee_engine_moe_assignments_total{kind="live"}': 2.4e6,
          "bee2bee_engine_latent_tokens_read_total": 4.0e8,
          "bee2bee_engine_moe_expert_load_max": 3.5,
          'bee2bee_engine_hbm_bytes{component="latent"}': 3.3e8}
    ctx.update(m0={}, m1=m1, t0=100.0, t1=151.0, polls=[(120.0, m1)],
               device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0}, profile={"header": {"ts": time.time(), "duration_s": 4.0}},
               _scope_reduce_moe_mla={"busy_s": 3.6, "scopes": scopes})
    return ctx


SCOPES = {"moe.router": 0.05, "moe.dispatch": 0.1, "moe.experts": 2.5, "moe.shared": 0.1,
          "moe.combine": 0.1, "mla.q_proj": 0.1, "mla.kv_proj": 0.05, "mla.write": 0.05,
          "mla.read": 0.2, "mla.out": 0.1}


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    """Each new metric reads a number where its scope, counter and section are
    there, and None (never an exception) from a trace WITHOUT its scopes, from
    scrapes without its counters, and from a configuration without its section
    (ROADMAP Queue 2 item 10h): what the parent's program, or another
    configuration, gives it."""
    sys.path[:0] = [str(BENCH)]
    import run as bench_run

    def read(ctx):
        return bench_run.read_metric("layer_metrics", spec, ctx)

    full = read(_counted_ctx(CONF, SCOPES))
    assert full is not None and full > 0
    if "roofline" in spec or "share" in spec:
        assert full < 100.0
    traced = spec in ("joyai.moe.time_share", "joyai.mla.time_share",
                      "joyai.moe.experts_roofline", "joyai.mla.read_roofline")
    other = _counted_ctx(CONF, {"ssm.step": 1.0, "kv.write": 0.2})  # another model's capture
    assert (read(other) is None) == traced
    bare = _counted_ctx(CONF, SCOPES)
    bare.update(m0={}, m1={}, polls=[])
    assert read(bare) is None or spec.endswith("time_share")
    no_section = {k: v for k, v in CONF.items() if k not in ("latent", "moe")}
    needs_section = spec in ("joyai.moe.experts_roofline", "joyai.mla.read_roofline",
                             "joyai.moe.experts_hit_share")
    assert (read(_counted_ctx(no_section, SCOPES)) is None) == needs_section


def test_the_manifest_gains_one_cell_and_seven_metrics_with_their_own_lists():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w for w in M["workloads"] if w["config"] == "joyai-llm-flash-5l"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "decode-wide-closed", 1)]
    new = M["per_layer"][-len(NEW):]  # appended, in this order
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "tok_s" for m in new)
    assert not [m["name"] for m in M["per_layer"][:-len(NEW)] if m["name"].startswith("joyai.")]
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    # (not ttft_p90_ms: its spread over six seeds read 0.8 / 4.2 / 8.6 % on the chip against
    # the 5 % a new cell is admitted under, PERF.md section 6)
    assert mine - set(NEW) == {
        "ttft_p50_ms", "tok_s", "engine.queue_wait_mean_ms",
        "engine.prefill_mean_ms", "engine.compiles_in_window", "sched.step_mean_ms",
        "sched.batch_fill_mean", "gap_p90_ms", "device.idle_share", "gateway.dispatch_mean_ms",
        "sched.admit_time_share", "sched.fetch_wait_share", "pool.used_peak_share"}
    # no entry without a list reaches the new cell but setup_s
    assert [m["name"] for m in M["end_to_end"] + M["per_layer"] if "workloads" not in m] == ["setup_s"]
    # the existing mix, shared with the h1 cell: the two differ by the model alone
    h1 = next(w for w in M["workloads"] if w["name"] == "h1-decode-wide-closed")
    assert h1["traffic"] == cells[0]["traffic"]


def test_seeded_weights_are_balanced_on_the_words_the_load_generator_sends():
    """core.balance_router_bias balances the seeded selection bias on text of
    the load generator's own words: a router's even load holds on the text it
    was balanced on (PERF.md section 6)."""
    import loadgen

    from bee2bee_tpu.models import core

    assert core.BALANCE_WORDS == loadgen.WORDS


@pytest.fixture
def joyai_tree(tree):
    shutil.copy(FIXTURES / "tiny-joyai.json", tree / "benchmark/configs/tiny-joyai.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-joyai", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-joyai.json", "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-joyai", "config": "tiny-joyai",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-joyai")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb, tolerance=None):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    if tolerance is not None:
        job["tolerance"] = tolerance
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_joyai.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(joyai_tree):
    rc, line, lines, err = run_cell(joyai_tree, "--workload", "tiny-joyai", "--seed", "3000000017",
                                    "--seconds", "3", "--trace", "1", "--rehearse-on-cpu",
                                    timeout=600.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["min_gap"] is not None and ref["near_tie_positions"] >= 0
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # the counters' readers read; a CPU run prints no device-trace metric
    assert {"joyai.moe.experts_hit_share", "joyai.moe.load_max"} <= set(got)
    assert not {"joyai.moe.time_share", "joyai.moe.experts_roofline", "joyai.mla.time_share",
                "joyai.mla.read_roofline", "joyai.latent.bytes_share"} & set(got)
    assert 0.0 < got["joyai.moe.experts_hit_share"] <= 100.0 and got["joyai.moe.load_max"] >= 1.0
    job_path = joyai_tree / ".bench_home/tiny-joyai/reference_job.json"
    for perturb in ({"drop": "routed_scaling_factor"}, {"drop": "shared_expert"},
                    {"drop": "e_score_correction_bias"}, {"bias_in_weights": True},
                    {"no_k_rope": True},
                    {"activation_dtype": "float8_e4m3fn"}):
        rc, res = _reference(joyai_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False and res["walk_ok"] is False, (perturb, res)
        assert res["mean_margin"] > res["mean_margin_limit"], (perturb, res)
    # the mean decides, not the walk: under a tolerance so wide that no context is
    # abandoned, a model without its shared expert still comes out not correct
    rc, res = _reference(joyai_tree, job_path, {"drop": "shared_expert"}, tolerance=100.0)
    assert rc == 1 and res["ok"] is False and res["walk_ok"] is True, res
    assert res["mean_margin"] > res["mean_margin_limit"], res
