"""The nemotron cell's own files: the count file of experts in a LATENT by hand,
every new reader on a fixture and on an empty context (None, never an exception),
the manifest's new entries, the configuration file against the catalog's row and
the program's preset, and the cell at tiny size on the CPU: run.py boots
``tiny-nemotron`` (E M E M * M, 4 of 16 experts held in a latent of 24),
``reference_nemotron_h.py`` decides ``correct``, the TRACED line carries every
per-layer name whose list holds the cell (less the device trace's, which a CPU
run never prints), each ONE-thing-wrong reference comes out NOT correct against
the same served text, and a server that does not know the model (the PARENT's
tree) fails fast and alone. Written so that entries a later PR appends do not
break it."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import latent_moe_bytes
import ssm_bytes

CELL = "nemotron-decode-wide-closed"
NAME = "nemotron-3-super-120b-a12b-11l-e128"
NEW = ["nemotron.moe.time_share", "nemotron.moe.experts_roofline",
       "nemotron.moe.latent_time_share", "nemotron.moe.here_share",
       "nemotron.moe.experts_hit_share", "nemotron.ssm.time_share", "nemotron.ssm.state_roofline"]
CONF = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
PERTURBED = [{"activation": "relu"}, {"activation": "gated"}, {"drop": "shared_expert"},
             {"expert_first": 0}, {"n_groups": 1}, {"rope": True},
             {"activation_dtype": "float8_e4m3fn"}]
HIT = "bee2bee_engine_moe_experts_hit_total"
CALLS = "bee2bee_engine_moe_layer_calls_total"
HERE = 'bee2bee_engine_moe_assignments_total{kind="live"}'
AWAY = 'bee2bee_engine_moe_assignments_total{kind="elsewhere"}'
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_latent_work_by_hand_on_the_configurations_numbers():
    moe = CONF["moe"]
    assert latent_moe_bytes.expert_bytes(moe) == 2 * 1024 * 2688 * 2 == 11_010_048 == moe["expert_bytes"]
    assert latent_moe_bytes.latent_bytes(moe) == 2 * 4096 * 1024 * 2 == 16_777_216 == moe["latent_bytes"]
    assert latent_moe_bytes.shared_bytes(moe) == 2 * 4096 * 5376 * 2 == 88_080_384 == moe["shared_bytes"]
    # ONE decode step of 64 live rows: 5 layer calls, 120 of each layer's 128
    # held experts hit, 5 x 1,408 assignments of which a quarter lie here
    nbytes, flops = latent_moe_bytes.work(600, 5, 1760, 5280, moe, ("experts",))
    assert nbytes == 600 * 11_010_048 == 6_606_028_800  # the issue's 6.6 GB of held experts
    assert flops == 2 * 1760 * 5_505_024
    assert nbytes / PEAK["hbm_bytes_per_s"] == pytest.approx(0.008066, rel=1e-3)  # memory-bound
    assert flops / PEAK["bf16_flops_per_s"] < 0.0002
    whole = latent_moe_bytes.work(600, 5, 1760, 5280, moe)
    assert whole[0] == nbytes + 5 * (16_777_216 + 88_080_384)
    tokens = (1760 + 5280) / 22  # 64 rows x 5 layers
    assert tokens == 320 and whole[1] == flops + tokens * (16_777_216 + 88_080_384)
    assert latent_moe_bytes.work(600, 5, 1760, 5280, moe, ("latent",)) == (
        5 * 16_777_216, 320 * 16_777_216.0)
    assert latent_moe_bytes.shared_bytes(dict(moe, n_shared_experts=0)) == 0.0
    with pytest.raises(KeyError, match="gate"):
        latent_moe_bytes.work(1, 1, 1, 1, moe, ("gate",))


def test_the_state_section_counts_five_layers_and_the_pool_one():
    st = CONF["state"]
    row = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert ssm_bytes.state_bytes_per_row(st) == row == 21_278_720
    assert st["ssm_bytes_per_row"] + st["conv_bytes_per_row"] == row
    nbytes, flops = ssm_bytes.decode_step(st)
    assert nbytes == 2 * row and flops == 5 * 5 * 128 * 64 * 128
    assert 64 * row == pytest.approx(1.362e9, rel=1e-3)  # 1.36 GB at 64 rows
    assert st["in_proj_width"] == 18560 and st["conv_channels"] == 8192 + 2 * 8 * 128
    assert CONF["kv"]["n_layers"] == 1 and CONF["kv"]["bytes_per_token"] == 2 * 2 * 128 * 2 == 1024


def test_the_configuration_file_keeps_the_catalog_row_and_the_issues_letter():
    row = CONF
    assert (row["hidden_size"], row["num_hidden_layers"], row["num_attention_heads"],
            row["num_key_value_heads"], row["head_dim"], row["moe_latent_size"],
            row["moe_intermediate_size"], row["moe_shared_expert_intermediate_size"],
            row["vocab_size"], row["n_routed_experts"], row["num_experts_per_tok"],
            row["mamba_num_heads"], row["mamba_head_dim"], row["ssm_state_size"], row["n_groups"],
            row["conv_kernel"], row["expand"], row["chunk_size"]) == (
                4096, 88, 32, 2, 128, 1024, 2688, 5376, 131072, 512, 22, 128, 64, 128, 8, 4, 2, 128)
    assert len(row["hybrid_override_pattern"]) == 88 and "-" not in row["hybrid_override_pattern"]
    assert [row["hybrid_override_pattern"].count(c) for c in "ME*"] == [40, 40, 8]
    assert row["hybrid_override_pattern"][row["layer_first"]:][:row["layers"]] == "EMEMEMEMEM*"
    assert row["model_type"] == "nemotron_h" and row["mlp_hidden_act"] == "relu2"
    assert row["routed_scaling_factor"] == 5 and row["tie_word_embeddings"] is False
    assert row["reduced"] == ["layers", "n_routed_experts", "vocab_size",
                              "max_position_embeddings", "num_nextn_predict_layers"]
    assert set(row["reduced_why"]) == set(row["reduced"])
    assert (row["layers"], row["layers_published"], row["n_routed_experts_held"],
            row["vocab_size_held"], row["max_position_embeddings"],
            row["num_nextn_predict_layers"], row["num_nextn_predict_layers_published"]) == (
                11, 88, 128, 32768, 2048, 0, 1)
    if os.path.exists(CATALOG):  # every published key under its own name, but the reduced
        pub = next(json.loads(ln) for ln in open(CATALOG)
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in ln)["config"]
        assert {k for k, v in pub.items() if row.get(k) != v} == {
            "max_position_embeddings", "num_nextn_predict_layers"}
    srv = row["server"]["config_json"]
    assert (srv["max_seq_len"], srv["max_batch_size"], srv["kv_block_size"],
            srv["kv_pool_blocks"]) == (2048, 64, 16, 3200)
    mix = json.loads((BENCH / "traffic" / "decode-wide-closed.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] + 32
    assert srv["kv_pool_blocks"] >= 64 * -(-longest // 16) + 1
    assert row["server"]["env"]["BEE2BEE_ADMISSION"]["max_concurrent"] == mix["callers"] == 96
    assert (row["state"]["n_layers"], row["kv"]["n_layers"], row["moe"]["n_expert_layers"]) == (5, 1, 5)
    assert (row["moe"]["n_experts"], row["moe"]["n_experts_held"], row["moe"]["experts_per_token"],
            row["moe"]["d_latent"], row["moe"]["d_ff"], row["moe"]["d_ff_shared"],
            row["moe"]["matrices"]) == (512, 128, 22, 1024, 2688, 5376, 2)
    ref = row["reference"]
    assert ref["module"] == "reference_nemotron_h" and 0 < ref["mean_margin_limit"] < ref["tolerance"]
    assert 0 < ref["near_tie"] < 0.01
    assert set(row["assumed"]) >= {"attention_positions", "selection_bias", "latent", "mixer_init",
                                   "router_precision", "state_dtype", "weights", "tokenizer"}
    assert "4 chips" in row["deployment"].replace("FOUR", "4") and "8 pipeline stages" in row["deployment"]


def test_the_program_s_preset_says_what_the_file_says():
    sys.path[:0] = [str(ROOT)]
    import reference_nemotron_h as plain
    from bee2bee_tpu.models.config import config_from_hf, get_config

    want, have = plain.dims_of_preset(get_config(NAME)), plain.dims_of_file(CONF)
    assert {k: have[k] for k in want} == want
    assert config_from_hf(CONF, name=NAME) == get_config(NAME)
    plan = plain.layer_plan(CONF)
    assert plan[:3] == [("moe", 0), ("mamba", 0), ("moe", 1)] and plan[-2:] == [("mamba", 4), ("attention", 0)]


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


SCOPES = {"ssm.in_proj": 0.35, "ssm.conv": 0.05, "ssm.step": 0.8, "ssm.state_write": 0.02,
          "ssm.out_proj": 0.2, "attn.qkv": 0.02, "attn.write": 0.01, "attn.read": 0.02,
          "attn.out": 0.02, "moe.router": 0.06, "moe.dispatch": 0.15, "moe.experts": 1.5,
          "moe.experts/latent.in": 0.04, "moe.experts/latent.out": 0.03,
          "moe.combine": 0.12, "moe.shared": 0.2, "head.logits": 0.1}


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold the counters the new readers ask for, whose
    capture reduced to ``scopes`` and whose client saw one stream decode through
    the traced interval; a TPU's device record."""
    from loadgen import Record, Spec

    ctx = _empty_ctx(config)
    # a 51 s window: 2,000 decode steps of 64 rows, 120 of 128 held experts hit a layer
    m1 = {HIT: 2000 * 600.0, CALLS: 2000 * 5.0, HERE: 2000 * 1760.0, AWAY: 2000 * 5280.0}
    now = time.monotonic()
    rec = Record(Spec(64, 128, "x", "mix"), now - 30.0, now - 30.0,
                 events=[(now - 20.0 + 0.5 * i, "x" * 32) for i in range(40)],
                 t_end=now, tokens=40 * 32)
    ctx.update(m0={}, m1=m1, t0=now - 40.0, t1=now + 11.0, polls=[(now - 10.0, m1)],
               records=[rec], device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0},
               profile={"header": {"ts": time.time() - 12.0, "duration_s": 4.0}},
               _scope_reduce={"busy_s": 3.9, "scopes": scopes},
               _scope_reduce_nemotron={"busy_s": 3.9, "scopes": scopes})
    return ctx


def test_the_fixture_names_the_scope_readers_own_keys_and_the_regex_books_the_longer_name():
    import re

    import nemotron_scopes
    import scope_common

    assert nemotron_scopes.KEY == "_scope_reduce_nemotron"
    ctx = _counted_ctx(CONF, SCOPES)
    assert scope_common.scopes(ctx)["scopes"] is SCOPES
    assert nemotron_scopes.scopes(ctx)["scopes"] is SCOPES
    rx = re.compile(nemotron_scopes.PATTERN)
    for op_name, label in (
            ("jit(f)/prog.decode/while/body/moe.experts/latent.in/dot_general", "moe.experts/latent.in"),
            ("jit(f)/prog.decode/while/body/moe.experts/latent.out/dot_general", "moe.experts/latent.out"),
            ("jit(f)/prog.decode/while/body/moe.experts/pallas_call", "moe.experts"),
            ("jit(f)/prog.decode/while/body/moe.shared/mlp.down/dot_general", "moe.shared"),
            ("jit(f)/prog.decode/while/body/moe.combine/add", "moe.combine")):
        assert rx.search(op_name).group(1) == label
    assert rx.search("jit(f)/prog.decode/while/body/ssm.step/pallas_call") is None
    # block_scopes books the nested scopes to the part that holds them
    import block_scopes

    assert block_scopes.part_of("prog.decode/while/body/moe.experts/latent.in/dot_general") == "moe.experts"


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    full = _read(spec, _counted_ctx(CONF, SCOPES))
    assert full is not None and 0 < full <= 100.0, (spec, full)
    if spec.endswith(("time_share", "roofline")):
        other = _counted_ctx(CONF, {"mla.read": 1.0, "mlp.down": 0.2, "kv.write": 1.0})
        assert _read(spec, other) is None  # another model's capture: none of these scopes
    if spec.startswith("nemotron.moe.") and spec.endswith(("time_share", "roofline")):
        # granite's capture has moe.* and no latent.*: these metrics are this cell's
        granite = _counted_ctx(CONF, {k: v for k, v in SCOPES.items() if "latent" not in k})
        assert _read(spec, granite) is None
    no_counters = _counted_ctx(CONF, SCOPES)
    no_counters.update(m0={}, m1={}, polls=[], records=[])
    assert _read(spec, no_counters) is None or spec.endswith("time_share")
    # a server that prints no kind="elsewhere": the share's readers read nothing
    parent = _counted_ctx(CONF, SCOPES)
    parent["m1"] = {k: v for k, v in parent["m1"].items() if k != AWAY}
    if spec in ("nemotron.moe.experts_roofline", "nemotron.moe.here_share"):
        assert _read(spec, parent) is None
    needs_section = spec.endswith(("roofline", "hit_share", "here_share"))
    assert (_read(spec, _counted_ctx(_bare(CONF), SCOPES)) is None) == needs_section


def test_the_experts_roofline_is_the_counters_work_over_the_grouped_products_time():
    ctx = _counted_ctx(CONF, SCOPES)
    got = _read("nemotron.moe.experts_roofline", ctx)
    share = 4.0 / 51.0  # the traced interval's share of the window
    nbytes, flops = latent_moe_bytes.work(
        2000 * 600 * share, 2000 * 5 * share, 2000 * 1760 * share, 2000 * 5280 * share,
        CONF["moe"], ("experts",))
    least = max(nbytes / PEAK["hbm_bytes_per_s"], flops / PEAK["bf16_flops_per_s"])
    assert got == pytest.approx(100.0 * least / 1.5, rel=1e-6)  # moe.experts LESS latent.*
    assert _read("nemotron.moe.experts_hit_share", ctx) == pytest.approx(100.0 * 600 / (5 * 128))
    assert _read("nemotron.moe.here_share", ctx) == pytest.approx(25.0)
    assert _read("nemotron.moe.time_share", ctx) == pytest.approx(100.0 * 2.1 / 3.9)
    assert _read("nemotron.moe.latent_time_share", ctx) == pytest.approx(100.0 * 0.07 / 3.9)
    assert _read("nemotron.ssm.time_share", ctx) == pytest.approx(100.0 * 1.42 / 3.9)


def test_the_manifest_gains_one_configuration_one_cell_and_seven_metrics():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in M["configs"] if c["name"] == NAME)
    assert conf["reduced"] == CONF["reduced"] and conf["file"] == f"benchmark/configs/{NAME}.json"
    assert conf["source"] == CONF["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    cells = [w for w in M["workloads"] if w["config"] == conf["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "decode-wide-closed", 1)]
    assert all(len(w["why"]) <= 200 for w in M["workloads"]) and len(conf["why"]) <= 200
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"][0] == CELL and by_name[name]["moves"] == "tok_s"
        assert (BENCH / "layer_metrics" / f"{name}.json").is_file()
    names = [m["name"] for m in M["per_layer"]]
    assert [n for n in names if n.startswith("nemotron.")] == NEW  # in this order
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    assert {"tok_s", "sched.step_mean_ms", "device.idle_share", "sched.window_steps_mean"} <= mine
    # every shared list granite's cell of this traffic is on, and, since its TTFT
    # is steady here (twelve seeds spread by 0.4 % against 4: PERF.md section 4),
    # ``ttft_p50_ms`` with the four per-layer metrics that move it, as h1's cell
    granite = {m["name"] for m in M["end_to_end"] + M["per_layer"]
               if "granite-decode-wide-closed" in m.get("workloads", ())
               and not m["name"].startswith("granite.")}
    ttft = {"ttft_p50_ms", "engine.queue_wait_mean_ms", "engine.prefill_mean_ms",
            "engine.compiles_in_window", "gateway.dispatch_mean_ms"}
    assert granite | ttft == mine - set(NEW)
    h1 = {m["name"] for m in M["end_to_end"] + M["per_layer"]
          if "h1-decode-wide-closed" in m.get("workloads", ())}
    assert ttft <= h1 and "ttft_p90_ms" not in mine and "request_p50_ms" not in mine
    assert {m["name"] for m in M["per_layer"] if m["name"] in mine and m["moves"] != "tok_s"} == (
        ttft - {"ttft_p50_ms"})
    assert all("setup_s" == m["name"] or "workloads" in m for m in M["end_to_end"])
    for name in NEW + ["granite.moe.here_share"]:
        spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()


@pytest.fixture
def nemotron_tree(tree, tmp_path, monkeypatch):
    # (a compile cache of its own: this sandbox's XLA:CPU cannot always load
    # another boot's stored programs, .claude/skills/verify/SKILL.md)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    shutil.copy(FIXTURES / "tiny-nemotron.json", tree / "benchmark/configs/tiny-nemotron.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-nemotron", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-nemotron.json",
                                "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-nemotron-cell", "config": "tiny-nemotron",
                                  "traffic": "tiny-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-nemotron-cell")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_nemotron_h.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=900,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(nemotron_tree):
    rc, line, lines, err = run_cell(nemotron_tree, "--workload", "tiny-nemotron-cell", "--seed",
                                    "3000000058", "--seconds", "3", "--trace", "1",
                                    "--rehearse-on-cpu", timeout=900.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert phases["boot"]["kv"]["cache_layers"] == 1
    assert phases["boot"]["kv"]["bytes_per_token"] == 2 * 2 * 16 * 2  # a bf16 pool, ONE layer
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["mean_margin"] <= ref["mean_margin_limit"]
    assert line["correct"] is True and line["failed"] == 0
    # the TRACED line carries every per-layer name whose list holds the cell,
    # but those read from the device trace, which a CPU run never prints
    M = json.loads((nemotron_tree / "BENCHMARK.json").read_text())
    listed = {m["name"]: m["source"] for m in M["per_layer"]
              if "tiny-nemotron-cell" in m.get("workloads", ())}
    assert set(NEW) <= set(listed)
    want = {n for n, source in listed.items() if source != "device_trace"}
    # (pool.run_page_share counts the ragged kernel's pages: it reads on a TPU alone)
    assert want - set(line["metrics"]) <= {"engine.e2e_mean_ms", "pool.run_page_share"}, (
        want - set(line["metrics"]))
    assert not {n for n, source in listed.items() if source == "device_trace"} & set(line["metrics"])
    assert {"tok_s", "ttft_p50_ms", "setup_s"} <= set(phases["end_to_end"])
    assert 0 < line["metrics"]["nemotron.moe.here_share"]["value"] < 100
    assert 0 < line["metrics"]["nemotron.moe.experts_hit_share"]["value"] <= 100
    job_path = nemotron_tree / ".bench_home/tiny-nemotron-cell/reference_job.json"
    for perturb in PERTURBED:
        rc, res = _reference(nemotron_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False, (perturb, res)
    # W_out before the weighting is the same function: the plain reading
    rc, res = _reference(nemotron_tree, job_path, {"w_out": "before_weighting"})
    assert rc == 0 and res["mean_margin"] == pytest.approx(ref["mean_margin"], abs=1e-5)


def test_a_server_that_does_not_know_the_model_fails_fast_and_alone(nemotron_tree):
    """The PARENT's tree on the new cell: ``serve-tpu --model <unknown>`` exits
    at once, run.py reports it, prints no result line and leaves no process."""
    conf_path = nemotron_tree / "benchmark/configs/tiny-nemotron.json"
    conf = json.loads(conf_path.read_text())
    conf["server"]["model"] = "no-such-latent-model"
    conf_path.write_text(json.dumps(conf))
    t = time.monotonic()
    rc, line, lines, err = run_cell(nemotron_tree, "--workload", "tiny-nemotron-cell", "--seed", "1",
                                    "--seconds", "1", "--trace", "0", "--rehearse-on-cpu")
    assert rc != 0 and line is None and time.monotonic() - t < 60.0
    assert "server child exited" in err and "no model config matches" in err
    left = subprocess.run(["pgrep", "-af", "no-such-latent-model"], capture_output=True, text=True)
    assert not [ln for ln in left.stdout.splitlines() if "pgrep" not in ln]
