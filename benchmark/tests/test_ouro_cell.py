"""The Ouro cell's own files: the weight bytes of a looped step by hand, every
new reader on a fixture and on an empty context (None, never an exception), the
manifest's new entries, and the cell at tiny size on the CPU: run.py boots
``tiny-ouro`` (3 layers x 3 passes), ``reference_ouro.py`` decides ``correct``,
each ONE-thing-wrong reference comes out NOT correct against the same served
text, and a server that does not know the model (the PARENT's tree) fails fast
and leaves the other cells' lines untouched. Written so that entries a later PR
appends do not break it."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import kv_mixed_bytes
import loop_bytes

CELL = "ouro-paragraph-closed"
NEW = ["ouro.attn.time_share", "ouro.attn.read_roofline", "ouro.weights.time_share",
       "ouro.weights.stream_roofline"]
CONF = json.loads((BENCH / "configs" / "ouro-2.6b.json").read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
PERTURBED = [{"passes": 2}, {"no_norm_between_passes": True},
             {"pass_reads_previous_cache": True}, {"no_post_norms": True},
             {"activation_dtype": "float8_e4m3fn"}]


def test_loop_bytes_by_hand():
    loop = CONF["loop"]
    layer = (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048) * 2
    assert loop["layer_bytes"] == layer == 102_776_832
    assert loop["head_bytes"] == 49_152 * 2048 * 2 == 201_326_592
    # a device call streams the 48 layers FOUR times and the head once
    assert loop_bytes.step_bytes(loop) == 4 * 48 * layer + 201_326_592 == 19_934_478_336
    assert loop_bytes.step_bytes(loop) / 819e9 == pytest.approx(0.02434, rel=1e-3)  # 24.3 ms
    assert loop_bytes.position_flops(loop) == 19_934_478_336  # 2 flops a 2-byte weight
    # 100 decode steps of 16 rows: memory-bound (16 x 19.9 GFLOP = 1.6 ms of compute)
    least, by = loop_bytes.least_seconds(100, 16, 0, 0, loop, PEAK)
    assert least == by["memory"] == pytest.approx(100 * 19_934_478_336 / 819e9)
    # 10 prefill calls over 8 x 128 positions each: compute-bound, 103.6 ms a call
    least, by = loop_bytes.least_seconds(0, 16, 10, 10 * 1024, loop, PEAK)
    assert least == by["compute"] == pytest.approx(10 * 19_934_478_336 * 1024 / 197e12)
    assert least / 10 == pytest.approx(0.1036, rel=1e-3)
    # a one-row prefill of 64 tokens is still memory-bound (6.5 ms of compute)
    assert loop_bytes.least_seconds(0, 16, 1, 64, loop, PEAK)[1]["compute"] == 0.0
    # both kinds in one interval are not averaged into one bound
    least, by = loop_bytes.least_seconds(100, 16, 10, 10 * 1024, loop, PEAK)
    assert by["memory"] > 0 and by["compute"] > 0 and least == by["memory"] + by["compute"]


def test_the_read_counts_192_cache_layers_of_one_kind():
    kv = CONF["kv"]
    assert kv_mixed_bytes.kinds_of(kv) == [(192, None)]
    assert kv_mixed_bytes.layer_token_bytes(kv) == 2 * 16 * 128 * 2 == 8192
    # a cached token is 1.5 MiB over the 192 (pass, layer) caches
    assert kv["n_layers"] * kv_mixed_bytes.layer_token_bytes(kv) == 1_572_864
    nbytes, flops = kv_mixed_bytes.decode_token(200, kv)
    assert nbytes == 192 * 200 * 8192 and flops == 192 * 200 * 4 * 16 * 128


def test_the_configuration_file_keeps_the_catalog_row_and_the_issues_letter():
    row = CONF
    assert (row["hidden_size"], row["num_hidden_layers"], row["num_attention_heads"],
            row["num_key_value_heads"], row["head_dim"], row["intermediate_size"],
            row["vocab_size"], row["total_ut_steps"], row["early_exit_threshold"],
            row["rope_theta"], row["rms_norm_eps"]) == (
                2048, 48, 16, 16, 128, 5632, 49152, 4, 1, 1000000, 1e-06)
    assert row["layer_types"] == ["full_attention"] * 48 and row["model_type"] == "ouro"
    assert row["reduced"] == ["max_position_embeddings"] and row["max_position_embeddings"] == 2048
    srv = row["server"]["config_json"]
    assert (srv["max_seq_len"], srv["max_batch_size"], srv["kv_block_size"]) == (2048, 16, 16)
    # 16 rows of the mix's longest request + a 32-step window always fit
    mix = json.loads((BENCH / "traffic" / "paragraph-closed.json").read_text())
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] + 32
    assert srv["kv_pool_blocks"] >= 16 * -(-longest // 16) + 1
    assert row["kv"]["n_layers"] == 192 == row["loop"]["passes"] * row["loop"]["layers"]
    assert row["server"]["env"]["BEE2BEE_ADMISSION"]["max_concurrent"] == mix["callers"] == 24
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.5,
                                    "min": 32, "max": 128}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.3,
                                    "min": 64, "max": 160}
    assert mix["probes"] == {"count": 32, "prompt_tokens": 64, "output_tokens": 8}
    assert mix["warmup"] == [{"prompt_tokens": 64, "output_tokens": 64, "count": 16},
                             {"prompt_tokens": 128, "output_tokens": 160, "count": 16}]
    assert mix["set_size"] % 8 == 0 and mix["steady_requests"] == 24 and mix["quiet_s"] == 5
    ref = row["reference"]
    assert ref["module"] == "reference_ouro" and 0 < ref["mean_margin_limit"] < ref["tolerance"]
    assert set(row["assumed"]) >= {"cache_per_pass", "sandwich_norms", "norm_between_passes",
                                   "exit_gate", "weights", "tokenizer"}


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
    bare = {k: v for k, v in config.items() if k != "loop"}
    bare["kv"] = {k: v for k, v in config["kv"].items() if k != "kinds"}
    return bare


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_none_from_an_empty_run(spec):
    """No scrape, no poll, no trace, a configuration without ``loop`` / ``kv.kinds``:
    what a program without the scopes and the counter gives. None, never a raise."""
    assert _read(spec, _empty_ctx(_bare(CONF))) is None
    assert _read(spec, _empty_ctx(CONF)) is None


# (the first traced run's capture, rounded: my chip run, PR 46)
SCOPES = {"attn.qkv": 0.2, "attn.rope": 0.02, "attn.write": 0.2, "attn.read": 0.8,
          "attn.out": 0.23, "mlp.gate_up": 1.13, "mlp.down": 0.61, "loop.norm": 0.001,
          "head.logits": 0.025, "while/body/dynamic_slice": 0.62}


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold the counters the new readers ask for, whose
    capture reduced to ``scopes`` and whose client saw one stream decode through
    the traced interval; a TPU's device record."""
    from loadgen import Record, Spec

    ctx = _empty_ctx(config)
    # a 51 s window: 1,200 decode steps and 160 prefill calls of a 4-pass model
    m1 = {'bee2bee_engine_loop_passes_total{kind="decode"}': 4 * 1200.0,
          'bee2bee_engine_loop_passes_total{kind="prefill"}': 4 * 160.0,
          'bee2bee_engine_prefill_tokens_total{kind="real"}': 160 * 3 * 70.0,
          'bee2bee_engine_prefill_tokens_total{kind="pad"}': 5000.0}
    now = time.monotonic()
    rec = Record(Spec(64, 128, "x", "mix"), now - 30.0, now - 30.0,
                 events=[(now - 20.0 + 0.5 * i, "x" * 32) for i in range(40)],
                 t_end=now, tokens=40 * 32)
    ctx.update(m0={}, m1=m1, t0=now - 40.0, t1=now + 11.0, polls=[(now - 10.0, m1)],
               records=[rec], device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0},
               profile={"header": {"ts": time.time() - 12.0, "duration_s": 4.0}},
               _scope_reduce_loop={"busy_s": 3.9, "scopes": scopes},
               _scope_reduce_attn_moe={"busy_s": 3.9, "scopes": scopes})
    return ctx


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    full = _read(spec, _counted_ctx(CONF, SCOPES))
    assert full is not None and 0 < full < 100.0, (spec, full)
    other = _counted_ctx(CONF, {"ssm.step": 1.0, "kv.write": 0.2, "moe.experts": 1.0})
    assert _read(spec, other) is None  # another model's capture: none of these scopes
    no_counters = _counted_ctx(CONF, SCOPES)
    no_counters.update(m0={}, m1={}, polls=[], records=[])
    assert _read(spec, no_counters) is None or spec.endswith("time_share")
    needs_section = spec.endswith("roofline")
    assert (_read(spec, _counted_ctx(_bare(CONF), SCOPES)) is None) == needs_section


def test_the_weight_roofline_is_the_counters_work_over_the_scopes_time():
    ctx = _counted_ctx(CONF, SCOPES)
    got = _read("ouro.weights.stream_roofline", ctx)
    share = 4.0 / 51.0  # the traced interval's share of the window
    steps, calls, positions = 1200 * share, 160 * share, 160 * 3 * 70.0 * share
    least, _ = loop_bytes.least_seconds(steps, 16, calls, positions, CONF["loop"], PEAK)
    # qkv, out, gate_up, down, head AND the layer scan's unscoped slices of its stacked
    # weights (where wq / wk / wv leave HBM); not rope / read / write / norm
    under = 0.2 + 0.23 + 1.13 + 0.61 + 0.025 + 0.62
    assert got == pytest.approx(100.0 * least / under, rel=1e-6)
    assert _read("ouro.weights.time_share", ctx) == pytest.approx(100.0 * under / 3.9)
    assert _read("ouro.attn.time_share", ctx) == pytest.approx(100.0 * 1.45 / 3.9)
    # without the slices the same capture would read over 100 % of the roofline
    assert 100.0 * least / (under - 0.62) > 105.0


def test_the_capture_regex_books_an_unscoped_weight_slice_and_never_steals_a_scoped_op():
    import re

    import ouro_scopes

    rx = re.compile(ouro_scopes.PATTERN)
    path = "jit(_decode)/prog.decode/while/body/closed_call/while/body/closed_call/"
    assert rx.search(path + "while/body/dynamic_slice").group(1) == "while/body/dynamic_slice"
    assert rx.search(path + "while/body/closed_call/attn.qkv/dot_general").group(1) == "attn.qkv"
    assert rx.search(path + "while/body/closed_call/attn.read/while/body/dynamic_slice"
                     ).group(1) == "attn.read"
    assert rx.search(path + "loop.norm/mul").group(1) == "loop.norm"
    assert rx.search("jit(_decode)/prog.decode/while/body/add") is None


def test_the_manifest_gains_one_configuration_one_cell_and_four_metrics():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in M["configs"] if c["name"] == "ouro-2.6b")
    assert conf["reduced"] == ["max_position_embeddings"] and conf["file"].endswith("ouro-2.6b.json")
    cells = [w for w in M["workloads"] if w["config"] == conf["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "paragraph-closed", 1)]
    assert not any(w["chips"] == 4 for w in M["workloads"])
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:  # each with a list of its own that holds this cell (later PRs may append)
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "tok_s"
    names = [m["name"] for m in M["per_layer"]]
    assert [n for n in names if n.startswith("ouro.")] == NEW  # in this order
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    assert {"tok_s", "ttft_p50_ms", "engine.queue_wait_mean_ms", "engine.compiles_in_window",
            "device.idle_share", "pool.used_peak_share"} <= mine
    st = {m["name"] for m in M["per_layer"]
          if "smallthinker-doc-closed" in m.get("workloads", ()) and not m["name"].startswith("st.")}
    assert st <= mine  # every shared list the smallthinker cell is on
    assert not {n for n in mine if "kernel.ragged" in n
                or n.startswith(("joyai.", "st.", "long.", "ssm.", "h1."))}
    assert "request_p50_ms" not in mine


@pytest.fixture
def ouro_tree(tree):
    shutil.copy(FIXTURES / "tiny-ouro.json", tree / "benchmark/configs/tiny-ouro.json")
    shutil.copy(FIXTURES / "tiny-paragraph-closed.json",
                tree / "benchmark/traffic/tiny-paragraph-closed.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-ouro", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-ouro.json", "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-ouro-cell", "config": "tiny-ouro",
                                  "traffic": "tiny-paragraph-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-ouro-cell")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_ouro.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(ouro_tree):
    rc, line, lines, err = run_cell(ouro_tree, "--workload", "tiny-ouro-cell", "--seed",
                                    "3000000046", "--seconds", "3", "--trace", "1",
                                    "--rehearse-on-cpu", timeout=900.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert phases["boot"]["kv"]["cache_layers"] == 9
    assert phases["boot"]["kv"]["bytes_per_token"] == 9 * 2 * 4 * 16 * 2  # a bf16 pool
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["mean_margin"] <= ref["mean_margin_limit"]
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    # the shared counters' readers read; a CPU run prints no device-trace metric
    assert {"sched.step_mean_ms", "pool.used_peak_share", "engine.prefill_calls_per_s"} <= got
    assert not set(NEW) & got
    job_path = ouro_tree / ".bench_home/tiny-ouro-cell/reference_job.json"
    for perturb in PERTURBED:
        rc, res = _reference(ouro_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False, (perturb, res)


def test_a_server_that_does_not_know_the_model_fails_fast_and_alone(ouro_tree):
    """The PARENT's tree on the new cell: ``serve-tpu --model <unknown>`` exits
    at once, run.py reports it (``Server.check_alive("booting")``), prints no
    result line, leaves no process, and another cell of the same manifest runs
    as before (ROADMAP.md Queue 2 item 11j)."""
    conf_path = ouro_tree / "benchmark/configs/tiny-ouro.json"
    conf = json.loads(conf_path.read_text())
    conf["server"]["model"] = "no-such-looped-model"
    conf_path.write_text(json.dumps(conf))
    t = time.monotonic()
    rc, line, lines, err = run_cell(ouro_tree, "--workload", "tiny-ouro-cell", "--seed", "1",
                                    "--seconds", "1", "--trace", "0", "--rehearse-on-cpu")
    assert rc != 0 and line is None and time.monotonic() - t < 60.0
    assert "server child exited" in err and "no model config matches" in err
    left = subprocess.run(["pgrep", "-af", "no-such-looped-model"], capture_output=True, text=True)
    assert not [ln for ln in left.stdout.splitlines() if "pgrep" not in ln]
    rc, line, lines, err = run_cell(ouro_tree, "--workload", "tiny-closed", "--seed", "3000000002",
                                    "--seconds", "3", "--trace", "0", "--rehearse-on-cpu")
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err[-2000:]
