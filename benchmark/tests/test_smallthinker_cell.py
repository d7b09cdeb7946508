"""The SmallThinker cell's own files: the byte counts by hand, every new reader
on a fixture and on an empty context (None, never an exception), the manifest's
new entries, and the cell at tiny size on the CPU: run.py boots
``tiny-smallthinker`` with a chunked prefill and contexts past its window,
``reference_smallthinker.py`` decides ``correct``, the counter readers read, and
each ONE-thing-wrong reference comes out NOT correct against the same served
text. Written so that entries a later PR appends do not break it."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from conftest import BENCH, FIXTURES, ROOT, run_cell

import kv_mixed_bytes
import moe_bytes

CELL = "smallthinker-doc-closed"
NEW = ["st.moe.time_share", "st.moe.experts_roofline", "st.moe.experts_hit_share",
       "st.moe.load_max", "st.attn.time_share", "st.attn.read_roofline",
       "st.pool.behind_window_share"]
CONF = json.loads((BENCH / "configs" / "smallthinker-21b-a3b-8l.json").read_text())
PERTURBED = [{"no_window": True}, {"rope_full_layers": True}, {"router_input": "ffn_norm"},
             {"activation": "silu"}, {"router_weights": "sigmoid"},
             {"activation_dtype": "float8_e4m3fn"}]


def test_kv_mixed_bytes_by_hand():
    kv = CONF["kv"]
    assert kv_mixed_bytes.kinds_of(kv) == [(2, None), (6, 4096)]
    assert kv_mixed_bytes.layer_token_bytes(kv) == 2 * 4 * 128 * 2 == 2048
    # one decode token over 6,300 cached: 2 layers see 6,300 keys, 6 see 4,096
    nbytes, flops = kv_mixed_bytes.decode_token(6300, kv)
    assert nbytes == (2 * 6300 + 6 * 4096) * 2048 == 76_136_448
    assert flops == (2 * 6300 + 6 * 4096) * 4 * 28 * 128
    assert flops / nbytes == 7.0  # 7 query heads a KV head: memory-bound far under 240 flop/B
    # inside the window every layer sees the context, as kv_bytes.py would have it
    assert kv_mixed_bytes.decode_token(1000, kv) == (8 * 1000 * 2048, 8 * 1000 * 4 * 28 * 128)
    # a causal prefill of 6,144: pairs 6144 x 6145 / 2 full, 4096 x 4097 / 2 + 2048 x 4096 windowed
    assert kv_mixed_bytes.visible_pairs(6144, None) == 18_877_440
    assert kv_mixed_bytes.visible_pairs(6144, 4096) == 8_390_656 + 8_388_608
    assert kv_mixed_bytes.visible_pairs(100, 4096) == 5050
    nbytes, flops = kv_mixed_bytes.prefill(6144, kv)
    assert nbytes == 6144 * 8 * 2048
    assert flops == (2 * 18_877_440 + 6 * 16_779_264) * 4 * 28 * 128
    assert flops / 197e12 > nbytes / 819e9  # compute-bound: 10.1 ms against 0.12
    # what the gauges count: a row of 6,300 holds 8 x 6,300, 6 x (6,300 - 4,096 + 1) behind
    assert kv_mixed_bytes.held_behind_window(6300, kv) == (50_400, 6 * 2205)
    assert kv_mixed_bytes.held_behind_window(4000, kv) == (32_000, 0)
    with pytest.raises(ValueError):
        kv_mixed_bytes.kinds_of(dict(kv, kinds=[{"layers": 3, "window": None}]))


def test_the_experts_bytes_through_moe_bytes():
    moe = CONF["moe"]
    assert moe_bytes.expert_bytes(moe) == 3 * 2560 * 768 * 2 == 11_796_480 == moe["expert_bytes"]
    # a 32-row decode step: 8 calls that hit 490 of 512 experts, no shared expert
    nbytes, flops = moe_bytes.expert_work(490, 8, 32 * 6 * 8, moe)
    assert nbytes == 490 * 11_796_480 and flops == 1536 * 11_796_480
    assert nbytes / 819e9 == pytest.approx(0.007058, rel=1e-3)  # memory-bound: 7.1 ms


def test_the_configuration_file_keeps_the_published_keys():
    row = CONF
    assert row["num_hidden_layers"] == 52 and row["layers"] == 8 and row["reduced"] == ["layers"]
    assert (row["hidden_size"], row["num_attention_heads"], row["num_key_value_heads"],
            row["head_dim"], row["moe_ffn_hidden_size"], row["moe_num_primary_experts"],
            row["moe_num_active_primary_experts"], row["vocab_size"], row["sliding_window_size"],
            row["max_position_embeddings"]) == (2560, 28, 4, 128, 768, 64, 6, 151936, 4096, 16384)
    assert row["sliding_window_layout"] == row["rope_layout"] == [0, 1, 1, 1] * 13
    srv = row["server"]["config_json"]
    assert (srv["max_seq_len"], srv["prefill_chunk"], srv["max_batch_size"],
            srv["kv_pool_blocks"]) == (16384, 2048, 32, 19200)
    assert sum(k["layers"] for k in row["kv"]["kinds"]) == row["kv"]["n_layers"] == 8
    ref = row["reference"]
    assert ref["module"] == "reference_smallthinker" and 0 < ref["near_tie"] < 0.1
    assert 0 < ref["mean_margin_limit"] < ref["tolerance"]  # the mean decides `correct`
    mix = json.loads((BENCH / "traffic" / "doc-closed.json").read_text())
    assert mix["callers"] == 48 and mix["prompt_tokens"] == {"dist": "uniform", "min": 4096,
                                                             "max": 8192}
    # ISSUE 43's letter (Tentpole 5); set_size alone was left to the first chip run
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                                    "min": 192, "max": 768}
    assert mix["probes"] == {"count": 8, "prompt_tokens": 6144, "output_tokens": 8}
    assert mix["set_size"] % 8 == 0 and mix["steady_requests"] == 48
    assert mix["warmup"] == [{"prompt_tokens": 4096, "output_tokens": 192, "count": 32},
                             {"prompt_tokens": 8192, "output_tokens": 768, "count": 32}]
    assert "prefill_buckets" not in srv and 0 < ref["window_read_limit"] < 1


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


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_none_from_an_empty_run(spec):
    """No scrape, no poll, no trace, a configuration without ``moe`` / ``kv.kinds``:
    what the PARENT's program gives a metric this PR adds. None, never a raise."""
    bare = {k: v for k, v in CONF.items() if k != "moe"}
    bare["kv"] = {k: v for k, v in CONF["kv"].items() if k != "kinds"}
    assert _read(spec, _empty_ctx(bare)) is None
    assert _read(spec, _empty_ctx(CONF)) is None


SCOPES = {"moe.router": 0.05, "moe.dispatch": 0.1, "moe.experts": 1.9, "moe.combine": 0.1,
          "attn.qkv": 0.2, "attn.rope": 0.02, "attn.write": 0.05, "attn.read": 1.0,
          "attn.out": 0.1}


def _counted_ctx(config, scopes):
    """A traced run whose scrapes hold every counter and gauge the new readers
    ask for, whose capture reduced to ``scopes`` and whose client saw one
    stream decode through the traced interval; a TPU's device record."""
    from loadgen import Record, Spec

    ctx = _empty_ctx(config)
    m1 = {"bee2bee_engine_moe_experts_hit_total": 2.9e5, "bee2bee_engine_moe_layer_calls_total": 6.0e3,
          'bee2bee_engine_moe_assignments_total{kind="live"}': 3.0e6,
          "bee2bee_engine_moe_expert_load_max": 2.1,
          "bee2bee_engine_kv_tokens_held": 1.6e6, "bee2bee_engine_kv_tokens_behind_window": 4.0e5}
    now = time.monotonic()
    rec = Record(Spec(6144, 400, "x", "mix"), now - 30.0, now - 30.0,
                 events=[(now - 20.0 + 0.5 * i, "x" * 32) for i in range(40)],
                 t_end=now, tokens=40 * 32)
    ctx.update(m0={}, m1=m1, t0=now - 40.0, t1=now + 11.0, polls=[(now - 10.0, m1)],
               records=[rec], device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               trace={"window_s": 4.0},
               profile={"header": {"ts": time.time() - 12.0, "duration_s": 4.0}},
               _scope_reduce_moe_mla={"busy_s": 3.8, "scopes": scopes},
               _scope_reduce_attn_moe={"busy_s": 3.8, "scopes": scopes})
    return ctx


@pytest.mark.parametrize("spec", NEW)
def test_every_new_metric_reads_from_a_full_run_and_none_without_its_source(spec):
    full = _read(spec, _counted_ctx(CONF, SCOPES))
    assert full is not None and full > 0, spec
    if "roofline" in spec or "share" in spec:
        assert full < 100.0
    traced = spec in ("st.moe.time_share", "st.moe.experts_roofline", "st.attn.time_share",
                      "st.attn.read_roofline")
    other = _counted_ctx(CONF, {"ssm.step": 1.0, "kv.write": 0.2})  # another model's capture
    assert (_read(spec, other) is None) == traced
    bare = _counted_ctx(CONF, SCOPES)
    bare.update(m0={}, m1={}, polls=[], records=[])
    assert _read(spec, bare) is None or spec.endswith("time_share")
    no_section = {k: v for k, v in CONF.items() if k != "moe"}
    no_section["kv"] = {k: v for k, v in CONF["kv"].items() if k != "kinds"}
    needs_section = spec in ("st.moe.experts_roofline", "st.moe.experts_hit_share",
                             "st.attn.read_roofline")
    assert (_read(spec, _counted_ctx(no_section, SCOPES)) is None) == needs_section


def test_the_behind_window_share_is_the_gauges_largest_ratio():
    ctx = _counted_ctx(CONF, SCOPES)
    assert _read("st.pool.behind_window_share", ctx) == pytest.approx(25.0)


def test_the_manifest_gains_one_configuration_one_cell_and_seven_metrics():
    M = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in M["configs"] if c["name"] == "smallthinker-21b-a3b-8l")
    assert conf["reduced"] == ["layers"] and conf["file"].endswith("smallthinker-21b-a3b-8l.json")
    cells = [w for w in M["workloads"] if w["config"] == conf["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "doc-closed", 1)]
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:  # each with a list of its own that holds this cell (later PRs may append)
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "tok_s"
    names = [m["name"] for m in M["per_layer"]]
    assert [n for n in names if n.startswith("st.")] == NEW  # in this order
    mine = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", ())}
    # the issue's three end-to-end metrics, and every shared per-layer metric the joyai cell has
    assert {"tok_s", "ttft_p50_ms", "engine.queue_wait_mean_ms", "engine.prefill_mean_ms",
            "engine.compiles_in_window", "gateway.dispatch_mean_ms"} <= mine
    joyai = {m["name"] for m in M["per_layer"]
             if "joyai-decode-wide-closed" in m.get("workloads", ()) and "joyai." not in m["name"]}
    assert joyai <= mine
    # the kernel.ragged_* readers take every custom call: here also the grouped products
    assert not {n for n in mine if "kernel.ragged" in n or n.startswith(("joyai.", "long."))}
    assert "ttft_p90_ms" not in mine and "request_p50_ms" not in mine


def test_seeded_weights_are_evened_on_the_words_the_load_generator_sends():
    import loadgen

    from bee2bee_tpu.models import core

    assert core.BALANCE_WORDS == loadgen.WORDS


@pytest.fixture
def st_tree(tree):
    shutil.copy(FIXTURES / "tiny-smallthinker.json",
                tree / "benchmark/configs/tiny-smallthinker.json")
    shutil.copy(FIXTURES / "tiny-doc-closed.json", tree / "benchmark/traffic/tiny-doc-closed.json")
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-smallthinker", "source": "test preset", "reduced": [],
                                "file": "benchmark/configs/tiny-smallthinker.json",
                                "why": "CPU rehearsal"})
    manifest["workloads"].append({"name": "tiny-st", "config": "tiny-smallthinker",
                                  "traffic": "tiny-doc-closed", "chips": 1, "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("tiny-st")
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return tree


def _reference(tree, job_path, perturb, tolerance=None):
    job = json.loads(job_path.read_text())
    job["perturb"] = perturb
    if tolerance is not None:
        job["tolerance"] = tolerance
    job_path.write_text(json.dumps(job))
    proc = subprocess.run([sys.executable, "benchmark/reference_smallthinker.py", str(job_path)],
                          cwd=tree, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu_and_its_perturbed_references_fail(st_tree):
    rc, line, lines, err = run_cell(st_tree, "--workload", "tiny-st", "--seed", "3000000043",
                                    "--seconds", "3", "--trace", "1", "--rehearse-on-cpu",
                                    timeout=900.0)
    assert rc == 0, err[-2000:]
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    ref = phases["correctness"]
    assert ref["ok"] is True and ref["decode_checked"] >= 4 and ref["forks_dropped"] == 0, ref
    assert ref["prompt_tokens"] == 60 and ref["min_gap"] is not None
    assert line["correct"] is True and line["failed"] == 0
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # the counters' readers read; a CPU run prints no device-trace metric
    assert {"st.moe.experts_hit_share", "st.moe.load_max", "st.pool.behind_window_share"} <= set(got)
    assert not {"st.moe.time_share", "st.moe.experts_roofline", "st.attn.time_share",
                "st.attn.read_roofline"} & set(got)
    assert 0.0 < got["st.moe.experts_hit_share"] <= 100.0 and got["st.moe.load_max"] >= 1.0
    # contexts of 48-144 behind a window of 24 in 3 layers of 4: most of what they hold
    assert 30.0 < got["st.pool.behind_window_share"] < 75.0
    job_path = st_tree / ".bench_home/tiny-st/reference_job.json"
    for perturb in PERTURBED:
        rc, res = _reference(st_tree, job_path, perturb)
        assert rc == 1 and res["ok"] is False, (perturb, res)
        assert res["mean_margin"] > res["mean_margin_limit"], (perturb, res)
