#!/usr/bin/env python
"""Headline benchmark: serving throughput of the bee2bee_tpu engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.

The reference (Chatit-cloud/BEE2BEE) publishes no benchmark numbers
(BASELINE.md: `published: {}`); its serving hot path is torch
`model.generate` via HF transformers (reference bee2bee/hf.py:35-44,
services.py:85-116). The baseline is therefore measured live: the same
distilgpt2 architecture driven through torch's greedy generate with KV
cache on CPU — exactly the reference's execution path. `vs_baseline` is
our aggregate serving throughput divided by that.

What runs (BASELINE.md's north star: output tok/s/chip + p50 latency):
- distilgpt2, concurrency 1 and 8 through the continuous-batching
  scheduler (8 concurrent requests share decode chunks — the serving
  configuration; the reference path cannot batch at all);
- p50 request latency over short requests at the headline concurrency;
- MFU on TPU: 2 * n_params * tok/s / chip peak bf16 FLOPs;
- gemma-2b rung (random init, bf16) at concurrency 1, 8, and 32 on TPU
  (decode is weight-bound at 2.5B, so batch rides nearly free; MFU is
  computed from the highest concurrency that completed) — BASELINE
  ladder step 2 — skipped off-TPU (CPU would take minutes/tok).

The full run (`python bench.py`, no argument) measures the device: it
needs a TPU and fails without one — it never shrinks itself onto a CPU.
One process touches jax (a chip belongs to one process at a time). A rung
that fails is recorded and the others still run, then the run exits
non-zero. The named standalone rungs (`python bench.py spec_model`,
...) run on whatever backend jax resolves and stamp it; scripts/lint.sh
uses two of them on the CPU as counts-and-ratio checks.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")

NEW_TOKENS = 256
PROMPT_LEN = 64
BASELINE_NEW_TOKENS = 64  # torch-CPU is slow; rate is stable over 64
P50_REQUESTS = 8
P50_NEW_TOKENS = 64
# THE repetitive-prompt workload of the spec and ragged rungs: one
# period, tiled to PROMPT_LEN — both rungs must draft over the SAME
# prompt or their acceptance numbers stop being comparable across rounds
SPEC_PERIOD = [11, 23, 5, 99, 42, 7, 310, 18]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _bench_concurrency(eng, prompts: list[list[int]], new_tokens: int) -> dict:
    """Aggregate tok/s + per-request latencies for len(prompts) concurrent
    greedy requests through the scheduler. Any failed request fails the
    bench — a silently shrunken sample would masquerade as a perf drop."""
    results: list = [None] * len(prompts)
    errors: list = []

    def run(i):
        try:
            results[i] = eng.generate(
                prompts[i], max_new_tokens=new_tokens, temperature=0.0
            )
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)}/{len(prompts)} requests failed") from errors[0]
    total = sum(r.new_tokens for r in results if r)
    lats = sorted(r.latency_s for r in results if r)
    return {
        "tokens": total,
        "wall_s": round(wall, 4),
        "tok_per_s": round(total / wall, 2) if wall > 0 else 0.0,
        "p50_latency_s": round(lats[len(lats) // 2], 4) if lats else None,
    }


def _introspect_stamp(eng=None) -> dict:
    """Engine-economics stamp for a rung artifact (ISSUE 15): per-root
    compile counts + wall-time from the process registry (cumulative —
    they survive engine close), plus, given a still-live engine, its
    MFU/goodput window and HBM ledger. Never throws: a stamp must not
    fail a rung."""
    try:
        from bee2bee_tpu.engine.introspect import bench_snapshot

        snap = bench_snapshot()
        if eng is not None:
            live = eng.introspect.refresh()
            if live.get("goodput"):
                snap["goodput"] = live["goodput"]
            if live.get("hbm"):
                snap["hbm"] = live["hbm"]
        return snap
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def bench_model(name: str, max_seq_len: int, concurrencies=(1, 8),
                new_tokens: int = NEW_TOKENS, dtype: str = "bfloat16",
                quantize: str = "none") -> dict:
    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        name,
        engine_config=EngineConfig(
            max_seq_len=max_seq_len, max_batch=max(concurrencies), dtype=dtype,
            cache_dtype=dtype, quantize=quantize,
        ),
    )
    try:
        info = eng.info
        n_params, platform = info["n_params"], info["platform"]
        rng_prompts = [
            [1 + (i * 37 + j) % 500 for j in range(PROMPT_LEN)]
            for i in range(max(16, max(concurrencies)))
        ]
        log(f"{name}: warmup (compile) on {platform}...")
        eng.generate(rng_prompts[0], max_new_tokens=new_tokens, temperature=0.0)

        out: dict = {"n_params": n_params, "platform": platform}
        for c in concurrencies:
            # best of two; a level that fails (e.g. OOM at batch 32)
            # fails the rung — a partial rung must not read as a result
            best = max(
                (_bench_concurrency(eng, rng_prompts[:c], new_tokens)
                 for _ in range(2)),
                key=lambda r: r["tok_per_s"],
            )
            out[f"batch{c}"] = best
            log(f"{name} concurrency {c}: {best['tok_per_s']} tok/s "
                f"(p50 {best['p50_latency_s']}s)")

        # p50 over short interactive requests at the headline concurrency
        short = _bench_concurrency(
            eng, rng_prompts[:min(P50_REQUESTS, max(concurrencies))],
            P50_NEW_TOKENS,
        )
        out["p50_latency_s_short"] = short["p50_latency_s"]

        from bee2bee_tpu.engine.introspect import peak_flops_per_device

        peak = peak_flops_per_device(platform, info["device_kind"])
        headline = out[f"batch{max(concurrencies)}"]["tok_per_s"]
        out["mfu"] = round(2 * n_params * headline / peak, 5)
        out["introspect"] = _introspect_stamp(eng)
        return out
    finally:
        # a failed rung (e.g. OOM at high concurrency) is recorded by main —
        # the engine's HBM + scheduler thread must not outlive the attempt
        eng.close()


def bench_paged(msl: int, new_tokens: int) -> dict:
    """Paged-cache rung: ONE active request on a max_batch=8 engine — the
    exact configuration where the rectangular cache paid its measured 4x
    idle-row tax. Records the paged gather counters (what the decode step
    actually read vs the rectangular equivalent) plus single-stream tok/s
    so rectangular-vs-paged tracks across rounds."""
    import time as _time

    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.engine.paged import ceil_div

    eng = InferenceEngine(
        "distilgpt2",
        engine_config=EngineConfig(max_seq_len=msl, max_batch=8),
    )
    try:
        prompt = [1 + j % 500 for j in range(PROMPT_LEN)]
        eng.generate(prompt, max_new_tokens=8, temperature=0.0)  # warm/compile
        t0 = _time.perf_counter()
        r = eng.generate(prompt, max_new_tokens=new_tokens, temperature=0.0)
        wall = _time.perf_counter() - t0
        st = eng.scheduler.stats
        bs = eng.engine_cfg.kv_block_size
        out = {
            "platform": jax.devices()[0].platform,
            "tok_per_s": round(r.new_tokens / wall, 2) if wall > 0 else 0.0,
            "block_size": bs,
            "blocks_read_per_step": st.paged_blocks_read_last_step,
            "live_blocks": st.paged_live_blocks,
            # what the same one-active-row step reads on the rectangular
            # path: every row streams full capacity
            "rect_equiv_blocks_per_step": 8 * ceil_div(eng.max_seq_len, bs),
            "blocks_hwm": st.paged_blocks_hwm,
            "blocks_copied": st.paged_blocks_copied,
        }
        log(
            f"paged rung: {out['tok_per_s']} tok/s single-stream at "
            f"max_batch=8; {out['blocks_read_per_step']} blocks/step read "
            f"vs rectangular-equivalent {out['rect_equiv_blocks_per_step']}"
        )
        out["introspect"] = _introspect_stamp(eng)
        return out
    finally:
        eng.close()


def bench_spec(msl: int, new_tokens: int) -> dict:
    """Speculative-decoding rung (ISSUE 4): single-stream greedy on a
    REPETITIVE prompt — the workload class (chat transcripts, code, RAG
    contexts) where n-gram self-drafting pays. Runs the same prompt with
    spec off and on and reports tok/s for both plus drafted/accepted/
    acceptance, so rounds can track whether acceptance (the mechanism)
    and the tok/s ratio (the win) move together."""
    import time as _time

    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    prompt = (SPEC_PERIOD * (PROMPT_LEN // len(SPEC_PERIOD) + 1))[:PROMPT_LEN]
    out: dict = {"platform": jax.devices()[0].platform}
    for label, k in (("off", 0), ("on", 8)):
        eng = InferenceEngine(
            "distilgpt2",
            engine_config=EngineConfig(
                max_seq_len=msl, max_batch=1, spec_tokens=k
            ),
        )
        try:
            eng.generate(prompt, max_new_tokens=8, temperature=0.0)  # warm
            # counters start AFTER warm-up: the rung's acceptance must
            # describe exactly the timed run it reports tok/s for
            st = eng.scheduler.stats
            steps0, drafted0, accepted0 = (
                st.spec_steps, st.spec_drafted, st.spec_accepted
            )
            t0 = _time.perf_counter()
            r = eng.generate(prompt, max_new_tokens=new_tokens, temperature=0.0)
            wall = _time.perf_counter() - t0
            entry = {
                "tok_per_s": round(r.new_tokens / wall, 2) if wall > 0 else 0.0,
                "new_tokens": r.new_tokens,
            }
            if k:
                drafted = st.spec_drafted - drafted0
                accepted = st.spec_accepted - accepted0
                entry.update(
                    spec_tokens=k,
                    spec_steps=st.spec_steps - steps0,
                    drafted=drafted,
                    accepted=accepted,
                    acceptance=round(accepted / drafted, 3) if drafted else 0.0,
                )
            out[f"spec_{label}"] = entry
        finally:
            eng.close()
    off, on = out["spec_off"]["tok_per_s"], out["spec_on"]["tok_per_s"]
    out["speedup"] = round(on / off, 3) if off > 0 else 0.0
    log(
        f"spec rung: {on} tok/s with spec vs {off} without "
        f"(x{out['speedup']}, acceptance "
        f"{out['spec_on'].get('acceptance')})"
    )
    out["introspect"] = _introspect_stamp()
    return out


def bench_spec_model(new_tokens: int = 64, n_streams: int = 2) -> dict:
    """Model-tier speculative decoding rung (ISSUE 19 acceptance): four
    cells on NON-repetitive prompts — the workload class where n-gram
    lookup finds nothing and the tier ladder must escalate to a real
    drafter model. Cells: spec off / n-gram only / model tier resident
    beside the target / model tier streamed from a BEE2BEE_DISAGG=draft
    mesh peer (killed mid-generation to certify the typed degradation
    path: peer_lost -> local tier, zero dropped generations). The
    drafter is the SAME tiny-llama at the same seed — weight-identical
    to the target, the CPU proxy for a well-trained small drafter, so
    model-tier acceptance approaches 1.0 while n-gram sits near 0. Each
    spec cell reports measured per-tier acceptance and acceptance-
    weighted tok/s (tok/s x acceptance — the share of throughput that
    arrived via verified drafts). Standalone: ``python bench.py
    spec_model``."""
    import asyncio
    import contextlib
    import time as _time

    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    K = 6
    plen = 48
    # j*97 mod 499 has period 499: within 48+64 tokens no n-gram ever
    # recurs, so the prompt gives the n-gram tier nothing to match
    prompts = [
        [1 + (j * 97 + s * 131) % 499 for j in range(plen)]
        for s in range(max(n_streams, 1))
    ]
    ekw = dict(
        max_seq_len=256, dtype="float32", cache_dtype="float32",
        decode_chunk=4, prefill_buckets=(16, 32, 64),
        # small probe budget so the n-gram tier fails its audition
        # within ~2 spec steps and the run actually exercises the model
        # tier (at the default 64, short generations never escalate)
        spec_probe_tokens=12,
    )

    def _spec_tiers(eng) -> dict:
        return (eng.introspect.meter.refresh() or {}).get("spec_tiers", {})

    def _tiers_delta(before: dict, after: dict) -> dict:
        out = {}
        for tier, e in after.items():
            d = e["drafted"] - before.get(tier, {}).get("drafted", 0)
            a = e["accepted"] - before.get(tier, {}).get("accepted", 0)
            if d > 0:
                out[tier] = {
                    "drafted": d, "accepted": a,
                    "acceptance": round(a / d, 3),
                }
        return out

    out: dict = {
        "platform": jax.devices()[0].platform,
        "spec_tokens": K,
        "new_tokens": new_tokens,
    }

    def one_local(spec: int, drafter: str) -> dict:
        eng = InferenceEngine(
            "tiny-llama",
            engine_config=EngineConfig(
                max_batch=1, spec_tokens=spec, drafter=drafter, **ekw
            ),
        )
        try:
            # warm long enough that the ladder escalates and the drafter
            # tier compiles its roots DURING warm-up — the timed run must
            # measure steady-state decode, not first-compile
            eng.generate(prompts[0], max_new_tokens=24, temperature=0.0)
            # counters start AFTER warm-up; tier state is per request, so
            # the timed run starts fresh on the n-gram tier and escalates
            # mid-run exactly as production rows do
            st = eng.scheduler.stats
            d0, a0 = st.spec_drafted, st.spec_accepted
            tiers0 = _spec_tiers(eng)
            t0 = _time.perf_counter()
            r = eng.generate(
                prompts[0], max_new_tokens=new_tokens, temperature=0.0
            )
            wall = _time.perf_counter() - t0
            entry = {
                "tok_per_s": round(r.new_tokens / wall, 2) if wall > 0 else 0.0,
                "new_tokens": r.new_tokens,
                "token_ids": list(r.token_ids),
            }
            if spec:
                drafted = st.spec_drafted - d0
                accepted = st.spec_accepted - a0
                acc = accepted / drafted if drafted else 0.0
                entry.update(
                    drafted=drafted, accepted=accepted,
                    acceptance=round(acc, 3),
                    acceptance_weighted_tok_per_s=round(
                        entry["tok_per_s"] * acc, 2
                    ),
                    tiers=_tiers_delta(tiers0, _spec_tiers(eng)),
                )
            else:
                entry["acceptance_weighted_tok_per_s"] = 0.0
            return entry
        finally:
            eng.close()

    out["off"] = one_local(0, "")
    out["ngram"] = one_local(K, "")
    out["model_local"] = one_local(K, "tiny-llama")

    async def mesh_cell() -> dict:
        from bee2bee_tpu.engine import scheduler as sched_mod
        from bee2bee_tpu.meshnet.node import P2PNode
        from bee2bee_tpu.services.tpu import TPUService

        serve_node = P2PNode(host="127.0.0.1", port=0)
        draft_node = P2PNode(host="127.0.0.1", port=0, disagg_role="draft")
        eng = None
        try:
            for n in (serve_node, draft_node):
                n.ping_interval_s = 0.2
                await n.start()
            await draft_node.connect_bootstrap(serve_node.addr)
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None,
                lambda: draft_node.enable_draft_server(
                    "tiny-llama", spec_tokens=K, dtype="float32",
                    max_rows=max(4, n_streams),
                ),
            )
            eng = InferenceEngine(
                "tiny-llama",
                engine_config=EngineConfig(
                    max_batch=n_streams, spec_tokens=K, drafter="mesh", **ekw
                ),
            )
            serve_node.add_service(TPUService("tiny-llama", engine=eng))
            # the serving node picks its draft peer off the gossiped
            # telemetry digest (disagg_role rides it) — push one round
            await draft_node.gossip_telemetry()
            await asyncio.sleep(0.3)
            await asyncio.to_thread(  # compile warm, long enough for the
                # ladder to escalate and exercise the mesh round trip
                eng.generate, prompts[0], max_new_tokens=24, temperature=0.0
            )
            deg0 = sched_mod._C_SPEC_DEGRADED.total()
            tiers0 = _spec_tiers(eng)
            t0 = _time.perf_counter()
            tasks = [
                asyncio.create_task(asyncio.to_thread(
                    eng.generate, prompts[s], max_new_tokens=new_tokens,
                    temperature=0.0,
                ))
                for s in range(n_streams)
            ]
            # wait until the mesh tier has actually served drafts, then
            # kill the draft peer MID-generation: the typed degradation
            # ladder (peer_lost -> local tier, zero dropped generations)
            # is the thing this cell certifies
            engaged = False
            for _ in range(600):
                await asyncio.sleep(0.05)
                if any(t.done() for t in tasks):
                    break
                d = _spec_tiers(eng).get("mesh", {}).get("drafted", 0)
                if d > tiers0.get("mesh", {}).get("drafted", 0):
                    engaged = True
                    break
            with contextlib.suppress(Exception):
                await draft_node.stop()
            results = await asyncio.gather(*tasks, return_exceptions=True)
            wall = _time.perf_counter() - t0
            ok = [r for r in results if not isinstance(r, BaseException)]
            total_new = sum(r.new_tokens for r in ok)
            tiers = _tiers_delta(tiers0, _spec_tiers(eng))
            mesh_t = tiers.get("mesh", {})
            acc = mesh_t.get("acceptance", 0.0)
            md = getattr(eng.scheduler, "mesh_drafter", None)
            return {
                "streams": n_streams,
                "completed": len(ok),
                "dropped": n_streams - len(ok),
                "new_tokens_total": total_new,
                "tok_per_s": round(total_new / wall, 2) if wall > 0 else 0.0,
                "mesh_engaged_before_kill": engaged,
                "degraded_rows": sched_mod._C_SPEC_DEGRADED.total() - deg0,
                "dead_reason": getattr(md, "dead_reason", None),
                "tiers": tiers,
                "acceptance_weighted_tok_per_s": round(
                    (total_new / wall if wall > 0 else 0.0) * acc, 2
                ),
                # greedy parity: drafts (mesh or local) must never change
                # the sampled sequence — stream 0 matches the spec-off run
                "parity_vs_off": bool(
                    not isinstance(results[0], BaseException)
                    and list(results[0].token_ids) == out["off"]["token_ids"]
                ),
            }
        finally:
            if eng is not None:
                eng.close()
            for n in (draft_node, serve_node):
                with contextlib.suppress(Exception):
                    await n.stop()

    try:
        out["model_mesh"] = asyncio.run(mesh_cell())
    except Exception as e:  # noqa: BLE001 — keep the local cells' artifact
        log(f"spec_model mesh cell failed: {e}")
        out["model_mesh"] = {"error": str(e)}

    off_ids = out["off"].pop("token_ids")
    for cell in ("ngram", "model_local"):
        out[cell]["parity_vs_off"] = out[cell].pop("token_ids") == off_ids
    ml = out["model_local"]
    out["acceptance_gate"] = {
        "model_tier_acceptance": ml.get("tiers", {}).get("model", {}).get(
            "acceptance"
        ),
        "ngram_acceptance": out["ngram"].get("acceptance"),
        "weighted_beats_off": (
            ml["acceptance_weighted_tok_per_s"]
            > out["off"]["acceptance_weighted_tok_per_s"]
        ),
        "weighted_beats_ngram": (
            ml["acceptance_weighted_tok_per_s"]
            > out["ngram"]["acceptance_weighted_tok_per_s"]
        ),
    }
    log(
        f"spec_model rung: model tier acceptance "
        f"{out['acceptance_gate']['model_tier_acceptance']} vs ngram "
        f"{out['acceptance_gate']['ngram_acceptance']}; weighted tok/s "
        f"{ml['acceptance_weighted_tok_per_s']} (model-local) vs "
        f"{out['ngram']['acceptance_weighted_tok_per_s']} (ngram); mesh "
        f"cell completed {out['model_mesh'].get('completed')}/{n_streams} "
        f"(degraded typed: {out['model_mesh'].get('dead_reason')})"
    )
    out["introspect"] = _introspect_stamp()
    return out


def bench_ragged(msl: int, new_tokens: int) -> dict:
    """Ragged paged-attention rung (ISSUE 8): the kernel OFF (dense
    attention over the gathered block view) vs ON (attention='flash' —
    ops/ragged.py reading the pool directly), same paged pool both ways,
    single-stream greedy. Two workloads per side: plain decode tok/s,
    and spec decode (--spec 8 on the repetitive prompt) reporting
    acceptance and acceptance-weighted tok/s (tok/s × acceptance — the
    share of throughput that arrived via verified drafts), so rounds can
    judge the paged+flash+spec composition as one number. Per-rung
    platform stamp (PR 6 bench hygiene): CPU rungs run the interpret-mode
    kernel and are NOT comparable to TPU rungs — judged per-platform."""
    import time as _time

    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # interpret-mode pallas on CPU is orders of magnitude slower than
        # the compiled kernel — smoke-scale so the rung still lands
        new_tokens = min(new_tokens, 16)
    # the spec cells need enough decode for the model's own output to
    # develop the repetition the drafter feeds on (bench_spec measured
    # acceptance 1.0 at 32 tokens on this workload; 16 is too short)
    spec_new_tokens = max(new_tokens, 32)
    rep_prompt = (SPEC_PERIOD * (PROMPT_LEN // len(SPEC_PERIOD) + 1))[:PROMPT_LEN]
    plain_prompt = [1 + j % 500 for j in range(PROMPT_LEN)]
    out: dict = {"platform": platform}
    for label, attn in (("off", "dense"), ("on", "flash")):
        for mode, spec in (("decode", 0), ("spec", 8)):
            eng = InferenceEngine(
                "distilgpt2",
                engine_config=EngineConfig(
                    max_seq_len=msl, max_batch=1, attention=attn,
                    spec_tokens=spec,
                ),
            )
            try:
                prompt = rep_prompt if spec else plain_prompt
                eng.generate(prompt, max_new_tokens=4, temperature=0.0)
                st = eng.scheduler.stats
                d0, a0 = st.spec_drafted, st.spec_accepted
                t0 = _time.perf_counter()
                r = eng.generate(
                    prompt,
                    max_new_tokens=spec_new_tokens if spec else new_tokens,
                    temperature=0.0,
                )
                wall = _time.perf_counter() - t0
                entry = {
                    "tok_per_s": (
                        round(r.new_tokens / wall, 2) if wall > 0 else 0.0
                    ),
                    "new_tokens": r.new_tokens,
                }
                if spec:
                    drafted = st.spec_drafted - d0
                    accepted = st.spec_accepted - a0
                    acc = accepted / drafted if drafted else 0.0
                    entry.update(
                        spec_tokens=spec,
                        drafted=drafted,
                        accepted=accepted,
                        acceptance=round(acc, 3),
                        acceptance_weighted_tok_per_s=round(
                            entry["tok_per_s"] * acc, 2
                        ),
                    )
                out[f"ragged_{label}_{mode}"] = entry
            finally:
                eng.close()
    off, on = (
        out["ragged_off_decode"]["tok_per_s"],
        out["ragged_on_decode"]["tok_per_s"],
    )
    out["decode_speedup"] = round(on / off, 3) if off > 0 else 0.0
    log(
        f"ragged rung [{platform}]: decode {on} tok/s kernel-on vs {off} "
        f"kernel-off (x{out['decode_speedup']}); spec-on acceptance "
        f"{out['ragged_on_spec'].get('acceptance')} "
        f"(acceptance-weighted "
        f"{out['ragged_on_spec'].get('acceptance_weighted_tok_per_s')} "
        f"tok/s)"
    )
    out["introspect"] = _introspect_stamp()
    return out


def bench_router_fairness(duration_s: float = 6.0) -> dict:
    """Router-fairness rung (ISSUE 7 acceptance): two tenants at 4:1
    weights drive an open-loop load (scripts/loadgen.py) against ONE
    saturated loopback node — admission max_concurrent=1, a FakeService
    with a fixed per-request delay — and the rung reports per-tenant
    completed tokens / TTFT / typed-shed counts plus the gold:bronze
    token ratio, which WDRR fairness should hold near 4.0 under
    saturation. No model, no accelerator: this rung is platform-
    independent and runnable standalone via ``python bench.py
    router_fairness``."""
    import asyncio
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from scripts.loadgen import TenantLoad, run_loadgen

    async def run() -> dict:
        from aiohttp.test_utils import TestServer

        from bee2bee_tpu.api import build_app
        from bee2bee_tpu.meshnet.node import P2PNode
        from bee2bee_tpu.router import (
            AdmissionConfig,
            AdmissionController,
            TenantRegistry,
            parse_tenant_config,
        )
        from bee2bee_tpu.services.fake import FakeService

        node = P2PNode(host="127.0.0.1", port=0)
        await node.start()
        server = None
        try:
            # 32 tokens/request at ~40 ms each through ONE slot ≈ 25 req/s
            # capacity; two tenants offering ~25/s each = 2x saturation
            node.add_service(FakeService(
                "bench-model", reply="tok " * 32, exec_delay_s=0.04
            ))
            node.tenants = TenantRegistry(parse_tenant_config({
                "gold": {"api_key": "k-gold", "weight": 4},
                "bronze": {"api_key": "k-bronze", "weight": 1},
            }))
            node.admission = AdmissionController(
                config=AdmissionConfig(
                    max_concurrent=1, max_queue=512, tenant_queue=400,
                    queue_timeout_s=duration_s + 60.0,
                ),
                weights=node.tenants.weights(),
            )
            server = TestServer(build_app(node))
            await server.start_server()
            report = await run_loadgen(
                f"http://127.0.0.1:{server.port}",
                [
                    TenantLoad("gold", "k-gold", rate_per_s=25.0,
                               max_new_tokens=32),
                    TenantLoad("bronze", "k-bronze", rate_per_s=25.0,
                               max_new_tokens=32),
                ],
                duration_s=duration_s,
            )
            gold = report["tenants"]["gold"]
            bronze = report["tenants"]["bronze"]
            report["weights"] = {"gold": 4.0, "bronze": 1.0}
            # the IN-WINDOW ratio: after arrivals stop, draining the
            # backlog serves everyone regardless of weight, so the total
            # ratio converges to the arrival ratio — only completions
            # inside the saturated window show the WDRR allocation
            report["token_ratio_gold_bronze"] = (
                round(
                    gold["completed_tokens_in_window"]
                    / bronze["completed_tokens_in_window"], 3,
                )
                if bronze["completed_tokens_in_window"] else None
            )
            report["admission_tenant_tokens"] = dict(
                node.admission.tenant_tokens
            )
            return report
        finally:
            if server is not None:
                await server.close()
            await node.stop()

    out = asyncio.run(run())
    log(
        f"router_fairness rung: gold:bronze in-window token ratio "
        f"{out.get('token_ratio_gold_bronze')} at 4:1 weights "
        f"(gold {out['tenants']['gold']['completed_tokens_in_window']:g} "
        f"tok, bronze "
        f"{out['tenants']['bronze']['completed_tokens_in_window']:g} tok, "
        f"rejected {out['tenants']['gold']['rejected']} / "
        f"{out['tenants']['bronze']['rejected']})"
    )
    return out


def bench_fleet_elastic(duration_s: float = 24.0, tail_s: float = 12.0,
                        base_rate: float = 8.0, swing: float = 10.0) -> dict:
    """Elastic-fleet rung (ISSUE 13 acceptance): a diurnal ramp with a
    ``swing``x traffic swing drives a loopback fleet — one controller
    front door + one active replica + two warm standbys — and the rung
    records whether NODE COUNT FOLLOWS LOAD (scale-out on sustained
    fleet-wide fast-burn, scale-in back to standby over the idle tail)
    while SLO fast-burn stays bounded instead of running away.

    Model-free (FakeService behind a contention lock, so service time
    grows with per-replica concurrency exactly like a serialized
    accelerator) and platform-independent; the client-side dispatcher
    spreads arrivals over the CURRENT router-eligible set — in-process
    loopback shares one metrics registry, so the router's digest-scored
    spreading cannot differentiate replicas here and the spread is the
    load balancer's job, while the CONTROLLER (lease, burn decisions,
    probe gate, drain) is the thing under test. Standalone:
    ``python bench.py fleet_elastic``."""
    import asyncio
    import contextlib
    import threading
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from scripts.loadgen import (
        TenantLoad,
        TenantStats,
        _fire,
        _window_report,
        profile_multiplier,
    )

    async def run() -> dict:
        import random

        import aiohttp
        from aiohttp.test_utils import TestServer

        from bee2bee_tpu.api import build_app
        from bee2bee_tpu.fleet import FleetConfig
        from bee2bee_tpu.health import SloTracker, parse_slo_config
        from bee2bee_tpu.meshnet.node import P2PNode
        from bee2bee_tpu.router import AdmissionConfig
        from bee2bee_tpu.services.fake import FakeService

        class ContendedFake(FakeService):
            """Service time = lock wait + hold: per-replica concurrency
            shows up in service.execute_ms the way a serialized decode
            loop would — the latency signal the SLO burns against. The
            clock starts BEFORE the lock (result_dict's t0), so queueing
            behind the replica's serial resource is what the histogram
            measures."""

            def __init__(self, *a, hold_s=0.02, **kw):
                super().__init__(*a, **kw)
                self._hold_s = hold_s
                self._serial = threading.Lock()

            def execute(self, params):
                t0 = time.time()
                self.calls.append(dict(params))
                with self._serial:
                    time.sleep(self._hold_s)
                text = self._reply_for(params)
                n = len(text.split())
                out = self.result_dict(text, n, t0, self.price_per_token)
                out["timing"] = self._timing(t0, n)
                return out

        MODEL = "fleet-bench"
        cfg = FleetConfig(
            model=MODEL, min_replicas=1, max_replicas=3,
            out_sustain_ticks=2, in_sustain_ticks=8,
            scale_out_cooldown_s=2.0, scale_in_cooldown_s=2.0,
            ack_timeout_s=5.0, settle_timeout_s=5.0, probe_timeout_s=10.0,
            action_timeout_s=20.0, lease_ttl_s=0.3, claim_stagger_s=0.1,
        )
        slo_cfg = parse_slo_config([{
            "name": "exec_p95", "kind": "latency",
            "metric": "service.execute_ms", "threshold_ms": 96.0,
            "target": 0.95,
        }])
        # controller = non-serving front door; 1 active + 2 warm standbys
        ctrl = P2PNode(host="127.0.0.1", port=0, fleet_controller=True)
        replicas = [
            P2PNode(host="127.0.0.1", port=0,
                    fleet_state=None if i == 0 else "standby")
            for i in range(3)
        ]
        nodes = [ctrl] + replicas
        servers: dict[str, TestServer] = {}
        try:
            for node in nodes:
                node.ping_interval_s = 0.1
                node.health.ttl_s = 1.5
                node.fleet.config = cfg
                node.fleet.lease.ttl_s = cfg.lease_ttl_s
                node.slo = SloTracker(
                    objectives=list(slo_cfg),
                    fast_window_s=3.0, slow_window_s=15.0,
                )
                # slo_shed OFF for this rung: every loopback node reads
                # the ONE process registry, so a burning histogram would
                # shed traffic on freshly-added replicas that are in
                # fact idle — shed-before-melt is pinned by the router
                # tests; this rung measures the SCALE loop
                node.admission.config = AdmissionConfig(
                    max_concurrent=32, max_queue=512, tenant_queue=400,
                    queue_timeout_s=30.0, shed_burn_rate=1e9,
                )
                await node.start()
            for node in replicas:
                node.add_service(ContendedFake(MODEL, reply="tok " * 16))
            for node in nodes[1:]:
                assert await ctrl.connect_bootstrap(node.addr)
            for _ in range(100):
                if all(len(n.peers) == len(nodes) - 1 for n in nodes):
                    break
                await asyncio.sleep(0.05)
            for node in replicas:
                await node.announce_service(node.local_services["fake"])
                server = TestServer(build_app(node))
                await server.start_server()
                servers[node.peer_id] = server
            for node in nodes:
                await node.gossip_telemetry()
            for _ in range(100):
                if ctrl.fleet.is_leader:
                    break
                await asyncio.sleep(0.05)
            assert ctrl.fleet.is_leader, "controller never claimed the lease"

            mult = profile_multiplier("ramp", swing)
            tenant = TenantLoad("fleet", rate_per_s=base_rate,
                                prompt="fleet bench", max_new_tokens=16)
            stats = TenantStats()
            timeline: list[dict] = []
            t0 = time.perf_counter()
            total_s = duration_s + tail_s
            inflight: set = set()

            def eligible_urls() -> list[str]:
                agg = ctrl.fleet.status()["aggregates"] or {}
                ids = [p for p in (agg.get("eligible_ids") or [])
                       if p in servers]
                if not ids:
                    ids = [replicas[0].peer_id]
                return [f"http://127.0.0.1:{servers[p].port}" for p in ids]

            async def sampler():
                while time.perf_counter() - t0 < total_s:
                    agg = ctrl.fleet.status()["aggregates"] or {}
                    timeline.append({
                        "t_s": round(time.perf_counter() - t0, 2),
                        "eligible": agg.get("eligible"),
                        "standby": len(agg.get("standby") or []),
                        "warming": len(agg.get("warming") or []),
                        "draining": len(agg.get("draining") or []),
                        "burning": agg.get("burning"),
                        "burn_fast_max": agg.get("burn_fast_max"),
                    })
                    await asyncio.sleep(0.5)

            async def driver(session):
                rr = 0
                while True:
                    now = time.perf_counter()
                    if now - t0 >= duration_s:
                        return  # the idle tail drives nothing
                    urls = eligible_urls()
                    url = urls[rr % len(urls)]
                    rr += 1
                    stats.sent += 1
                    stats.sent_ts.append(now)
                    task = asyncio.ensure_future(
                        _fire(session, url, tenant, stats)
                    )
                    inflight.add(task)
                    task.add_done_callback(inflight.discard)
                    rate = base_rate * mult((now - t0) / duration_s)
                    await asyncio.sleep(random.expovariate(max(rate, 1e-6)))

            async with aiohttp.ClientSession() as session:
                sample_task = asyncio.create_task(sampler())
                await driver(session)
                # idle tail: headroom sustains, the fleet breathes back in
                await asyncio.sleep(tail_s)
                await sample_task
                if inflight:
                    await asyncio.wait(set(inflight), timeout=30.0)

            windows = _window_report(
                [stats], t0, duration_s, duration_s / 12.0, mult
            )
            counts = [e["eligible"] for e in timeline
                      if e["eligible"] is not None]
            burns = [e["burn_fast_max"] for e in timeline
                     if e["burn_fast_max"] is not None]
            tail_entries = [e for e in timeline if e["t_s"] > duration_s]
            return {
                "model_free": True,
                "profile": {"name": "ramp", "swing": swing,
                            "base_rate_per_s": base_rate,
                            "duration_s": duration_s, "tail_s": tail_s},
                "windows": windows,
                "timeline": timeline,
                "replicas_min": min(counts) if counts else None,
                "replicas_max": max(counts) if counts else None,
                "replicas_final": counts[-1] if counts else None,
                "burn_fast_peak": max(burns) if burns else None,
                "burn_fast_final": burns[-1] if burns else None,
                "tail_burning_samples": sum(
                    1 for e in tail_entries if (e["burning"] or 0) > 0
                ),
                "completed": stats.completed,
                "shed": dict(stats.rejected),
                "errors": stats.errors,
                "controller": {
                    "stats": dict(ctrl.fleet.stats),
                    "decisions_tail": list(ctrl.fleet.decisions)[-10:],
                },
            }
        finally:
            for server in servers.values():
                with contextlib.suppress(Exception):
                    await server.close()
            for node in nodes:
                with contextlib.suppress(Exception):
                    await node.stop()

    out = asyncio.run(run())
    # the PR 6 platform stamp — model-free, but the artifact still says
    # what machine produced the numbers
    try:
        import jax

        out["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001 — standalone runs skip the probe
        out["platform"] = "unknown"
    log(
        f"fleet_elastic rung: replicas {out['replicas_min']}→"
        f"{out['replicas_max']}→{out['replicas_final']} across a "
        f"{out['profile']['swing']}x ramp, burn_fast peak "
        f"{out['burn_fast_peak']} final {out['burn_fast_final']}, "
        f"completed {out['completed']}, shed {out['shed']}"
    )
    return out


def bench_fleet_sim(sizes=(10, 50, 200), seed: int = 0,
                    delta_n: int = 50, delta_ticks: int = 6) -> dict:
    """Deterministic fleet-sim rung (ISSUE 17 acceptance): N P2PNode
    control planes on one loop over the simnet virtual transport/clock —
    gossip convergence and router decision quality at N ∈ {10, 50, 200},
    plus the delta-gossip scaling fix measured before/after by toggling
    ``gossip_delta_enabled`` on the same seeded 50-node fleet.

    Per size: bootstrap cost (virtual AND wall — wall is the python work,
    the scaling-fix regression surface), ticks to full (observer,
    subject) digest coverage, and the scored-routing fraction — for every
    node, the share of its remote candidates the router can score from
    fresh digests when asked to pick (1.0 = every decision is
    telemetry-informed, the fleet claim). Model-free, wire-free,
    platform-independent; virtual time costs nothing, so the numbers are
    replay-stable modulo host speed. Standalone:
    ``python bench.py fleet_sim``."""
    import asyncio
    import statistics as _stats

    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.simnet import FleetSim

    def _scored_fraction(sim) -> dict:
        """Router decision quality: fraction of remote candidates with a
        fresh digest at pick time, plus whether a real pick() runs in
        scored mode fleet-wide."""
        fracs = []
        scored_mode = 0
        for node in sim.alive():
            cands = [
                {
                    "provider_id": pid,
                    "price_per_token": 0.0,
                    "_latency": info.get("rtt_ms"),
                    "local": False,
                }
                for pid, info in node.peers.items()
            ]
            if not cands:
                continue
            fresh = node.health.fresh()
            fracs.append(
                sum(1 for c in cands if c["provider_id"] in fresh) / len(cands)
            )
            winner, decision = node.router.pick(cands, fresh)
            if winner is not None and decision.get("mode") == "scored":
                scored_mode += 1
        return {
            "mean": round(_stats.mean(fracs), 4) if fracs else 0.0,
            "min": round(min(fracs), 4) if fracs else 0.0,
            "picks_scored": scored_mode,
        }

    async def measure_size(n: int) -> dict:
        sim = FleetSim(n, seed=seed, trace_enabled=False)
        t_wall = time.time()
        try:
            await sim.start()
            boot_wall = time.time() - t_wall
            boot_virtual = sim.clock.time() - 1_700_000_000.0
            ticks = 0
            while sim.gossip_coverage() < 1.0 and ticks < 10:
                await sim.run_for(sim.ping_interval_s)
                ticks += 1
            return {
                "n": n,
                "bootstrap_wall_s": round(boot_wall, 3),
                "bootstrap_virtual_s": round(boot_virtual, 3),
                "converge_ticks": ticks,
                "gossip_coverage": round(sim.gossip_coverage(), 4),
                "routing": _scored_fraction(sim),
                "wall_s": round(time.time() - t_wall, 3),
            }
        finally:
            await sim.stop()

    async def measure_delta(enabled: bool) -> dict:
        sim = FleetSim(delta_n, seed=seed, trace_enabled=False)
        t_wall = time.time()
        try:
            await sim.start()
            for node in sim.nodes:
                node.gossip_delta_enabled = enabled
            await sim.run_for(delta_ticks * sim.ping_interval_s)
            reg = get_registry()
            return {
                "delta_enabled": enabled,
                "telemetry_frames": int(
                    reg.counter("mesh.frames_sent", "frames sent by op")
                    .value(op="telemetry")
                ),
                "telemetry_bytes": int(
                    reg.counter("mesh.bytes_sent", "payload bytes sent by op")
                    .value(op="telemetry")
                ),
                "suppressed": int(
                    reg.counter(
                        "mesh.gossip_suppressed",
                        "telemetry broadcasts skipped by delta suppression",
                    ).total()
                ),
                "wall_s": round(time.time() - t_wall, 3),
            }
        finally:
            await sim.stop()

    async def run() -> dict:
        out: dict = {"seed": seed, "sizes": {}}
        for n in sizes:
            out["sizes"][str(n)] = await measure_size(n)
        # the scaling-fix before/after: same fleet, same seed, delta
        # suppression off vs on — frames/bytes on the wire per 6 ticks
        off = await measure_delta(False)
        on = await measure_delta(True)
        ratio = (
            round(off["telemetry_frames"] / on["telemetry_frames"], 2)
            if on["telemetry_frames"] else None
        )
        out["delta_gossip"] = {
            "n": delta_n, "ticks": delta_ticks,
            "off": off, "on": on, "frames_ratio_off_over_on": ratio,
        }
        return out

    out = asyncio.run(run())
    # the PR 6 platform stamp — model-free, but the artifact still says
    # what machine produced the numbers
    try:
        import jax

        out["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001 — standalone runs skip the probe
        out["platform"] = "unknown"
    biggest = out["sizes"][str(max(sizes))]
    dg = out["delta_gossip"]
    log(
        f"fleet_sim rung: {biggest['n']} nodes bootstrap "
        f"{biggest['bootstrap_wall_s']}s wall / "
        f"{biggest['bootstrap_virtual_s']}s virtual, converged in "
        f"{biggest['converge_ticks']} tick(s), scored-routing "
        f"{biggest['routing']['mean']}; delta-gossip "
        f"{dg['off']['telemetry_frames']}→{dg['on']['telemetry_frames']} "
        f"telemetry frames over {dg['ticks']} ticks at n={dg['n']} "
        f"({dg['frames_ratio_off_over_on']}x)"
    )
    return out


def bench_migration(duration_tokens: int = 96, n_streams: int = 3) -> dict:
    """Live-migration rung (ISSUE 9 acceptance): a 3-node loopback mesh
    under concurrent streaming load; node A drains mid-decode and the
    rung reports TTFT + inter-token gaps per mode, the MIGRATION PAUSE
    (widest inter-chunk gap — the client-visible cost of the handoff)
    for KV-resume vs forced re-prefill failover, and the scheduler
    counters pinning zero re-prefill on the happy path. tiny-llama with
    random-init weights (identical rng seeds stand in for a shared
    checkpoint), so the rung runs on any platform; judge per the rung's
    own platform stamp. Standalone: ``python bench.py migration``."""
    import asyncio
    import time as _time

    import jax
    import numpy as np

    async def one_mode(force_reprefill: bool) -> dict:
        from bee2bee_tpu.engine import EngineConfig, InferenceEngine
        from bee2bee_tpu.meshnet.node import P2PNode
        from bee2bee_tpu.services.tpu import TPUService

        cfg = dict(
            max_seq_len=256, prefill_buckets=(16, 32, 64),
            decode_chunk=4, max_batch=max(4, n_streams),
        )
        nodes, svcs = [], []
        try:
            for _ in range(3):
                node = P2PNode(host="127.0.0.1", port=0)
                node.ping_interval_s = 0.2
                await node.start()
                svc = TPUService("tiny-llama", engine=InferenceEngine(
                    "tiny-llama", engine_config=EngineConfig(**cfg)
                ))
                node.add_service(svc)
                nodes.append(node)
                svcs.append(svc)
            for node in nodes[1:]:
                await node.connect_bootstrap(nodes[0].addr)
            await asyncio.sleep(0.3)
            for node, svc in zip(nodes, svcs):
                await node.announce_service(svc)
            for node in nodes:
                await node.gossip_telemetry()
            await asyncio.sleep(0.3)
            a = nodes[0]
            a.migration.force_reprefill = force_reprefill
            # warm every engine's compile paths: the source's CONCURRENT
            # batch shapes (the measured run admits n_streams rows) and
            # each target's batch-1 prefill/decode — so the measured
            # pause is the migration, not first-compile
            await asyncio.gather(*[
                asyncio.to_thread(
                    svcs[0].engine.generate, f"warm {i}", max_new_tokens=8
                )
                for i in range(n_streams)
            ])
            for svc in svcs[1:]:
                await asyncio.to_thread(
                    svc.engine.generate, "warm target", max_new_tokens=8
                )

            # timestamp TOKEN events, not text chunks: the fallback
            # tokenizer's UTF-8 holdback can delay text flushes, while
            # token events fire per decode chunk (and per bridged chunk
            # after the migration) — exactly the client-visible cadence
            chunk_ts: list[list[float]] = [[] for _ in range(n_streams)]
            t_submit = [0.0] * n_streams

            def consume(i):
                for ev in svcs[0].engine.generate_stream(
                    f"stream {i} counts tokens over and over",
                    max_new_tokens=duration_tokens,
                ):
                    if ev.get("done"):
                        return ev["result"]
                    chunk_ts[i].append(_time.perf_counter())

            tasks = []
            for i in range(n_streams):
                t_submit[i] = _time.perf_counter()
                tasks.append(asyncio.create_task(asyncio.to_thread(consume, i)))
            # let every stream admit AND produce a few chunks, then drain
            # mid-decode (a request still inside its admission burst is
            # invisible to checkpoint and would be silently kept local)
            for _ in range(1500):
                await asyncio.sleep(0.02)
                rows = svcs[0].engine.scheduler.live_requests()
                if (len(rows) >= n_streams
                        and all(len(ts) >= 2 for ts in chunk_ts)):
                    break
            t_drain = _time.perf_counter()
            summary = await a.begin_drain()
            results = await asyncio.gather(*tasks, return_exceptions=True)
            ok = [r for r in results if not isinstance(r, BaseException)]
            ttft_ms, pause_ms, e2e_s = [], [], []
            for i, ts in enumerate(chunk_ts):
                if not ts:
                    continue
                ttft_ms.append((ts[0] - t_submit[i]) * 1000.0)
                e2e_s.append(ts[-1] - t_submit[i])
                post = [t for t in ts if t > t_drain]
                pre = [t for t in ts if t <= t_drain]
                if post and pre:
                    pause_ms.append((post[0] - pre[-1]) * 1000.0)
            sched = svcs[0].engine.scheduler.stats
            imported = sum(s.engine.scheduler.stats.migrated_in
                           for s in svcs[1:])
            reprefills = sum(s.engine.scheduler.stats.import_reprefills
                             for s in svcs[1:])
            return {
                "completed": len(ok),
                "drain_summary": {k: v for k, v in summary.items()
                                  if k != "draining"},
                "migrated_out": sched.migrated_out,
                "migrated_in": imported,
                "import_reprefills": reprefills,
                "ttft_ms_mean": round(np.mean(ttft_ms), 1) if ttft_ms else None,
                "migration_pause_ms_mean": (
                    round(np.mean(pause_ms), 1) if pause_ms else None
                ),
                "migration_pause_ms_max": (
                    round(max(pause_ms), 1) if pause_ms else None
                ),
                "e2e_s_mean": round(np.mean(e2e_s), 3) if e2e_s else None,
            }
        finally:
            for node in nodes:
                try:
                    await node.stop()
                except Exception:  # noqa: BLE001
                    pass
            for svc in svcs:
                if svc.engine is not None:
                    svc.engine.close()

    resume = asyncio.run(one_mode(force_reprefill=False))
    reprefill = asyncio.run(one_mode(force_reprefill=True))
    out = {
        "platform": jax.devices()[0].platform,
        "streams": n_streams,
        "new_tokens": duration_tokens,
        "migration_resume": resume,
        "reprefill_failover": reprefill,
    }
    log(
        f"migration rung: drain pause mean "
        f"{resume.get('migration_pause_ms_mean')} ms (KV resume, "
        f"{resume.get('import_reprefills')} re-prefills) vs "
        f"{reprefill.get('migration_pause_ms_mean')} ms (re-prefill "
        f"failover); TTFT mean {resume.get('ttft_ms_mean')} ms"
    )
    return out


def bench_pipeline_interleave(
    stage_counts=(1, 2, 4), n_requests: int = 16, hop_ms: float = 5.0
) -> dict:
    """MPMD interleaved pipeline rung (ISSUE 10 acceptance): a loopback
    mesh of 1/2/4 stage workers + coordinator serving MIXED traffic —
    staggered open-loop arrivals with varied prompt/budget lengths, so
    admission prefills keep landing mid-decode — through the lockstep
    barrier session vs the free-running interleaved session (2 microbatch
    groups both ways). Reports aggregate decode tok/s, coordinator sends,
    and the bubble fraction measured from the stage.task spans inside the
    timed window (health.bubble_from_spans — the stitched-trace
    derivation; the loopback mesh shares one tracer, so no stitch hop).

    ``hop_ms`` of per-task latency is injected at every worker (the chaos
    delay harness) to emulate DISTINCT-host stage links: in-process
    loopback stages share cores, so raw compute overlap is zero-sum there
    (docs/PERF.md round 5 measured exactly that), and what the
    interleaved scheduler actually buys — admission prefills and
    stragglers no longer parking every other group — only shows once a
    chain's latency isn't pure shared-core compute. The 2-stage rung is
    the acceptance signal; the 4-stage in-process rung runs 5 nodes of
    websocket+XLA on the bench host's cores and its readings are
    correspondingly noisier (judge per the platform stamp, best-of-2
    each way). tiny-llama-4l (4 layers splits 4 ways) with random-init
    weights runs anywhere. Standalone: ``python bench.py
    pipeline_interleave``."""
    import asyncio
    import time as _time

    import jax

    MODEL = "tiny-llama-4l"
    SEED = 0
    MICROBATCHES = 2

    async def one(n_stages: int, interleave: bool) -> dict:
        from bee2bee_tpu.engine.stage_runner import StageRunner
        from bee2bee_tpu.health import bubble_from_spans
        from bee2bee_tpu.meshnet.chaos import ChaosStage
        from bee2bee_tpu.meshnet.node import P2PNode
        from bee2bee_tpu.meshnet.pipeline import PipelineCoordinator
        from bee2bee_tpu.tracing import get_tracer

        workers = [
            P2PNode(host="127.0.0.1", port=0) for _ in range(n_stages)
        ]
        coord = P2PNode(host="127.0.0.1", port=0)
        nodes = [*workers, coord]
        for n in nodes:
            await n.start()
        sess = None
        chaoses = []
        try:
            loop = asyncio.get_running_loop()
            for i, w in enumerate(workers):
                runner = await loop.run_in_executor(
                    None,
                    lambda i=i: StageRunner(
                        MODEL, n_stages=n_stages, stage=i, max_seq_len=256,
                        dtype="float32", rng_seed=SEED,
                    ),
                )
                w.add_stage_runner(runner)
            for w in workers:
                await coord.connect_bootstrap(w.addr)
            for _ in range(200):
                if len(coord.peers) >= n_stages:
                    break
                await asyncio.sleep(0.05)
            coordinator = PipelineCoordinator(
                coord, MODEL, stage_peers=[w.peer_id for w in workers],
                max_seq_len=256, dtype="float32", rng_seed=SEED,
            )
            await coordinator.load(timeout=300.0)
            sess = coordinator.session(
                max_batch=4, n_microbatches=MICROBATCHES,
                interleave=interleave,
            )
            prompts = [
                [1 + (i * 13 + j) % 300 for j in range(8 + 8 * (i % 3))]
                for i in range(n_requests)
            ]
            budgets = [8 + 4 * (i % 3) for i in range(n_requests)]
            # warm EVERY prefill bucket (16 and 32) into every group's
            # compile cache: a mid-window XLA compile lands on whichever
            # mode ran first and drowns the scheduling effect under test
            for _ in range(MICROBATCHES):
                await asyncio.gather(*(
                    sess.generate([1] * ln, max_new_tokens=2,
                                  temperature=0.0)
                    for ln in (9, 24)
                ))
            # emulate distinct-host stage links: per-task wire latency
            chaoses = [
                ChaosStage(w, action="delay", at_step=1,
                           delay_s=hop_ms / 1000.0)
                for w in workers
            ]

            async def submit(i: int):
                await asyncio.sleep(0.03 * i)  # open-loop arrivals
                return await sess.generate(
                    prompts[i], max_new_tokens=budgets[i], temperature=0.0
                )

            best = None
            for _rep in range(2):
                base_sends = sess.stats["tasks_sent"]
                w0 = _time.time() * 1000.0
                t0 = _time.perf_counter()
                outs = await asyncio.gather(
                    *(submit(i) for i in range(n_requests))
                )
                wall = _time.perf_counter() - t0
                w1 = _time.time() * 1000.0
                tokens = sum(len(o) for o in outs)
                bubble = bubble_from_spans(
                    get_tracer().recent(limit=4096, name="stage.task"),
                    w0, w1,
                )
                entry = {
                    "tok_per_s": (
                        round(tokens / wall, 2) if wall > 0 else 0.0
                    ),
                    "tokens": tokens,
                    "wall_s": round(wall, 4),
                    "coordinator_sends": (
                        sess.stats["tasks_sent"] - base_sends
                    ),
                    "bubble_fraction": (
                        bubble.get("bubble_fraction") if bubble else None
                    ),
                }
                if best is None or entry["tok_per_s"] > best["tok_per_s"]:
                    best = entry
            return best
        finally:
            for ch in chaoses:
                ch.restore()
            if sess is not None:
                await sess.close()
            for n in nodes:
                try:
                    await n.stop()
                except Exception:  # noqa: BLE001
                    pass

    out: dict = {
        "platform": jax.devices()[0].platform,
        "requests": n_requests,
        "microbatches": MICROBATCHES,
        "hop_ms": hop_ms,
        "stages": {},
    }
    for s in stage_counts:
        lockstep = asyncio.run(one(s, interleave=False))
        interleaved = asyncio.run(one(s, interleave=True))
        off, on = lockstep["tok_per_s"], interleaved["tok_per_s"]
        entry = {
            "lockstep": lockstep,
            "interleaved": interleaved,
            "speedup": round(on / off, 3) if off > 0 else 0.0,
        }
        out["stages"][str(s)] = entry
        log(
            f"pipeline_interleave [{out['platform']}] {s} stage(s): "
            f"{on} tok/s interleaved vs {off} lockstep "
            f"(x{entry['speedup']}; bubble "
            f"{interleaved['bubble_fraction']} vs "
            f"{lockstep['bubble_fraction']})"
        )
    return out


def _kv_sessions_at_capacity(eng, prompt_len: int, hold: int,
                             max_sessions: int = 63,
                             wall_budget_s: float = 120.0) -> int:
    """Submit a burst of streamed sessions and count how many were
    resident when the pool first backpressured (stats.paged_alloc_waits
    flips — the scheduler's typed pool_exhausted requeue). The burst
    admits in one scheduler pass between decode windows, so the count
    reflects the pool's admission capacity through the REAL admission
    path — not an arithmetic projection — with minimal skew from holder
    rows growing mid-measurement. ``max_sessions`` must exceed any
    plausible capacity (and stay under max_batch) or the pool never
    backpressures and the measurement is void."""
    import queue as _q

    sch = eng.scheduler
    reqs: list = []
    t0 = time.perf_counter()
    try:
        for i in range(max_sessions):
            prompt = [1 + (i * 13 + j) % 500 for j in range(prompt_len)]
            req = eng._make_request(prompt, hold, 0.0, 0, 1.0, None, stream=True)
            sch.submit(req)
            reqs.append(req)
        # wait for the backpressure event, then let the burst's first
        # tokens land (they come back in one sync after the admit pass)
        while (
            sch.stats.paged_alloc_waits == 0
            and time.perf_counter() - t0 < wall_budget_s
        ):
            time.sleep(0.01)
        time.sleep(0.5)
        return sum(1 for r in reqs if r.out_ids and r.finish is None)
    finally:
        for r in reqs:
            r.cancelled = True
        deadline = time.perf_counter() + 60
        for r in reqs:
            while r.finish is None and time.perf_counter() < deadline:
                try:
                    ev = r.events.get(timeout=5)
                except _q.Empty:
                    continue
                if ev.get("done"):
                    break


def bench_kv_quant(msl: int = 256) -> dict:
    """Quantized-KV-pool rung (ISSUE 12): bf16 vs int8 pool at the SAME
    pool HBM byte budget — sessions-at-capacity (rows admitted before the
    first pool_exhausted backpressure), decode tok/s at concurrency 4,
    and the bytes one mid-decode row exports for migration (the
    drain-pause payload, which the int8 pool roughly halves). Per-rung
    platform stamp per PR 6 bench hygiene: on CPU these are PROXY numbers
    for the ~2x-sessions-per-chip claim until the rung runs on the chip — the
    capacity ratio is geometry (block counts at equal bytes), so it
    transfers; the tok/s deltas do not."""
    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.models.config import get_config

    name = "distilgpt2"
    BS = 16
    PROMPT = 48
    cfg = get_config(name)
    # bytes per pool block: K + V pages, plus the int8 layout's
    # per-page-per-head f32 scales (~0.4% at BS=16, hd=64)
    elems = cfg.n_layers * cfg.n_kv_heads * BS * cfg.head_dim
    block_bytes = {
        "bfloat16": 2 * elems * 2,
        "int8": 2 * elems * 1 + 2 * cfg.n_layers * cfg.n_kv_heads * 4,
    }
    budget = 56 * block_bytes["bfloat16"]  # a deliberately tight pool
    out: dict = {
        "platform": jax.devices()[0].platform,
        "pool_hbm_budget_bytes": int(budget),
        "block_size": BS,
        "prompt_tokens": PROMPT,
    }
    for mode in ("bfloat16", "int8"):
        blocks = max(4, budget // block_bytes[mode])
        eng = InferenceEngine(
            name,
            engine_config=EngineConfig(
                max_seq_len=msl, max_batch=64, kv_pool_blocks=int(blocks),
                kv_block_size=BS, cache_dtype=mode, decode_chunk=4,
                prefill_buckets=(64,),
            ),
        )
        try:
            prompt = [1 + j % 500 for j in range(PROMPT)]
            eng.generate(prompt, max_new_tokens=4, temperature=0.0)  # compile
            admitted = _kv_sessions_at_capacity(
                eng, PROMPT, hold=msl - PROMPT - 8
            )
            prompts = [
                [1 + (i * 37 + j) % 500 for j in range(PROMPT)] for i in range(4)
            ]
            thr = _bench_concurrency(eng, prompts, 32)
            # one mid-decode row's export payload = the drain-pause bytes
            gen = eng.generate_stream(prompt, max_new_tokens=64, temperature=0.0)
            for ev in gen:
                if ev.get("done") or len(ev.get("tokens") or []) >= 1:
                    break
            mig_bytes = 0
            live = eng.scheduler.live_requests()
            if live:
                snap = eng.scheduler.checkpoint(live[0])
                if snap:
                    mig_bytes = sum(
                        a.nbytes for a in (snap.pop("_kv", None) or {}).values()
                    )
            gen.close()
            out[mode] = {
                "pool_blocks": int(blocks),
                "sessions_at_capacity": admitted,
                "decode_tok_per_s_c4": thr["tok_per_s"],
                "migration_bytes_per_row": int(mig_bytes),
            }
        finally:
            eng.close()
    bf, q8 = out["bfloat16"], out["int8"]
    if bf["sessions_at_capacity"]:
        out["capacity_ratio"] = round(
            q8["sessions_at_capacity"] / bf["sessions_at_capacity"], 3
        )
    if q8["migration_bytes_per_row"]:
        out["migration_bytes_ratio"] = round(
            bf["migration_bytes_per_row"] / q8["migration_bytes_per_row"], 3
        )
    log(
        f"kv_quant rung [{out['platform']}]: sessions-at-capacity "
        f"{bf['sessions_at_capacity']} (bf16, {bf['pool_blocks']} blocks) vs "
        f"{q8['sessions_at_capacity']} (int8, {q8['pool_blocks']} blocks) at "
        f"equal HBM; decode c4 {bf['decode_tok_per_s_c4']} vs "
        f"{q8['decode_tok_per_s_c4']} tok/s; migration bytes/row "
        f"{bf['migration_bytes_per_row']} vs {q8['migration_bytes_per_row']}"
    )
    out["introspect"] = _introspect_stamp()
    return out


def bench_lora_multi(msl: int = 256, new_tokens: int = 32,
                     n_adapters: int = 8) -> dict:
    """Batched multi-LoRA serving rung (ISSUE 14): N adapters resident
    over ONE engine, mixed batches with per-row adapter selection in the
    same decode step.

    Three readings: (1) per-adapter greedy PARITY vs dedicated merged-
    weights reference engines (f32 — bf16 argmax near-ties would flip on
    math-order differences, the same reason the flash parity test pins
    f32); (2) mixed-batch decode tok/s (8 rows, round-robin adapters)
    vs the SAME engine serving 8 adapter-less rows — the reported
    overhead of the per-row gather+rank-r einsums; (3) pool residency/
    churn counters. Platform-stamped per PR 6 bench hygiene."""
    import jax

    from bee2bee_tpu.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.models import core
    from bee2bee_tpu.train.lora import LoraConfig, init_lora, merge_lora

    lcfg = LoraConfig(rank=8, alpha=16.0)
    out: dict = {
        "platform": jax.devices()[0].platform,
        "n_adapters": n_adapters,
        "rank": lcfg.rank,
    }

    # ---- parity leg (f32, small budget): pool row == merged engine
    fcfg = dict(max_seq_len=128, dtype="float32", cache_dtype="float32")
    eng = InferenceEngine(
        "distilgpt2",
        engine_config=EngineConfig(max_batch=8, max_adapters=n_adapters, **fcfg),
    )
    try:
        base = core.restack_layers(eng.params)
        names = []
        adapters_by_name = {}
        for i in range(n_adapters):
            name = f"tenant{i}"
            ad = jax.tree.map(
                lambda x, i=i: x + 0.01 * (i + 1),
                init_lora(eng.model_cfg, lcfg, jax.random.key(i + 1)),
            )
            eng.load_adapter(name, ad, lcfg)
            names.append(name)
            adapters_by_name[name] = ad
        prompt = [1 + j % 500 for j in range(64)]
        parity_ok = 0
        for name in names[:2]:  # 2 merged references bound the rung's cost
            ref = InferenceEngine(
                "distilgpt2",
                params=merge_lora(base, jax.device_get(adapters_by_name[name]),
                                  lcfg),
                engine_config=EngineConfig(max_batch=1, **fcfg),
            )
            try:
                got = eng.generate(prompt, max_new_tokens=8, temperature=0.0,
                                   adapter=name)
                want = ref.generate(prompt, max_new_tokens=8, temperature=0.0)
                parity_ok += int(got.token_ids == want.token_ids)
            finally:
                ref.close()
        out["parity_checked"] = 2
        out["parity_ok"] = parity_ok

        # ---- throughput leg: 8 mixed rows vs 8 base rows, SAME engine
        prompts = [
            [1 + (i * 37 + j) % 500 for j in range(64)] for i in range(8)
        ]
        eng.generate(prompts[0], max_new_tokens=8, temperature=0.0)  # warm

        def run_batch(rows):
            results: list = [None] * len(rows)
            errors: list = []

            def run(i, adapter):
                try:
                    results[i] = eng.generate(
                        prompts[i], max_new_tokens=new_tokens,
                        temperature=0.0, adapter=adapter,
                    )
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=run, args=(i, a))
                for i, a in enumerate(rows)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                raise RuntimeError(
                    f"{len(errors)}/{len(rows)} rows failed"
                ) from errors[0]
            total = sum(r.new_tokens for r in results)
            return round(total / wall, 2) if wall > 0 else 0.0

        run_batch([None] * 8)  # warm the batch-8 trace too
        base_tps = run_batch([None] * 8)
        mixed_rows = [names[i % n_adapters] for i in range(8)]
        run_batch(mixed_rows)  # warm the adapter trace
        mixed_tps = run_batch(mixed_rows)
        out["base_tok_per_s"] = base_tps
        out["mixed_tok_per_s"] = mixed_tps
        out["overhead"] = (
            round(1.0 - mixed_tps / base_tps, 4) if base_tps > 0 else None
        )
        out["pool"] = eng.adapter_pool.info
        log(
            f"lora_multi rung [{out['platform']}]: {n_adapters} adapters, "
            f"parity {parity_ok}/2, mixed {mixed_tps} tok/s vs base "
            f"{base_tps} ({out['overhead']:.1%} overhead)"
        )
        out["introspect"] = _introspect_stamp(eng)
        return out
    finally:
        eng.close()


def bench_obs_overhead(
    tokens: int = 200_000, cadence_s: float = 0.005, repeats: int = 3,
) -> dict:
    """Observatory sampler overhead rung (ISSUE 20 acceptance): a tight
    token-shaped hot loop (counter incs + gauge/histogram feeds — the
    metric writes a real decode step makes) timed with the observatory
    OFF, then with a background thread running the real registry-backed
    collectors at a cadence compressed 1000x below production (5 ms vs
    5 s), so the measured ratio is a hard upper bound on the production
    duty cycle. No model, no accelerator: platform-independent and
    runnable standalone via ``python bench.py obs_overhead``."""
    import threading

    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.obs import OBS_CADENCE_S, Observatory

    reg = get_registry()
    c_tok = reg.counter("engine.tokens_generated", "tokens generated")
    g_goodput = reg.gauge("engine.goodput_tokens_per_s", "goodput")
    h_wait = reg.histogram("engine.queue_wait_ms", "queue wait")

    def hot_loop(n: int) -> float:
        """The loop under measurement: per-token metric writes plus the
        gauge/histogram feeds a real decode step performs per window."""
        t0 = time.perf_counter()
        for i in range(n):
            c_tok.inc()
            if i % 64 == 0:
                g_goodput.set(float(i % 4096))
                h_wait.observe(float(i % 97))
        return n / (time.perf_counter() - t0)

    class _NullRecorder:
        """The synthetic gauge feed looks like collapsing goodput to the
        watchdog; swallow its incidents so the measurement times the
        sampler, not incident-bundle snapshots of a fake collapse."""

        def incident(self, *a, **kw):
            return None

    def timed_on(n: int) -> tuple[float, int]:
        obs = Observatory(
            collectors=None, cadence_s=cadence_s, recorder=_NullRecorder()
        )
        stop = threading.Event()
        samples = {"n": 0}

        def sampler() -> None:
            while not stop.is_set():
                obs.sample_once()
                samples["n"] += 1
                stop.wait(cadence_s)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        try:
            rate = hot_loop(n)
        finally:
            stop.set()
            th.join(timeout=5.0)
        return rate, samples["n"]

    hot_loop(tokens // 10)  # warmup: interned ints, branch caches
    off_rates, on_rates, sample_counts = [], [], []
    for _ in range(repeats):
        off_rates.append(hot_loop(tokens))
        rate, n_samples = timed_on(tokens)
        on_rates.append(rate)
        sample_counts.append(n_samples)
    # best-of across repeats on both sides: scheduler noise only ever
    # subtracts throughput, so max-vs-max is the cleanest overhead ratio
    off, on = max(off_rates), max(on_rates)
    ratio = round(on / off, 4) if off > 0 else 0.0
    compression = OBS_CADENCE_S / cadence_s
    out = {
        "off": {"tok_per_s": round(off, 1), "tokens": tokens},
        "on": {
            "tok_per_s": round(on, 1),
            "tokens": tokens,
            "samples": sum(sample_counts),
        },
        "ratio_on_off": ratio,
        "sample_cadence_s": cadence_s,
        "cadence_compression_x": compression,
        # overhead observed at the compressed cadence, scaled back to the
        # production cadence: the number OBSERVABILITY.md quotes
        "production_overhead_frac": round(max(1.0 - ratio, 0.0) / compression, 8),
        "repeats": repeats,
    }
    log(
        f"obs_overhead rung: on {out['on']['tok_per_s']} tok/s vs off "
        f"{out['off']['tok_per_s']} tok/s at {cadence_s * 1000:.0f}ms cadence "
        f"(x{ratio} — production-cadence overhead "
        f"~{out['production_overhead_frac'] * 100:.5f}%)"
    )
    return out


def bench_reference_path() -> float:
    """The reference's hot loop: HF transformers greedy generate on torch CPU
    (reference hf.py:35-44 minus tokenization — token ids in, ids out)."""
    try:
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel
    except Exception as e:  # torch missing/broken: report absolute tok/s only
        log(f"torch baseline unavailable: {e}")
        return 0.0

    cfg = GPT2Config(
        vocab_size=50257, n_positions=1024, n_embd=768, n_layer=6, n_head=12
    )
    model = GPT2LMHeadModel(cfg).eval()
    ids = torch.arange(1, PROMPT_LEN + 1).unsqueeze(0)
    with torch.no_grad():
        model.generate(  # warmup
            ids, max_new_tokens=8, do_sample=False, use_cache=True, pad_token_id=0
        )
        t0 = time.perf_counter()
        out = model.generate(
            ids, max_new_tokens=BASELINE_NEW_TOKENS, do_sample=False,
            use_cache=True, pad_token_id=0,
        )
        dt = time.perf_counter() - t0
    n_new = out.shape[1] - ids.shape[1]
    rate = n_new / dt if dt > 0 else 0.0
    log(f"reference path (torch cpu): {n_new} tok in {dt:.2f}s -> {rate:.2f} tok/s")
    return rate


def _use_compile_cache() -> None:
    from bee2bee_tpu.utils import enable_compile_cache

    log(f"jax compile cache: {enable_compile_cache()}")


def main() -> int:
    _use_compile_cache()
    import traceback

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # the full run measures the device; a CPU number under a device
        # metric's name is worse than no number (r03-r05 published CPU
        # runs into the TPU trend series this way)
        log(f"the full bench needs a TPU; jax resolved {platform!r}. "
            "Model-free and counts-only rungs run standalone: "
            "python bench.py <rung>")
        return 1
    extras: dict = {}
    failed: list[str] = []

    def rung(key: str, fn, *a, **kw) -> None:
        """Run one rung. A failure is recorded (traceback to stderr, the
        error in the artifact) and the remaining rungs still run; main
        then exits non-zero — a failed rung never hides behind rc 0."""
        try:
            extras[key] = fn(*a, **kw)
        except Exception as e:  # noqa: BLE001 — boundary: report, go on
            log(f"{key} rung FAILED:\n{traceback.format_exc()}")
            extras[key] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(key)

    tokens, msl = NEW_TOKENS, 1024
    # the headline rung is not guarded: without it there is no artifact
    distil = bench_model(
        "distilgpt2", max_seq_len=msl, concurrencies=(1, 8), new_tokens=tokens
    )
    extras["distilgpt2"] = distil

    # paged KV cache counters (ISSUE 1 acceptance: per-step cache reads
    # proportional to live blocks; one-active-row at max_batch=8 must not
    # pay the rectangular idle-row tax)
    rung("paged_distilgpt2", bench_paged, msl, tokens)
    # speculative-decoding rung (ISSUE 4 acceptance: single-stream tok/s
    # + acceptance rate on a repetitive-prompt workload)
    rung("spec_distilgpt2", bench_spec, msl, tokens)
    # model-tier speculative decoding rung (ISSUE 19 acceptance: model
    # drafter acceptance > 0.4 where n-gram ~0 on non-repetitive
    # prompts, acceptance-weighted tok/s beats the off and ngram cells,
    # mesh cell degrades typed with zero dropped generations)
    rung("spec_model", bench_spec_model)
    # ragged paged-attention rung (ISSUE 8 acceptance: paged + flash +
    # spec composed — decode tok/s and spec acceptance-weighted tok/s,
    # kernel off vs on, judged per the rung's own platform stamp)
    rung("ragged_distilgpt2", bench_ragged, msl, tokens)
    # quantized-KV-pool rung (ISSUE 12 acceptance: >=1.8x sessions-at-
    # capacity at equal pool HBM, migration bytes per row ~halved)
    rung("kv_quant_distilgpt2", bench_kv_quant)
    # batched multi-LoRA rung (ISSUE 14 acceptance: 8+ adapters served
    # from one engine in mixed batches, per-adapter greedy parity vs the
    # merged-weights reference, tok/s overhead vs adapter-less decode)
    rung("lora_multi", bench_lora_multi)
    # per-tenant fairness rung (ISSUE 7 acceptance: ~4:1 completed-token
    # ratio at 4:1 weights under saturation) — model-free
    rung("router_fairness", bench_router_fairness)
    # elastic-fleet rung (ISSUE 13 acceptance: node count follows a 10x
    # diurnal traffic swing with SLO fast-burn bounded; probe-gated
    # scale-out, drain-to-standby scale-in) — model-free loopback fleet
    rung("fleet_elastic", bench_fleet_elastic)
    # deterministic fleet-sim rung (ISSUE 17 acceptance: gossip
    # convergence + scored-routing fraction at 10/50/200 virtual nodes,
    # delta-gossip before/after) — model-free, virtual transport/clock
    rung("fleet_sim", bench_fleet_sim)
    # live-migration rung (ISSUE 9 acceptance: drain pause for KV resume
    # vs re-prefill failover on a 3-node loopback mesh under load; the
    # happy path must show zero re-prefills)
    rung("migration", bench_migration)
    # interleaved-pipeline rung (ISSUE 10 acceptance: interleaved >=
    # lockstep decode tok/s at 2+ stages on loopback, bubble fraction
    # before/after from the stage.task spans)
    rung("pipeline_interleave", bench_pipeline_interleave)
    # BASELINE rung 2; random init — nothing downloads. Decode is
    # weight-bound at 2.5B params, so batch 32 rides nearly free:
    # the cache adds ~19 MB/row against 5 GB of weights per step
    rung("gemma-2b", bench_model, "gemma-2b", max_seq_len=1024,
         concurrencies=(1, 8, 32), new_tokens=64)
    # int8 weight-only quant: decode is weight-bound, so halved
    # weight bytes should show directly in tok/s (models/quant.py)
    rung("gemma-2b-int8", bench_model, "gemma-2b", max_seq_len=1024,
         concurrencies=(1, 8), new_tokens=64, quantize="int8")

    # serving-telemetry snapshot (ISSUE 5): every rung above ran through
    # the instrumented engine in THIS process, so the registry holds the
    # round's real TTFT/TPOT/queue-wait distributions and the tracer its
    # per-span percentiles — the perf trajectory carries distributions,
    # not just aggregate throughput
    from bee2bee_tpu.metrics import get_registry
    from bee2bee_tpu.tracing import get_tracer

    extras["telemetry"] = {
        "metrics": get_registry().snapshot(),
        "tracer_stats": get_tracer().stats(),
    }

    # round-level engine-economics stamp (ISSUE 15): cumulative compile
    # counts/wall-time per jit root across every rung above — benchdiff
    # reads rung-level stamps; this is the round's compile bill
    extras["introspect"] = _introspect_stamp()
    if failed:
        extras["failed_rungs"] = failed

    ref = bench_reference_path()
    headline = distil["batch8"]["tok_per_s"]
    extras["single_stream_tok_per_s"] = distil["batch1"]["tok_per_s"]
    extras["p50_latency_s"] = distil["p50_latency_s_short"]
    vs = round(headline / ref, 3) if ref > 0 else 0.0
    print(
        json.dumps(
            {
                "metric": "serve_tokens_per_sec_distilgpt2_batch8",
                "value": round(headline, 2),
                "unit": "tok/s",
                # artifact layout version (scripts/benchdiff.py refuses
                # majors it doesn't understand, so the trajectory tool
                # can evolve without silently misreading old rounds)
                "schema_version": 2,
                # prominent, TOP-LEVEL platform record (ROADMAP bench
                # hygiene): BENCH_*.json consumers must never have to dig
                # extras to learn what hardware produced the number
                "platform": platform,
                "device_kind": jax.devices()[0].device_kind,
                "device_count": len(jax.devices()),
                "vs_baseline": vs,
                "extras": extras,
            }
        ),
        flush=True,
    )
    if failed:
        log(f"FAILED rungs: {', '.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    # `python bench.py router_fairness`: the model-free fairness rung
    # standalone (no jax import) — prints the rung's
    # JSON alone so CI can gate on the token ratio directly
    if len(sys.argv) > 1 and sys.argv[1] == "router_fairness":
        print(json.dumps(bench_router_fairness()), flush=True)
        sys.exit(0)
    # `python bench.py fleet_elastic`: the elastic-fleet diurnal-ramp rung
    # standalone (model-free loopback fleet)
    if len(sys.argv) > 1 and sys.argv[1] == "fleet_elastic":
        print(json.dumps(bench_fleet_elastic()), flush=True)
        sys.exit(0)
    # `python bench.py fleet_sim`: the deterministic fleet-sim rung
    # standalone (virtual transport + clock)
    if len(sys.argv) > 1 and sys.argv[1] == "fleet_sim":
        print(json.dumps(bench_fleet_sim()), flush=True)
        sys.exit(0)
    # `python bench.py migration`: the live-migration drain rung standalone
    # (tiny random-init model — runs on whatever backend jax resolves)
    if len(sys.argv) > 1 and sys.argv[1] == "migration":
        print(json.dumps(bench_migration()), flush=True)
        sys.exit(0)
    # `python bench.py pipeline_interleave`: the MPMD interleave rung
    # standalone (tiny random-init model, loopback mesh, any platform)
    if len(sys.argv) > 1 and sys.argv[1] == "pipeline_interleave":
        print(json.dumps(bench_pipeline_interleave()), flush=True)
        sys.exit(0)
    # `python bench.py kv_quant`: the quantized-KV capacity rung standalone
    # (distilgpt2, bf16-vs-int8 pool at equal HBM budget, any platform)
    if len(sys.argv) > 1 and sys.argv[1] == "kv_quant":
        _use_compile_cache()
        print(json.dumps(bench_kv_quant()), flush=True)
        sys.exit(0)
    # `python bench.py lora_multi`: the batched multi-LoRA rung standalone
    # (distilgpt2, 8 adapters over one engine, parity + mixed-batch tok/s)
    if len(sys.argv) > 1 and sys.argv[1] == "lora_multi":
        _use_compile_cache()
        print(json.dumps(bench_lora_multi()), flush=True)
        sys.exit(0)
    # `python bench.py spec_model`: the model-tier speculative-decoding
    # rung standalone (tiny random-init models, loopback mesh cell, any
    # platform). Prints a FULL mini-artifact (schema_version, top-level
    # platform stamp, rung under extras) rather than the bare rung so
    # scripts/benchdiff.py can gate two standalone runs against each
    # other — that is the scripts/lint.sh trajectory gate.
    # `python bench.py obs_overhead`: the observatory sampler-overhead
    # rung standalone (pure-python hot loop, no model). Prints a FULL mini-artifact whose headline is the on/off
    # throughput RATIO so scripts/benchdiff.py can gate it run-to-run —
    # a ratio near 1.0 is the ISSUE 20 "negligible overhead" criterion.
    if len(sys.argv) > 1 and sys.argv[1] == "obs_overhead":
        rung = bench_obs_overhead()
        print(json.dumps({
            "metric": "obs_overhead_tok_per_s_ratio",
            "value": rung["ratio_on_off"],
            "unit": "ratio",
            "schema_version": 2,
            # pure-python CPU loop: the platform stamp is honest and
            # constant, so benchdiff never refuses on platform mismatch
            "platform": "cpu",
            "extras": {"obs_overhead": rung},
        }), flush=True)
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "spec_model":
        _use_compile_cache()
        import jax as _jax

        rung = bench_spec_model()
        print(json.dumps({
            "metric": "spec_model_acceptance_weighted_tok_per_s",
            "value": rung["model_local"]["acceptance_weighted_tok_per_s"],
            "unit": "tok/s",
            "schema_version": 2,
            "platform": _jax.devices()[0].platform,
            "extras": {"spec_model": rung},
        }), flush=True)
        sys.exit(0)
    sys.exit(main())
