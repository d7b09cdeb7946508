#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the serving path runs on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the tensor-parallel path, four chips

Default run, one JSON object per line:

- ``build``   which piece codec is in use (native C++ or hashlib);
- ``serve``   the real server (``python -m bee2bee_tpu serve-tpu --model
  gemma-2b --attention auto``: published widths, all 18 layers, bf16,
  seeded random weights) booted as this script's ONE jax child, then
  driven through the HTTP gateway the way a user would: a greedy
  ``POST /chat``, a streamed ``POST /v1/chat/completions`` and 8
  concurrent ``/chat``. The request set is sent three times: passes 1 and
  2 are labelled warm-ups (the server compiles each shape inside the first
  request that needs it, and a shape is batch bucket x table width, so the
  second pass, which starts from the batch width the first one left, still
  meets new ones — compile time is SET-UP), pass 3 is what the line reports.
  The printed TTFT / tok/s are SMOKE VALUES from one run: they prove the
  path ran, they are not measurements;
- ``device``  platform / device_kind / count / memory as the server child
  reports them through ``engine.info`` (``GET /providers``) — never
  assumed, and anything but ``tpu`` fails;
- ``kernel``  after the server child has exited (a chip belongs to one
  process at a time), a second child runs the ragged paged-attention
  kernel COMPILED (``interpret=False``) against the dense path
  (``models/core._attention`` over the gathered view) at the gemma-2b
  head shape;
- ``cache``   the compile-cache directory in use and what the run added.

With ``--chips 4`` only the multi-chip path runs: zephyr-7b (mistral-7b
widths, 32 layers, bf16) served on ``--mesh-shape model:4``, per-chip
parameter bytes checked, and — in a child that starts after the server has
exited — the same config cut to 8 layers built on one chip and on the
four-chip mesh from one seed, logits compared.

This process never imports jax. It exits non-zero, with ``"ok": false``
on its last line, when any phase fails: no CPU fallback, no smaller model.
The last line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"
SEED = 0
NEW_TOKENS = 64
PROMPT_CHARS = 63  # + BOS = a 64-token prompt under the byte tokenizer
CONCURRENT = 8
WARMUP_PASSES = 2
T_START = time.monotonic()
# the driver allows 1200 s; optional work starts only while this much is left
OPTIONAL_WORK_DEADLINE_S = 600.0

# The server sheds follow-ups with `503 slo_shed` once its SLO fast window
# burns (router/admission.py). A cold compile rides inside the first
# request of each shape and breaches the default 2048 ms TTFT objective,
# so a boot with an empty compile cache can shed everything after its
# warm-ups. A boot that hits this is repeated with shedding switched off
# through the admission configuration the node already reads; the SLO
# tracker itself keeps running. A shed request is never counted as answered.
NO_SHED = {"BEE2BEE_ADMISSION": json.dumps({"shed_burn_rate": 1e9})}

# kernel-vs-dense tolerance on bf16 attention outputs (inputs ~ N(0,1),
# outputs up to ~4 in magnitude): |kernel - dense| <= ATOL + RTOL * |dense|
# elementwise. bf16 keeps 8 mantissa bits (eps = 2^-8 ~ 0.0039, one ulp at
# magnitude 2..4 is 0.0156). The dense path rounds QK^T to bf16 BEFORE the
# softmax (scores up to ~64 at hd=256 carry up to 0.25 absolute error, 0.016
# after the 1/sqrt(hd) scale, i.e. ~1.6% on a probability); the kernel keeps
# scores in f32 and rounds P to bf16 before PV instead; both round the
# output once. 2e-2 + 2e-2*|x| is ~5 eps of the value plus one ulp of an
# O(1) value — far below what a wrong page, mask or head mapping produces
# (differences of the outputs' own magnitude).
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2

# one-chip vs four-chip logits tolerance (max abs difference; seeded random
# weights give logits of std ~1, printed beside it). Tensor parallelism
# splits each row-parallel matmul's reduction (wo, w_down: 2 per layer, 16
# at 8 layers) over 4 chips and all-reduces bf16 partial sums, so every one
# of them rounds in a different order than the one-chip sum: a random walk
# of ~16 steps of 2^-8 relative error on O(1) activations, amplified
# through the residual stream. 0.125 leaves that room and is still an order
# of magnitude under what a wrong shard or KV-head mapping produces
# (differences of the logits' own std).
TP_LOGITS_TOL = 0.125
TP_COMPARE_LAYERS = 8


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def elapsed() -> float:
    return round(time.monotonic() - T_START, 1)


class SmokeFailure(RuntimeError):
    """A phase failed; the message goes on the last line."""


# ------------------------------------------------------------------ HTTP


def _http(method: str, url: str, body: dict | None = None, timeout: float = 600.0):
    """(status, parsed JSON or text). Never raises on an HTTP error status."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8", errors="replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def _prompt(rng: random.Random) -> str:
    words = ("mesh", "node", "token", "cache", "block", "shard", "queue",
             "route", "draft", "batch", "page", "head", "layer", "chip")
    out = ""
    while len(out) < PROMPT_CHARS:
        out += rng.choice(words) + " "
    return out[:PROMPT_CHARS]


def _judge(status, body) -> dict:
    """One non-streamed /chat answer -> {ok, shed, ...smoke values}."""
    if status == 503 and isinstance(body, dict) and body.get("error_kind") == "slo_shed":
        return {"ok": False, "shed": True, "status": status}
    if status != 200 or not isinstance(body, dict) or body.get("error"):
        return {"ok": False, "shed": False, "status": status,
                "error": str(body)[:300]}
    tokens, text = int(body.get("tokens") or 0), body.get("text") or ""
    # non-finite logits argmax to token 0 (pad), which decodes to nothing:
    # an empty generation is how NaNs show through this gateway (it
    # returns no logprobs)
    good = tokens > 0 and len(text) > 0
    return {
        "ok": good, "shed": False, "status": status, "tokens": tokens,
        "chars": len(text), "finish_reason": body.get("finish_reason"),
        "ttft_ms_smoke": body.get("ttft_ms"),
        "tok_per_s_smoke": body.get("tokens_per_sec"),
        **({} if good else {"error": "empty generation"}),
    }


def chat(base: str, model: str, prompt: str) -> dict:
    status, body = _http("POST", f"{base}/chat", {
        "prompt": prompt, "model": model, "max_new_tokens": NEW_TOKENS,
        "temperature": 0.0,
    })
    return _judge(status, body)


def chat_streamed(base: str, model: str, prompt: str) -> dict:
    """POST /v1/chat/completions with stream=true; reads the SSE events."""
    req = urllib.request.Request(
        f"{base}/v1/chat/completions", method="POST",
        data=json.dumps({
            "model": model, "stream": True, "temperature": 0.0,
            "max_tokens": NEW_TOKENS,
            "messages": [{"role": "user", "content": prompt}],
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    first = None
    chunks = chars = 0
    finish = error = None
    done = False
    try:
        with urllib.request.urlopen(req, timeout=600.0) as resp:
            status = resp.status
            for raw in resp:
                line = raw.decode("utf-8", errors="replace").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                ev = json.loads(payload)
                if ev.get("error"):
                    error = str(ev["error"])[:300]
                    continue
                choice = (ev.get("choices") or [{}])[0]
                piece = (choice.get("delta") or {}).get("content") or ""
                if piece:
                    chunks += 1
                    chars += len(piece)
                    if first is None:
                        first = time.monotonic()
                finish = choice.get("finish_reason") or finish
    except urllib.error.HTTPError as e:
        body = e.read().decode("utf-8", errors="replace")
        shed = e.code == 503 and "slo_shed" in body
        return {"ok": False, "shed": shed, "status": e.code,
                **({} if shed else {"error": body[:300]})}
    wall = time.monotonic() - t0
    good = status == 200 and done and error is None and chars > 0
    return {
        "ok": good, "shed": False, "status": status, "chunks": chunks,
        "chars": chars, "finish_reason": finish,
        "ttft_ms_smoke": round((first - t0) * 1000.0, 1) if first else None,
        "wall_s_smoke": round(wall, 3),
        **({} if good else {"error": error or "no content / no [DONE]"}),
    }


def chat_concurrent(base: str, model: str, prompts: list[str]) -> dict:
    t0 = time.monotonic()
    results = []
    with ThreadPoolExecutor(len(prompts)) as pool:
        for fut in [pool.submit(chat, base, model, p) for p in prompts]:
            try:
                results.append(fut.result())
            except Exception as e:  # noqa: BLE001 — reported as this request's failure
                results.append({"ok": False, "shed": False,
                                "error": f"{type(e).__name__}: {e}"})
    wall = time.monotonic() - t0
    tokens = sum(r.get("tokens", 0) for r in results if r["ok"])
    return {
        "ok": all(r["ok"] for r in results),
        "shed": any(r["shed"] for r in results),
        "answered": sum(r["ok"] for r in results), "sent": len(results),
        "tokens": tokens, "wall_s_smoke": round(wall, 3),
        "aggregate_tok_per_s_smoke": round(tokens / wall, 2) if wall > 0 else None,
        "errors": [r.get("error") or f"status {r.get('status')}"
                   for r in results if not r["ok"]][:3],
    }


def scrape_metrics(base: str) -> dict:
    """Compile counts/seconds per jit root and hbm_bytes per component."""
    status, text = _http("GET", f"{base}/metrics", timeout=60.0)
    if status != 200 or not isinstance(text, str):
        raise SmokeFailure(f"GET /metrics -> {status}")
    out: dict = {"compiles": {}, "compile_seconds": {}, "hbm_bytes": {}}
    for line in text.splitlines():
        for name, key in (
            ("bee2bee_engine_compiles_total", "compiles"),
            ("bee2bee_engine_compile_seconds_total", "compile_seconds"),
            ("bee2bee_engine_hbm_bytes", "hbm_bytes"),
        ):
            if line.startswith(name + "{"):
                label = line[line.index('"') + 1:line.rindex('"')]
                out[key][label] = round(float(line.rsplit(" ", 1)[1]), 3)
    return out


# ------------------------------------------------------------ the server


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """``python -m bee2bee_tpu serve-tpu ...`` as a child process: the one
    process that touches jax while it lives."""

    def __init__(self, model: str, mesh_shape: str | None, env_extra: dict, tag: str):
        self.model = model
        ws_port, api_port = _free_port(), _free_port()
        self.base = f"http://127.0.0.1:{api_port}"
        OUT.mkdir(exist_ok=True)
        self.log_path = OUT / f"smoke_server_{tag}.log"
        cmd = [sys.executable, "-m", "bee2bee_tpu", "serve-tpu",
               "--model", model, "--attention", "auto",
               "--port", str(ws_port), "--api-port", str(api_port)]
        if mesh_shape:
            cmd += ["--mesh-shape", mesh_shape]
        env = dict(os.environ)
        # hermetic node state (config.json, logs) inside the checkout
        env["BEE2BEE_TPU_HOME"] = str(OUT / "smoke_home")
        env["BEE2BEE_HOST"] = "127.0.0.1"
        env.update(env_extra)
        self.cmd = " ".join(cmd)
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 40) -> str:
        try:
            return "\n".join(self.log_path.read_text(errors="replace").splitlines()[-n:])
        except OSError:
            return ""

    def check_alive(self, what: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"server child exited rc={rc} while {what}; log tail:\n{self.log_tail()}"
            )

    def provider(self) -> dict | None:
        """This node's own provider record for the model (``engine`` in it
        is ``engine.info``), once the model is announced."""
        status, body = _http("GET", f"{self.base}/providers", timeout=60.0)
        if status == 200 and isinstance(body, dict):
            for p in body.get("providers") or []:
                if p.get("local") and self.model in (p.get("models") or []):
                    return p
        return None

    def wait_serving(self, require_platform: str, timeout_s: float = 900.0) -> dict:
        """Wait until the model is announced; returns its provider record.
        Fails EARLY — before the weights are built — when the process's
        jax backend is not the required platform."""
        platform_checked = False
        while time.monotonic() - self.t0 < timeout_s:
            self.check_alive("booting")
            try:
                if not platform_checked:
                    status, home = _http("GET", f"{self.base}/", timeout=30.0)
                    if status == 200 and isinstance(home, dict):
                        accel = (home.get("metrics") or {}).get("accelerator") or {}
                        if accel.get("platform") != require_platform:
                            raise SmokeFailure(
                                f"jax in the server child found platform="
                                f"{accel.get('platform')!r} "
                                f"({accel.get('device_kinds')}), need "
                                f"{require_platform!r}: no accelerator, no smoke"
                            )
                        platform_checked = True
                prov = self.provider()
                if prov is not None:
                    self.boot_s = round(time.monotonic() - self.t0, 1)
                    return prov
            except OSError:  # URLError, refused, reset, timed out
                pass  # gateway not up yet
            time.sleep(1.0)
        raise SmokeFailure(
            f"server not serving after {timeout_s:.0f} s; log tail:\n{self.log_tail()}"
        )

    def stop(self) -> None:
        """Stop the child and wait for it: the chip must be free before
        the next child starts."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30.0)
        self._log.close()


def device_record(engine_info: dict) -> dict:
    return {"platform": engine_info.get("platform"),
            "kind": engine_info.get("device_kind"),
            "count": engine_info.get("device_count")}


def serve_boot(model: str, mesh_shape: str | None, env_extra: dict, tag: str,
               require_platform: str = "tpu") -> dict:
    """Boot the server, drive the request set WARMUP_PASSES + 1 times (the
    last pass is reported), scrape its own accounts, stop it. Returns the
    serve line (``ok`` False when any reported request failed or was shed)."""
    rng = random.Random(SEED)
    single, streamed = _prompt(rng), _prompt(rng)
    burst = [_prompt(rng) for _ in range(CONCURRENT)]
    srv = Server(model, mesh_shape, env_extra, tag)
    try:
        prov = srv.wait_serving(require_platform)
        info = prov.get("engine") or {}
        line: dict = {
            "phase": "serve", "boot": tag, "cmd": srv.cmd, "model": model,
            "env": env_extra, "boot_to_serving_s": srv.boot_s,
            "n_params": info.get("n_params"), "dtype": info.get("dtype"),
            "mesh": info.get("mesh"), "max_seq_len": info.get("max_seq_len"),
            "attention_resolved": info.get("attention"),
            "device": device_record(info),
        }
        if info.get("platform") != require_platform:
            raise SmokeFailure(
                f"engine.info reports platform={info.get('platform')!r}, "
                f"need {require_platform!r}"
            )
        want_attention = "flash" if require_platform == "tpu" else "dense"
        if info.get("attention") != want_attention:
            raise SmokeFailure(
                f"--attention auto resolved to {info.get('attention')!r}, not "
                f"{want_attention!r}: the ragged kernel would not run"
            )

        def request_set() -> dict:
            return {
                "chat": chat(srv.base, model, single),
                "v1_stream": chat_streamed(srv.base, model, streamed),
                f"chat_x{CONCURRENT}": chat_concurrent(srv.base, model, burst),
            }

        t0 = time.monotonic()
        warm = request_set()
        for _ in range(WARMUP_PASSES - 1):
            again = request_set()
            warm = {k: {"ok": v["ok"] and again[k]["ok"],
                        "shed": v["shed"] or again[k]["shed"]}
                    for k, v in warm.items()}
        line["warmup_passes_s_setup"] = round(time.monotonic() - t0, 1)
        after_warm = scrape_metrics(srv.base)
        srv.check_alive("warming up")
        reported = request_set()
        after = scrape_metrics(srv.base)
        srv.check_alive("serving")
        line["warmup"] = {k: {"ok": v["ok"], "shed": v["shed"]} for k, v in warm.items()}
        line["requests_smoke_values"] = reported
        line["compiles"] = after["compiles"]
        line["compile_seconds_setup"] = after["compile_seconds"]
        line["compiles_during_reported_pass"] = (
            sum(after["compiles"].values()) - sum(after_warm["compiles"].values())
        )
        line["hbm_bytes"] = after["hbm_bytes"]
        final = (srv.provider() or {}).get("engine") or {}
        hbm = (final.get("introspect") or {}).get("hbm") or {}
        line["devices"] = hbm.get("devices")
        line["shed"] = sorted(
            f"{which}:{k}" for which, rs in (("warmup", warm), ("reported", reported))
            for k, v in rs.items() if v["shed"]
        )
        line["ok"] = all(v["ok"] for v in reported.values())
        line["elapsed_s"] = elapsed()
        return line
    finally:
        srv.stop()


# ------------------------------------------------------- children on jax
# (run as `python -c "import chip_smoke; chip_smoke.child_...()"` from ROOT,
# each only after the server child has exited)


def _run_child(entry: str, timeout_s: float = 900.0) -> None:
    """Run one jax child that prints its own phase line; non-zero rc fails."""
    proc = subprocess.run(
        [sys.executable, "-c", f"import chip_smoke; chip_smoke.{entry}()"],
        cwd=ROOT, timeout=timeout_s,
    )
    if proc.returncode != 0:
        raise SmokeFailure(f"child {entry} failed rc={proc.returncode}")


def _require_tpu(jax):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"jax found platform={dev.platform!r}, need 'tpu'")
    return dev


# The kernel child's cases: the two shapes the serve phase issues at the
# gemma-2b head shape, then the production shapes no other phase puts on a
# chip. (H, Hkv, hd), rows, queries a row, table width, sliding window, and
# each row's LENGTH range (a row's table maps ceil(length / 16) distinct
# pool blocks, the rest is the null block 0, as the engine's pow2-wide
# batch-wide tables are).
KERNEL_CASES = {
    "decode": dict(heads=(8, 1, 256), B=8, T=1, MB=8, lengths=(2, 127)),
    "prefill_chunk": dict(heads=(8, 1, 256), B=1, T=64, MB=4, lengths=(64, 64)),
    # phi-3-mini, the short cell's decode: 16 rows of 32-384 tokens in a
    # 32-page table, so most rows end with dead tiles
    "phi3_decode": dict(heads=(32, 32, 96), B=16, T=1, MB=32, lengths=(32, 384)),
    # the long cell's: a 4-row bucket, 128-page tables nearly all live
    "phi3_long_decode": dict(heads=(32, 32, 96), B=4, T=1, MB=128,
                             lengths=(1600, 1900)),
    # one shard of mistral-7b under model:4: 2 KV heads, groups of 4
    "mistral_shard_decode": dict(heads=(8, 2, 128), B=32, T=1, MB=64,
                                 window=4096, lengths=(64, 1000)),
    "phi3_prefill_2048": dict(heads=(32, 32, 96), B=1, T=2048, MB=128,
                              lengths=(2048, 2048)),
    # falcon-h1's attention, the wide cell's decode: GQA 20/4 x 128, 64 rows
    "h1_decode": dict(heads=(20, 4, 128), B=64, T=1, MB=32, lengths=(64, 500)),
    # smallthinker's, the document cell's decode (PR 44): GQA 28/4 x 128, 32
    # rows 4k-8k deep in 1,024-page tables behind the 4,096 window
    "st_decode": dict(heads=(28, 4, 128), B=32, T=1, MB=1024, window=4096,
                      lengths=(4300, 8400)),
}
# microseconds a call of the one-page-one-head kernel this one replaced, on
# the same inputs (tree ee64104, TPU v5 lite, my chip run, PR 27; the two
# gemma cases sit on the host's ~230 us a dispatch, not on the device):
# printed beside each case's own time
ONE_PAGE_KERNEL_US = {
    "decode": 243.9, "prefill_chunk": 234.1, "phi3_decode": 3386.7,
    "phi3_long_decode": 6079.7, "mistral_shard_decode": 1175.6,
    "phi3_prefill_2048": 22128.3,
}
KERNEL_TIMED_CALLS = 20
KERNEL_BLOCK = 16  # EngineConfig.kv_block_size default


def _kernel_case(name: str, case: dict, rng) -> dict:
    """One case of the kernel child: build the pool and the tables, run the
    compiled kernel against the dense path, time KERNEL_TIMED_CALLS calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.ops.ragged import ragged_paged_attention

    cfg = get_config("gemma-2b")  # core._attention reads no shape from it
    BS = KERNEL_BLOCK
    H, Hkv, hd = case["heads"]
    B, T, MB, window = case["B"], case["T"], case["MB"], case.get("window", 0)
    lengths = rng.integers(case["lengths"][0], case["lengths"][1] + 1, size=B)
    pages = -(-lengths // BS)
    NB = int(pages.sum()) + 1
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.bfloat16)
    # one layer of core.init_paged_pool's ``kv`` leaf: K beside V, page-major
    kv_pool = jnp.asarray(rng.standard_normal((NB, 2, Hkv, BS, hd)), jnp.bfloat16)
    # every row maps its own distinct pool blocks; past its live extent
    # the table holds the null block 0
    ids = iter(rng.permutation(np.arange(1, NB)))
    tables = np.zeros((B, MB), np.int32)
    for b in range(B):
        tables[b, : pages[b]] = [next(ids) for _ in range(pages[b])]
    tables = jnp.asarray(tables)
    off = jnp.asarray(lengths - T, jnp.int32)

    def kernel(q, kv_pool, tables, off):
        return ragged_paged_attention(
            q, kv_pool, tables, off, window=window, interpret=False
        )

    def dense(q, kv_pool, tables, off):
        # the engine's dense path: gather the mapped blocks into the
        # [B, S, Hkv, hd] views, mask by position, core._attention
        S = MB * BS
        k, v = jnp.transpose(kv_pool[tables], (2, 0, 1, 4, 3, 5)).reshape(
            2, B, S, Hkv, hd)
        qpos = (off[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :])[:, :, None]
        kvpos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
        mask = kvpos <= qpos
        if window:
            mask = mask & (kvpos > qpos - window)
        return core._attention(q, k, v, mask[:, None], cfg)

    args = (q, kv_pool, tables, off)
    lowered = jax.jit(kernel).lower(*args)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    got = np.asarray(compiled(*args), np.float32)
    want = np.asarray(jax.jit(dense)(*args), np.float32)
    t0 = time.perf_counter()
    for _ in range(KERNEL_TIMED_CALLS):
        out = compiled(*args)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / KERNEL_TIMED_CALLS * 1e6
    diff = np.abs(got - want)
    worst = float(np.max(diff / (KERNEL_ATOL + KERNEL_RTOL * np.abs(want))))
    in_place = _in_place_case(args, window)
    return {
        **in_place,
        "H": H, "Hkv": Hkv, "hd": hd, "B": B, "T": T, "table_width": MB,
        "window": window, "live_pages": int(pages.sum()),
        "out_shape": list(got.shape),
        "finite": bool(np.isfinite(got).all()),
        "tpu_custom_call_in_lowered_text": has_kernel,
        "max_abs_diff_vs_dense": float(np.max(diff)),
        "worst_diff_over_tolerance": worst,
        "out_abs_max": float(np.max(np.abs(want))),
        "us_per_call": round(us, 1),
        "one_page_kernel_us_per_call": ONE_PAGE_KERNEL_US.get(name),
        "ok": bool(
            has_kernel and np.isfinite(got).all() and got.shape == (B, T, H * hd)
            and worst <= 1.0 and in_place["write_matches_scatter"]
            and in_place["stacked_read_matches_sliced_read"]
        ),
    }


def _in_place_case(args, window) -> dict:
    """The same case on a STACKED, lane-aligned pool of two layers, as
    core.forward runs it on a TPU since PR 29: ONE page-write of the chunk's K
    and V into layer 1, compiled and donated, against XLA's scatter (bit for
    bit in every block a row owns), then the kernel reading layer 1 in place
    against its read of the unaligned slice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.ops.ragged import paged_kv_write, ragged_paged_attention

    q, kv_pool, tables, off = args
    BS, T = KERNEL_BLOCK, q.shape[1]
    Hkv, hd = kv_pool.shape[2], kv_pool.shape[-1]
    half = (q[:, :, :Hkv] * 0.5).astype(kv_pool.dtype)  # [B, T, Hkv, hd]
    new = jnp.stack([half, half * 0.5], axis=2)  # K beside V, as a page lies
    positions = off[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(tables, positions // BS, axis=1)
    want = np.asarray(kv_pool.at[blk, :, :, positions % BS].set(new), np.float32)
    lanes = ((0, 0),) * 5 + ((0, -hd % 128),)
    other = jnp.flip(kv_pool, axis=1)  # layer 0: must come back untouched
    stacked = jnp.pad(jnp.stack([other, kv_pool]), lanes)
    write = jax.jit(
        lambda pool, new: paged_kv_write(
            pool, new, tables, off, jnp.int32(1), interpret=False),
        donate_argnums=(0,),
    ).lower(stacked, new).compile()
    stacked = write(stacked, new)
    got = np.asarray(stacked, np.float32)
    pad_is_zero = not got[..., hd:].any()
    got = got[..., :hd]
    t0 = time.perf_counter()
    for _ in range(KERNEL_TIMED_CALLS):
        stacked = write(stacked, new)
    stacked.block_until_ready()
    us = (time.perf_counter() - t0) / KERNEL_TIMED_CALLS * 1e6

    def read(pool, layer=None):
        return ragged_paged_attention(
            q, pool, tables, off, window=window, interpret=False, layer=layer)

    sliced = jax.jit(read)(stacked[1, ..., :hd])
    in_place = jax.jit(read)(stacked, jnp.int32(1))
    return {
        "write_matches_scatter": bool(
            np.array_equal(got[1][1:], want[1:]) and pad_is_zero
            and np.array_equal(got[0], np.asarray(other, np.float32))
        ),
        "write_us_per_call": round(us, 1),
        # the same pages through 128 lanes instead of hd: the kernel-vs-dense
        # tolerance (the zero lanes may regroup the MXU's partial sums)
        "stacked_read_matches_sliced_read": bool(np.all(
            np.abs(np.asarray(in_place, np.float32) - np.asarray(sliced, np.float32))
            <= KERNEL_ATOL + KERNEL_RTOL * np.abs(np.asarray(sliced, np.float32))
        )),
    }


def _latent_case(B: int, T: int, MB: int, ctx: int) -> dict:
    """JoyAI-LLM-Flash's latent page-write + read at the published shapes
    (32 heads over ONE 576-wide row a token, stored in 640 lanes, values its
    first 512 columns) on a stacked pool of two layers, compiled: the write
    against XLA's scatter (bit for bit), the read against the dense path over
    the gathered rows (core._latent_attention), microseconds a call each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.ops.ragged import paged_kv_write, ragged_paged_attention

    cfg = get_config("joyai-llm-flash-5l")
    W, R, H, BS = cfg.latent_width, cfg.mla_kv_rank, cfg.n_heads, KERNEL_BLOCK
    rng = np.random.default_rng(SEED)
    lens = np.minimum(rng.integers(ctx // 2, ctx + 1, size=B), MB * BS - T).astype(np.int32)
    off = jnp.asarray(lens)
    need = -(-(lens + T) // BS)
    tables = np.zeros((B, MB), np.int32)
    nxt = 1
    for b in range(B):
        tables[b, :need[b]] = np.arange(nxt, nxt + need[b])
        nxt += need[b]
    NB = nxt + 1
    tables = jnp.asarray(tables)
    ks = jax.random.split(jax.random.key(SEED), 3)
    rows = jax.random.normal(ks[0], (NB, 1, BS, W), jnp.bfloat16)
    q = (jax.random.normal(ks[1], (B, T, H, W), jnp.float32) * 0.2).astype(jnp.bfloat16)
    new = jax.random.normal(ks[2], (B, T, 1, W), jnp.bfloat16)
    sm = 1.0 / (cfg.mla_nope_dim + cfg.mla_rope_dim) ** 0.5
    positions = off[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(tables, positions // BS, axis=1)
    want_rows = rows.at[blk, 0, positions % BS].set(new[:, :, 0])
    stacked = jnp.pad(jnp.stack([rows * 0, rows]), ((0, 0),) * 4 + ((0, -W % 128),))
    write = jax.jit(
        lambda pool, new: paged_kv_write(
            pool, new, tables, off, jnp.int32(1), interpret=False),
        donate_argnums=(0,)).lower(stacked, new).compile()
    stacked = write(stacked, new)
    got_rows = np.asarray(stacked[1, ..., :W], np.float32)
    wrote = bool(np.array_equal(got_rows[1:], np.asarray(want_rows, np.float32)[1:])
                 and not np.asarray(stacked[0], np.float32).any()
                 and not np.asarray(stacked[1, ..., W:], np.float32).any())

    def clock(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(KERNEL_TIMED_CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / KERNEL_TIMED_CALLS * 1e6

    t0 = time.perf_counter()
    for _ in range(KERNEL_TIMED_CALLS):
        stacked = write(stacked, new)
    stacked.block_until_ready()
    write_us = (time.perf_counter() - t0) / KERNEL_TIMED_CALLS * 1e6
    lowered = jax.jit(lambda q, pool: ragged_paged_attention(
        q, pool, tables, off, sm_scale=sm, interpret=False,
        layer=jnp.int32(1), v_width=R)).lower(q, stacked)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    got, read_us = clock(lowered.compile(), q, stacked)

    def dense(q, pool):
        lat = pool[1][tables].reshape(B, MB * BS, -1)[..., :W]
        mask = jnp.arange(MB * BS)[None, None, :] <= positions[:, :, None]
        return core._latent_attention(q, lat, mask[:, None], R, sm).reshape(B, T, H * R)

    want, dense_us = clock(jax.jit(dense), q, stacked)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.abs(got - want)
    worst = float(np.max(diff / (KERNEL_ATOL + KERNEL_RTOL * np.abs(want))))
    live_rows = int((lens + T).sum())
    return {
        "B": B, "T": T, "H": H, "row": W, "values": R, "table_width": MB,
        "live_rows": live_rows, "tpu_custom_call_in_lowered_text": has_kernel,
        "write_matches_scatter": wrote, "write_us_per_call": round(write_us, 1),
        "worst_diff_over_tolerance": worst, "max_abs_diff_vs_dense": float(diff.max()),
        "us_per_call": round(read_us, 1), "dense_us_per_call": round(dense_us, 1),
        "published_row_GBs": round(live_rows * W * 2 / read_us / 1e3, 1),
        "ok": bool(has_kernel and wrote and np.isfinite(got).all() and worst <= 1.0),
    }


def _grouped_case(tokens: int, device_kind: str, layers: int = 4) -> dict:
    """The dropless expert layer's grouped product (ops/grouped.py) at the
    published shapes: ``tokens`` x 8 sorted assignments over 256 experts of
    2048 x 768, the experts a STACK of ``layers`` layers read in place at layer
    2, compiled, against a per-row dense product of the gathered matrices;
    then a scan over the layers as core.forward runs it, microseconds a matrix
    a layer and the share of reading the touched experts once at the chip's
    peak."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.ops.grouped import grouped_matmul

    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    hbm = peaks[device_kind]["hbm_bytes_per_s"]
    E, D, F, K = 256, 2048, 768, 8
    rng = np.random.default_rng(SEED + tokens)
    flat = np.concatenate([rng.choice(E, K, replace=False) for _ in range(tokens)])
    gs = np.bincount(flat, minlength=E).astype(np.int32)
    M, touched = tokens * K, int((gs > 0).sum())
    w = jax.random.normal(jax.random.key(SEED), (layers, E, D, F), jnp.bfloat16) * 0.02
    x = jax.random.normal(jax.random.key(SEED + 1), (M, D), jnp.bfloat16)
    gsd = jnp.asarray(gs)

    def product(x, w, layer):
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * E,), jnp.int32), gsd, (layer * E,))
        return grouped_matmul(x, w.reshape(layers * E, D, F), sizes, interpret=False)

    lowered = jax.jit(product).lower(x, w, jnp.int32(2))
    has_kernel = "tpu_custom_call" in lowered.as_text()
    got = np.asarray(lowered.compile()(x, w, jnp.int32(2)), np.float32)
    eid = np.repeat(np.arange(E), gs)
    worst, step = 0.0, 256  # the dense side in blocks: a gathered matrix a row
    for s in range(0, min(M, 1024), step):
        want = np.asarray(jnp.einsum(
            "md,mdf->mf", x[s:s + step], w[2][eid[s:s + step]],
            preferred_element_type=jnp.float32))
        d = np.abs(got[s:s + step] - want)
        worst = max(worst, float(np.max(d / (KERNEL_ATOL + KERNEL_RTOL * np.abs(want)))))

    def every_layer(x, w):
        def one(acc, layer):
            return acc + product(x, w, layer)[:, :8].astype(jnp.float32).sum(), None
        return jax.lax.scan(one, jnp.float32(0), jnp.arange(layers, dtype=jnp.int32))[0]

    fn = jax.jit(every_layer)
    jax.block_until_ready(fn(x, w))
    t0 = time.perf_counter()
    for _ in range(KERNEL_TIMED_CALLS):
        out = fn(x, w)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / (KERNEL_TIMED_CALLS * layers) * 1e6
    floor_us = touched * D * F * 2 / hbm * 1e6
    return {
        "assignments": M, "experts": E, "touched": touched, "K": D, "N": F,
        "tpu_custom_call_in_lowered_text": has_kernel,
        "worst_diff_over_tolerance": worst, "us_per_matrix_per_layer": round(us, 1),
        "touched_once_at_peak_us": round(floor_us, 1),
        "share_of_hbm_peak": round(floor_us / us, 4),
        "ok": bool(has_kernel and np.isfinite(got).all() and worst <= 1.0),
    }


def _state_step_case(device_kind: str, preset: str, rows: int = 64) -> dict:
    """The one-step state kernel (ops/ssm_step.py) at a wide cell's shape:
    ``rows`` x the mixer of ``preset`` (falcon-h1-34b-6l: 32 heads x [128, 256]
    float32, 2 groups, 6 state layers; granite-4.0-h-small-10l-e36: 128 heads
    x [64, 128], 1 group, 9 state layers), a stack as deep as the
    configuration's state. One compiled, donated call on layer 3 against the
    XLA recurrence (the new slice bit for bit - the same float32 products in
    the same order; y within the bound any two orders of an N-term sum keep;
    every other layer untouched), then KERNEL_TIMED_CALLS passes of a scan
    over all the layers with the state as its carry, as core.forward calls
    it: microseconds a call and the GB/s of its one read + one write of a
    layer's slice against the chip's peak (benchmark/peaks.json: 819 on a
    v5e), beside a BARE COPY of the same blocks (the same call with the body
    ``hout[...] = h[...]``: what the block's DMAs alone take) and the XLA
    lines in the same scan (there the compiler fuses the update into the
    write-back and reads the slice a second time for y; outside a scan it
    does not)."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.ops import ssm_step
    from bee2bee_tpu.ops.ssm_step import _head_tile, ssm_state_step, ssm_state_step_xla

    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    hbm_gbs = peaks[device_kind]["hbm_bytes_per_s"] / 1e9  # an unlisted device is an error
    cfg = get_config(preset)
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    layers = cfg.state_layers
    B, layer = rows, jnp.int32(3)
    ks = jax.random.split(jax.random.key(SEED), 6)
    state = jax.random.normal(ks[0], (layers, B, H, P, N), jnp.float32)
    dt = jnp.abs(jax.random.normal(ks[1], (B, H))) * 0.3
    x = jax.random.normal(ks[2], (B, H, P))
    Bm, Cm = (jax.random.normal(k, (B, G, N)) for k in ks[3:5])
    A = -jnp.exp(jax.random.normal(ks[5], (H,)))

    def xla(state, layer, *inputs):  # what every decode step ran before the kernel
        h, y = ssm_state_step_xla(state[layer], *inputs)
        return state.at[layer].set(h), y

    args = (layer, dt, x, Bm, Cm, A)
    want_state, want_y = jax.jit(xla)(state, *args)
    mag = jnp.sum(jnp.abs(  # sum_n |h C|: what bounds two orders of y's sum
        want_state[3].reshape(B, G, H // G, P, N) * Cm[:, :, None, None, :]), -1)
    lowered = jax.jit(
        lambda st, *a: ssm_state_step(st, *a, interpret=False), donate_argnums=(0,)
    ).lower(state, *args)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    kernel = lowered.compile()
    got_state, got_y = kernel(state + 0, *args)
    state_diff = float(jnp.max(jnp.abs(got_state[3] - want_state[3])))
    others_same = bool(jnp.array_equal(got_state[:3], state[:3])
                       and jnp.array_equal(got_state[4:], state[4:]))
    y_diff = np.abs(np.asarray(got_y) - np.asarray(want_y))
    y_bound = 2 * (N - 1) * float(np.finfo(np.float32).eps) * np.asarray(mag).reshape(B, H, P)
    del want_state

    def timed(step, st):
        def every_layer(st):
            def one(carry, li):
                st, acc = carry
                st, y = step(st, li, dt, x + 0 * acc[:1, :1, :1], Bm, Cm, A)
                return (st, acc + y), None
            return jax.lax.scan(
                one, (st, jnp.zeros((B, H, P), jnp.float32)),
                jnp.arange(layers, dtype=jnp.int32))[0]

        fn = jax.jit(every_layer, donate_argnums=(0,))
        st, _ = fn(st)  # compiles; outside the clock
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        for _ in range(KERNEL_TIMED_CALLS):
            st, acc = fn(st)
        jax.block_until_ready((st, acc))
        return (time.perf_counter() - t0) / (KERNEL_TIMED_CALLS * layers) * 1e6, st

    def copy_body(*refs, **_):  # the same call's blocks, moved and nothing else
        h_ref, hout_ref, y_ref = refs[-3:]
        hout_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    us, st = timed(lambda *a: ssm_state_step(*a, interpret=False), got_state)
    with mock.patch.object(ssm_step, "_step_kernel", copy_body):
        copy_us, st = timed(lambda *a: ssm_state_step(*a, interpret=False), st)
    del st
    xla_us, _ = timed(xla, state)
    moved = 2 * B * H * P * N * 4
    gbs = moved / us / 1e3
    return {
        "preset": preset, "B": B, "layers": layers, "H": H, "P": P, "N": N, "groups": G,
        "head_tile": _head_tile(H, P, N)[0],
        "tpu_custom_call_in_lowered_text": has_kernel,
        "state_max_abs_diff_vs_xla": state_diff,
        "other_layers_untouched": others_same,
        "y_max_abs_diff_vs_xla": float(y_diff.max()),
        "y_worst_diff_over_bound": float(np.max(y_diff / (y_bound + 1e-30))),
        "us_per_call": round(us, 1),
        "bare_copy_us_per_call": round(copy_us, 1),
        "xla_us_per_call": round(xla_us, 1),
        "bytes_moved_per_call": moved,
        "GBs": round(gbs, 1),
        "share_of_hbm_peak": round(gbs / hbm_gbs, 4),
        "ok": bool(has_kernel and state_diff == 0.0 and others_same
                   and np.all(y_diff <= y_bound)),
    }


# The read alone where a copy may pass a page (PR 53): smallthinker's decode
# and 2,048 chunk (GQA 28 / 4 x 128, 32 KB pages, 1,024-page tables), full
# and behind the 4,096 window. (rows, queries a row, each row's LENGTH range)
READ_RUNS_CASES = {
    "st_decode": dict(B=32, T=1, lengths=(4300, 8400)),
    "st_chunk_2048": dict(B=1, T=2048, lengths=(6144, 8192)),
}
# The pools PR 59 moved onto the kernel's own copies, at their cells' decode
# shapes: (H, Hkv, pool width), table width, the contexts of the cell's mix,
# and ``ahead``: every row owns 0-2 blocks past its offset (the decode
# window's), which the page operands copy and the kernel's own copies skip
OWN_COPIES_CASES = {
    # ouro-2.6b: 16 MHA heads x 128, pages of 128 KB, a tile of 8
    "ouro_decode": dict(B=16, T=1, heads=(16, 16, 128), MB=32, lengths=(40, 250),
                        ahead=2),
    # joyai's latent rows: 32 heads over ONE 640-lane row, pages of 20 KB
    "joyai_decode": dict(B=64, T=1, heads=(32, 1, 640), MB=32, v_width=512,
                         lengths=(60, 480), ahead=2),
    # phi-3-mini's lane-aligned pool: 32 MHA heads x 128, pages of 256 KB
    "phi3_decode": dict(B=16, T=1, heads=(32, 32, 128), MB=32, lengths=(40, 420),
                        ahead=2),
    "phi3_long_decode": dict(B=4, T=1, heads=(32, 32, 128), MB=128,
                             lengths=(1560, 1850), ahead=2, layers=16),
}


def _bare_read_us(n_bytes: int, piece_bytes: int = 2**20) -> float:
    """Microseconds to bring ``n_bytes`` of one array into VMEM and nothing
    else: a pipelined pallas read in pieces of 1 MB (what a tile of the
    ragged read moves a step), the body one store."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rows = piece_bytes // (128 * 2)
    n = max(n_bytes // piece_bytes, 1)
    x = jnp.ones((n, rows, 128), jnp.bfloat16)

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[0, :8].astype(jnp.float32)

    fn = jax.jit(lambda x: pl.pallas_call(
        body, grid=(n,), in_specs=[pl.BlockSpec((1, rows, 128), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(x))
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(KERNEL_TIMED_CALLS):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / KERNEL_TIMED_CALLS * 1e6 * n_bytes / (n * piece_bytes)


def _read_runs_case(case: dict, window: int, run_bytes=(None, 0), layers: int = 8) -> dict:
    """ragged_paged_attention alone on a stacked pool of ``layers`` layers,
    a scan over them as core.forward's layer loop calls it, on tables whose
    every row is ONE ascending run of pool blocks (what the allocator hands
    a prompt since PR 53) and on the same pages PERMUTED (every copy group
    broken: the page-by-page side), each under the planned copy group and
    with ``ops.ragged._RUN_BYTES`` = 0 (R = 1: the page-operand program, the
    parent's kernel byte for byte; any other budget: the copy groups PR 53
    tried before it kept the whole tile): microseconds a call, the GB/s of the
    pages its items bring, and a bare read of the same bytes. The two tables
    name the same keys in the same order: their outputs must be bit-equal,
    and within the kernel tolerance of the page-operand program's."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.ops import ragged

    (H, Hkv, hd), BS = case.get("heads", (28, 4, 128)), KERNEL_BLOCK
    MB, v_width = case.get("MB", 1024), case.get("v_width")
    B, T = case["B"], case["T"]
    rng = np.random.default_rng(SEED)
    lengths = rng.integers(case["lengths"][0], case["lengths"][1] + 1, size=B)
    # (a row's table maps the blocks its tokens fill and those it owns ahead)
    pages = np.minimum(
        -(-lengths // BS) + rng.integers(0, case.get("ahead", 0) + 1, size=B), MB)
    NB = int(pages.sum()) + 1
    key = jax.random.key(SEED)
    parts = (1,) if v_width else (2, Hkv)  # a latent row's unit axis
    pool = jax.random.normal(key, (layers, NB, *parts, BS, hd), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, hd), jnp.bfloat16)
    off = jnp.asarray(lengths - T, jnp.int32)
    runs = np.zeros((B, MB), np.int32)
    start = 1
    for b in range(B):
        runs[b, : pages[b]] = np.arange(start, start + pages[b])
        start += pages[b]
    perm = np.concatenate([[0], 1 + rng.permutation(NB - 1)]).astype(np.int32)
    inverse = np.argsort(perm)
    tables = {"runs": (pool, runs)}
    # block perm[i] of the permuted pool holds what block i held
    tables["permuted"] = (pool[:, inverse], perm[runs])

    def every_layer():  # a function of its own a budget: jit keys on the function
        def read(q, pool, table, off):
            def one(acc, li):
                out = ragged.ragged_paged_attention(
                    q, pool, table, off, window=window, interpret=False, layer=li,
                    v_width=v_width)
                return acc + out.astype(jnp.float32), None
            return jax.lax.scan(
                one, jnp.zeros((B, T, H * (v_width or hd)), jnp.float32),
                jnp.arange(layers, dtype=jnp.int32))[0]
        return jax.jit(read)

    shapes = dict(heads=Hkv, group=H // Hkv, chunk=T, head_dim=hd, block_size=BS,
                  itemsize=2, latent=bool(v_width))
    page_bytes = len(parts) * Hkv * BS * hd * 2
    # the pages a call's items bring under the planned copy group (the page
    # operands bring every entry of a live tile: ``pages`` below), and the
    # bytes of the tokens a query can see (the roofline's numerator)
    moved = sum(ragged.read_counts(runs, np.asarray(off), window, **shapes)[2:]
                ) * page_bytes
    seen = np.minimum(lengths, window) if window else lengths
    line: dict = {"B": B, "T": T, "window": window, "table_width": MB,
                  "layers": layers, "bytes_per_call": moved,
                  "bytes_needed_per_call": int(seen.sum()) * page_bytes // BS}
    outs = {}
    for budget in run_bytes:
        patch = mock.patch.object(
            ragged, "_RUN_BYTES", ragged._RUN_BYTES if budget is None else budget)
        with patch:
            R = ragged._tile_plan(Hkv, H // Hkv, T, hd, BS, MB, 2, False,
                                  latent=bool(v_width))[3]
            counts = ragged.read_counts(runs, np.asarray(off), window, **shapes)
            fn = every_layer()  # the budget is read at trace time
            for name, (pl_, tb) in tables.items():
                args = (q, pl_, jnp.asarray(tb), off)
                out = fn(*args)
                out.block_until_ready()
                t0 = time.perf_counter()
                for _ in range(KERNEL_TIMED_CALLS):
                    out = fn(*args)
                out.block_until_ready()
                us = (time.perf_counter() - t0) / (KERNEL_TIMED_CALLS * layers) * 1e6
                outs[R, name] = np.asarray(out)
                line[f"R{R}_{name}"] = {
                    "us_per_call": round(us, 1), "GBs": round(moved / us / 1e3, 1),
                    "items": counts[0], "pages": counts[2] + counts[3],
                    "pages_in_run_on_run_tables": counts[2],
                }
    line["bare_read_us"] = round(_bare_read_us(moved), 1)
    base = outs[1, "permuted"]
    line["bit_equal_runs_vs_permuted"] = all(
        np.array_equal(outs[R, "runs"], outs[R, "permuted"]) for R, _ in outs)
    line["worst_diff_over_tolerance_vs_page_operands"] = float(max(
        np.max(np.abs(o - base) / (layers * (KERNEL_ATOL + KERNEL_RTOL * np.abs(base / layers))))
        for o in outs.values()))
    line["bit_equal_to_page_operands"] = all(
        np.array_equal(o, outs[1, name]) for (_, name), o in outs.items())
    line["ok"] = bool(line["bit_equal_runs_vs_permuted"]
                      and line["worst_diff_over_tolerance_vs_page_operands"] <= 1.0)
    return line


def _read_runs_cases() -> dict:
    """Case ``st_read_runs``: the ragged read alone at smallthinker's decode
    and chunk shapes, behind the 4,096 window and without, run tables against
    permuted ones, the kernel's own copies against the page operands."""
    cases = {
        f"{name}_window{window}": _read_runs_case(case, window)
        for name, case in READ_RUNS_CASES.items() for window in (4096, 0)
    }
    return {**cases, "ok": all(c["ok"] for c in cases.values())}


def _own_copies_cases() -> dict:
    """Case ``own_copies`` (PR 59): the read alone at ouro's, joyai's and
    phi-3's decode shapes, rows that own blocks ahead of their offsets: the
    kernel's own copies (no copy past the frontier) against the page operands
    those pools had (``_RUN_BYTES`` 0), run tables and permuted ones."""
    cases = {
        name: _read_runs_case(case, 0, layers=case.get("layers", 32))
        for name, case in OWN_COPIES_CASES.items()
    }
    return {**cases, "ok": all(c["ok"] for c in cases.values())}


def child_kernel() -> None:
    """The ragged kernel, compiled, against the dense path on the chip
    (16-slot pages, bf16), and its microseconds a call; then the state-step
    kernel at falcon-h1's and granite's shapes against the XLA recurrence,
    and the read alone on run tables and permuted ones (``st_read_runs``)."""
    from bee2bee_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    dev = _require_tpu(jax)
    rng = np.random.default_rng(SEED)
    line: dict = {"phase": "kernel", "device_kind": dev.device_kind,
                  "block": KERNEL_BLOCK, "dtype": "bfloat16",
                  "tolerance": f"|d| <= {KERNEL_ATOL} + {KERNEL_RTOL}*|dense|",
                  "cases": {n: _kernel_case(n, c, rng) for n, c in KERNEL_CASES.items()}}
    line["cases"]["h1_state_step"] = _state_step_case(dev.device_kind, "falcon-h1-34b-6l")
    line["cases"]["granite_state_step"] = _state_step_case(
        dev.device_kind, "granite-4.0-h-small-10l-e36")
    line["cases"]["st_read_runs"] = _read_runs_cases()  # PR 53
    line["cases"]["own_copies"] = _own_copies_cases()  # PR 59
    # JoyAI-LLM-Flash (PR 39): the latent pool's kernels, the grouped product
    line["cases"]["joyai_latent_decode"] = _latent_case(64, 1, 64, 700)
    line["cases"]["joyai_latent_decode_table8"] = _latent_case(64, 1, 8, 120)
    line["cases"]["joyai_latent_prefill512"] = _latent_case(1, 512, 32, 0)
    line["cases"]["joyai_grouped_512"] = _grouped_case(64, dev.device_kind)
    line["cases"]["joyai_grouped_4096"] = _grouped_case(512, dev.device_kind)
    line["ok"] = all(c["ok"] for c in line["cases"].values())
    line["elapsed_s"] = elapsed()
    emit(line)
    if not line["ok"]:
        sys.exit(1)


def child_tp_compare() -> None:
    """zephyr-7b cut to TP_COMPARE_LAYERS layers, built on device 0 alone
    and on the four-chip model:4 mesh from one seed: prefill logits and a
    few decode-step logits through the paged pool must agree; and the
    mesh engine's decode step must hold the kernel AND the TP all-reduces."""
    from bee2bee_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.engine.engine import EngineConfig, InferenceEngine
    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.parallel import MeshSpec, build_mesh

    dev = _require_tpu(jax)
    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, jax found {len(jax.devices())}")
    cfg = dataclasses.replace(get_config("zephyr-7b"), n_layers=TP_COMPARE_LAYERS)
    ecfg = EngineConfig(max_seq_len=256, max_batch=8, attention="auto", rng_seed=SEED)
    rng = np.random.default_rng(SEED)
    T, N_DECODE, BS = 64, 4, ecfg.kv_block_size
    tokens = rng.integers(3, cfg.vocab_size, size=T + N_DECODE).astype(np.int32)
    MB = 8  # 128 positions: the prompt, the decode steps, headroom
    tables = np.arange(1, MB + 1, dtype=np.int32)[None, :]

    def logits_through_pool(eng) -> np.ndarray:
        """[1 + N_DECODE, V]: the prompt's last-position logits, then one
        [1, 1] step per following token — the engine's own params, pool,
        block tables and attention function."""
        step = jax.jit(
            lambda params, toks, pool, off: core.forward(
                params, eng.model_cfg, toks, pool, off,
                attn_fn=eng._attn_fn(), block_tables=tables,
            ),
            donate_argnums=(2,),
        )
        pool = eng.new_pool()
        logits, pool = step(eng.params, tokens[None, :T], pool, np.int32(0))
        rows = [np.asarray(logits[0, -1], np.float32)]
        for i in range(N_DECODE):
            logits, pool = step(
                eng.params, tokens[None, T + i:T + i + 1], pool, np.int32(T + i)
            )
            rows.append(np.asarray(logits[0, -1], np.float32))
        return np.stack(rows)

    one = InferenceEngine(cfg, engine_config=ecfg)  # degenerate mesh: device 0
    try:
        attn_one = one.engine_cfg.attention
        ref = logits_through_pool(one)
    finally:
        one.close()
    del one
    four = InferenceEngine(
        cfg, mesh=build_mesh(MeshSpec(model=4)), engine_config=ecfg
    )
    try:
        attn_four = four.engine_cfg.attention
        got = logits_through_pool(four)
        # the decode step program itself (the scheduler's root), compiled
        # for the mesh: kernel + TP collectives must both be in it
        sch = four.scheduler
        B = 8
        text = jax.jit(sch._decode_fn, donate_argnums=(2,)).lower(
            four.params, np.zeros(B, np.int32), sch.cache.pool, np.zeros(B, np.int32),
            np.zeros(B, np.float32), np.zeros(B, np.int32), np.ones(B, np.float32),
            None, jax.random.key(0), np.zeros((B, MB), np.int32),
            steps=np.int32(ecfg.decode_chunk),
        ).compile().as_text()
        n_kernel = text.count("tpu_custom_call")
        n_allreduce = text.count("all-reduce(") + text.count("all-reduce-start(")
        shard_bytes = sorted(
            (s.device.id, s.data.nbytes)
            for s in four.params["layers"]["mlp"]["w_up"].addressable_shards
        )
    finally:
        four.close()
    diff = float(np.max(np.abs(got - ref)))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
    ok = bool(
        np.isfinite(ref).all() and np.isfinite(got).all()
        and got.shape == (1 + N_DECODE, cfg.vocab_size)
        and attn_one == "flash" and attn_four == "flash"
        and diff <= TP_LOGITS_TOL and n_kernel > 0 and n_allreduce > 0
    )
    emit({
        "phase": "tp_compare", "device_kind": dev.device_kind,
        "config": f"zephyr-7b widths, n_layers={TP_COMPARE_LAYERS} (cut from 32 so "
                  "one chip holds it), bf16, seed %d" % SEED,
        "attention": {"one_chip": attn_one, "model:4": attn_four},
        "compared": f"last-position logits of a {T}-token prefill + {N_DECODE} "
                    "decode steps through the paged pool",
        "logits_shape": list(got.shape), "finite": bool(np.isfinite(got).all()),
        "logits_std": float(np.std(ref)), "max_abs_diff": diff,
        "tolerance": TP_LOGITS_TOL, "argmax_agreement": agree,
        "decode_step_compiled_text": {"tpu_custom_call": n_kernel,
                                      "all_reduce": n_allreduce},
        "w_up_shard_bytes_by_device": shard_bytes,
        "ok": ok, "elapsed_s": elapsed(),
    })
    if not ok:
        sys.exit(1)


# ------------------------------------------------------------------ phases


def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for f in p.rglob("*") if f.is_file()) if p.is_dir() else 0


def run_one_chip() -> dict:
    from bee2bee_tpu import native
    from bee2bee_tpu.utils import compile_cache_dir

    emit({"phase": "build",
          "piece_codec": "native" if native.available() else "hashlib",
          "native_version": native.version()})
    cache_dir = compile_cache_dir()
    n0 = cache_entries(cache_dir)

    first = serve_boot("gemma-2b", None, {}, "a")
    emit(first)
    emit({"phase": "device", **first["device"], "per_device": first["devices"]})
    n1 = cache_entries(cache_dir)

    _run_child("child_kernel")
    n2 = cache_entries(cache_dir)

    # a second boot: REQUIRED when the first one shed (then with shedding
    # off, see NO_SHED), otherwise only while time is left — its compile
    # seconds show whether the persistent cache hits
    second = None
    if first["shed"] or time.monotonic() - T_START < OPTIONAL_WORK_DEADLINE_S:
        second = serve_boot("gemma-2b", None, NO_SHED if first["shed"] else {}, "b")
        second["why"] = (
            "boot a shed requests with the default SLO configuration: repeated "
            "with shedding switched off (BEE2BEE_ADMISSION)" if first["shed"]
            else "second boot on the warm compile cache"
        )
        emit(second)
    emit({
        "phase": "cache", "dir": cache_dir,
        "from_env_JAX_COMPILATION_CACHE_DIR": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": n0, "added_by_boot_a": n1 - n0,
        "added_by_kernel_child": n2 - n1,
        "added_by_boot_b": cache_entries(cache_dir) - n2 if second else None,
        "compile_seconds_boot_a": first["compile_seconds_setup"],
        "compile_seconds_boot_b": second["compile_seconds_setup"] if second else None,
    })
    served = second if first["shed"] else first
    if not served["ok"]:
        raise SmokeFailure("serve: a reported request failed or was shed")
    if second is not None and not first["shed"] and not second["ok"]:
        raise SmokeFailure("serve: the second boot failed a reported request")
    return served["device"]


def run_four_chips() -> dict:
    serve = serve_boot("zephyr-7b", "model:4", NO_SHED, "tp4")
    serve["why_no_shed"] = (
        "cold compiles breach the default TTFT objective and the node then "
        "sheds follow-ups (established on one chip); four-chip time is not "
        "spent on a second boot"
    )
    emit(serve)
    devices = serve["devices"] or []
    weights = [d.get("components", {}).get("weights", 0) for d in devices]
    total = sum(weights)
    spread_ok = (
        len(devices) == 4 and total > 0 and min(weights) > 0
        and max(weights) <= total / 3.0
    )
    emit({"phase": "device", **serve["device"],
          "weights_bytes_by_device": {d["id"]: w for d, w in zip(devices, weights)},
          "bytes_in_use_by_device": {d["id"]: d.get("bytes_in_use") for d in devices},
          "rule": "every chip holds some, none more than a third of the total",
          "ok": spread_ok})
    if not spread_ok:
        raise SmokeFailure(f"parameters are not spread over 4 chips: {weights}")
    if not serve["ok"]:
        raise SmokeFailure("serve: a reported request failed or was shed")
    if serve["device"]["count"] != 4:
        raise SmokeFailure(f"engine mesh has {serve['device']['count']} devices, not 4")
    _run_child("child_tp_compare", timeout_s=1500.0)
    return serve["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the tensor-parallel path (zephyr-7b on "
                         "model:4) and what it is compared with")
    args = ap.parse_args()
    try:
        device = run_four_chips() if args.chips == 4 else run_one_chip()
    except Exception as e:  # noqa: BLE001 — boundary: report on the last line
        if not isinstance(e, SmokeFailure):
            import traceback

            traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
              "elapsed_s": elapsed()})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
