#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It measures the SERVED path from the client's side: ``python -m bee2bee_tpu
serve-tpu`` is this run's one jax child while it lives, driven over its HTTP
gateway with streamed requests. This process imports no jax. In order:

1. boot the server child from the cell's configuration file; anything but a
   TPU with the cell's chip count fails the run (no result line, exit != 0);
2. probes and warm-up passes from the mix's own callers, then the mix itself
   until no new program has compiled for a while: all of it is ``setup_s``;
3. the window of ``--seconds``, opened while the callers keep running; they
   run on past its end until the burst of events then in the making has
   arrived (``loadgen.Load.drain``), so that both edges count alike;
4. ``--trace 1`` only: 1 s polls of ``/metrics`` and one ``/debug/profile``
   capture started a third of the way into the window;
5. a compile inside the window makes ``correct`` false;
6. stop the server child and wait for it;
7. the correctness child (``reference.py``) on the cell's chips, then — traced
   runs — the trace reduction child (``trace_reduce.py``, held to the CPU);
8. the result line, last on stdout; everything else goes before it.

``--rehearse-on-cpu`` (tests only) accepts the CPU backend, marks the line
``"rehearsal": true`` and prints no metric whose source is the device trace.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import random
import subprocess
import sys
import time
import zipfile
from pathlib import Path

T_START = time.monotonic()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE / "readers")]

from loadgen import Load, Spec, make_prompt, percentile, summarize  # noqa: E402
from promtext import delta, parse, total  # noqa: E402
from server import BenchFailure, Server, device_record  # noqa: E402

TRACE_SECONDS = 4.0  # the one /debug/profile capture of a traced run (a mix may shorten it)
POLL_SECONDS = 1.0
COMPILES = "bee2bee_engine_compiles_total"


def say(obj: dict) -> None:
    """An earlier line of stdout (never the last)."""
    print(json.dumps(obj), flush=True)


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except OSError as e:
        raise BenchFailure(f"cannot read {path}: {e}") from e


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration file, mix file) by the names in BENCHMARK.json."""
    manifest = load_json(root / "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(root / entry["file"])
    config["_file"] = entry["file"]
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return manifest, cell, config, mix


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------- the drive


async def scrape(session, base: str) -> dict:
    async with session.get(f"{base}/metrics") as resp:
        return parse(await resp.text())


def warmup_passes(mix: dict, seed: int, callers: int) -> list[list[Spec]]:
    """The probe pass, then one pass per warm-up shape of the mix, each of
    one request per caller (or the shape's own ``count``) so that every shape
    is met at the batch width the mix reaches."""
    rng = random.Random(seed ^ 0xB0B)
    pr = mix["probes"]
    passes = [[Spec(pr["prompt_tokens"], pr["output_tokens"],
                    make_prompt(rng, pr["prompt_tokens"]), "probe")
               for _ in range(int(pr["count"]))]]
    for shape in mix["warmup"]:
        passes.append([Spec(shape["prompt_tokens"], shape["output_tokens"],
                            make_prompt(rng, shape["prompt_tokens"]), "warmup")
                       for _ in range(int(shape.get("count", callers)))])
    return passes


async def drive(srv: Server, mix: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Warm-up, steady state, window. Returns what the readers need."""
    import aiohttp

    out: dict = {"polls": [], "profile": None}
    trace_s = float(mix.get("trace_seconds", TRACE_SECONDS))
    load = Load(srv.base, srv.model, mix, seed)
    callers = int(mix.get("callers") or mix.get("warmup_callers"))
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        t = time.monotonic()
        await load.run_passes(session, warmup_passes(mix, seed, callers), callers)
        srv.check_alive("warming up")
        bad = [r.error for r in load.records if not r.ok]
        say({"phase": "warmup_passes", "seconds": time.monotonic() - t,
             "requests": len(load.records), "failed": len(bad), "errors": sorted(set(bad))[:3]})
        if bad:
            raise BenchFailure(
                f"{len(bad)} of {len(load.records)} probe / warm-up requests failed "
                f"({sorted(set(bad))[:3]}); the server's first complaints:\n{srv.log_errors()}")

        async def profile(at: float) -> None:
            await asyncio.sleep(max(0.0, at - time.monotonic()))
            async with session.post(f"{srv.base}/debug/profile",
                                    json={"duration_s": trace_s}) as resp:
                out["profile"] = {"header": await resp.json()}

        async def poll(until_t: float) -> None:
            while time.monotonic() < until_t:
                out["polls"].append((time.monotonic(), await scrape(session, srv.base)))
                await asyncio.sleep(POLL_SECONDS)

        async def until() -> float:
            # the mix runs. The window opens at the moment a fixed AMOUNT of
            # work has ended (``steady_requests`` of the mix, and as many
            # again while a program compiled in the last ``quiet_s``): a
            # stretch of time would open it at a random phase of the loop
            start = time.monotonic()
            quiet = {"seen": total(await scrape(session, srv.base), COMPILES), "at": start}

            async def watch_compiles() -> None:
                while True:
                    await asyncio.sleep(1.0)
                    seen = total(await scrape(session, srv.base), COMPILES)
                    if seen != quiet["seen"]:
                        quiet["seen"], quiet["at"] = seen, time.monotonic()

            watcher = asyncio.ensure_future(watch_compiles())
            need = step = int(mix["steady_requests"])
            try:
                while True:
                    while load.mix_done < need:
                        await asyncio.sleep(0.002)
                        if time.monotonic() - start > float(mix["warmup_limit_s"]):
                            srv.check_alive("reaching the steady state")
                            raise BenchFailure(
                                f"no steady state after {mix['warmup_limit_s']} s of the mix "
                                f"({load.mix_done} requests ended, last compile "
                                f"{time.monotonic() - quiet['at']:.0f} s ago)")
                    if time.monotonic() - quiet["at"] >= float(mix["quiet_s"]):
                        break
                    need += step
            finally:
                watcher.cancel()
                await asyncio.gather(watcher, return_exceptions=True)
            out["m0"] = await scrape(session, srv.base)
            out["t0"] = t0 = time.monotonic()
            out["setup_s"] = t0 - T_START
            side = []
            if trace:
                side = [asyncio.ensure_future(poll(t0 + seconds)),
                        asyncio.ensure_future(profile(t0 + seconds / 3.0))]
            await asyncio.sleep(t0 + seconds - time.monotonic())
            out["t1"] = time.monotonic()
            out["m1"] = await scrape(session, srv.base)
            if side:
                await asyncio.gather(*side)
            return out["t1"]

        if mix["loop"] == "closed":
            await load.run_closed(session, until)
        elif mix["loop"] == "open":
            horizon = float(mix["warmup_limit_s"]) + seconds + 60.0
            await load.run_open(session, until, horizon)
        else:
            raise BenchFailure(f"mix loop {mix['loop']!r}: closed or open")
    out["records"] = load.records
    out["late_s"] = load.late_s
    return out


# ---------------------------------------------------------------- children


def run_child(cmd: list[str], env: dict, timeout_s: float, what: str) -> dict:
    """Run one child to its end; its last stdout line is a JSON object."""
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=timeout_s)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        res = {"ok": False, "error": f"{what}: no result; stderr tail: {proc.stderr[-1500:]}"}
    res["rc"] = proc.returncode
    return res


def check_correct(home: Path, config: dict, mix: dict, records, platform: str,
                  env: dict) -> dict:
    """The reference child on the probes' served text (``reference.py`` says how)."""
    probes = [r for r in records if r.spec.phase == "probe"]
    job = {
        "config_file": config["_file"], "platform": platform,
        "tolerance": config["reference"]["tolerance"],
        "output_tokens": mix["probes"]["output_tokens"],
        "probes": [{"prompt": r.spec.prompt, "text": "".join(t for _, t in r.events)}
                   for r in probes if r.ok],
    }
    path = home / "reference_job.json"
    path.write_text(json.dumps(job))
    t = time.monotonic()
    module = config["reference"].get("module", "reference")  # a file of this directory
    res = run_child([sys.executable, str(HERE / f"{module}.py"), str(path)], env, 900.0,
                    "reference child")
    res["seconds"] = time.monotonic() - t
    res["probes_failed"] = sum(not r.ok for r in probes)
    return res


def fetch_trace(home: Path, prof: dict | None, srv: Server) -> Path | None:
    """Fetch the capture's zip while the server still lives and unpack its .xplane.pb."""
    header = (prof or {}).get("header") or {}
    if not header.get("id"):
        return None
    zpath = home / "profile.zip"
    srv.fetch_profile(header["id"], zpath)
    with zipfile.ZipFile(zpath) as zf:
        names = [n for n in zf.namelist() if n.endswith(".xplane.pb")]
        if not names:
            return None
        (home / "profile.xplane.pb").write_bytes(zf.read(names[0]))
    zpath.unlink()
    return home / "profile.xplane.pb"


# ---------------------------------------------------------------- readers


def read_metric(spec_dir: str, name: str, ctx: dict):
    """A metric is a small file of its own (``<spec_dir>/<name>.json``: reader
    kind + parameters); a reader kind is a module of its own
    (``readers/<kind>.py`` with ``read(ctx, params)``). A reader that finds
    nothing to read returns None and the metric is left out."""
    spec = load_json(HERE / spec_dir / f"{name}.json")
    path = HERE / "readers" / f"{spec['reader']}.py"
    mod_spec = importlib.util.spec_from_file_location(f"reader_{spec['reader']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, spec.get("params", {}))


# ---------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tests only: accept the CPU backend, print no device metric")
    args = ap.parse_args()
    root = HERE.parent
    manifest, cell, config, mix = load_cell(root, args.workload)
    if not (root / "bee2bee_tpu" / "__main__.py").is_file():
        raise BenchFailure("no bee2bee_tpu package beside benchmark/: nothing to measure")
    platform = "cpu" if args.rehearse_on_cpu else "tpu"
    child_env = dict(os.environ)
    child_env.pop("BENCH_RUN", None)
    if args.rehearse_on_cpu:
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["XLA_FLAGS"] = (child_env.get("XLA_FLAGS", "") +
                                  f" --xla_force_host_platform_device_count={cell['chips']}")
    home = root / ".bench_home" / cell["name"]

    srv = Server(root, config, home, child_env)
    xplane = None
    try:
        info = srv.wait_serving(900.0, platform)
        dev = device_record(info)
        if dev["platform"] != platform or dev["count"] != cell["chips"]:
            raise BenchFailure(
                f"the server child runs on {dev['count']} x {dev['platform']!r} "
                f"({dev['kind']}); the cell needs {cell['chips']} x {platform!r}")
        say({"phase": "boot", "seconds": time.monotonic() - T_START, "cmd": srv.cmd,
             "attention": info.get("attention"), "kv": info.get("kv"), "device": dev})
        run = asyncio.run(drive(srv, mix, args.seed, args.seconds, bool(args.trace)))
        srv.check_alive("measuring")
        dev = device_record(srv.wait_serving(60.0))
        if args.trace:
            xplane = fetch_trace(home, run["profile"], srv)
    finally:
        srv.stop()

    t0, t1 = run["t0"], run["t1"]
    client = summarize(run["records"], t0, t1, mix["loop"])
    compiles = delta(run["m0"], run["m1"], COMPILES) or 0.0
    ref = check_correct(home, config, mix, run["records"], platform, child_env)
    correct = bool(ref.get("ok")) and compiles == 0 and client["failed"] == 0
    say({"phase": "window", "seconds": t1 - t0, "attempted": client["attempted"],
         "failed": client["failed"], "errors": client["errors"],
         "ttft_samples": len(client["ttft_ms"]), "gap_samples": len(client["gap_ms"]),
         "tokens": client["tokens"], "compiles_in_window": compiles,
         "generator_late_ms": {"mean": 1000 * sum(run["late_s"]) / len(run["late_s"]),
                               "max": 1000 * max(run["late_s"])} if run["late_s"] else None})
    say({"phase": "correctness", **ref})
    (home / "window.json").write_text(json.dumps({
        "t0": t0, "t1": t1, "loop": mix["loop"],
        "records": [{"phase": r.spec.phase, "t_ref": r.t_ref, "t_send": r.t_send, "t_end": r.t_end,
                     "tokens": r.tokens, "error": r.error,
                     "events": [[t, len(text)] for t, text in r.events]}
                    for r in run["records"]]}))

    ctx = {
        "cell": cell, "config": config, "mix": mix, "client": client,
        "records": run["records"], "t0": t0, "t1": t1, "setup_s": run["setup_s"],
        "m0": run["m0"], "m1": run["m1"], "polls": run["polls"],
        "profile": run["profile"], "trace": None, "device": dev,
        "peaks": load_json(HERE / "peaks.json"), "percentile": percentile,
    }
    if xplane is not None:
        env = dict(child_env, JAX_PLATFORMS="cpu")
        red = run_child([sys.executable, str(HERE / "trace_reduce.py"), str(xplane)],
                        env, 600.0, "trace reduction child")
        if red.get("rc") == 0:
            ctx["trace"] = red
        else:
            say({"phase": "trace_reduce", "error": red.get("error")})

    def read_group(group: str, spec_dir: str) -> dict:
        found = {}
        for m in metrics_of(manifest, group, cell["name"]):
            if args.rehearse_on_cpu and m["source"] == "device_trace":
                continue  # a CPU run is never written under the name of a device metric
            value = read_metric(spec_dir, m["name"], ctx)
            if value is not None:
                found[m["name"]] = {"value": value, "unit": m["unit"]}
        return found

    metrics = read_group("end_to_end", "e2e_metrics")
    if args.trace:  # a traced run shows its (perturbed) end-to-end numbers on an earlier line
        say({"phase": "end_to_end", **{k: v["value"] for k, v in metrics.items()}})
        metrics = read_group("per_layer", "layer_metrics")
    line: dict = {"correct": correct, "attempted": client["attempted"],
                  "failed": client["failed"], "metrics": metrics, "device": dev}
    tr = ctx["trace"]
    if tr and not args.rehearse_on_cpu:
        if not tr.get("busy_s"):
            raise BenchFailure("the traced interval holds no device operation")
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    if args.rehearse_on_cpu:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr)
        sys.exit(1)
