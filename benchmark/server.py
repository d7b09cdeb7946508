"""The system under test as a child process: ``python -m bee2bee_tpu serve-tpu``.

The benchmark's own process never imports jax while this child lives: a chip
belongs to one process at a time. Everything the benchmark learns about the
device comes from the child's own HTTP surface (``GET /providers``,
``GET /metrics``, ``POST /debug/profile``, read by ``run.py``). The boot / readiness / stop
pattern is copied from ``chip_smoke.py`` (PR 21), which stays the smoke.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path


class BenchFailure(RuntimeError):
    """The run cannot give a result: no result line is printed, exit != 0."""


def http(method: str, url: str, body: dict | None = None, timeout: float = 60.0):
    """(status, parsed JSON or text). An HTTP error status is returned, not raised."""
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


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One ``serve-tpu`` child booted from a configuration file's ``server``
    section. Node state (config.json, profiles) lives under ``home``, a fixed
    directory inside the checkout; the compile cache is where
    ``bee2bee_tpu.utils.compile_cache_dir()`` puts it (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set)."""

    def __init__(self, root: Path, config: dict, home: Path, extra_env: dict | None = None):
        srv = config["server"]
        self.model = srv["model"]
        home.mkdir(parents=True, exist_ok=True)
        (home / "config.json").write_text(json.dumps(srv.get("config_json", {}), indent=1))
        ws_port, api_port = _free_port(), _free_port()
        self.base = f"http://127.0.0.1:{api_port}"
        self.cmd = [sys.executable, "-m", "bee2bee_tpu", "serve-tpu",
                    "--model", self.model, *srv.get("flags", []),
                    "--port", str(ws_port), "--api-port", str(api_port)]
        env = dict(os.environ)
        env.update({
            "BEE2BEE_TPU_HOME": str(home),
            "BEE2BEE_INCIDENT_DIR": str(home / "incidents"),
            "BEE2BEE_HOST": "127.0.0.1",
            "BEE2BEE_LOG_FILE": "",  # stderr only: it goes to the log below
        })
        env.update({k: v if isinstance(v, str) else json.dumps(v)
                    for k, v in srv.get("env", {}).items()})
        env.update(extra_env or {})
        self.log_path = home / "server.log"
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 30) -> str:
        try:
            return "\n".join(self.log_path.read_text(errors="replace").splitlines()[-n:])
        except OSError:
            return ""

    def log_errors(self, n: int = 12) -> str:
        """The first lines of the log that are neither access lines nor INFO."""
        try:
            lines = self.log_path.read_text(errors="replace").splitlines()
        except OSError:
            return ""
        odd = [ln[:400] for ln in lines if " INFO " not in ln and ln.strip()]
        return "\n".join(odd[:n])

    def check_alive(self, what: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(
                f"server child exited rc={rc} while {what}; log tail:\n{self.log_tail()}"
            )

    def provider(self) -> dict | None:
        """This node's own provider record for the model (``engine`` in it is
        ``engine.info``), once the model is announced."""
        status, body = http("GET", f"{self.base}/providers")
        if status == 200 and isinstance(body, dict):
            for p in body.get("providers") or []:
                if p.get("local") and self.model in (p.get("models") or []):
                    return p
        return None

    def wait_serving(self, timeout_s: float, require_platform: str | None = None) -> dict:
        """Wait until the model is announced; returns ``engine.info``. With
        ``require_platform`` it fails EARLY, before the weights are built, when
        the child's jax backend is another one: no accelerator, no run."""
        checked = require_platform is None
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self.check_alive("booting")
            try:
                if not checked:
                    status, home = http("GET", f"{self.base}/", timeout=30.0)
                    if status == 200 and isinstance(home, dict):
                        accel = (home.get("metrics") or {}).get("accelerator") or {}
                        if accel.get("platform") != require_platform:
                            raise BenchFailure(
                                f"jax in the server child found platform="
                                f"{accel.get('platform')!r}, the cell needs {require_platform!r}")
                        checked = True
                prov = self.provider()
                if prov is not None:
                    return prov.get("engine") or {}
            except OSError:  # URLError, refused, reset: gateway not up yet
                pass
            time.sleep(0.5)
        raise BenchFailure(
            f"server not serving after {timeout_s:.0f} s; log tail:\n{self.log_tail()}"
        )

    def fetch_profile(self, prof_id: str, dest: Path) -> None:
        with urllib.request.urlopen(
            f"{self.base}/debug/profile?id={prof_id}", timeout=300.0
        ) as resp, open(dest, "wb") as out:
            while chunk := resp.read(1 << 20):
                out.write(chunk)

    def stop(self) -> None:
        """SIGINT and wait: the chip must be free before the next child starts."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30.0)
        self._log.close()


def device_record(info: dict) -> dict:
    """platform / kind / count and the fullest chip's peak bytes, as the
    server child's ``engine.info`` reports them."""
    devices = ((info.get("introspect") or {}).get("hbm") or {}).get("devices") or []
    peaks = [d.get("peak_bytes_in_use") or d.get("bytes_in_use") or 0 for d in devices]
    return {
        "platform": info.get("platform"),
        "kind": info.get("device_kind"),
        "count": info.get("device_count"),
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }
