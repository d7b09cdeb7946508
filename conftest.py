"""Root conftest: ensure pytest runs on an 8-device virtual CPU mesh.

The tests never touch an accelerator: they need `JAX_PLATFORMS=cpu` and
eight forced host devices, and both must be in the environment BEFORE jax
is imported. Where they are not, pytest is re-exec'd once with them set.
The re-exec happens in pytest_configure, after stopping global capture so
the new process inherits the real stdout. The decision reads the
environment only — importing jax here would initialize a backend (and on a
machine with a chip, take the chip) in a process about to be replaced.

This is the multi-chip test strategy SURVEY §4 prescribes: all parallelism
tests exercise real jax.sharding meshes on 8 virtual CPU devices.
"""

import os
import sys

_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def _needs_reexec() -> bool:
    if os.environ.get("_BEE2BEE_TEST_REEXEC") == "1":
        return False
    return (
        os.environ.get("JAX_PLATFORMS", "") != "cpu"
        or _DEVICES_FLAG not in os.environ.get("XLA_FLAGS", "")
    )


def pytest_configure(config):
    if not _needs_reexec():
        return
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if _DEVICES_FLAG not in flags:
        env["XLA_FLAGS"] = f"{flags} {_DEVICES_FLAG}=8".strip()
    env["_BEE2BEE_TEST_REEXEC"] = "1"
    capman = config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        capman.stop_global_capturing()
    sys.stdout.flush()
    sys.stderr.flush()
    os.execvpe(sys.executable, [sys.executable, "-m", "pytest", *sys.argv[1:]], env)
