"""Test fixtures. The 8-device virtual CPU mesh is enforced by the ROOT
conftest (../conftest.py), which re-execs pytest with the right env
before fd capture starts; here we only verify it took effect."""

import os
import tempfile

os.environ.setdefault(
    "BEE2BEE_TPU_HOME",
    os.path.join(tempfile.gettempdir(), "bee2bee_tpu_test_home"),
)

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

# Persistent XLA compile cache: every test that builds a fresh
# InferenceEngine creates NEW jax.jit objects, so identical tiny-model
# programs recompile per test without it (the in-memory jit cache is per
# closure). The persistent cache dedupes by HLO hash across engines and
# across files — measured ~2.5x on the second identical engine+generate
# in-process — which is what keeps the tier-1 suite inside its wall-clock
# budget. The directory is PER RUN (unless BEE2BEE_JAX_CACHE pins one):
# a run killed mid-write (the tier-1 timeout sends SIGKILL) leaves a
# truncated entry, and XLA hard-aborts the next process that loads it —
# a shared /tmp path turned one killed run into a poisoned suite.
# Never fatal — a read-only /tmp just skips it.
try:  # pragma: no cover - environment-dependent
    import atexit  # noqa: E402
    import shutil  # noqa: E402

    import jax

    _cache_base = os.environ.get("BEE2BEE_JAX_CACHE")
    _CACHE_PINNED = bool(_cache_base)
    if not _cache_base:
        _cache_base = tempfile.mkdtemp(prefix="bee2bee_jax_cache_")
        atexit.register(shutil.rmtree, _cache_base, ignore_errors=True)
    jax.config.update("jax_compilation_cache_dir", _cache_base)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

    # Quarantine of the pre-existing XLA segfault (CHANGES.md PR 12
    # note): ~545 tests into a tier-1 run this container died at rc=139
    # inside backend.deserialize_executable. Rotating only the DISK
    # cache moved the crash into backend_compile at the same aged-
    # process point — so deserialization was a symptom; the trigger is
    # XLA work in a process aged into hundreds of live executables
    # (the crashing file passes standalone either way). Guards:
    # - default: per test module, the persistent cache dir ROTATES (an
    #   entry is only ever read by the file that wrote it) AND the
    #   in-process jit/executable caches are CLEARED (fixture below) —
    #   the process never ages past one file's worth of XLA state,
    #   while within-file engine reuse (a file's engines share one
    #   config — the dominant win) survives. Measured: 574 dots, zero
    #   F, no crash at the 870s cap vs 543-then-rc=139 before.
    # - BEE2BEE_JAX_CACHE_NO_DESERIALIZE=1 additionally disables cache
    #   READS outright (writes continue, so pinned BEE2BEE_JAX_CACHE
    #   dirs still warm up) — the belt-and-suspenders escape hatch.
    # jax._src.compilation_cache is PRIVATE API — its own try, so a jax
    # upgrade that moves it degrades only the quarantine (no rotation,
    # no read-disable), never the public persistent-cache setup above
    try:
        from jax._src import compilation_cache as _jax_cc

        if os.environ.get("BEE2BEE_JAX_CACHE_NO_DESERIALIZE"):
            _jax_cc.get_executable_and_time = (
                lambda *a, **kw: (None, None)
            )
    except Exception:
        _jax_cc = None
except Exception:
    _jax_cc = None
    _CACHE_PINNED = True  # unknown cache state: never rotate blindly


# files whose tests deliberately break things (killed peers, black-holed
# stages): an introduced hang here must fail THAT test, not eat the whole
# tier-1 wall-clock budget. The cap is ini-configurable (chaos_test_timeout)
# and per-test overridable via @pytest.mark.async_timeout(seconds).
_CHAOS_FILES = (
    "test_chaos", "test_failover", "test_pipeline_interleave", "test_fleet",
)


@pytest.fixture(autouse=True, scope="module")
def _fresh_jax_cache_per_module():
    """Per-FILE jax state rotation (see the quarantine note above):
    the persistent cache dir rotates so no entry outlives its writer's
    module, and the IN-PROCESS jit/executable caches are cleared so the
    process never ages into the hundreds-of-live-executables state the
    segfault needs — within-file reuse (a file's engines share one
    config) survives both. A pinned BEE2BEE_JAX_CACHE opts out of the
    dir rotation — the operator asked for cross-run sharing."""
    if _jax_cc is None or _CACHE_PINNED:
        yield
        return
    import gc
    import tempfile as _tf

    d = _tf.mkdtemp(prefix="mod_", dir=_cache_base)
    try:
        gc.collect()  # release dead engines' executables first
        jax.clear_caches()
        _jax_cc.set_cache_dir(d)
        _jax_cc.reset_cache()
    except Exception:
        pass
    yield


def pytest_addoption(parser):
    parser.addini(
        "chaos_test_timeout",
        "per-test wall-clock cap (seconds) for async tests in the chaos/"
        "failover files (0 disables)",
        default="240",
    )


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests via asyncio.run (pytest-asyncio isn't in this
    image). Sync fixtures work normally; use async context managers instead
    of async fixtures. Chaos/failover tests run under a wall-clock cap —
    pytest-timeout isn't in the image either, so the cap rides the same
    asyncio.run bridge."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        timeout = None
        marker = pyfuncitem.get_closest_marker("async_timeout")
        if marker is not None and marker.args:
            timeout = float(marker.args[0])
        elif any(f in str(pyfuncitem.fspath) for f in _CHAOS_FILES):
            timeout = float(pyfuncitem.config.getini("chaos_test_timeout"))
        if timeout:
            async def _capped():
                await asyncio.wait_for(fn(**kwargs), timeout=timeout)

            asyncio.run(_capped())
        else:
            asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="session", autouse=True)
def _verify_cpu_mesh():
    # The root conftest re-execs pytest onto CPU with 8 virtual devices;
    # by the time any test runs, that must have taken effect.
    import jax

    assert jax.default_backend() == "cpu" and jax.device_count() == 8, (
        f"expected 8 virtual CPU devices, got {jax.device_count()} on "
        f"{jax.default_backend()}"
    )


@pytest.fixture
def tmp_home(tmp_path, monkeypatch):
    monkeypatch.setenv("BEE2BEE_TPU_HOME", str(tmp_path))
    return tmp_path
