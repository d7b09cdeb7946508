"""engine/programs.StoredPrograms: a jit root's programs kept on disk whole,
loaded by the next boot without a trace (ISSUE 42's boot warm-up)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine.programs import StoredPrograms


@pytest.fixture
def store(tmp_path):
    """The programs live beside the compile cache jax is configured with."""
    was = jax.config.jax_compilation_cache_dir, jax.config.jax_persistent_cache_enable_xla_caches
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    # the suite lets XLA keep its kernel caches there, and such a process stores nothing
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    yield tmp_path / "programs"
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_enable_xla_caches", was[1])


def _root(salt="a", calls=None):
    def body(acc, x, scale=None):
        if calls is not None:
            calls.append(x.shape)  # runs when the body is traced, never after
        return acc + (x * 2 if scale is None else x * scale)

    return StoredPrograms(
        "double", jax.jit(body, donate_argnums=(0,)),
        lambda acc, x, scale=None: (x.shape[0], scale is None),
        salt=salt, devices=[jax.devices()[0]])


def _args(n):
    return jnp.zeros((n,), jnp.float32), np.arange(n, dtype=np.float32)


def test_a_warmed_program_is_stored_and_the_next_boot_loads_it_untraced(store):
    traced: list = []
    first = _root(calls=traced)
    assert first.warm(*_args(4)) is False and len(list(store.glob("double-*.bin"))) == 1
    assert traced == [(4,)]
    np.testing.assert_array_equal(first(*_args(4)), [0, 2, 4, 6])
    assert traced == [(4,)]  # the resident program ran, not the jit root

    traced.clear()
    second = _root(calls=traced)  # the next boot
    assert second.warm(*_args(4)) is True and traced == []
    np.testing.assert_array_equal(second(*_args(4)), [0, 2, 4, 6])
    assert traced == []
    # shapes instead of arrays name the same program
    third = _root()
    assert third.warm(jax.ShapeDtypeStruct((4,), jnp.float32),
                      jax.ShapeDtypeStruct((4,), np.float32)) in (True, False)
    # another call compiles on first use through the jit root, as ever
    np.testing.assert_array_equal(second(*_args(3)), [0, 2, 4])
    assert traced == [(3,)]
    np.testing.assert_array_equal(second(*_args(4), scale=np.float32(3)), [0, 3, 6, 9])


def test_the_key_holds_the_salt_the_signature_and_the_build(store, monkeypatch):
    assert _root().warm(*_args(4)) is False
    assert _root().warm(*_args(4)) is True
    assert _root(salt="b").warm(*_args(4)) is False  # another configuration
    assert _root().warm(*_args(8)) is False  # another shape
    acc, x = _args(4)
    assert _root().warm(acc, x.astype(np.int32)) is False  # another dtype
    import bee2bee_tpu.engine.programs as programs

    monkeypatch.setattr(programs, "_build_digest", lambda: "another build")
    assert _root().warm(*_args(4)) is False
    assert len(list(store.glob("double-*.bin"))) == 5


def test_a_file_that_cannot_be_loaded_is_compiled_again_and_replaced(store):
    assert _root().warm(*_args(4)) is False
    (path,) = store.glob("double-*.bin")
    path.write_bytes(b"not a program")
    again = _root()
    assert again.warm(*_args(4)) is False
    np.testing.assert_array_equal(again(*_args(4)), [0, 2, 4, 6])
    assert _root().warm(*_args(4)) is True


def test_a_process_without_a_compile_cache_keeps_its_programs_in_memory(store):
    jax.config.update("jax_compilation_cache_dir", None)
    traced: list = []
    root = _root(calls=traced)
    assert root.warm(*_args(4)) is False and root.warm(*_args(4)) is True
    np.testing.assert_array_equal(root(*_args(4)), [0, 2, 4, 6])
    assert traced == [(4,)] and not store.exists()
    assert _root().warm(*_args(4)) is False  # nothing was stored


def test_nothing_is_stored_where_xla_keeps_kernel_caches_of_its_own(store):
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    root = _root()
    assert root.warm(*_args(4)) is False and not store.exists()
    np.testing.assert_array_equal(root(*_args(4)), [0, 2, 4, 6])


def test_a_loaded_program_runs_on_its_own_devices_in_a_process_that_sees_more(store):
    assert len(jax.devices()) > 1  # the suite's eight virtual CPU devices
    assert _root().warm(*_args(4)) is False
    loaded = _root()
    assert loaded.warm(*_args(4)) is True
    out = loaded(*_args(4))
    assert out.devices() == {jax.devices()[0]}
