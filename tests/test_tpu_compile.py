"""Compile the main path's kernels for a DESCRIBED TPU v5e — no chip needed.

The TPU's compiler is installed beside jax and compiles for a topology that
is described, not attached (on-chip-measurement guide, section 2.3). These
cases guard what interpret mode cannot see: a block shape Mosaic refuses, a
slice off the tiling, too much VMEM, a kernel that cannot be partitioned.
Each compiles in about two seconds and asserts the Mosaic kernel
(``tpu_custom_call``) is in the compiled program. Nothing runs: a compile
that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture OF THIS FILE, never
at import: only one process at a time may load the TPU library, the suite
runs under several workers that all import every test file, and a module
that decides at import whether its tests exist breaks collection for all of
them. Compiles happen in the test's own process (a child could not load the
library this worker holds).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops.flash import flash_attention
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn, ragged_paged_attention
from bee2bee_tpu.parallel.mesh import AXES

BS = 16  # EngineConfig.kv_block_size default
NB = 1041  # the gemma-2b engine's default pool at max_batch=8, 2048 context


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "cannot describe here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again): keep the cache off around this file's compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _ragged_args(sharding, *, B, T, H, Hkv, hd, MB, pool_dtype=jnp.bfloat16):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        sds((B, T, H, hd), jnp.bfloat16),
        sds((Hkv, NB, BS, hd), pool_dtype),
        sds((Hkv, NB, BS, hd), pool_dtype),
        sds((B, MB), jnp.int32),
        sds((B,), jnp.int32),
    )


def _heads(model: str) -> dict:
    cfg = get_config(model)
    return {"H": cfg.n_heads, "Hkv": cfg.n_kv_heads, "hd": cfg.head_dim}


# (B, T, table width) follow the engine's own bucketing: decode is [B, 1]
# on the pow2 batch ladder, a prompt prefills as one [1, bucket] chunk
# (64 .. max_seq_len), spec-verify is [B, K+1]; table widths are pow2
RAGGED_CASES = {
    "gemma-2b-decode": ("gemma-2b", dict(B=8, T=1, MB=8)),
    "gemma-2b-decode-full-table": ("gemma-2b", dict(B=8, T=1, MB=128)),
    "gemma-2b-prefill-64": ("gemma-2b", dict(B=1, T=64, MB=4)),
    "gemma-2b-prefill-2048": ("gemma-2b", dict(B=1, T=2048, MB=128)),
    "gemma-2b-spec-verify-k6": ("gemma-2b", dict(B=8, T=7, MB=8)),
    "llama-3-8b-gqa-decode": ("llama-3-8b", dict(B=8, T=1, MB=8)),
    "zephyr-7b-gqa-prefill-64": ("zephyr-7b", dict(B=1, T=64, MB=4)),
    "distilgpt2-mha-decode": ("distilgpt2", dict(B=8, T=1, MB=8)),
    # head size 96 (off the 128-lane tiling: Mosaic refuses a hand-started
    # DMA from such a pool, the kernel's BlockSpec copies pass): the
    # benchmark's two cells and their 2048-bucket prefill
    "phi-3-mini-decode": ("phi-3-mini", dict(B=16, T=1, MB=32)),
    "phi-3-mini-decode-long": ("phi-3-mini", dict(B=4, T=1, MB=128)),
    "phi-3-mini-prefill-2048": ("phi-3-mini", dict(B=1, T=2048, MB=128)),
}


@pytest.mark.parametrize("case", sorted(RAGGED_CASES))
def test_ragged_kernel_compiles_for_v5e(one_chip, case):
    model, shape = RAGGED_CASES[case]
    text = _compiled_text(
        lambda q, k, v, t, o: ragged_paged_attention(q, k, v, t, o, interpret=False),
        *_ragged_args(one_chip, **_heads(model), **shape),
    )
    assert "tpu_custom_call" in text


def test_ragged_kernel_int8_pool_compiles_for_v5e(one_chip):
    """The quantized pool variant: int8 pages + per-page-per-head f32
    scales riding the scalar-prefetch channel."""
    h = _heads("gemma-2b")
    q, k, v, t, o = _ragged_args(
        one_chip, **h, B=8, T=1, MB=8, pool_dtype=jnp.int8
    )
    scale = jax.ShapeDtypeStruct((h["Hkv"], NB), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, t, o, ks, vs: ragged_paged_attention(
            q, k, v, t, o, interpret=False, k_scale=ks, v_scale=vs
        ),
        q, k, v, t, o, scale, scale,
    )
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    """The contiguous-K/V kernel at T = S = 1024 (gemma-2b heads)."""
    h = _heads("gemma-2b")

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        sds((1, 1024, h["H"], h["hd"])),
        sds((1, 1024, h["Hkv"], h["hd"])),
        sds((1, 1024, h["Hkv"], h["hd"])),
    )
    assert "tpu_custom_call" in text


def test_ragged_kernel_under_shard_map_compiles_for_four_chips(topo):
    """Tensor-parallel serving: the attn_fn the engine builds for a
    model:4 mesh runs the kernel per shard (q heads and the pool's kv
    heads over `model`: zephyr-7b's 8 kv heads are 2 a chip). The mesh is
    the four DESCRIBED devices, so interpret mode resolves off from the
    mesh itself — the same rule the engine follows on the chip."""
    cfg = get_config("zephyr-7b")
    mesh = Mesh(np.array(topo.devices, dtype=object).reshape(1, 1, 1, 4), AXES)
    attn = make_ragged_attn_fn(mesh)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    B, T, MB = 8, 1, 8
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pool = sds((Hkv, NB, BS, hd), jnp.bfloat16, P("model"))
    text = _compiled_text(
        lambda q, k, v, tables, positions, window: attn(
            q, k, v, window, cfg, positions=positions, block_tables=tables
        ),
        sds((B, T, H, hd), jnp.bfloat16, P(None, None, "model", None)),
        pool, pool,
        sds((B, MB), jnp.int32, P()),
        sds((B, T), jnp.int32, P()),
        sds((1,), jnp.int32, P()),
    )
    assert "tpu_custom_call" in text
