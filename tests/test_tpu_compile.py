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

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from bee2bee_tpu.models import core, partition
from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops.flash import flash_attention
from bee2bee_tpu.ops.ragged import (
    _tile_plan, make_ragged_attn_fn, paged_kv_write, ragged_paged_attention,
)
from bee2bee_tpu.ops.ssm_step import ssm_state_step
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


@pytest.fixture
def mosaic_state_step(monkeypatch):
    """core.ssm_mixer's state step asks the DEFAULT backend whether to run
    interpreted, and that is the CPU here: steer it from the test, so the
    program compiled for the described chip holds the kernel the chip runs."""
    monkeypatch.setattr(
        "bee2bee_tpu.ops.ssm_step.interpret_off_tpu", lambda mesh=None: False)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _ragged_args(sharding, *, B, T, H, Hkv, hd, MB, pool_dtype=jnp.bfloat16,
                 layers=0):
    """(q, the ``kv`` pool, tables, offsets): the pool one layer's slice
    [NB, 2, Hkv, BS, hd], or with ``layers`` the stacked 6-D leaf as the
    engine stores it on a TPU (lane-aligned: core.init_paged_pool)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = ((NB, 2, Hkv, BS, hd) if not layers
            else (layers, NB, 2, Hkv, BS, -(-hd // 128) * 128))
    return (
        sds((B, T, H, hd), jnp.bfloat16),
        sds(pool, pool_dtype),
        sds((B, MB), jnp.int32),
        sds((B,), jnp.int32),
    )


def _heads(model) -> dict:
    """A model's head layout, or the dict itself (one shard's share)."""
    if isinstance(model, dict):
        return model
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
    # the rest of chip_smoke.KERNEL_CASES (PR 31: every shape the chip times
    # compiles here first): one shard of mistral-7b under model:4 behind its
    # sliding window, falcon-h1's GQA 20/4 at 64 rows; then phi-3's spec
    # verify, and the benchmark's decode shapes on the STACKED lane-aligned
    # pool with a traced layer, as core.forward issues them on a TPU
    "mistral-7b-shard-decode-window": (
        dict(H=8, Hkv=2, hd=128), dict(B=32, T=1, MB=64, window=4096)),
    "falcon-h1-decode": ("falcon-h1-34b", dict(B=64, T=1, MB=32)),
    "phi-3-mini-spec-verify-k6": ("phi-3-mini", dict(B=16, T=7, MB=32)),
    "phi-3-mini-decode-stacked": ("phi-3-mini", dict(B=16, T=1, MB=32, layers=2)),
    "phi-3-mini-prefill-2048-stacked": (
        "phi-3-mini", dict(B=1, T=2048, MB=128, layers=2)),
    "falcon-h1-decode-stacked": ("falcon-h1-34b", dict(B=64, T=1, MB=32, layers=2)),
    # the smallthinker cell's on-chip check of the read (benchmark/
    # reference_smallthinker.window_read): 4 decode rows and one 2,048-query
    # chunk at the 1,024-page table, behind the 4,096 window
    "smallthinker-window-read-decode": (
        "smallthinker-21b-a3b-8l", dict(B=4, T=1, MB=1024, layers=2, window=4096)),
    "smallthinker-window-read-chunk": (
        "smallthinker-21b-a3b-8l", dict(B=1, T=2048, MB=1024, layers=2, window=4096)),
    # PR 59: pages of 128 KB and more on the kernel's own copies — ouro's
    # decode call and 64 bucket (16 MHA heads x 128: a tile of 8 pages is ONE
    # copy group), phi-3's lane-aligned decode above (4 pages of 256 KB); its
    # 128 bucket takes 8 of the 16 heads a step and keeps the page operands
    "ouro-2.6b-decode-stacked": ("ouro-2.6b", dict(B=16, T=1, MB=32, layers=2)),
    "ouro-2.6b-prefill-64-stacked": ("ouro-2.6b", dict(B=4, T=64, MB=32, layers=2)),
    "ouro-2.6b-prefill-128-stacked": ("ouro-2.6b", dict(B=4, T=128, MB=32, layers=2)),
}
# the copy group (ops/ragged._tile_plan's R) a case's call must plan: which
# form compiled above follows from the shapes
RAGGED_COPY_GROUPS = {
    "ouro-2.6b-decode-stacked": 8, "ouro-2.6b-prefill-64-stacked": 8,
    "ouro-2.6b-prefill-128-stacked": 1, "phi-3-mini-decode-stacked": 4,
    "phi-3-mini-decode": 1, "phi-3-mini-prefill-2048-stacked": 1,
    "falcon-h1-decode-stacked": 32, "smallthinker-window-read-decode": 32,
}


@pytest.mark.parametrize("case", sorted(RAGGED_CASES))
def test_ragged_kernel_compiles_for_v5e(one_chip, case):
    model, shape = RAGGED_CASES[case]
    shape = dict(shape)
    window = shape.pop("window", None)
    layer = jnp.int32(1) if shape.get("layers") else None
    text = _compiled_text(
        lambda q, kv, t, o: ragged_paged_attention(
            q, kv, t, o, window=window, interpret=False, layer=layer),
        *_ragged_args(one_chip, **_heads(model), **shape),
    )
    assert "tpu_custom_call" in text
    if case in RAGGED_COPY_GROUPS:
        h = _heads(model)
        hd = -(-h["hd"] // 128) * 128 if shape.get("layers") else h["hd"]
        assert _tile_plan(h["Hkv"], h["H"] // h["Hkv"], shape["T"], hd, BS,
                          shape["MB"], 2, False)[3] == RAGGED_COPY_GROUPS[case]


@pytest.mark.parametrize("model,B,MB", [("gemma-2b", 8, 8), ("phi-3-mini", 16, 32)])
def test_ragged_kernel_int8_pool_compiles_for_v5e(one_chip, model, B, MB):
    """The quantized pool variant: int8 pages + per-page-per-head f32
    scales riding the scalar-prefetch channel."""
    h = _heads(model)
    q, kv, t, o = _ragged_args(
        one_chip, **h, B=B, T=1, MB=MB, pool_dtype=jnp.int8
    )
    scale = jax.ShapeDtypeStruct((NB, 2, h["Hkv"]), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda q, kv, t, o, sc: ragged_paged_attention(
            q, kv, t, o, interpret=False, scale=sc
        ),
        q, kv, t, o, scale,
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


@pytest.mark.parametrize("model,shape", [
    ("zephyr-7b", (1, 1, 1, 4)), ("zephyr-7b", (2, 1, 1, 2)),
    # PR 53: one KV head of four a shard, a page of 8 KB: the kernel's own
    # copies (the pool ONE operand of each shard's call), a tile a run
    ("smallthinker-21b-a3b-8l", (1, 1, 1, 4)), ("falcon-h1-34b", (1, 1, 1, 4)),
], ids=["model-4", "data-2-model-2", "smallthinker-model-4", "falcon-h1-model-4"])
def test_ragged_kernel_under_shard_map_compiles_for_four_chips(topo, model, shape):
    """Tensor-parallel serving: the attn_fn the engine builds for a
    model:4 mesh runs the kernel per shard (q heads and the pool's kv
    heads over `model`: zephyr-7b's 8 kv heads are 2 a chip); on 2x2 the
    rows split over `data` too, and each shard builds its work list from
    its own rows. The mesh is the four DESCRIBED devices, so interpret mode
    resolves off from the mesh itself — the same rule the engine follows
    on the chip."""
    cfg = get_config(model)
    mesh = Mesh(np.array(topo.devices, dtype=object).reshape(shape), AXES)
    attn = make_ragged_attn_fn(mesh)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    B, T, MB = 8, 1, 8
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pool = sds((NB, 2, Hkv, BS, hd), jnp.bfloat16, P(None, None, "model"))
    text = _compiled_text(
        lambda q, kv, tables, positions, window: attn(
            q, kv, None, window, cfg, positions=positions, block_tables=tables
        ),
        sds((B, T, H, hd), jnp.bfloat16, P(None, None, "model", None)),
        pool,
        sds((B, MB), jnp.int32, P()),
        sds((B, T), jnp.int32, P()),
        sds((1,), jnp.int32, P()),
    )
    assert "tpu_custom_call" in text


# ------------------------- the pool written and read in place (PR 29)
#
# Whenever XLA writes (or slices) what Mosaic reads, layout assignment gives
# the layer loop's carry XLA's layout and re-lays a pool slice - or the whole
# pool - for the kernel in EVERY layer: 40-52 % of the device's time before
# PR 29. These cases keep that trap shut: they compile whole `core.forward`
# programs and read the compiler's own listing.

PAGE_WRITE_CASES = {
    # (model, B, T): decode, spec verify K+1 = 7, one prefill bucket
    "phi-3-mini-decode": ("phi-3-mini", 16, 1),
    "phi-3-mini-spec-verify-k6": ("phi-3-mini", 16, 7),
    "phi-3-mini-prefill-2048": ("phi-3-mini", 1, 2048),
    "falcon-h1-decode": ("falcon-h1-34b", 64, 1),
    "gemma-2b-mqa-256-decode": ("gemma-2b", 8, 1),
    "distilgpt2-x64-prefill-128": ("distilgpt2", 1, 128),
}


@pytest.mark.parametrize("case", sorted(PAGE_WRITE_CASES))
def test_page_write_kernel_compiles_for_v5e(one_chip, case):
    """The write half alone, on a stacked pool of two layers, donated: ONE
    Mosaic call for K and V, aliased in place."""
    model, B, T = PAGE_WRITE_CASES[case]
    h = _heads(model)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda pool, new, t, o, lay: paged_kv_write(
            pool, new, t, o, lay, 3, 2000, interpret=False),
        donate_argnums=(0,),
    ).lower(
        sds((2, 384, 2, h["Hkv"], BS, h["hd"]), jnp.bfloat16),
        sds((B, T, 2, h["Hkv"], h["hd"]), jnp.bfloat16),
        sds((B, 128), jnp.int32), sds((B,), jnp.int32), sds((), jnp.int32),
    ).compile()
    assert _custom_calls(compiled.as_text()) == 1
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def _custom_calls(text: str, scope: str = "") -> int:
    """The Mosaic calls of a compiled module (those traced under ``scope``:
    the ``op_name`` of an instruction's metadata holds its scopes' path)."""
    return sum(
        "tpu_custom_call" in ln
        and re.search(rf'op_name="[^"]*{re.escape(scope)}', ln) is not None
        for ln in text.splitlines())


_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")
_HLO_ARRAY = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def _top_level_ops(text: str):
    """(name, result types, opcode, line) of every instruction of a compiled
    module outside the bodies of its fusions (a fusion counts by its result)."""
    fused = set(re.findall(r"calls=%?([\w.-]+)", text))
    skip = True
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if head:
            skip = head[1] in fused
            continue
        m = None if skip else _HLO_LINE.match(line)
        if m:
            yield m[1], m[2], m[3], line


def _ops_of_size(text: str, sizes: set[int]):
    """(name, result types, opcode, line) of the instructions of a compiled
    module that PRODUCE an array of one of ``sizes`` elements, whatever its
    dims. Not counted: the plumbing that only passes an array along
    (parameter, tuple, get-tuple-element, bitcast, while), the inside of
    fusions (a fusion counts by its result) and the Mosaic calls."""
    for name, result, op, line in _top_level_ops(text):
        if op in (
            "parameter", "tuple", "get-tuple-element", "bitcast", "while"
        ) or "tpu_custom_call" in line:
            continue
        elems = {
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for _, dims in _HLO_ARRAY.findall(result)
        }
        if elems & sizes:
            yield name, result, op, line


def _pool_sized_ops(text: str, slice_elems: int, layers: int) -> list[str]:
    """The instructions of a compiled module that PRODUCE an array with as
    many elements as one layer's pool slice, or as the stacked pool
    (_ops_of_size: a relayout, a select, a slice, a write-back and their
    bitcast-fused forms all keep the count; no weight or state of these
    models shares it; the Mosaic calls alias the pool)."""
    return [f"{name} {op}" for name, _, op, _ in
            _ops_of_size(text, {slice_elems, slice_elems * layers})]


def _products_on(text: str, shape: tuple[int, ...]) -> int:
    """The fusions of a compiled module that hold a plain ``[rows, D] x [D, N]``
    product (``bf_io->bf``) AND take an array of ``shape`` as a parameter: a
    stacked weight read where it lies (the fusion slices its layer out of the
    stack inside, beside the product)."""
    operand = "bf16[" + ",".join(map(str, shape)) + "]"
    n, takes = 0, False
    for line in text.splitlines():
        head = re.match(r"^%?fused_computation[\w.-]* \((.*)\) -> .*\{\s*$", line)
        if head:
            takes = operand in head[1]
        elif line.startswith("}"):
            takes = False
        elif takes and " convolution(" in line and "dim_labels=bf_io->bf" in line:
            n, takes = n + 1, False
    return n


def _forward_program(cfg, B, T, MB, nb, mesh=None, sharding=None):
    """(lowered `core.forward` over a donated float pool, elements of one
    layer's slice of the pool a device: K and V): weights and pool are shapes placed by the
    engine's own partition rules on ``mesh``, or whole on ``sharding``; the
    pool is allocated as the engine allocates it for this path on a TPU,
    lane-aligned (core.init_paged_pool), and laid out by the device's
    default."""
    attn = make_ragged_attn_fn(mesh, interpret=False)
    whole = sharding if mesh is None else NamedSharding(mesh, P())

    def place(tree, shardings=None):
        return jax.tree.map(
            lambda a, sh=whole: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, *([shardings] if shardings is not None else []),
        )

    params = jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    pool = jax.eval_shape(lambda: core.init_paged_pool(
        cfg, nb, BS, jnp.bfloat16, lane_aligned=True))
    stored = whole if mesh is None else NamedSharding(
        mesh, partition.paged_cache_spec(cfg, mesh))
    params = place(params, mesh and partition.param_shardings(params, mesh, cfg))
    pool = place(pool, {name: stored for name in pool})  # K beside V, or latent rows
    if cfg.has_ssm:  # the rows' recurrent state rides the same carry
        pool = dict(pool, **place(
            jax.eval_shape(lambda: core.init_ssm_state(cfg, B, jnp.float32))))

    def step(params, ids, pool, off, tables, floor, ceil):
        # a prefill chunk's rows each have a write floor and a write ceil
        return core.forward(
            params, cfg, ids, pool, off, attn_fn=attn, block_tables=tables,
            paged_write_floor=floor if T > 1 else None,
            paged_write_ceil=ceil if T > 1 else None,
        )

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=whole)

    lowered = jax.jit(step, donate_argnums=(2,)).lower(
        params, ints(B, T), pool, ints(B), ints(B, MB), ints(B), ints(B))
    shard = mesh.shape["model"] if mesh is not None else 1
    (leaf,) = (a for name, a in pool.items() if name in ("kv", "latent"))
    return lowered, int(np.prod(leaf.shape[1:])) // shard


# the cells' shapes, two layers deep (B, T, table width, pool blocks: one
# more than the cells', so that no stacked weight has a pool slice's element
# count - at 384 blocks phi-3's [2, 3072, 3072] projections do)
IN_PLACE_CASES = {
    "phi-3-mini-decode": ("phi-3-mini", 16, 1, 32, 385),
    "phi-3-mini-prefill-128": ("phi-3-mini", 1, 128, 8, 385),
    "phi-3-mini-prefill-4x128": ("phi-3-mini", 4, 128, 8, 385),  # one group of a burst
    "phi-3-mini-prefill-2048": ("phi-3-mini", 1, 2048, 128, 385),  # the long cell's bucket
    "falcon-h1-prefill-8x128": ("falcon-h1-34b", 8, 128, 8, 3201),
    "falcon-h1-decode": ("falcon-h1-34b", 64, 1, 32, 3201),
}


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_forward_keeps_the_pool_in_place(one_chip, mosaic_state_step, case):
    """No instruction of the program but the two kernels' aliased calls
    produces an array as large as one layer's slice of the 6-D ``kv`` leaf,
    as its K half or V half alone, or as the leaf: no slice, no copy, no
    transpose, no select, no write-back in the layer loop, and no relayout
    of the pool where the program is entered and left (phi-3's 96 stored
    in 128 lanes: at 96 the device's default puts the BLOCK axis minor-most
    and every program re-laid the whole pool in and out). The temporaries
    stay under one layer's slice."""
    model, B, T, MB, nb = IN_PLACE_CASES[case]
    cfg = dataclasses.replace(get_config(model), n_layers=2)
    lowered, slice_elems = _forward_program(cfg, B, T, MB, nb, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "while(" in text, "no layer loop in the compiled text"
    # ONE page-write (K beside V) and the read a layer (+ falcon-h1's state step)
    assert _custom_calls(text) == 2 + (cfg.has_ssm and T == 1)
    assert _custom_calls(text, "kv.write") == 1
    assert _pool_sized_ops(text, slice_elems, 2) == []
    assert _pool_sized_ops(text, slice_elems // 2, 2) == []
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= 2 * slice_elems * 2  # the leaf in place
    if not cfg.has_ssm:  # falcon-h1 re-lays weights of its own (PERF.md)
        assert analysis.temp_size_in_bytes < slice_elems * 2


@pytest.mark.parametrize("B,T", [(8, 1), (4, 128)], ids=["decode", "prefill-4x128"])
def test_forward_keeps_the_pool_in_place_under_model_4(topo, B, T):
    """`model:4`: both kernels run per shard of the pool's kv heads inside
    shard_maps over the same pool spec; the program still holds no op of a
    (per-device) layer slice's size. A decode step, and one grouped prefill
    whose rows have a write floor and a write ceil each."""
    cfg = dataclasses.replace(get_config("zephyr-7b"), n_layers=2)
    mesh = Mesh(np.array(topo.devices, dtype=object).reshape(1, 1, 1, 4), AXES)
    lowered, slice_elems = _forward_program(cfg, B, T, 8, NB, mesh=mesh)
    text = lowered.compile().as_text()
    assert "while(" in text, "no layer loop in the compiled text"
    assert _custom_calls(text) == 2 and _custom_calls(text, "kv.write") == 1
    assert _pool_sized_ops(text, slice_elems, 2) == []
    assert _pool_sized_ops(text, slice_elems // 2, 2) == []


# ------------------ the recurrent state stepped in place (PR 34, falcon-h1)

STATE_STEP_CASES = {
    # (rows, heads, head size, state size, groups)
    "falcon-h1-decode": (64, 32, 128, 256, 2),
    "falcon-h1-decode-one-row": (1, 32, 128, 256, 2),
    "tiny-falcon-h1-decode": (4, 4, 8, 16, 2),  # blocks off the (8, 128) tiling
    "mamba2-64x128-decode": (8, 24, 64, 128, 1),
    "granite-decode": (64, 128, 64, 128, 1),
    "four-groups-a-block-decode": (8, 16, 16, 128, 4),
}


@pytest.mark.parametrize("case", sorted(STATE_STEP_CASES))
def test_state_step_kernel_compiles_for_v5e(one_chip, case):
    """The one-step state kernel alone, on a stacked state of two layers with
    a traced layer, donated: one Mosaic call, the state aliased in place and
    no temporary of a head tile's size beside it."""
    B, H, P, N, G = STATE_STEP_CASES[case]

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda st, lay, dt, x, b, c, a: ssm_state_step(
            st, lay, dt, x, b, c, a, interpret=False),
        donate_argnums=(0,),
    ).lower(
        sds(2, B, H, P, N), sds(dtype=jnp.int32), sds(B, H), sds(B, H, P),
        sds(B, G, N), sds(B, G, N), sds(H),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= 2 * B * H * P * N * 4  # (more: the lane pad)
    assert analysis.temp_size_in_bytes < B * H * P * 4 * 8  # dt x, exp(dt A): no state


# the temporaries of the program below at the parent of PR 34 (tree d549c9f,
# where XLA's fused dynamic-update-slice stepped the state; my compile for
# the described v5e, PR 34): the kernel may not cost the program more
H1_DECODE_TEMP_BYTES_BEFORE = 2_670_592


def test_forward_steps_the_state_in_place(one_chip, mosaic_state_step):
    """falcon-h1's decode forward (64 rows, two layers): the layer loop holds
    the state-step kernel under the scope the benchmark books it by, no
    instruction but that aliased call produces an array as large as one
    layer's state or the stacked state (no slice, no dynamic-update-slice,
    no copy: 1.62 GB at six layers would not fit the chip), and the
    temporaries are no larger than before the kernel."""
    B = 64
    cfg = dataclasses.replace(get_config("falcon-h1-34b"), n_layers=2)
    lowered, _ = _forward_program(cfg, B, 1, 32, 3201, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3  # the page-write (K beside V), the read, the state step
    assert sum("ssm.step/pallas_call" in ln for ln in calls) == 1
    state_elems = B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    assert _pool_sized_ops(text, state_elems, 2) == []
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= 2 * state_elems * 4
    assert analysis.temp_size_in_bytes <= H1_DECODE_TEMP_BYTES_BEFORE


# ------- JoyAI-LLM-Flash (PR 39): the latent pool's two kernels and the
# dropless expert layer's grouped products, at the published shapes


@pytest.fixture
def mosaic_grouped(monkeypatch):
    """ops/grouped.py asks the DEFAULT backend whether to run interpreted (the
    CPU here): steer it, so the compiled program holds the chip's kernel."""
    monkeypatch.setattr(
        "bee2bee_tpu.ops.grouped.interpret_off_tpu", lambda mesh=None: False)


JOYAI = get_config("joyai-llm-flash-5l")
LATENT_CASES = {
    # (B, T, table width): the cell's decode step at its widest table, a
    # narrow one, and the largest prefill bucket its prompts reach
    "decode-64-rows": (64, 1, 64),
    "decode-table-8": (64, 1, 8),
    "prefill-512": (1, 512, 32),
    # PR 59: the cell's decode call (tables 32 wide) — a tile of 16 latent
    # pages of 20 KB is ONE copy group of the kernel's own copies
    "decode-table-32": (64, 1, 32),
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_write_and_read_compile_for_v5e(one_chip, case):
    """One latent row a token (576 stored in 640 lanes), a unit axis for heads:
    the page-write stores it in place (aliased), the read fetches a page tile
    ONCE — since PR 59 by the kernel's own copies out of the pool where it
    lies (640 = 5 x 128 lanes: a page is one aligned stretch), no V — and
    returns the 32 heads' 512-wide latent outputs."""
    B, T, MB = LATENT_CASES[case]
    W, R, H = JOYAI.latent_width, JOYAI.mla_kv_rank, JOYAI.n_heads
    Tp, _, group = _tile_plan(1, H, T, 640, BS, MB, 2, False, latent=True)[1:]
    assert group == Tp == min(16, MB)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((2, 3201, 1, BS, 640), jnp.bfloat16)
    tables, offs, lay = sds((B, MB), jnp.int32), sds((B,), jnp.int32), sds((), jnp.int32)
    wrote = jax.jit(
        lambda pool, new, t, o, lay: paged_kv_write(
            pool, new, t, o, lay, 0, 2000, interpret=False),
        donate_argnums=(0,),
    ).lower(pool, sds((B, T, 1, W), jnp.bfloat16), tables, offs, lay).compile()
    assert "tpu_custom_call" in wrote.as_text()
    assert wrote.memory_analysis().alias_size_in_bytes > 0
    read = jax.jit(
        lambda q, pool, t, o, lay: ragged_paged_attention(
            q, pool, t, o, interpret=False, layer=lay, v_width=R,
            sm_scale=192 ** -0.5)
    ).lower(sds((B, T, H, W), jnp.bfloat16), pool, tables, offs, lay).compile()
    text = read.as_text()
    assert "tpu_custom_call" in text
    assert f"bf16[{B},{T},{H * R}]" in text.replace(" ", "")  # no second copy for values
    assert read.memory_analysis().temp_size_in_bytes < 2 * 3201 * BS * 640 * 2


def _dropless_layer_compiled(cfg, B, T, sharding):
    """core._moe_dropless alone, compiled for the described chip at ``cfg``'s
    published widths: the three expert stacks whole (read in place by a traced
    layer index), one layer's router and shared expert, ``x`` [B, T, D] bf16."""
    shapes = jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))["layers"]["moe"]
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    names = ("w_gate", "w_up", "w_down")
    experts = place({n: shapes[n] for n in names})
    rest = place(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        {n: a for n, a in shapes.items() if n not in names}))
    x = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16, sharding=sharding)
    lay = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    return jax.jit(
        lambda x, rest, experts, lay: core._moe_dropless(
            x, rest, cfg, experts=experts, layer=lay)
    ).lower(x, rest, experts, lay).compile()


@pytest.mark.parametrize("tokens", [64, 512])
def test_dropless_expert_layer_compiles_for_v5e(one_chip, mosaic_grouped, tokens):
    """512 and 4,096 assignments over 256 experts of 2048 x 768: three Mosaic
    grouped products over the STACKED experts of four layers, read in place
    (no instruction produces an array of a layer's expert matrix's size: no
    slice of the stack, no re-laid copy)."""
    cfg = JOYAI
    compiled = _dropless_layer_compiled(cfg, tokens, 1, one_chip)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    one_matrix = cfg.n_experts * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_expert_layers) == []
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix * 2 // 4


# (model, B, T): smallthinker's 2,048-token prefill chunk (M = 12,288 sorted
# rows of 2,560) and its 32-row decode step; granite's 64-row step under its
# expert share (M = 640 rows of 4,096, half of them in the pad group)
COMBINE_CASES = {
    "st-chunk-2048": ("smallthinker-21b-a3b-8l", 1, 2048),
    "st-decode-32": ("smallthinker-21b-a3b-8l", 32, 1),
    "granite-share-decode-64": ("granite-4.0-h-small-10l-e36", 64, 1),
}


@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_the_combine_makes_no_float32_array_of_the_sorted_rows(
        one_chip, mosaic_grouped, case):
    """``moe.combine`` gathers the grouped product's rows as the product left
    them (bf16) and converts, masks, weights and sums them in ONE fusion: no
    instruction of the compiled layer (a fusion counts by its result) makes a
    float32 array of M x D elements, whatever its dims (``f32[12288,2560]``
    twice at the chunk before PR 57), and the part's ONE array of that size is
    the gather's, in the stream's dtype: gathered choice-major, its [k, N, D]
    view is no copy (viewed [N, k, D] the rows were re-laid, k being no
    multiple of a tile's rows: a ``reshape`` as long as the gather itself)."""
    model, B, T = COMBINE_CASES[case]
    cfg = get_config(model)
    text = _dropless_layer_compiled(cfg, B, T, one_chip).as_text()
    assert _custom_calls(text) == 3
    sized = list(_ops_of_size(
        text, {B * T * cfg.n_experts_per_tok * cfg.d_model}))
    assert [name for name, result, _, _ in sized if "f32[" in result] == []
    combine = [(op, line) for _, _, op, line in sized if "moe.combine" in line]
    assert [op for op, _ in combine] == ["fusion"] and "gather" in combine[0][1], combine


def test_the_balancing_pass_compiles_for_v5e_beside_the_weights(one_chip, mosaic_grouped):
    """core.balance_router_bias at the published widths (32 rows x 256 tokens,
    four passes): its temporaries fit beside the 11.1 GB of weights on a
    15.75 GB chip, the expert stacks are read in place by the three grouped
    products, and all that leaves is the [4, 256] float32 bias."""
    cfg = JOYAI
    shapes = jax.eval_shape(
        lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(jnp.bfloat16)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(core.balance_router_bias, static_argnums=1).lower(args, cfg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    one_matrix = cfg.n_experts * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_expert_layers) == []
    analysis = compiled.memory_analysis()
    assert analysis.output_size_in_bytes == cfg.n_expert_layers * cfg.n_experts * 4
    assert analysis.argument_size_in_bytes + analysis.temp_size_in_bytes < 13.0e9


@pytest.mark.parametrize("B,T,MB", [(64, 1, 64), (1, 128, 8)])
def test_forward_keeps_the_latent_pool_and_the_expert_stacks_in_place(
        one_chip, mosaic_grouped, B, T, MB):
    """joyai-llm-flash cut to the dense layer + two expert layers: the pool is
    touched by the page-write and the read alone (one call each a group of
    like layers), the expert stacks by the three grouped products alone; no
    instruction produces a pool slice, the pool, or an expert matrix stack."""
    cfg = dataclasses.replace(JOYAI, n_layers=3)
    lowered, slice_elems = _forward_program(cfg, B, T, MB, 3201, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("while(") >= 2, "one layer loop a group of like layers"
    assert _custom_calls(text) >= 7  # 2 writes, 2 reads, 3 grouped products
    assert _custom_calls(text, "mla.write") == 2  # one a group of like layers
    assert _pool_sized_ops(text, slice_elems, 3) == []
    one_matrix = cfg.n_experts * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, 2) == []
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= 3 * slice_elems * 2  # the pool in place
    assert analysis.temp_size_in_bytes < one_matrix * 2 // 4


@pytest.mark.parametrize("B,T,MB", [(64, 1, 64), (1, 512, 32), (8, 128, 8)],
                         ids=["joyai-decode", "joyai-prefill-512", "joyai-prefill-8x128"])
def test_the_cell_programs_fit_one_chip_at_full_depth(one_chip, mosaic_grouped, B, T, MB):
    """joyai-llm-flash-5l as the cell serves it (5 layers, 256 experts, 3,200
    pool blocks): the decode step at 64 rows, the largest prefill bucket and
    the widest group of a burst hold both latent custom calls under their scopes, alias the pool in
    place, make no expert-stack-sized array and stay under the chip's 15.75
    GB (arguments + temporaries + what the outputs add beyond the alias)."""
    lowered, slice_elems = _forward_program(JOYAI, B, T, MB, 3200, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert re.search(r"mla\.write[\w.]* = .*tpu_custom_call", text), "the page-write under its scope"
    assert re.search(r"mla\.read[\w.]* = .*tpu_custom_call", text), "the read under its scope"
    one_matrix = JOYAI.n_experts * JOYAI.d_model * JOYAI.expert_ff
    assert _pool_sized_ops(text, one_matrix, JOYAI.n_expert_layers) == []
    assert _pool_sized_ops(text, slice_elems, JOYAI.n_layers) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= JOYAI.n_layers * slice_elems * 2  # the pool in place
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 11.0e9 < total < 15.75e9, total
    assert m.temp_size_in_bytes < one_matrix * 2 // 4


# ---- smallthinker-21b-a3b-8l (PR 43): full NoPE layers beside roped window
# layers, every layer 64 dropless ReGLU experts; a 1,024-page table, a binding
# window, 2,048-token prefill chunks

SMALLTHINKER = get_config("smallthinker-21b-a3b-8l")


@pytest.mark.parametrize("B,T,MB", [(32, 1, 1024), (32, 1, 512), (1, 2048, 512)],
                         ids=["st-decode-1024", "st-decode-512", "st-prefill-chunk-2048"])
def test_the_smallthinker_cell_programs_fit_one_chip_at_full_depth(
        one_chip, mosaic_grouped, B, T, MB):
    """smallthinker-21b-a3b-8l as the cell serves it (8 layers, 64 experts,
    19,200 pool blocks): the 32-row decode step at both table widths and a
    2,048-token prefill chunk hold the page-write and the read under their
    ``attn.*`` scopes, alias the pool in place, make no array the size of the
    pool, a layer's slice of it or an expert matrix stack, and stay under the
    chip's 15.75 GB."""
    cfg = SMALLTHINKER
    lowered, slice_elems = _forward_program(cfg, B, T, MB, 19200, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("while(") >= 1, "one layer loop: every layer is an expert layer"
    # (an instruction is named by its innermost scope: kv.write inside attn.write)
    assert re.search(r"kv\.write[\w.]* = .*tpu_custom_call.*attn\.write/kv\.write", text), \
        "the page-write under its scope"
    assert re.search(r"attn\.read[\w.]* = .*tpu_custom_call", text), "the read under its scope"
    # ONE page-write a layer stores K beside V, and one read fetches them
    assert _custom_calls(text, "kv.write") == 1 and _custom_calls(text, "attn.read") == 1
    one_matrix = cfg.n_experts * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_layers) == []
    # no copy, transpose or re-layout of the 6-D leaf, a layer's slice of it,
    # or a slice's K half or V half, outside the two Mosaic calls
    assert _pool_sized_ops(text, slice_elems, cfg.n_layers) == []
    assert _pool_sized_ops(text, slice_elems // 2, cfg.n_layers) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= cfg.n_layers * slice_elems * 2  # the pool in place
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 12.5e9 < total < 15.75e9, total


def test_the_router_centring_pass_compiles_for_v5e_beside_the_weights(one_chip, mosaic_grouped):
    """core.center_router at the published widths (32 rows x 256 tokens, one
    pass): its temporaries fit beside the 7.9 GB of weights, the expert stacks
    are read in place, and all that leaves is the [8, 2560, 64] router."""
    cfg = SMALLTHINKER
    shapes = jax.eval_shape(
        lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(jnp.bfloat16)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(core.center_router, static_argnums=1).lower(args, cfg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    # (the stack's size only: the batch's 32 x 256 x 6 sorted rows of 2,560
    # happen to hold as many elements as ONE layer's 64 x 2,560 x 768 matrix)
    stack = cfg.n_layers * cfg.n_experts * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, stack, 1) == []
    analysis = compiled.memory_analysis()
    assert analysis.output_size_in_bytes == cfg.n_layers * cfg.d_model * cfg.n_experts * 2
    assert analysis.argument_size_in_bytes + analysis.temp_size_in_bytes < 13.0e9


# ---- ouro-2.6b (PR 46): 48 dense layers run FOUR times a token, a layer of
# cache a (pass, layer): 192 cache layers, a 336-block pool of 8.46 GB

OURO = get_config("ouro-2.6b")


@pytest.mark.parametrize("B,T,MB", [(16, 1, 32), (8, 128, 8)],
                         ids=["ouro-decode-16", "ouro-prefill-8x128"])
def test_the_ouro_cell_programs_keep_the_pool_in_place_through_both_loops(one_chip, B, T, MB):
    """ouro-2.6b as the cell serves it (48 layers x 4 passes, 336 pool blocks):
    the 16-row decode step at the cell's widest table and the widest prefill
    group of a burst (``PREFILL_GROUP_ROWS`` tops at 8 rows of a 128 bucket)
    run the layer loop INSIDE the pass loop (two ``while``s, not 4 x 48 unrolled
    layers: ONE page-write and ONE read in the text), alias the 8.46 GB pool in
    and out with no second pool-sized buffer, a layer's slice of it or a
    pass's 48 layers of it, and fit the chip's 15.75 GB."""
    cfg = OURO
    lowered, slice_elems = _forward_program(cfg, B, T, MB, 336, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("while(") == 2, "the layer loop inside the pass loop, neither unrolled"
    assert re.search(r"kv\.write[\w.]* = .*tpu_custom_call.*attn\.write/kv\.write", text)
    assert re.search(r"attn\.read[\w.]* = .*tpu_custom_call", text), "the read under its scope"
    assert _custom_calls(text, "kv.write") == 1 and _custom_calls(text, "attn.read") == 1
    for scope in ("mlp.gate_up", "mlp.down", "loop.norm", "head.logits"):
        assert re.search(rf'op_name="[^"]*{re.escape(scope)}', text), scope
    for layers in (cfg.cache_layers, cfg.n_layers):  # the pool, or one pass's share of it
        assert _pool_sized_ops(text, slice_elems, layers) == []
        assert _pool_sized_ops(text, slice_elems // 2, layers) == []
    m = compiled.memory_analysis()
    pool_bytes = cfg.cache_layers * slice_elems * 2
    assert pool_bytes == 336 * 16 * 1_572_864 == 8_455_716_864
    assert m.alias_size_in_bytes >= pool_bytes  # in and out, in place
    # no second pool among the temporaries, and since PR 48 no transposed copy
    # of the wq / wk / wv stacks (1.21 GB) in either program
    assert m.temp_size_in_bytes < 100e6
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 13.7e9 < total < 15.75e9, total


# ---- granite-4.0-h-small-10l-e36 (PR 51): a Mamba-2 mixer OR attention a layer
# (m m m m m a m m m m), 36 of every layer's 72 experts held: three runs of like
# layers over a 9-deep state, a 1-deep pool and a [10, 36, ...] expert stack

GRANITE = get_config("granite-4.0-h-small-10l-e36")


@pytest.mark.parametrize("B,T,MB", [(64, 1, 64), (8, 128, 8), (1, 512, 32)],
                         ids=["granite-decode-64", "granite-prefill-8x128",
                              "granite-prefill-512"])
def test_the_granite_cell_programs_run_by_runs_and_keep_state_pool_and_experts_in_place(
        one_chip, mosaic_state_step, mosaic_grouped, B, T, MB):
    """The cut preset as the cell serves it (3,200 pool blocks, 64 rows): one
    ``while`` a run of like layers; the 2.45 GB state, the pool and the 6.79 GB
    expert stack are the runs' carries, touched by the Mosaic calls and a
    layer's own slice alone (no instruction produces a copy of the state, of
    an expert matrix stack, or of a layer's share of one); every part runs
    under its scope; and the program fits the chip's 15.75 GB."""
    cfg = GRANITE
    lowered, slice_elems = _forward_program(cfg, B, T, MB, 3200, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("while(") >= 3, "one layer loop a run of like layers"
    assert _custom_calls(text, "kv.write") == 1 and _custom_calls(text, "attn.read") == 1
    assert _custom_calls(text, "moe.experts") == 3 * len(cfg.layer_runs)
    if T == 1:  # the state-step kernel in each recurrent run
        assert _custom_calls(text, "ssm.step") == 2
    for scope in ("ssm.in_proj", "ssm.out_proj", "attn.qkv", "attn.out", "moe.router",
                  "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "head.logits"):
        assert re.search(rf'op_name="[^"]*{re.escape(scope)}', text), scope
    one_matrix = cfg.experts_held * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_layers) == []
    state_layer = B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    if T == 1:  # a longer chunk writes its layer's slice back (one fusion a layer)
        assert _pool_sized_ops(text, state_layer, cfg.state_layers) == []
    else:
        whole = [ln for ln in _pool_sized_ops(text, state_layer, cfg.state_layers)
                 if f"[{cfg.state_layers}," in ln and "dynamic-update-slice" not in ln]
        assert whole == []
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"granite {B}x{T}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, alias "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, total {total / 1e9:.2f} GB")
    assert total < 15.75e9, total


def test_the_granite_centring_program_fits_beside_the_weights(one_chip, mosaic_grouped):
    """core.center_router at the cut preset: 9.93 GB of weights in, ten
    [4096, 72] routers out, the balancing batch's temporaries beside them."""
    cfg = dataclasses.replace(GRANITE, max_seq_len=2048)
    shapes = jax.eval_shape(
        lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(jnp.bfloat16)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(core.center_router, static_argnums=1).lower(args, cfg).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == cfg.n_layers * cfg.d_model * cfg.n_experts * 2
    print(f"granite centring: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB")
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9


# ---- nemotron-3-super-120b-a12b-11l-e128 (PR 58): a layer is ONE of a Mamba-2
# mixer, attention or a LatentMoE layer (E M E M E M E M E M *), 128 of every
# expert layer's 512 experts held: TWO layer bodies (the unit E M five times,
# then *) over a 5-deep state, a 1-deep pool and a [5, 128, ...] expert stack

NEMOTRON = get_config("nemotron-3-super-120b-a12b-11l-e128")


@pytest.mark.parametrize("B,T,MB", [(64, 1, 64), (8, 128, 8), (1, 512, 32)],
                         ids=["nemotron-decode-64", "nemotron-prefill-8x128",
                              "nemotron-prefill-512"])
def test_the_nemotron_cell_programs_hold_two_layer_bodies_and_keep_their_stacks_in_place(
        one_chip, mosaic_state_step, mosaic_grouped, B, T, MB):
    """The cut preset as the cell serves it (3,200 pool blocks, 64 rows): one
    ``while`` a run of the repeated UNIT (two layer loops: eleven runs of like
    layers would be eleven); the 1.36 GB state, the pool and the 7.05 GB expert
    stack are the runs' carries, touched by the Mosaic calls and a layer's own
    slice alone; an expert is TWO grouped products in the latent's width; every
    part runs under its scope, the latent projections NESTED in ``moe.experts``;
    and the program fits the chip's 15.75 GB."""
    cfg = NEMOTRON
    lowered, slice_elems = _forward_program(cfg, B, T, MB, 3200, sharding=one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(cfg.layer_units) == 2
    assert _custom_calls(text, "kv.write") == 1 and _custom_calls(text, "attn.read") == 1
    assert _custom_calls(text, "moe.experts") == 2  # up and down, in ONE body
    if T == 1:  # the state-step kernel, once: the unit's mixer
        assert _custom_calls(text, "ssm.step") == 1
    for scope in ("ssm.in_proj", "ssm.out_proj", "attn.qkv", "attn.out", "moe.router",
                  "moe.dispatch", "moe.experts", "moe.experts/latent.in",
                  "moe.experts/latent.out", "moe.combine", "moe.shared", "head.logits"):
        assert re.search(rf'op_name="[^"]*{re.escape(scope)}', text), scope
    one_matrix = cfg.experts_held * cfg.expert_in * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_expert_layers) == []
    state_layer = B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    if T == 1:  # a longer chunk writes its layer's slice back (one fusion a layer)
        assert _pool_sized_ops(text, state_layer, cfg.state_layers) == []
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"nemotron {B}x{T}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, alias "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, total {total / 1e9:.2f} GB, "
          f"whiles {text.count('while(')}")
    assert total < 15.75e9, total


def test_the_nemotron_balancing_program_fits_beside_the_weights(one_chip, mosaic_grouped):
    """core.balance_router_bias at the cut preset: 9.30 GB of weights in, five
    [512] selection biases out, the balancing batch's temporaries beside them."""
    cfg = NEMOTRON
    shapes = jax.eval_shape(
        lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(jnp.bfloat16)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(core.balance_router_bias, static_argnums=1).lower(args, cfg).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes >= cfg.n_expert_layers * cfg.n_experts * 4  # (tiled: 8 rows)
    print(f"nemotron balancing: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB")
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9


# ---- q / k / v read where they lie (PR 48): to core.QKV_IN_PLACE_ROWS rows a
# barrier keeps the three products plain, so each takes the stacked parameter
# and the layer index; the parent's folded the head split into them and
# copied every layer's matrix out of the stack first (and the whole stacks
# once a call)

QKV_IN_PLACE_CASES = {
    # (model, B, T, table width, pool blocks) at the CELL's depth: a stack of
    # two phi-3 layers is small enough for the compiler to prefetch it whole
    "phi-3-mini-decode": IN_PLACE_CASES["phi-3-mini-decode"],
    "falcon-h1-decode": ("falcon-h1-34b-6l", *IN_PLACE_CASES["falcon-h1-decode"][1:]),
    "ouro-decode-16": ("ouro-2.6b", 16, 1, 32, 336),
    "st-decode-32": ("smallthinker-21b-a3b-8l", 32, 1, 512, 19200),
    "phi-3-mini-prefill-2048": IN_PLACE_CASES["phi-3-mini-prefill-2048"],  # the long cell's bucket
    "st-prefill-chunk-2048": ("smallthinker-21b-a3b-8l", 1, 2048, 512, 19200),  # the st cell's chunk
}


@pytest.mark.parametrize("case", sorted(QKV_IN_PLACE_CASES))
def test_a_call_reads_wq_wk_wv_where_they_lie(
        one_chip, mosaic_state_step, mosaic_grouped, case):
    """The cells' decode steps and their widest prefills: outside the Mosaic
    calls no instruction produces an array as large as one layer's ``wq``,
    ``wk`` or ``wv`` or as their stacks (no slice out of the stack, no
    re-laid copy of it), and a product takes each stacked parameter as its
    own operand (``wo`` too where it shares ``wq``'s shape). (ouro's
    temporaries, 1.21 GB of transposed stacks before: the ouro test above.)"""
    model, B, T, MB, nb = QKV_IN_PLACE_CASES[case]
    cfg = get_config(model)
    assert B * T <= core.QKV_IN_PLACE_ROWS
    lowered, _ = _forward_program(cfg, B, T, MB, nb, sharding=one_chip)
    assert "optimization_barrier" in lowered.as_text()
    text = lowered.compile().as_text()
    D, L = cfg.d_model, cfg.n_layers
    widths = [cfg.n_heads * cfg.head_dim] + 2 * [cfg.n_kv_heads * cfg.head_dim]
    for n in set(widths):
        assert _pool_sized_ops(text, D * n, L) == [], n
        same = widths.count(n) + (n == D)  # wo [L, H * hd, D]
        assert _products_on(text, (L, D, n)) == same, n
    assert len(re.findall(
        r'convolution\(.*op_name="[^"]*attn\.qkv/dot_general', text)) == 3


def test_a_call_of_more_rows_keeps_the_head_split_in_its_products(one_chip):
    """Above the boundary (4,096 rows: the folded form read 2.7 % better there
    on the chip) the program is the parent's: no barrier in the lowered text."""
    cfg = dataclasses.replace(get_config("phi-3-mini"), n_layers=2)
    assert 2 * 2048 > core.QKV_IN_PLACE_ROWS
    lowered, _ = _forward_program(cfg, 2, 2048, 128, 385, sharding=one_chip)
    assert "optimization_barrier" not in lowered.as_text()


# ---- k-exaone-236b-a23b-5l-e16 (PR 54): L(dense) L L G L behind a 128-token
# window, 16 of every layer's 128 experts held, rows 0-19,199 of the vocabulary,
# and the MTP layer behind the trunk in the SAME program (the ``mtp`` tier)

EXAONE = get_config("k-exaone-236b-a23b-5l-e16")


def _exaone_engine_program(fn_name: str, B: int, T: int, MB: int, one_chip):
    """The engine's own verify / prefill function (engine.InferenceEngine's,
    on a stand-in that carries what it reads of ``self``) lowered for the
    described chip at the cell's sizes: 3,200 pool blocks, six cache layers."""
    from types import SimpleNamespace

    from bee2bee_tpu.engine.engine import InferenceEngine

    cfg = EXAONE
    attn = make_ragged_attn_fn(None, interpret=False)
    stand_in = SimpleNamespace(model_cfg=cfg, mtp_on=True, dtype=jnp.bfloat16,
                               _attn_fn=lambda: attn,
                               engine_cfg=SimpleNamespace(decode_chunk=32))
    stand_in._verify_step = lambda *a: InferenceEngine._verify_step(stand_in, *a)

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    pool = place(jax.eval_shape(lambda: core.init_paged_pool(
        cfg, 3200, BS, jnp.bfloat16, lane_aligned=True)))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def floats(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn = getattr(InferenceEngine, fn_name)
    if fn_name == "_spec_window_fn":  # [cur | draft], who drafts, the budgets; steps
        key = jax.eval_shape(lambda: jax.random.key(0))
        return jax.jit(
            lambda *a: fn(stand_in, *a[:-1], steps=a[-1]), donate_argnums=(5,)
        ).lower(params, ints(B), ints(B, T - 1), ints(B), ints(B), pool, ints(B),
                floats(B), ints(B), floats(B), None, key, ints(B, MB), ints())
    if fn_name == "_spec_verify_fn":
        key = jax.eval_shape(lambda: jax.random.key(0))
        return jax.jit(
            lambda *a: fn(stand_in, *a), donate_argnums=(4,)
        ).lower(params, ints(B), ints(B, T - 1), ints(B), pool, ints(B), floats(B),
                ints(B), floats(B), None, key, ints(B, MB))
    return jax.jit(
        lambda p, t, c, n, o, bt, fl, ce, nx: fn(
            stand_in, p, t, c, n, o, bt, fl, ce, mtp_next=nx),
        donate_argnums=(2,),
    ).lower(params, ints(B, T), pool, ints(B), ints(B), ints(B, MB), ints(B), ints(B),
            ints(B))


@pytest.mark.parametrize("fn,B,T,MB", [
    ("_spec_verify_fn", 64, 2, 64), ("_prefill_fn", 8, 128, 8), ("_prefill_fn", 1, 512, 32),
    ("_spec_window_fn", 64, 2, 64)],
    ids=["exaone-verify-64", "exaone-prefill-8x128", "exaone-prefill-512",
         "exaone-verify-window-64"])
def test_the_exaone_cell_programs_hold_the_mtp_layer_and_fit_the_chip(
        one_chip, mosaic_grouped, fn, B, T, MB):
    """The cut preset as the cell serves it (3,200 pool blocks, 64 rows): the
    verify step is ONE program (the [64, 2] chunk through the trunk, the
    verdict, the MTP layer behind it), a prefill runs the MTP layer over its
    chunk too; every part under its scope, the 3.62 GB expert stacks read where
    they lie, and all of it under the chip's 15.75 GB."""
    cfg = EXAONE
    compiled = _exaone_engine_program(fn, B, T, MB, one_chip).compile()
    text = compiled.as_text()
    scopes = ["attn.qkv", "attn.read", "attn.out", "moe.router", "moe.dispatch",
              "moe.experts", "moe.combine", "moe.shared", "mtp.proj",
              "mtp.block/attn.read", "mtp.block/moe.experts", "mtp.head"]
    if fn != "_prefill_fn":
        scopes += ["spec.verify", "spec.accept"]
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*{re.escape(scope)}', text), scope
    # the trunk's layers write and read their pages by layer, the MTP block its own
    assert _custom_calls(text, "kv.write") == 3 and _custom_calls(text, "attn.read") == 3
    one_matrix = cfg.experts_held * cfg.d_model * cfg.expert_ff
    assert _pool_sized_ops(text, one_matrix, cfg.n_expert_layers) == []
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"exaone {fn} {B}x{T}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, alias "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, total {total / 1e9:.2f} GB")
    assert 10.0e9 < total < 15.75e9, total


def test_the_exaone_centring_program_fits_beside_the_weights(one_chip, mosaic_grouped):
    """core.center_router at the cut preset: 9.09 GB of weights in, the four
    trunk routers and the MTP block's out, the balancing batch's temporaries
    beside them."""
    cfg = dataclasses.replace(EXAONE, max_seq_len=2048)
    shapes = jax.eval_shape(
        lambda: jax.jit(core._init_params, static_argnums=(0, 2))(
            cfg, jax.random.PRNGKey(0), jnp.dtype(jnp.bfloat16)))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(core.center_router, static_argnums=1).lower(args, cfg).compile()
    m = compiled.memory_analysis()
    routers = (cfg.n_expert_layers + cfg.mtp_layers) * cfg.d_model * cfg.n_experts * 2
    assert routers <= m.output_size_in_bytes < routers + 4096  # (+ the pair's table)
    print(f"exaone centring: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB")
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9
