"""Every model's program carries the parts of ``tracing.DEVICE_PARTS``: the
eight benchmarked configurations' ``core.forward`` (the multi-token-prediction
layer behind it where the model has one), cut to one or two layers at the
published widths, lowered on the CPU over the paged pool with the ragged
reader interpreted. Shapes only: nothing is compiled or run."""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from bee2bee_tpu.models import core
from bee2bee_tpu.models.config import get_config
from bee2bee_tpu.ops.ragged import make_ragged_attn_fn
from bee2bee_tpu.tracing import DEVICE_NESTED, DEVICE_PARTS, DEVICE_WRAPPERS

PARTS = set(DEVICE_PARTS)
WRAPPERS = set(DEVICE_WRAPPERS) | set(DEVICE_NESTED)
BS = 16  # pool block

# the benchmark's configurations (BENCHMARK.json ``configs``: their server
# models), each cut in depth only, with one layer of every kind it has
CUTS = {
    "phi-3-mini": {"n_layers": 2},
    "falcon-h1-34b-6l": {"n_layers": 1},
    "joyai-llm-flash-5l": {"n_layers": 2},  # the dense layer and an expert layer
    "smallthinker-21b-a3b-8l": {"n_layers": 2},
    "ouro-2.6b": {"n_layers": 1},
    "granite-4.0-h-small-10l-e36": {
        "n_layers": 2, "layer_types": ("mamba", "attention")},
    "k-exaone-236b-a23b-5l-e16": {"n_layers": 2},
    "nemotron-3-super-120b-a12b-11l-e128": {  # a layer of each of its kinds
        "n_layers": 3, "layer_types": ("moe", "mamba", "attention")},
}
SHAPES = {"decode": (4, 1), "prefill": (2, 32)}  # [B, T]


def _lowered_text(model: str, B: int, T: int, MB: int = 4, nb: int = 9) -> str:
    cfg = dataclasses.replace(get_config(model), **CUTS[model])
    attn = make_ragged_attn_fn(None, interpret=True)
    params = jax.eval_shape(
        lambda: core.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    pool = jax.eval_shape(
        lambda: core.init_paged_pool(cfg, nb, BS, jnp.bfloat16))
    if cfg.has_ssm:
        pool = dict(pool, **jax.eval_shape(
            lambda: core.init_ssm_state(cfg, B, jnp.float32)))

    def step(params, ids, pool, off, tables):
        kw = {"attn_fn": attn, "block_tables": tables}
        if not cfg.mtp_layers:
            return core.forward(params, cfg, ids, pool, off, **kw)
        logits, pool, hidden = core.forward(
            params, cfg, ids, pool, off, return_hidden=True, **kw)
        return logits, core.mtp_forward(
            params, cfg, hidden, ids, pool, off, **kw)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    return jax.jit(step).lower(
        params, ints(B, T), pool, ints(B), ints(B, MB)
    ).as_text(debug_info=True)


def _op_paths(text: str) -> list[tuple[str, str]]:
    """[(op kind, the op's name path with its callers' in front)] of a
    lowered module's text. An inner jit (``jnp.take``) or an interpreted
    kernel is a private function whose ops' names start at its own root: an
    op's path is joined to the path of EVERY call site that reaches its
    function, so a function one bare caller reaches shows a bare op."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    ops, calls, fn = [], {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func \w+ @(\w+)\(", line)
        if m:
            fn = m.group(1)
            continue
        m = re.search(r"loc\((#loc\d+)\)$", line)
        name = locs.get(m.group(1), "") if m else ""
        call = re.search(r"\bcall @(\w+)\(", line)
        if call:
            calls.setdefault(call.group(1), []).append((fn, name))
        kind = re.search(r"stablehlo\.([a-z_]+)", line)
        if kind:
            ops.append((fn, kind.group(1), name))

    def reach(fn, seen=()):
        if fn not in calls or fn in seen:
            return [""]
        return [path + "/" + name for caller, name in calls[fn]
                for path in reach(caller, seen + (fn,))]

    return [(kind, path + "/" + name)
            for fn, kind, name in ops for path in reach(fn)]


@pytest.mark.parametrize("phase", sorted(SHAPES))
@pytest.mark.parametrize("model", sorted(CUTS))
def test_every_product_of_a_benchmarked_model_carries_a_part(model, phase):
    """Every ``dot_general`` / ``convolution`` / custom call of the lowered
    program, and the embedding's gather, has a part of the table in its
    ``op_name``; and no dotted scope outside the table is opened."""
    ops = _op_paths(_lowered_text(model, *SHAPES[phase]))
    products = [(kind, path) for kind, path in ops
                if kind in ("dot_general", "convolution", "custom_call")]
    assert len(products) >= 10
    bare = [(kind, path) for kind, path in products
            if not set(path.split("/")) & PARTS]
    assert not bare, bare[:5]
    assert any(kind == "gather" and "embed.tokens" in path.split("/")
               for kind, path in ops)
    opened = {c for _, path in ops for c in path.split("/")
              if re.fullmatch(r"[a-z_]+\.[a-z_]+", c)}
    assert opened <= PARTS | WRAPPERS, sorted(opened - PARTS - WRAPPERS)
    assert {"embed.tokens", "norm.block", "head.logits"} <= opened
