"""Partition rules: param path → PartitionSpec over the named mesh axes.

This is the TP/EP layout for the BASELINE ladder (Llama-3-8B TP on v5e-8,
Mixtral EP on v5e-16). Megatron-style column/row split per block:

- wq/wk/wv: columns (head dim) on `model` → attention heads are sharded,
  no collective inside attention
- wo: rows on `model` → XLA inserts one psum (all-reduce) per layer
- w_up/w_gate: columns on `model`; w_down: rows on `model` → one psum
- tok_embed: vocab dim on `model` (all-gather of the embedding row);
  lm_head: vocab columns on `model` (logits computed sharded)
- MoE experts: E dim on `expert` axis; router replicated
- KV cache: kv-head dim on `model` (decode-time attention stays local)

All specs are expressed over param PATHS (tuple of pytree keys), so the
same rules drive (a) NamedSharding for jit, (b) the piece/shard manifest
(pieces.build_shard_manifest), and (c) checkpoint resharding.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import ModelConfig

# rules: suffix of the "/"-joined param path → PartitionSpec
# (leading L dim on layer-stacked params is never sharded → spec starts None)
_RULES: list[tuple[str, P]] = [
    ("tok_embed", P("model", None)),
    ("pos_embed", P(None, None)),
    ("lm_head", P(None, "model")),
    ("final_norm/scale", P(None)),
    ("final_norm/bias", P(None)),
    # attention (layer-stacked: [L, ...])
    ("attn/wq", P(None, None, "model")),
    ("attn/wk", P(None, None, "model")),
    ("attn/wv", P(None, None, "model")),
    ("attn/wo", P(None, "model", None)),
    ("attn/bq", P(None, "model")),
    ("attn/bk", P(None, "model")),
    ("attn/bv", P(None, "model")),
    ("attn/bo", P(None, None)),
    # dense mlp
    ("mlp/w_up", P(None, None, "model")),
    ("mlp/w_gate", P(None, None, "model")),
    ("mlp/w_down", P(None, "model", None)),
    ("mlp/b_up", P(None, "model")),
    ("mlp/b_down", P(None, None)),
    # moe: experts on `expert`, inner dims on `model`
    ("moe/router", P(None, None, None)),
    ("moe/w_up", P(None, "expert", None, "model")),
    ("moe/w_gate", P(None, "expert", None, "model")),
    ("moe/w_down", P(None, "expert", "model", None)),
    # norms
    ("ln1/scale", P(None, None)),
    ("ln1/bias", P(None, None)),
    ("ln2/scale", P(None, None)),
    ("ln2/bias", P(None, None)),
]


def _unquant_path(path: str) -> tuple[str, str | None]:
    """Strip a quantization leaf suffix: "attn/wq/q" -> ("attn/wq", "q").
    models/quant.py stores int8 weights as {"q","s"} subtrees; partition
    rules are written against the WEIGHT path."""
    if path.endswith(("/q", "/s")):
        return path[:-2], path[-1]
    return path, None


def spec_for_path(path: str) -> P:
    path, leaf = _unquant_path(path)
    for suffix, spec in _RULES:
        if path.endswith(suffix):
            if leaf == "s":
                # per-out-channel scales are the weight minus its IN axis
                # (dim -2): [L, out] for dense weights, [L, E, out] for MoE
                # experts — shard like the surviving axes of the weight
                return P(*spec[:-2], spec[-1])
            return spec
    return P()  # replicate by default


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def partition_specs(params) -> dict:
    """Pytree of PartitionSpec matching `params`' structure."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_path(_path_str(path)), params
    )


def _fits(leaf, spec: P, mesh: Mesh) -> bool:
    for dim, entry in zip(leaf.shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape.get(a, 1)
        if dim % n:
            return False
    return True


def kv_replicated(cfg: ModelConfig, mesh: Mesh) -> bool:
    """True when K/V heads must be replicated across the `model` axis:
    MQA/GQA with tp > n_kv_heads (e.g. gemma-2b's single kv head on a
    model=4 mesh). A width split of wk/wv would cut one kv head's hd dim
    across devices and break per-shard attention locality; whole-head
    replication keeps attention collective-free at the cost of duplicate
    K/V compute (tiny: Hkv=1 projections are ~1/(H) of attention width)."""
    tp = mesh.shape.get("model", 1)
    return tp > 1 and cfg.n_kv_heads % tp != 0


_KV_PARAM_SUFFIXES = ("attn/wk", "attn/wv", "attn/bk", "attn/bv")


def param_shardings(params, mesh: Mesh, cfg: ModelConfig | None = None):
    """Pytree of NamedSharding matching `params` (arrays or
    ShapeDtypeStructs — only shapes are read) per the rules. Params whose
    sharded dim doesn't divide the mesh axis (e.g. gpt2's prime vocab on
    tok_embed/lm_head) are replicated instead. With `cfg` given, MQA
    models replicate the K/V projections (see kv_replicated)."""
    specs = partition_specs(params)
    if cfg is not None and kv_replicated(cfg, mesh):
        specs = jax.tree_util.tree_map_with_path(
            lambda path, s: (
                P()
                if _unquant_path(_path_str(path))[0].endswith(_KV_PARAM_SUFFIXES)
                else s
            ),
            specs,
        )

    def sharding(leaf, spec):
        t = tuple(spec)
        if len(t) > getattr(leaf, "ndim", 0):
            # rules are written against STACKED [L, ...] weights; unstacked
            # per-layer leaves (core.unstack_layers, the CPU path) drop the
            # leading layer dim — trim leading spec entries to match. Only
            # None entries may be dropped: trimming a real mesh axis would
            # silently mask a rule/shape mismatch that must fail loudly.
            drop, t = t[: len(t) - leaf.ndim], t[len(t) - leaf.ndim:]
            if any(d is not None for d in drop):
                raise ValueError(
                    f"partition spec {spec} does not fit rank-{leaf.ndim} "
                    f"leaf: would drop sharded axes {drop}"
                )
        spec = P(*t)
        return NamedSharding(mesh, spec if _fits(leaf, spec, mesh) else P())

    return jax.tree.map(sharding, params, specs)


def shard_params(params, mesh: Mesh, cfg: ModelConfig | None = None):
    """Place params onto the mesh per the rules (host → device transfer:
    each device receives only its own shard of a host array)."""
    return jax.device_put(params, param_shardings(params, mesh, cfg))


def cache_spec(
    cfg: ModelConfig | None = None,
    mesh: Mesh | None = None,
    seq_sharded: bool = False,
) -> P:
    """KV cache [L, B, S, Hkv, hd]: batch on `data`, kv heads on `model`.

    With ``seq_sharded=True`` (the engine sets it iff attention='sp'),
    cache capacity S is sharded over `seq`: per-device cache memory is
    S/seq and long contexts scale with devices (parallel/sp_serving.py).
    It is NOT inferred from the mesh alone — dense/flash attention gathers
    the full cache per step, so a seq-sharded cache under them would be a
    silent per-step reshard, not a win. MQA meshes (kv_replicated) keep
    the kv-head dim replicated to match the replicated wk/wv projections."""
    seq = "seq" if seq_sharded and mesh is not None and mesh.shape.get("seq", 1) > 1 else None
    if cfg is not None and mesh is not None and kv_replicated(cfg, mesh):
        return P(None, "data", seq, None, None)
    return P(None, "data", seq, "model", None)


def paged_cache_spec(
    cfg: ModelConfig | None = None,
    mesh: Mesh | None = None,
    seq_sharded: bool = False,
) -> P:
    """Paged KV pool [L, num_blocks, 2, Hkv, block_size, hd] (the ``kv``
    leaf of core.init_paged_pool: K beside V, page-major): kv heads, axis
    3, on `model` — attention over the pool (ragged kernel) or its
    gathered view (dense) stays collective-free per shard. The BLOCK dim
    is never
    sharded: any row gathers arbitrary pool blocks, so splitting it would
    turn every gather into a cross-device reshard. With ``seq_sharded``
    (the engine sets it iff attention='sp') the SLOT dim, axis 4, shards
    over `seq`: per-device pool memory is 1/seq — the long-context capacity
    scaling of parallel/sp_serving — and the block gather stays local
    (it indexes only the block dim); XLA reshards the gathered view into
    the sp shard_map's contiguous [B, S/seq] layout per step, which is
    the collective sp attention pays anyway. MQA meshes (kv_replicated)
    replicate the kv-head dim to match wk/wv. A latent pool
    ([L, num_blocks, 1, block_size, W]) is replicated: the engine refuses
    a mesh for such a model."""
    if cfg is not None and cfg.has_mla:
        return P()
    seq = "seq" if seq_sharded and mesh is not None and mesh.shape.get("seq", 1) > 1 else None
    if cfg is not None and mesh is not None and kv_replicated(cfg, mesh):
        return P(None, None, None, None, seq, None)
    return P(None, None, None, "model", seq, None)


def paged_scale_spec(cfg: ModelConfig | None = None, mesh: Mesh | None = None) -> P:
    """Int8-pool quantization scales [L, num_blocks, 2, Hkv] f32: the
    kv-head dim shards exactly like the pool's (MQA replication
    included), the block dim never shards (same any-row-any-block
    argument as paged_cache_spec), and there is no slot dim — under
    attention='sp' the scales stay whole per shard and the gathered-view
    dequant broadcasts each page's scale across its (seq-sharded) slots
    locally."""
    if cfg is not None and mesh is not None and kv_replicated(cfg, mesh):
        return P(None, None, None, None)
    return P(None, None, None, "model")


def flat_partition_specs(
    params,
    mesh_axes: dict[str, int] | None = None,
    cfg: ModelConfig | None = None,
) -> dict[str, tuple]:
    """{path_str: spec-as-tuple} for pieces.build_shard_manifest, which
    wants mesh-axis names per tensor axis. With `mesh_axes` given, specs
    whose dims don't divide the axis size degrade to replicated — mirroring
    shard_params' fallback. With `cfg` given, the MQA K/V replication
    override matches shard_params too, keeping the manifest<->jit-sharding
    invariant (a peer's assembled pieces must equal its jit shard)."""
    out = {}
    tp = (mesh_axes or {}).get("model", 1)
    kv_repl = cfg is not None and tp > 1 and cfg.n_kv_heads % tp != 0

    def visit(path, leaf):
        ps = _path_str(path)
        spec = tuple(spec_for_path(ps))
        if kv_repl and _unquant_path(ps)[0].endswith(_KV_PARAM_SUFFIXES):
            spec = ()
        if mesh_axes:
            ok = all(
                e is None or leaf.shape[i] % mesh_axes.get(e, 1) == 0
                for i, e in enumerate(spec)
            )
            if not ok:
                spec = ()
        out[ps] = spec
        return leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return out


def validate_divisibility(cfg: ModelConfig, mesh: Mesh) -> None:
    """Fail fast when the model's dims don't divide the mesh axes."""
    tp = mesh.shape.get("model", 1)
    ep = mesh.shape.get("expert", 1)
    problems = []
    # n_kv_heads % tp != 0 is NOT fatal: kv_replicated() keeps K/V whole
    # per shard (MQA replication), so gemma-2b (Hkv=1) serves at model=4
    if (cfg.n_heads * cfg.head_dim) % tp:
        problems.append(f"attn width {cfg.n_heads * cfg.head_dim} vs model axis {tp}")
    if cfg.d_ff % tp:
        problems.append(f"d_ff={cfg.d_ff} vs model axis {tp}")
    # note: vocab (tok_embed/lm_head) indivisibility is NOT fatal —
    # shard_params falls back to replicating those params (gpt2's 50257
    # vocab is prime, yet gpt2 must still run TP on its other dims)
    if cfg.is_moe and cfg.n_experts % ep:
        problems.append(f"n_experts={cfg.n_experts} vs expert axis {ep}")
    if problems:
        raise ValueError(f"model {cfg.name} does not fit mesh {dict(mesh.shape)}: " + "; ".join(problems))
