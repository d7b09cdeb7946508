"""LoRA fine-tuning: low-rank adapters over the frozen base model.

The reference has no fine-tuning at all (its training protocol is a toy
per-layer MLP loop, reference node.py:99-182); this is a beyond-parity
capability, built the TPU way: adapters are stacked [L, ...] like the
base layers so the merged weights flow through the SAME `lax.scan`
transformer core (models/core.py) — one einsum over the layer dim merges
every layer's delta at once, and the whole merge lives INSIDE the jitted
train step, so XLA fuses it with the forward pass and the base weights'
TP sharding propagates to the merged result unchanged.

Freezing is by construction, not by optimizer masking: the merged weight
is `stop_gradient(W) + scaling * A @ B`, so `jax.grad` w.r.t. the
adapters is exact and the base never receives a gradient. Only the
adapters are optimizer state — Adam moments for a rank-8 distilgpt2
adapter set are ~100k floats, not 2x the model.

Usage:
    lcfg = LoraConfig(rank=8, targets=("wq", "wv"))
    trainer = LoraTrainer(model_cfg, base_params, lcfg, mesh=mesh)
    trainer.train_step(batch)                  # updates adapters only
    params = trainer.merged_params()           # serve/export (engine-ready)
    save_adapters(path, trainer.adapters, lcfg)  # ~MBs, not GBs
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..models import core
from ..models.config import ModelConfig
from ..models.partition import shard_params
from .trainer import (
    TrainConfig,
    TrainState,
    make_optimizer,
    make_step_from_loss,
    xent_loss_metrics,
)

# weights that can take an adapter: attention projections + MLP matmuls
ATTN_TARGETS = ("wq", "wk", "wv", "wo")
MLP_TARGETS = ("w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class LoraConfig:
    rank: int = 8
    # alpha/rank scaling (the LoRA paper's convention: delta = alpha/r * AB)
    alpha: float = 16.0
    # which projections get adapters; q+v is the paper's sweet spot
    targets: tuple = ("wq", "wv")
    # init std of A (B is zero-init so training starts at the base model)
    init_std: float = 0.02

    @property
    def scaling(self) -> float:
        return self.alpha / self.rank

    def __post_init__(self):
        bad = set(self.targets) - set(ATTN_TARGETS) - set(MLP_TARGETS)
        if bad:
            raise ValueError(
                f"unknown LoRA targets {sorted(bad)}; "
                f"known: {ATTN_TARGETS + MLP_TARGETS}"
            )
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")


def _group(target: str) -> str:
    return "attn" if target in ATTN_TARGETS else "mlp"


def validate_targets(cfg: ModelConfig, lcfg: LoraConfig) -> None:
    """Per-MODEL target check, run before any checkpoint load: the static
    LoraConfig check can't know that MoE models keep their MLP weights
    under layers['moe'] with an expert dim (unsupported for adapters), or
    that non-gated MLPs (gpt2's gelu) have no w_gate — failing here beats
    a KeyError after a multi-GB load."""
    mlp_t = [t for t in lcfg.targets if t in MLP_TARGETS]
    if cfg.is_moe and mlp_t:
        raise ValueError(
            f"LoRA MLP targets {mlp_t} unsupported on MoE model "
            f"{cfg.name!r} (expert weights are [L, E, ...]); use attention "
            f"targets {ATTN_TARGETS}"
        )
    if "w_gate" in lcfg.targets and not cfg.gated_mlp:
        raise ValueError(
            f"target 'w_gate' does not exist on {cfg.name!r} "
            f"(activation={cfg.activation!r} is not gated)"
        )


def init_lora(
    cfg: ModelConfig, lcfg: LoraConfig, key, dtype=jnp.float32
) -> dict:
    """Adapters pytree: {target: {"a": [L, in, r], "b": [L, r, out]}}.
    Shapes come from the base layout (core.init_params docstring): wq is
    [L, D, H*hd], wk/wv [L, D, Hkv*hd], wo [L, H*hd, D], mlp [L, D, F]/
    [L, F, D]. B zero-init makes step 0 exactly the base model."""
    validate_targets(cfg, lcfg)
    io = adapter_target_io(cfg)
    adapters = {}
    for t in lcfg.targets:
        din, dout = io[t]
        key, ka = jax.random.split(key)
        adapters[t] = {
            "a": (jax.random.normal(ka, (cfg.n_layers, din, lcfg.rank), dtype)
                  * lcfg.init_std),
            "b": jnp.zeros((cfg.n_layers, lcfg.rank, dout), dtype),
        }
    return adapters


def merge_lora(
    base_params: dict, adapters: dict, lcfg: LoraConfig, trainable: bool = False
) -> dict:
    """Base params with each targeted weight replaced by W + s*(A@B),
    batched over the stacked layer dim. trainable=True stops gradients at
    the base so jax.grad flows only to the adapters (the train path);
    trainable=False produces engine-ready merged params (the serve path).
    Works on the host (numpy in) or inside jit (tracers in)."""
    params = dict(base_params)
    layers = dict(params["layers"])
    for t, ab in adapters.items():
        g = _group(t)
        grp = dict(layers[g])
        w = grp[t]
        # numpy base AND numpy adapters (the engine's host-side quantized-
        # load path) merge host-side — jnp there would device_put the full
        # dense weights, the exact allocation that path exists to avoid.
        # Tracer adapters (train step) force jnp even over a numpy base:
        # the base then enters the trace as a constant.
        xp = (
            np
            if isinstance(w, np.ndarray) and isinstance(ab["a"], np.ndarray)
            else jnp
        )
        if trainable:
            w = jax.lax.stop_gradient(w)
        delta = xp.einsum(
            "lir,lro->lio", xp.asarray(ab["a"], xp.float32),
            xp.asarray(ab["b"], xp.float32),
        ) * lcfg.scaling
        grp[t] = (w.astype(xp.float32) + delta).astype(grp[t].dtype)
        layers[g] = grp
    params["layers"] = layers
    return params


class LoraTrainer:
    """Adapter-only training over a frozen base. Reuses the SPMD step
    machinery (trainer.make_step_from_loss): with a mesh, the batch is
    DP/SP-sharded and the base weights keep their TP sharding — the
    replicated adapters broadcast into the merge einsum and XLA inserts
    the gradient psums."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        base_params,
        lora_cfg: LoraConfig | None = None,
        train_cfg: TrainConfig | None = None,
        mesh=None,
        seed: int = 0,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model_cfg = model_cfg
        self.lora_cfg = lora_cfg or LoraConfig()
        self.train_cfg = train_cfg or TrainConfig()
        self.mesh = mesh
        if mesh is not None:
            base_params = shard_params(base_params, mesh)
        self.base_params = base_params
        adapters = init_lora(
            model_cfg, self.lora_cfg, jax.random.key(seed),
            dtype=jnp.dtype(self.train_cfg.param_dtype),
        )
        if mesh is not None:  # adapters replicate: rank-r dims never shard
            rep = NamedSharding(mesh, P())
            adapters = jax.device_put(adapters, rep)
        opt = make_optimizer(self.train_cfg)
        self.state = TrainState(
            step=jnp.zeros((), jnp.int32), params=adapters,
            opt_state=opt.init(adapters),
        )

        def loss(adapters, batch):
            merged = merge_lora(
                self.base_params, adapters, self.lora_cfg, trainable=True
            )
            ids = batch["input_ids"]
            logits, _ = core.forward(
                merged, model_cfg, ids, None, jnp.int32(0),
                remat=self.train_cfg.remat,
            )
            return xent_loss_metrics(logits, ids, batch.get("loss_mask"))

        batch_sharding = (
            NamedSharding(mesh, P("data", "seq")) if mesh is not None else None
        )
        self._step = make_step_from_loss(loss, self.train_cfg, batch_sharding)

    @property
    def adapters(self):
        return self.state.params

    def train_step(self, batch: dict) -> dict:
        from .trainer import globalize_batch

        self.state, metrics = self._step(
            self.state, globalize_batch(batch, self.mesh)
        )
        return {k: float(v) for k, v in metrics.items()}

    def merged_params(self):
        """Engine-ready params: base + trained deltas, same pytree layout
        as core.init_params — drop them straight into InferenceEngine."""
        return merge_lora(self.base_params, self.adapters, self.lora_cfg)


class AdapterLoadError(ValueError):
    """Typed adapter load/validation failure: a corrupt file, a tampered
    tensor, or factors whose shapes don't match the declared LoraConfig.
    Raised HOST-side (load/validate time), so a bad adapter is a clean
    error to the one caller — never a shape crash inside a jitted step
    that would fail every in-flight request on the engine."""


# adapter .npz layout version. v2 adds the per-tensor sha256 manifest
# (__meta_sha256, pieces.py discipline); v1 files (no version key) load
# without verification for backward compatibility.
ADAPTER_FORMAT_VERSION = 2


def adapter_target_io(cfg: ModelConfig) -> dict:
    """{target: (din, dout)} against the base layout (core.init_params
    schema) — THE one copy of the per-target shape map, shared by
    init_lora, shape validation, and the serving pool's factor stacks
    (adapters/pool.py); two copies would silently desynchronize pool
    allocation from load-time validation."""
    D, H, Hkv, hd, F = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    )
    return {
        "wq": (D, H * hd), "wk": (D, Hkv * hd), "wv": (D, Hkv * hd),
        "wo": (H * hd, D),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
    }


def validate_adapter_shapes(cfg: ModelConfig, adapters, lcfg: LoraConfig,
                            max_rank: int | None = None) -> None:
    """Check every A/B factor against the base layout implied by `cfg` and
    the rank/targets `lcfg` declares. AdapterLoadError on any mismatch —
    the typed gate every consumer (engine merge, AdapterPool.load, mesh
    fetch) runs before factors go anywhere near a jit trace."""
    try:
        validate_targets(cfg, lcfg)
    except AdapterLoadError:
        raise
    except ValueError as e:
        # validate_targets raises bare ValueError (the training-time
        # surface); here a per-model target mismatch is still the typed
        # load error — a mesh fetch of a MoE-incompatible adapter must
        # not masquerade as an infrastructure fetch_failed incident
        raise AdapterLoadError(str(e)) from e
    io = adapter_target_io(cfg)
    if set(adapters) != set(lcfg.targets):
        raise AdapterLoadError(
            f"adapter targets {sorted(adapters)} != declared "
            f"{sorted(lcfg.targets)}"
        )
    if max_rank is not None and lcfg.rank > max_rank:
        raise AdapterLoadError(
            f"adapter rank {lcfg.rank} exceeds pool rank {max_rank}"
        )
    for t, ab in adapters.items():
        din, dout = io[t]
        a_shape = tuple(getattr(ab.get("a"), "shape", ()))
        b_shape = tuple(getattr(ab.get("b"), "shape", ()))
        if a_shape != (cfg.n_layers, din, lcfg.rank):
            raise AdapterLoadError(
                f"adapter {t!r}: A shape {a_shape} != "
                f"{(cfg.n_layers, din, lcfg.rank)} for {cfg.name!r}"
            )
        if b_shape != (cfg.n_layers, lcfg.rank, dout):
            raise AdapterLoadError(
                f"adapter {t!r}: B shape {b_shape} != "
                f"{(cfg.n_layers, lcfg.rank, dout)} for {cfg.name!r}"
            )


def save_adapters(path, adapters, lora_cfg: LoraConfig) -> None:
    """One .npz with the adapter arrays + a versioned manifest: the
    LoraConfig needed to merge (rank/alpha/targets — a mismatched merge
    would be silently wrong scaling) and a per-tensor sha256 map (the
    pieces.py discipline), so load_adapters turns a corrupt or tampered
    file into a typed AdapterLoadError instead of garbage weights."""
    import json

    from ..models.loader import _flatten
    from ..utils import sha256_hex

    flat = {k: np.asarray(v) for k, v in _flatten(jax.device_get(adapters)).items()}
    hashes = {
        k: sha256_hex(np.ascontiguousarray(v).tobytes()) for k, v in flat.items()
    }
    flat["__meta_version"] = np.int64(ADAPTER_FORMAT_VERSION)
    flat["__meta_rank"] = np.int64(lora_cfg.rank)
    flat["__meta_alpha"] = np.float64(lora_cfg.alpha)
    flat["__meta_targets"] = np.array(",".join(lora_cfg.targets))
    flat["__meta_sha256"] = np.array(json.dumps(hashes, separators=(",", ":")))
    np.savez(path, **flat)


def load_adapters(path, model_cfg: ModelConfig | None = None) -> tuple[dict, LoraConfig]:
    """Load + verify an adapter .npz. v2 files carry a per-tensor sha256
    manifest that is checked tensor-by-tensor; with ``model_cfg`` the
    factor shapes are additionally validated against the base layout.
    Any mismatch is a typed AdapterLoadError."""
    import json

    from ..models.loader import _unflatten
    from ..utils import sha256_hex

    try:
        with np.load(path, allow_pickle=False) as z:
            files = set(z.files)
            missing = {"__meta_rank", "__meta_alpha", "__meta_targets"} - files
            if missing:
                raise AdapterLoadError(
                    f"{path}: not an adapter file (missing {sorted(missing)})"
                )
            lcfg = LoraConfig(
                rank=int(z["__meta_rank"]),
                alpha=float(z["__meta_alpha"]),
                targets=tuple(str(z["__meta_targets"]).split(",")),
            )
            flat = {k: z[k] for k in z.files if not k.startswith("__meta_")}
            version = int(z["__meta_version"]) if "__meta_version" in files else 1
            if version >= 2:
                hashes = json.loads(str(z["__meta_sha256"]))
                if set(hashes) != set(flat):
                    raise AdapterLoadError(
                        f"{path}: manifest names {sorted(hashes)} != "
                        f"tensors {sorted(flat)}"
                    )
                for k, arr in flat.items():
                    got = sha256_hex(np.ascontiguousarray(arr).tobytes())
                    if got != hashes[k]:
                        raise AdapterLoadError(
                            f"{path}: tensor {k!r} hash mismatch "
                            f"({got[:12]} != {hashes[k][:12]})"
                        )
    except AdapterLoadError:
        raise
    except ValueError as e:  # LoraConfig validation (bad rank/targets)
        raise AdapterLoadError(f"{path}: {e}") from e
    except Exception as e:  # zipfile/np.load corruption
        raise AdapterLoadError(f"{path}: unreadable adapter file: {e}") from e
    adapters = _unflatten(flat)
    if model_cfg is not None:
        validate_adapter_shapes(model_cfg, adapters, lcfg)
    return adapters, lcfg
