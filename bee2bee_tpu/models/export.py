"""Checkpoint export: our param pytree → standard interchange formats.

The TPU-native analogue of the reference's model-export surface
(reference hf.py:139-158 exports TorchScript and ONNX). Torch graph
formats make no sense for a jax/XLA stack, so the interchange story is:

- **HF-layout safetensors** (`export_hf`): the exact inverse of
  models/loader's name mapping, plus a matching HF ``config.json`` — any
  torch/transformers stack loads the result with ``from_pretrained``.
  Covers the GPT-2, Llama/Mistral/Mixtral/Gemma, Phi, and GPT-NeoX families, like the
  loader.
- **Native piece format** (loader.save_native): content-addressed shard
  pieces + manifest — the mesh-distribution and checkpoint/resume format.

Everything is offline and torch-free: safetensors files are written with
numpy (bf16 via the uint16 bit pattern, mirroring the loader's reader).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

from .config import ModelConfig

_DTYPE_NAMES = {
    "float32": "F32",
    "float16": "F16",
    "bfloat16": "BF16",
    "int64": "I64",
    "int32": "I32",
    "uint8": "U8",
    "bool": "BOOL",
}


def write_safetensors(path: str | Path, tensors: dict[str, np.ndarray],
                      metadata: dict[str, str] | None = None) -> None:
    """Minimal safetensors writer (header JSON + raw buffers) — the inverse
    of loader._read_safetensors, same no-torch rationale."""
    header: dict = {}
    if metadata:
        header["__metadata__"] = metadata
    bufs: list[bytes] = []
    offset = 0
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr)
        dt = _DTYPE_NAMES.get(arr.dtype.name)
        if dt is None:
            raise ValueError(f"unsupported export dtype {arr.dtype} for {name!r}")
        buf = (
            arr.view(np.uint16).tobytes() if dt == "BF16" else arr.tobytes()
        )
        header[name] = {
            "dtype": dt,
            "shape": list(arr.shape),
            "data_offsets": [offset, offset + len(buf)],
        }
        bufs.append(buf)
        offset += len(buf)
    blob = json.dumps(header).encode("utf-8")
    with open(path, "wb") as f:
        f.write(len(blob).to_bytes(8, "little"))
        f.write(blob)
        for buf in bufs:
            f.write(buf)


def _np(x, dtype=None) -> np.ndarray:
    arr = np.asarray(jax.device_get(x))
    if dtype is not None:
        arr = arr.astype(dtype)
    return np.ascontiguousarray(arr)


def _export_gpt2_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_gpt2: unstack layers, re-fuse q/k/v into
    the HF c_attn block."""
    layers = params["layers"]
    state = {
        "transformer.wte.weight": _np(params["tok_embed"], dtype),
        "transformer.wpe.weight": _np(params["pos_embed"], dtype),
        "transformer.ln_f.weight": _np(params["final_norm"]["scale"], dtype),
        "transformer.ln_f.bias": _np(params["final_norm"]["bias"], dtype),
        # tied embeddings (gpt2 family always ties): transformers expects
        # the key to exist even though it shares storage with wte
        "lm_head.weight": _np(params["tok_embed"], dtype),
    }
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        state[p + "ln_1.weight"] = _np(layers["ln1"]["scale"][i], dtype)
        state[p + "ln_1.bias"] = _np(layers["ln1"]["bias"][i], dtype)
        state[p + "ln_2.weight"] = _np(layers["ln2"]["scale"][i], dtype)
        state[p + "ln_2.bias"] = _np(layers["ln2"]["bias"][i], dtype)
        a = layers["attn"]
        state[p + "attn.c_attn.weight"] = np.concatenate(
            [_np(a["wq"][i], dtype), _np(a["wk"][i], dtype), _np(a["wv"][i], dtype)],
            axis=1,
        )
        state[p + "attn.c_attn.bias"] = np.concatenate(
            [_np(a["bq"][i], dtype), _np(a["bk"][i], dtype), _np(a["bv"][i], dtype)]
        )
        state[p + "attn.c_proj.weight"] = _np(a["wo"][i], dtype)
        state[p + "attn.c_proj.bias"] = _np(a["bo"][i], dtype)
        m = layers["mlp"]
        state[p + "mlp.c_fc.weight"] = _np(m["w_up"][i], dtype)
        state[p + "mlp.c_fc.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.c_proj.weight"] = _np(m["w_down"][i], dtype)
        state[p + "mlp.c_proj.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_bigcode_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_bigcode: nn.Linear [out, in] with the
    fused c_attn packing q block then k then v on the OUT dim."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    state = {
        "transformer.wte.weight": _np(params["tok_embed"], dtype),
        "transformer.wpe.weight": _np(params["pos_embed"], dtype),
        "transformer.ln_f.weight": _np(params["final_norm"]["scale"], dtype),
        "transformer.ln_f.bias": _np(params["final_norm"]["bias"], dtype),
        "lm_head.weight": (
            _np(params["tok_embed"], dtype) if cfg.tie_embeddings
            else t(params["lm_head"])
        ),
    }
    a = layers["attn"]
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        for ln, hf in (("ln1", "ln_1"), ("ln2", "ln_2")):
            state[p + f"{hf}.weight"] = _np(layers[ln]["scale"][i], dtype)
            state[p + f"{hf}.bias"] = _np(layers[ln]["bias"][i], dtype)
        state[p + "attn.c_attn.weight"] = np.concatenate(
            [t(a["wq"][i]), t(a["wk"][i]), t(a["wv"][i])], axis=0
        )
        state[p + "attn.c_attn.bias"] = np.concatenate(
            [_np(a[b][i], dtype) for b in ("bq", "bk", "bv")]
        )
        state[p + "attn.c_proj.weight"] = t(a["wo"][i])
        state[p + "attn.c_proj.bias"] = _np(a["bo"][i], dtype)
        m = layers["mlp"]
        state[p + "mlp.c_fc.weight"] = t(m["w_up"][i])
        state[p + "mlp.c_fc.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.c_proj.weight"] = t(m["w_down"][i])
        state[p + "mlp.c_proj.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_llama_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_llama: transpose back to HF [out, in] and
    undo the gemma (1 + w) rmsnorm fold."""
    layers = params["layers"]
    off = 1.0 if cfg.norm_plus_one else 0.0
    t = lambda a: _np(a, dtype).T
    norm = lambda a: _np(np.asarray(jax.device_get(a), np.float32) - off, dtype)
    state = {
        "model.embed_tokens.weight": _np(params["tok_embed"], dtype),
        "model.norm.weight": norm(params["final_norm"]["scale"]),
    }
    if "bias" in params["final_norm"]:  # stablelm: biased layernorms
        state["model.norm.bias"] = _np(params["final_norm"]["bias"], dtype)
    if not cfg.tie_embeddings:
        state["lm_head.weight"] = t(params["lm_head"])
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        if cfg.no_pre_norms:  # olmo2: output norms only
            state[p + "post_attention_layernorm.weight"] = norm(layers["ln1_post"]["scale"][i])
            state[p + "post_feedforward_layernorm.weight"] = norm(layers["ln2_post"]["scale"][i])
        elif cfg.post_norms:  # gemma-2 norm names (see loader._convert_llama)
            state[p + "input_layernorm.weight"] = norm(layers["ln1"]["scale"][i])
            state[p + "post_attention_layernorm.weight"] = norm(layers["ln1_post"]["scale"][i])
            state[p + "pre_feedforward_layernorm.weight"] = norm(layers["ln2"]["scale"][i])
            state[p + "post_feedforward_layernorm.weight"] = norm(layers["ln2_post"]["scale"][i])
        else:
            state[p + "input_layernorm.weight"] = norm(layers["ln1"]["scale"][i])
            state[p + "post_attention_layernorm.weight"] = norm(layers["ln2"]["scale"][i])
        if "ln1" in layers and "bias" in layers["ln1"]:  # stablelm: biased LNs
            state[p + "input_layernorm.bias"] = _np(layers["ln1"]["bias"][i], dtype)
            state[p + "post_attention_layernorm.bias"] = _np(layers["ln2"]["bias"][i], dtype)
        a = layers["attn"]
        for ours, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            state[p + f"self_attn.{hf}.weight"] = t(a[ours][i])
        if "bq" in a:  # qwen2: q/k/v-only bias
            for ours, hf in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
                state[p + f"self_attn.{hf}.bias"] = _np(a[ours][i], dtype)
        if "q_norm" in a:  # qwen3/gemma3: per-head q/k RMSNorm scales
            # (gemma-3 stores them zero-centered — undo the (1+w) fold)
            state[p + "self_attn.q_norm.weight"] = norm(a["q_norm"][i])
            state[p + "self_attn.k_norm.weight"] = norm(a["k_norm"][i])
        if cfg.is_moe:
            moe = layers["moe"]
            if "q_norm" in a:  # qwen3_moe names
                state[p + "mlp.gate.weight"] = t(moe["router"][i])
                for e in range(cfg.n_experts):
                    q = p + f"mlp.experts.{e}."
                    state[q + "gate_proj.weight"] = t(moe["w_gate"][i][e])
                    state[q + "down_proj.weight"] = t(moe["w_down"][i][e])
                    state[q + "up_proj.weight"] = t(moe["w_up"][i][e])
            else:  # mixtral names
                state[p + "block_sparse_moe.gate.weight"] = t(moe["router"][i])
                for e in range(cfg.n_experts):
                    q = p + f"block_sparse_moe.experts.{e}."
                    state[q + "w1.weight"] = t(moe["w_gate"][i][e])
                    state[q + "w2.weight"] = t(moe["w_down"][i][e])
                    state[q + "w3.weight"] = t(moe["w_up"][i][e])
        else:
            m = layers["mlp"]
            state[p + "mlp.gate_proj.weight"] = t(m["w_gate"][i])
            state[p + "mlp.up_proj.weight"] = t(m["w_up"][i])
            state[p + "mlp.down_proj.weight"] = t(m["w_down"][i])
    return state


def _export_phi_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_phi."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    state = {
        "model.embed_tokens.weight": _np(params["tok_embed"], dtype),
        "model.final_layernorm.weight": _np(params["final_norm"]["scale"], dtype),
        "model.final_layernorm.bias": _np(params["final_norm"]["bias"], dtype),
        "lm_head.weight": t(params["lm_head"]),
        "lm_head.bias": _np(params["lm_head_bias"], dtype),
    }
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        state[p + "input_layernorm.weight"] = _np(layers["ln1"]["scale"][i], dtype)
        state[p + "input_layernorm.bias"] = _np(layers["ln1"]["bias"][i], dtype)
        a = layers["attn"]
        for ours, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "dense")):
            state[p + f"self_attn.{hf}.weight"] = t(a[ours][i])
        for ours, hf in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj"), ("bo", "dense")):
            state[p + f"self_attn.{hf}.bias"] = _np(a[ours][i], dtype)
        m = layers["mlp"]
        state[p + "mlp.fc1.weight"] = t(m["w_up"][i])
        state[p + "mlp.fc1.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.fc2.weight"] = t(m["w_down"][i])
        state[p + "mlp.fc2.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_neox_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_neox (re-interleaves the fused QKV)."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    H, hd, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    state = {
        "gpt_neox.embed_in.weight": _np(params["tok_embed"], dtype),
        "gpt_neox.final_layer_norm.weight": _np(params["final_norm"]["scale"], dtype),
        "gpt_neox.final_layer_norm.bias": _np(params["final_norm"]["bias"], dtype),
        "embed_out.weight": t(params["lm_head"]),
    }
    a = layers["attn"]
    for i in range(cfg.n_layers):
        p = f"gpt_neox.layers.{i}."
        for ln, hf in (("ln1", "input_layernorm"), ("ln2", "post_attention_layernorm")):
            state[p + f"{hf}.weight"] = _np(layers[ln]["scale"][i], dtype)
            state[p + f"{hf}.bias"] = _np(layers[ln]["bias"][i], dtype)
        # ours [D, H*hd] -> HF fused [H, 3, hd, D] -> [3D, D]
        w3 = np.stack(
            [_np(a[k][i], dtype).T.reshape(H, hd, D) for k in ("wq", "wk", "wv")],
            axis=1,
        )
        b3 = np.stack(
            [_np(a[k][i], dtype).reshape(H, hd) for k in ("bq", "bk", "bv")],
            axis=1,
        )
        state[p + "attention.query_key_value.weight"] = w3.reshape(3 * D, D)
        state[p + "attention.query_key_value.bias"] = b3.reshape(3 * D)
        state[p + "attention.dense.weight"] = t(a["wo"][i])
        state[p + "attention.dense.bias"] = _np(a["bo"][i], dtype)
        m = layers["mlp"]
        state[p + "mlp.dense_h_to_4h.weight"] = t(m["w_up"][i])
        state[p + "mlp.dense_h_to_4h.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.dense_4h_to_h.weight"] = t(m["w_down"][i])
        state[p + "mlp.dense_4h_to_h.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_mpt_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_mpt (re-fuses the plain-thirds Wqkv)."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    state = {
        "transformer.wte.weight": _np(params["tok_embed"], dtype),
        "transformer.norm_f.weight": _np(params["final_norm"]["scale"], dtype),
        "lm_head.weight": (
            _np(params["tok_embed"], dtype) if cfg.tie_embeddings
            else t(params["lm_head"])
        ),
    }
    a = layers["attn"]
    for i in range(cfg.n_layers):
        p = f"transformer.blocks.{i}."
        state[p + "norm_1.weight"] = _np(layers["ln1"]["scale"][i], dtype)
        state[p + "norm_2.weight"] = _np(layers["ln2"]["scale"][i], dtype)
        state[p + "attn.Wqkv.weight"] = np.concatenate(
            [t(a[k][i]) for k in ("wq", "wk", "wv")], axis=0
        )
        state[p + "attn.out_proj.weight"] = t(a["wo"][i])
        m = layers["mlp"]
        state[p + "ffn.up_proj.weight"] = t(m["w_up"][i])
        state[p + "ffn.down_proj.weight"] = t(m["w_down"][i])
    return state


def _export_bloom_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_bloom (re-interleaves the biased fused
    QKV per head, restores the embedding LayerNorm)."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    H, hd, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    state = {
        "transformer.word_embeddings.weight": _np(params["tok_embed"], dtype),
        "transformer.word_embeddings_layernorm.weight": _np(
            params["embed_norm"]["scale"], dtype),
        "transformer.word_embeddings_layernorm.bias": _np(
            params["embed_norm"]["bias"], dtype),
        "transformer.ln_f.weight": _np(params["final_norm"]["scale"], dtype),
        "transformer.ln_f.bias": _np(params["final_norm"]["bias"], dtype),
        "lm_head.weight": (
            _np(params["tok_embed"], dtype) if cfg.tie_embeddings
            else t(params["lm_head"])
        ),
    }
    a = layers["attn"]
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        for ln, hf in (("ln1", "input_layernorm"),
                       ("ln2", "post_attention_layernorm")):
            state[p + f"{hf}.weight"] = _np(layers[ln]["scale"][i], dtype)
            state[p + f"{hf}.bias"] = _np(layers[ln]["bias"][i], dtype)
        w3 = np.stack(
            [_np(a[k][i], dtype).T.reshape(H, hd, D) for k in ("wq", "wk", "wv")],
            axis=1,
        )
        b3 = np.stack(
            [_np(a[k][i], dtype).reshape(H, hd) for k in ("bq", "bk", "bv")],
            axis=1,
        )
        state[p + "self_attention.query_key_value.weight"] = w3.reshape(3 * H * hd, D)
        state[p + "self_attention.query_key_value.bias"] = b3.reshape(3 * H * hd)
        state[p + "self_attention.dense.weight"] = t(a["wo"][i])
        state[p + "self_attention.dense.bias"] = _np(a["bo"][i], dtype)
        m = layers["mlp"]
        state[p + "mlp.dense_h_to_4h.weight"] = t(m["w_up"][i])
        state[p + "mlp.dense_h_to_4h.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.dense_4h_to_h.weight"] = t(m["w_down"][i])
        state[p + "mlp.dense_4h_to_h.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_falcon_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_falcon (re-fuses q/k/v: multi_query's
    q-block-then-kv rows for K=1, the per-head [H, 3, hd] interleave for
    K=H)."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    H, K, hd, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    state = {
        "transformer.word_embeddings.weight": _np(params["tok_embed"], dtype),
        "transformer.ln_f.weight": _np(params["final_norm"]["scale"], dtype),
        "transformer.ln_f.bias": _np(params["final_norm"]["bias"], dtype),
    }
    if cfg.tie_embeddings:
        state["lm_head.weight"] = _np(params["tok_embed"], dtype)
    else:
        state["lm_head.weight"] = t(params["lm_head"])
    a = layers["attn"]
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        state[p + "input_layernorm.weight"] = _np(layers["ln1"]["scale"][i], dtype)
        state[p + "input_layernorm.bias"] = _np(layers["ln1"]["bias"][i], dtype)
        q, k, v = (t(a[key][i]) for key in ("wq", "wk", "wv"))
        if K == 1:
            fused = np.concatenate([q, k, v], axis=0)  # [(H+2)*hd, D]
        else:  # K == H: [H, 3, hd] out-dim interleave
            fused = np.stack(
                [w.reshape(H, hd, D) for w in (q, k, v)], axis=1
            ).reshape(3 * H * hd, D)
        state[p + "self_attention.query_key_value.weight"] = fused
        state[p + "self_attention.dense.weight"] = t(a["wo"][i])
        m = layers["mlp"]
        state[p + "mlp.dense_h_to_4h.weight"] = t(m["w_up"][i])
        state[p + "mlp.dense_4h_to_h.weight"] = t(m["w_down"][i])
    return state


def _export_gptj_state(params, cfg: ModelConfig, dtype) -> dict[str, np.ndarray]:
    """Inverse of loader._convert_gptj."""
    layers = params["layers"]
    t = lambda a: _np(a, dtype).T
    state = {
        "transformer.wte.weight": _np(params["tok_embed"], dtype),
        "transformer.ln_f.weight": _np(params["final_norm"]["scale"], dtype),
        "transformer.ln_f.bias": _np(params["final_norm"]["bias"], dtype),
        "lm_head.weight": t(params["lm_head"]),
        "lm_head.bias": _np(params["lm_head_bias"], dtype),
    }
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        state[p + "ln_1.weight"] = _np(layers["ln1"]["scale"][i], dtype)
        state[p + "ln_1.bias"] = _np(layers["ln1"]["bias"][i], dtype)
        a = layers["attn"]
        for ours, hf in (("wq", "q_proj"), ("wk", "k_proj"),
                         ("wv", "v_proj"), ("wo", "out_proj")):
            state[p + f"attn.{hf}.weight"] = t(a[ours][i])
        m = layers["mlp"]
        state[p + "mlp.fc_in.weight"] = t(m["w_up"][i])
        state[p + "mlp.fc_in.bias"] = _np(m["b_up"][i], dtype)
        state[p + "mlp.fc_out.weight"] = t(m["w_down"][i])
        state[p + "mlp.fc_out.bias"] = _np(m["b_down"][i], dtype)
    return state


def _export_exaone_moe(params, cfg: ModelConfig, np_dtype) -> tuple[dict, dict]:
    """(state dict, config.json) of an exaone_moe model (K-EXAONE) under the
    names loader._convert_exaone_moe reads: EXAONE 4.0's for a block,
    DeepSeek-V3's for the expert layer and for the multi-token-prediction
    layer (layer ``num_hidden_layers`` of the file, with the trunk's embedding
    and head repeated as DeepSeek-V3's files repeat them). A configuration
    that holds a SHARE (experts, vocabulary rows) writes what it holds, the
    experts under their place among all (``num_experts_held`` /
    ``expert_first`` / ``vocab_size_held`` say so in config.json)."""
    if cfg.expert_share or cfg.vocab_published:
        raise ValueError(
            f"{cfg.name!r} holds a share of its experts or vocabulary: an "
            "exaone_moe checkpoint states every expert and row")
    t = lambda a: np.ascontiguousarray(_np(a, np_dtype).T)  # noqa: E731
    proj = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    state = {
        "model.embed_tokens.weight": _np(params["tok_embed"], np_dtype),
        "model.norm.weight": _np(params["final_norm"]["scale"], np_dtype),
        "lm_head.weight": t(params["lm_head"]),
    }

    def put(group, first: int):
        for j in range(len(group["ln1_post"]["scale"])):
            pre = f"model.layers.{first + j}."
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                state[f"{pre}self_attn.{theirs}.weight"] = t(group["attn"][ours][j])
            for n in ("q_norm", "k_norm"):
                state[f"{pre}self_attn.{n}.weight"] = _np(group["attn"][n][j], np_dtype)
            state[f"{pre}post_attention_layernorm.weight"] = _np(
                group["ln1_post"]["scale"][j], np_dtype)
            state[f"{pre}post_feedforward_layernorm.weight"] = _np(
                group["ln2_post"]["scale"][j], np_dtype)
            if "mlp" in group:
                for ours, theirs in proj:
                    state[f"{pre}mlp.{theirs}.weight"] = t(group["mlp"][ours][j])
                continue
            moe = group["moe"]
            state[f"{pre}mlp.gate.weight"] = t(moe["router"][j])
            for ours, theirs in proj:
                for e in range(cfg.n_experts):
                    state[f"{pre}mlp.experts.{e}.{theirs}.weight"] = t(moe[ours][j][e])
                state[f"{pre}mlp.shared_experts.{theirs}.weight"] = t(moe["shared"][ours][j])

    k, L = cfg.first_k_dense, cfg.n_layers
    if k:
        put(params["dense_layers"], 0)
    put(params["layers"], k)
    if cfg.mtp_layers:
        mtp = params["mtp"]
        put(mtp["block"], L)
        pre = f"model.layers.{L}."
        state[f"{pre}enorm.weight"] = _np(mtp["enorm"]["scale"], np_dtype)
        state[f"{pre}hnorm.weight"] = _np(mtp["hnorm"]["scale"], np_dtype)
        state[f"{pre}eh_proj.weight"] = t(mtp["eh_proj"])
        state[f"{pre}embed_tokens.weight"] = state["model.embed_tokens.weight"]
        state[f"{pre}shared_head.norm.weight"] = state["model.norm.weight"]
        state[f"{pre}shared_head.head.weight"] = state["lm_head.weight"]
    pattern = "".join(
        "L" if i in cfg.sliding_window_residues else "G"
        for i in range(cfg.sliding_window_every))
    types = [("sliding_attention" if pattern[i % len(pattern)] == "L"
              else "full_attention") for i in range(L)]
    conf = {
        "model_type": "exaone_moe", "architectures": ["ExaoneMoeForCausalLM"],
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": cfg.expert_ff,
        "num_hidden_layers": L, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "hidden_act": "silu", "rms_norm_eps": cfg.norm_eps,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
        "tie_word_embeddings": cfg.tie_embeddings,
        "sliding_window": cfg.sliding_window, "sliding_window_pattern": pattern,
        "layer_types": types,
        "sliding_windows": [cfg.sliding_window if ty == "sliding_attention" else 0
                            for ty in types],
        "first_k_dense_replace": k,
        "mlp_layer_types": ["dense"] * k + ["sparse"] * (L - k),
        "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.n_experts_per_tok,
        "num_shared_experts": cfg.n_shared_experts, "scoring_func": "sigmoid",
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": cfg.moe_scale,
        "num_nextn_predict_layers": cfg.mtp_layers,
    }
    if cfg.mtp_layers:
        conf.update(mtp_layer_types=["full_attention"], mtp_sliding_windows=[0])
    return state, conf


def hf_config_dict(cfg: ModelConfig, qkv_bias: bool | None = None,
                   qk_norm: bool | None = None) -> dict:
    """A transformers-compatible config.json for the exported checkpoint.

    `qkv_bias` overrides cfg.qkv_bias from the ACTUAL params ("bq" leaves
    present): a checkpoint loaded with biases under a biasless config must
    still export as qwen2, or transformers would silently drop the bias
    tensors the state dict carries."""
    if cfg.rope_scaling is not None and (cfg.pos_embedding != "rope"
                                         or cfg.parallel_block):
        # only the llama-branch config schema carries rope_scaling; any
        # other family would drop it on export and diverge in transformers
        raise ValueError(
            f"rope_scaling export is only supported for llama-branch "
            f"families; {cfg.name!r} would silently lose it"
        )
    if cfg.pos_embedding == "alibi" and not cfg.use_bias:  # mpt family
        H = cfg.n_heads
        if (cfg.n_kv_heads != H or (H & (H - 1)) or cfg.embedding_norm
                or cfg.norm != "layernorm" or cfg.norm_bias
                or cfg.activation != "gelu_exact"
                or cfg.d_ff != 4 * cfg.d_model):
            # transformers' MptMLP HARDCODES 4*hidden — any other ratio
            # would shape-mismatch (or silently re-init) on from_pretrained
            raise ValueError(
                "mpt export requires MHA with power-of-two heads, weight-"
                "only layernorms, no biases, exact gelu, and expansion "
                f"ratio 4 (transformers hardcodes it); got "
                f"kv={cfg.n_kv_heads}, heads={H}, act={cfg.activation!r}, "
                f"norm_bias={cfg.norm_bias}, d_ff={cfg.d_ff}"
            )
        return {
            "model_type": "mpt",
            "architectures": ["MptForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "d_model": cfg.d_model,
            "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads,
            "expansion_ratio": cfg.d_ff // cfg.d_model,
            "max_seq_len": cfg.max_seq_len,
            "no_bias": True,
            "layer_norm_epsilon": cfg.norm_eps,
            "attn_config": {"alibi": True},
            "tie_word_embeddings": cfg.tie_embeddings,
        }
    if cfg.pos_embedding == "alibi":  # bloom family
        if (cfg.n_kv_heads != cfg.n_heads or not cfg.use_bias
                or cfg.norm != "layernorm" or cfg.activation != "gelu"
                or not cfg.embedding_norm):
            # HF Bloom hardcodes MHA, biased linears, tanh gelu, and the
            # embedding LayerNorm — anything else would load in
            # transformers WITHOUT warning and silently diverge
            raise ValueError(
                "bloom export requires MHA, use_bias, layernorm, gelu, "
                f"and embedding_norm; got kv={cfg.n_kv_heads}, "
                f"act={cfg.activation!r}, bias={cfg.use_bias}, "
                f"norm={cfg.norm!r}, embedding_norm={cfg.embedding_norm}"
            )
        return {
            "model_type": "bloom",
            "architectures": ["BloomForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "n_layer": cfg.n_layers,
            "n_head": cfg.n_heads,
            "layer_norm_epsilon": cfg.norm_eps,
            "apply_residual_connection_post_layernorm": False,
            "slow_but_exact": False,
            "tie_word_embeddings": cfg.tie_embeddings,
            # BloomConfig has no position-table size (ALiBi); the wild
            # checkpoints carry training length as seq_length — keep it
            # so the config round-trips
            "seq_length": cfg.max_seq_len,
        }
    if cfg.pos_embedding == "learned" and cfg.n_kv_heads != cfg.n_heads:
        # gpt-bigcode family (starcoder): the only learned-pos MQA layout
        if cfg.n_kv_heads != 1:
            raise ValueError(
                "gpt_bigcode export requires n_kv_heads=1 (multi_query); "
                f"got kv={cfg.n_kv_heads}"
            )
        # declare the gelu dialect the weights were trained with — a
        # hardcoded tanh-approx would load in transformers WITHOUT
        # warning and silently diverge for exact-gelu configs
        act = {"gelu": "gelu_pytorch_tanh", "gelu_exact": "gelu"}.get(cfg.activation)
        if act is None:
            raise ValueError(
                f"gpt_bigcode export supports gelu activations only; got "
                f"{cfg.activation!r}"
            )
        return {
            "model_type": "gpt_bigcode",
            "architectures": ["GPTBigCodeForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "n_positions": cfg.max_seq_len,
            "n_embd": cfg.d_model,
            "n_layer": cfg.n_layers,
            "n_head": cfg.n_heads,
            "n_inner": cfg.d_ff,
            "layer_norm_epsilon": cfg.norm_eps,
            "activation_function": act,
            "multi_query": True,
            "tie_word_embeddings": cfg.tie_embeddings,
        }
    if cfg.pos_embedding == "learned":  # gpt2 family
        return {
            "model_type": "gpt2",
            "architectures": ["GPT2LMHeadModel"],
            "vocab_size": cfg.vocab_size,
            "n_positions": cfg.max_seq_len,
            "n_embd": cfg.d_model,
            "n_layer": cfg.n_layers,
            "n_head": cfg.n_heads,
            "n_inner": cfg.d_ff,
            "layer_norm_epsilon": cfg.norm_eps,
            "tie_word_embeddings": True,
        }
    if cfg.parallel_block and cfg.rope_style == "interleaved":  # gpt-j
        if cfg.rope_theta != 10000.0 or cfg.activation != "gelu":
            # HF's GPTJ hardcodes rotary base 10000 and gelu_new: a
            # checkpoint exported from an overridden config would load
            # in transformers WITHOUT warning and silently diverge
            raise ValueError(
                f"gpt-j export requires rope_theta=10000/activation='gelu' "
                f"(transformers hardcodes them); got theta={cfg.rope_theta}, "
                f"activation={cfg.activation!r}"
            )
        return {
            "model_type": "gptj",
            "architectures": ["GPTJForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "n_embd": cfg.d_model,
            "n_layer": cfg.n_layers,
            "n_head": cfg.n_heads,
            "n_inner": cfg.d_ff,
            "n_positions": cfg.max_seq_len,
            "rotary_dim": cfg.rotary_dim,
            "layer_norm_epsilon": cfg.norm_eps,
            "tie_word_embeddings": False,
            "activation_function": "gelu_new",
        }
    if cfg.parallel_block and cfg.parallel_norms == 2:  # gpt-neox family
        return {
            "model_type": "gpt_neox",
            "architectures": ["GPTNeoXForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "intermediate_size": cfg.d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rotary_emb_base": cfg.rope_theta,
            "rotary_pct": cfg.rotary_pct,
            "layer_norm_eps": cfg.norm_eps,
            "use_parallel_residual": True,
            "tie_word_embeddings": False,
            "hidden_act": "gelu",
        }
    if cfg.parallel_block and not cfg.use_bias:  # falcon family (bias-free
        # parallel block sharing one layernorm; phi's block is biased)
        if (cfg.n_kv_heads not in (1, cfg.n_heads) or cfg.mlp_bias
                or cfg.lm_head_bias or cfg.activation != "gelu_exact"
                or cfg.rotary_pct < 1.0):
            # HF Falcon hardcodes full rotary + erf gelu and only speaks
            # the multi_query / per-head-interleave KV layouts — anything
            # else would load in transformers and silently diverge
            raise ValueError(
                "falcon export requires n_kv_heads in (1, n_heads), full "
                "rotary, gelu_exact, and no mlp/lm_head biases; got "
                f"kv={cfg.n_kv_heads}, act={cfg.activation!r}, "
                f"rotary_pct={cfg.rotary_pct}"
            )
        return {
            "model_type": "falcon",
            "architectures": ["FalconForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "ffn_hidden_size": cfg.d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta,
            "layer_norm_epsilon": cfg.norm_eps,
            "multi_query": cfg.n_kv_heads == 1,
            "parallel_attn": True,
            "new_decoder_architecture": False,
            "alibi": False,
            "bias": False,
            "tie_word_embeddings": cfg.tie_embeddings,
            "activation": "gelu",
        }
    if cfg.parallel_block:  # phi family
        return {
            "model_type": "phi",
            "architectures": ["PhiForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta,
            "layer_norm_eps": cfg.norm_eps,
            "partial_rotary_factor": cfg.rotary_pct,
            "tie_word_embeddings": False,
            "hidden_act": "gelu_new",
        }
    if cfg.no_pre_norms:  # olmo2: post-norm-only blocks
        if (cfg.norm != "rmsnorm" or cfg.activation != "silu"
                or not cfg.post_norms or not (cfg.qk_norm and cfg.qk_norm_full)
                or cfg.rotary_pct < 1.0 or cfg.sliding_window or cfg.is_moe
                or cfg.attn_logit_softcap or cfg.logits_softcap
                or cfg.norm_plus_one or cfg.attn_scale or cfg.use_bias
                or cfg.qkv_bias or cfg.embedding_scale or cfg.embedding_norm
                or cfg.head_dim != cfg.d_model // cfg.n_heads):
            # Olmo2ForCausalLM hardcodes all of these — anything else
            # would load in transformers WITHOUT warning and diverge
            raise ValueError(
                f"olmo2 export requires rmsnorm/silu/full rotary/full-width "
                f"qk-norm and no window/softcaps/moe ({cfg.name!r})"
            )
        out = {
            "model_type": "olmo2",
            "architectures": ["Olmo2ForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "tie_word_embeddings": cfg.tie_embeddings,
        }
        if cfg.rope_scaling is not None:
            if cfg.rope_scaling[0] != "linear":
                raise ValueError("olmo2 export supports linear rope_scaling only")
            out["rope_scaling"] = {"rope_type": "linear",
                                   "factor": cfg.rope_scaling[1]}
        return out
    if cfg.norm == "layernorm":  # stablelm: the one llama-layout family
        # with biased LayerNorms (and a partial_rotary_factor field)
        if (cfg.norm_plus_one or cfg.is_moe or cfg.post_norms
                or cfg.qk_norm or cfg.sliding_window
                or cfg.activation != "silu" or cfg.rope_style != "half"
                or cfg.use_bias or cfg.mlp_bias or not cfg.norm_bias
                or cfg.embedding_scale or cfg.attn_logit_softcap
                or cfg.attn_scale or cfg.logits_softcap):
            # StableLmForCausalLM hardcodes silu / half rotary / biased
            # LNs with bias-free mlp — anything else would load in
            # transformers WITHOUT warning and silently diverge
            raise ValueError(
                f"stablelm export requires silu + half rotary + biased "
                f"layernorms and none of moe/post_norms/qk_norm/window/"
                f"softcaps ({cfg.name!r} doesn't fit)"
            )
        if cfg.head_dim != cfg.d_model // cfg.n_heads:
            raise ValueError(
                "stablelm export cannot carry head_dim overrides "
                f"(StableLmConfig has no head_dim field); got "
                f"{cfg.head_dim}"
            )
        out = {
            "model_type": "stablelm",
            "architectures": ["StableLmForCausalLM"],
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta,
            "layer_norm_eps": cfg.norm_eps,
            "partial_rotary_factor": cfg.rotary_pct,
            "use_qkv_bias": bool(cfg.qkv_bias if qkv_bias is None else qkv_bias),
            "tie_word_embeddings": cfg.tie_embeddings,
        }
        if cfg.rope_scaling is not None:
            if cfg.rope_scaling[0] != "linear":
                raise ValueError(
                    "stablelm export supports linear rope_scaling only"
                )
            out["rope_scaling"] = {"rope_type": "linear",
                                   "factor": cfg.rope_scaling[1]}
        return out
    if cfg.rotary_pct < 1.0:
        # none of the llama-branch config schemas carry partial rotary —
        # transformers would rotate every head dim and silently diverge
        raise ValueError(
            f"partial rotary (rotary_pct={cfg.rotary_pct}) is not "
            f"representable in the llama-branch export schemas"
        )
    base = {
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "head_dim": cfg.head_dim,
    }
    if cfg.sliding_window is not None:
        # EVERY llama-branch family carries the window when set (mixtral
        # and qwen2 too, not just the mistral model_type below) — an
        # export that drops it silently widens attention for HF consumers
        base["sliding_window"] = cfg.sliding_window
    if cfg.rope_scaling is not None:
        if cfg.rope_scaling[0] == "linear":
            base["rope_scaling"] = {"rope_type": "linear",
                                    "factor": cfg.rope_scaling[1]}
        elif cfg.rope_scaling[0] == "yarn":
            _, f, af, bf, bs, orig, trunc = cfg.rope_scaling
            base["rope_scaling"] = {
                # attention_factor written EXPLICITLY: the parse-time
                # inference already folded any mscale variants into it
                "rope_type": "yarn", "factor": f, "attention_factor": af,
                "beta_fast": bf, "beta_slow": bs,
                "original_max_position_embeddings": orig,
                "truncate": trunc,
            }
        else:  # llama3
            _, f, lo, hi, orig = cfg.rope_scaling
            base["rope_scaling"] = {
                "rope_type": "llama3", "factor": f,
                "low_freq_factor": lo, "high_freq_factor": hi,
                "original_max_position_embeddings": orig,
            }
    if cfg.is_moe:
        has_qk = cfg.qk_norm if qk_norm is None else qk_norm
        if has_qk:  # qwen3_moe: qk-norm + per-expert gate/up/down names
            out = {
                "model_type": "qwen3_moe",
                "architectures": ["Qwen3MoeForCausalLM"],
                "num_experts": cfg.n_experts,
                "num_experts_per_tok": cfg.n_experts_per_tok,
                "moe_intermediate_size": cfg.d_ff,
                # our routing renormalizes top-k weights; transformers must
                # too or the mixture weighting silently differs
                "norm_topk_prob": True,
                "decoder_sparse_step": 1,
                "mlp_only_layers": [],
                **base,
            }
            if cfg.sliding_window is not None:
                # Qwen3MoeConfig NULLS sliding_window unless this is set
                out["use_sliding_window"] = True
            return out
        return {
            "model_type": "mixtral",
            "architectures": ["MixtralForCausalLM"],
            "num_local_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            **base,
        }
    if cfg.norm_plus_one:  # gemma family
        act = ("gelu_pytorch_tanh" if cfg.activation == "geglu"
               else cfg.activation)
        has_qk_norm = cfg.qk_norm if qk_norm is None else qk_norm
        if cfg.post_norms and has_qk_norm:  # gemma-3 (text) — keyed on
            # the ACTUAL params like the qwen3 branch, so config.json and
            # the state dict can never describe different families
            if cfg.sliding_window is None or cfg.local_rope_theta is None:
                raise ValueError(
                    "gemma3 export requires sliding_window and "
                    "local_rope_theta (Gemma3TextConfig hardcodes the "
                    "dual-rope local/global structure)"
                )
            out = {
                "model_type": "gemma3_text",
                "architectures": ["Gemma3ForCausalLM"],
                "hidden_activation": act,
                "query_pre_attn_scalar": cfg.attn_scale or cfg.head_dim,
                "rope_local_base_freq": cfg.local_rope_theta,
                # explicit per-layer types: the periodic pattern written
                # out the way transformers stores it
                "layer_types": [
                    ("sliding_attention"
                     if (i % cfg.sliding_window_every)
                     in cfg.sliding_window_residues
                     else "full_attention")
                    for i in range(cfg.n_layers)
                ],
                **base,
            }
            if cfg.attn_logit_softcap:
                out["attn_logit_softcapping"] = cfg.attn_logit_softcap
            if cfg.logits_softcap:
                out["final_logit_softcapping"] = cfg.logits_softcap
            return out
        if cfg.post_norms:  # gemma-2
            if (cfg.sliding_window is None or cfg.sliding_window_every != 2
                    or cfg.sliding_window_residues != (0,)):
                # HF Gemma2 HARDCODES the every-2nd-layer alternation and
                # defaults an omitted sliding_window to 4096 — any other
                # windowing would load in transformers and silently
                # mismatch our per-layer masks
                raise ValueError(
                    "gemma2 export requires sliding_window set with "
                    f"sliding_window_every=2; got window="
                    f"{cfg.sliding_window}, every={cfg.sliding_window_every}"
                )
            return {
                "model_type": "gemma2",
                "architectures": ["Gemma2ForCausalLM"],
                "hidden_act": act,
                "hidden_activation": act,
                "attn_logit_softcapping": cfg.attn_logit_softcap,
                "final_logit_softcapping": cfg.logits_softcap,
                "query_pre_attn_scalar": cfg.attn_scale or cfg.head_dim,
                **base,
            }
        return {
            "model_type": "gemma",
            "architectures": ["GemmaForCausalLM"],
            # transformers >= 4.39 reads hidden_activation and warns on the
            # legacy hidden_act key alone — write both so any version loads
            # the tanh-approx gelu our geglu uses
            "hidden_act": act,
            "hidden_activation": act,
            **base,
        }
    is_qwen3 = cfg.qk_norm if qk_norm is None else qk_norm
    if is_qwen3:  # qwen3: per-head q/k RMSNorm (no qkv biases)
        out = {"model_type": "qwen3", "architectures": ["Qwen3ForCausalLM"],
               **base}
        if cfg.sliding_window is not None:
            out["use_sliding_window"] = True
            out["max_window_layers"] = 0
        return out
    is_qwen2 = cfg.qkv_bias if qkv_bias is None else qkv_bias
    if is_qwen2:
        out = {"model_type": "qwen2", "architectures": ["Qwen2ForCausalLM"], **base}
        if cfg.sliding_window is not None:
            # Qwen2Config defaults use_sliding_window=False, and its
            # max_window_layers default (28) keeps the FIRST 28 layers on
            # full attention — our window applies to every layer, so emit
            # 0 or HF silently ignores the window for <=28-layer models
            out["use_sliding_window"] = True
            out["max_window_layers"] = 0
        return out
    if cfg.sliding_window is not None:  # mistral family (zephyr-7b etc.):
        # exporting as plain llama would silently widen the attention
        # window for any consumer that respects config.json
        return {
            "model_type": "mistral",
            "architectures": ["MistralForCausalLM"],
            **base,
        }
    return {"model_type": "llama", "architectures": ["LlamaForCausalLM"], **base}


def export_hf(params, cfg: ModelConfig, out_dir: str | Path,
              dtype: str = "float32") -> Path:
    """Write ``out_dir/model.safetensors`` + ``config.json`` in the HF layout
    for this config's family. Round-trips through models/loader, and loads
    in torch/transformers via ``from_pretrained(out_dir)``."""
    from . import core

    params = core.restack_layers(params)  # no-op unless a CPU engine's
    # unstacked list — the exporters index stacked [L, ...] arrays
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    if cfg.moe_router == "sigmoid" and not cfg.moe_select_bias:
        # exaone_moe (K-EXAONE): a family of its own, MTP layer and all
        state, conf = _export_exaone_moe(
            params, cfg, np.dtype(dtype) if dtype != "bfloat16" else _bf16_dtype())
        write_safetensors(
            out / "model.safetensors", state,
            metadata={"format": "pt", "exported_by": "bee2bee_tpu"})
        (out / "config.json").write_text(json.dumps(conf, indent=2))
        return out
    # key the family choice on the ACTUAL params: a bias-carrying tree
    # under a biasless config must still export as qwen2 (see hf_config_dict)
    has_qkv_bias = (
        None if cfg.pos_embedding == "learned"
        else "bq" in params["layers"].get("attn", {})
    )
    # qwen3 keyed on the ACTUAL params too: config.json and the state
    # dict must describe the same family, or from_pretrained silently
    # random-inits (or drops) the q/k norm tensors
    has_qk_norm = (
        None if cfg.pos_embedding == "learned"
        else "q_norm" in params["layers"].get("attn", {})
    )
    # validate the config BEFORE building the state dict: unsupported
    # combos must die with hf_config_dict's explanation, not a KeyError
    # halfway through a tensor conversion
    cfg_json = hf_config_dict(cfg, qkv_bias=has_qkv_bias, qk_norm=has_qk_norm)
    np_dtype = np.dtype(dtype) if dtype != "bfloat16" else _bf16_dtype()
    if cfg.pos_embedding == "alibi" and not cfg.use_bias:  # mpt
        state = _export_mpt_state(params, cfg, np_dtype)
    elif cfg.pos_embedding == "alibi":
        state = _export_bloom_state(params, cfg, np_dtype)
    elif cfg.pos_embedding == "learned" and cfg.n_kv_heads != cfg.n_heads:
        state = _export_bigcode_state(params, cfg, np_dtype)
    elif cfg.pos_embedding == "learned":
        state = _export_gpt2_state(params, cfg, np_dtype)
    elif cfg.parallel_block and cfg.rope_style == "interleaved":
        # SAME ordering as hf_config_dict: the two dispatch chains must
        # classify a config identically or the config.json and tensor
        # names would describe different families
        state = _export_gptj_state(params, cfg, np_dtype)
    elif cfg.parallel_block and cfg.parallel_norms == 2:
        state = _export_neox_state(params, cfg, np_dtype)
    elif cfg.parallel_block and not cfg.use_bias:  # falcon — same position
        # in the chain as hf_config_dict's classification
        state = _export_falcon_state(params, cfg, np_dtype)
    elif cfg.parallel_block:
        state = _export_phi_state(params, cfg, np_dtype)
    else:
        state = _export_llama_state(params, cfg, np_dtype)
    write_safetensors(
        out / "model.safetensors", state,
        metadata={"format": "pt", "exported_by": "bee2bee_tpu"},
    )
    (out / "config.json").write_text(json.dumps(cfg_json, indent=2))
    return out


def _bf16_dtype():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)
