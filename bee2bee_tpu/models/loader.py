"""Checkpoint loading: local HF checkpoints → our param layout, plus a
native orbax format for checkpoint/resume (a capability the reference lacks
entirely — SURVEY §5 "Checkpoint/resume: none").

HF weight name mapping covers the GPT-2 and Llama/Mistral/Mixtral/Gemma
families (the reference loads these via transformers at hf.py:23-32; we map
tensor names directly so torch is never needed on the serving path —
safetensors files are read with numpy). Everything is offline: paths must
exist locally; nothing downloads.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .core import init_params


def _stack(arrs):
    return np.stack(arrs, axis=0)


def _read_safetensors(path: Path) -> dict[str, np.ndarray]:
    """Minimal safetensors reader (header JSON + raw buffers); avoids a torch
    dependency on the serving path."""
    out = {}
    dtype_map = {
        "F32": np.float32, "F16": np.float16,
        "I64": np.int64, "I32": np.int32, "U8": np.uint8, "BOOL": np.bool_,
    }
    # seek+read per tensor: peak host memory stays one-tensor-sized, not
    # whole-shard-sized (llama shards are ~5 GB each)
    with open(path, "rb") as f:
        n = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(n).decode("utf-8"))
        base = 8 + n
        for name, spec in header.items():
            if name == "__metadata__":
                continue
            start, end = spec["data_offsets"]
            f.seek(base + start)
            buf = f.read(end - start)
            if spec["dtype"] == "BF16":
                # widen bf16 via the uint16 bit pattern, independent of
                # whether this numpy has a native bfloat16
                raw_u16 = np.frombuffer(buf, np.uint16).reshape(spec["shape"])
                arr = (raw_u16.astype(np.uint32) << 16).view(np.float32)
            else:
                arr = np.frombuffer(buf, dtype_map[spec["dtype"]]).reshape(spec["shape"])
            out[name] = arr
    return out


def _load_hf_state(path: Path) -> dict[str, np.ndarray]:
    state: dict[str, np.ndarray] = {}
    st_files = sorted(path.glob("*.safetensors"))
    if st_files:
        for f in st_files:
            state.update(_read_safetensors(f))
        return state
    bins = sorted(path.glob("pytorch_model*.bin"))
    if bins:
        import torch  # cpu torch is available in this image

        for f in bins:
            sd = torch.load(f, map_location="cpu", weights_only=True)
            state.update({k: v.float().numpy() for k, v in sd.items()})
        return state
    raise FileNotFoundError(f"no safetensors or pytorch_model.bin under {path}")


def _convert_gpt2(state, cfg: ModelConfig) -> dict:
    """HF GPT-2 names → our layout. HF conv1d stores [in, out] already."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    L = cfg.n_layers
    layers = {
        "ln1": {"scale": _stack([g(f"h.{i}.ln_1.weight") for i in range(L)]),
                "bias": _stack([g(f"h.{i}.ln_1.bias") for i in range(L)])},
        "ln2": {"scale": _stack([g(f"h.{i}.ln_2.weight") for i in range(L)]),
                "bias": _stack([g(f"h.{i}.ln_2.bias") for i in range(L)])},
    }
    D = cfg.d_model
    qw, kw, vw, qb, kb, vb = [], [], [], [], [], []
    for i in range(L):
        w = g(f"h.{i}.attn.c_attn.weight")  # [D, 3D]
        b = g(f"h.{i}.attn.c_attn.bias")
        qw.append(w[:, :D]); kw.append(w[:, D:2 * D]); vw.append(w[:, 2 * D:])
        qb.append(b[:D]); kb.append(b[D:2 * D]); vb.append(b[2 * D:])
    layers["attn"] = {
        "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
        "bq": _stack(qb), "bk": _stack(kb), "bv": _stack(vb),
        "wo": _stack([g(f"h.{i}.attn.c_proj.weight") for i in range(L)]),
        "bo": _stack([g(f"h.{i}.attn.c_proj.bias") for i in range(L)]),
    }
    layers["mlp"] = {
        "w_up": _stack([g(f"h.{i}.mlp.c_fc.weight") for i in range(L)]),
        "b_up": _stack([g(f"h.{i}.mlp.c_fc.bias") for i in range(L)]),
        "w_down": _stack([g(f"h.{i}.mlp.c_proj.weight") for i in range(L)]),
        "b_down": _stack([g(f"h.{i}.mlp.c_proj.bias") for i in range(L)]),
    }
    return {
        "tok_embed": g("wte.weight"),
        "pos_embed": g("wpe.weight"),
        "layers": layers,
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }


def _convert_bigcode(state, cfg: ModelConfig) -> dict:
    """HF GPT-BigCode (starcoder/santacoder) names → our layout. Same
    names as gpt2 but nn.Linear ([out, in]) instead of Conv1D, and the
    fused c_attn packs [D + 2*kv_dim] on the OUT dim: all query heads,
    then k, then v (MQA: kv_dim = head_dim)."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L, D = cfg.n_layers, cfg.d_model
    kv = cfg.n_kv_heads * cfg.head_dim
    H, hd = cfg.n_heads, cfg.head_dim
    qw, kw, vw, qb, kb, vb = [], [], [], [], [], []
    for i in range(L):
        w = g(f"h.{i}.attn.c_attn.weight")  # [D + 2*kv, D]
        b = g(f"h.{i}.attn.c_attn.bias")
        if cfg.n_kv_heads == H:
            # multi_query=False packs q/k/v PER HEAD ([H, 3*hd] out-dims,
            # HF view(num_heads, 3*head_dim).split) — a sequential-thirds
            # split would scramble K/V across heads
            wr = w.reshape(H, 3, hd, D)
            br = b.reshape(H, 3, hd)
            for dst, bst, j in ((qw, qb, 0), (kw, kb, 1), (vw, vb, 2)):
                dst.append(np.ascontiguousarray(wr[:, j].reshape(H * hd, D).T))
                bst.append(np.ascontiguousarray(br[:, j].reshape(H * hd)))
        else:  # multi_query: query block, then one k head, then one v head
            qw.append(t(w[:D])); kw.append(t(w[D:D + kv])); vw.append(t(w[D + kv:]))
            qb.append(b[:D]); kb.append(b[D:D + kv]); vb.append(b[D + kv:])
    layers = {
        "ln1": {"scale": _stack([g(f"h.{i}.ln_1.weight") for i in range(L)]),
                "bias": _stack([g(f"h.{i}.ln_1.bias") for i in range(L)])},
        "ln2": {"scale": _stack([g(f"h.{i}.ln_2.weight") for i in range(L)]),
                "bias": _stack([g(f"h.{i}.ln_2.bias") for i in range(L)])},
        "attn": {
            "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
            "bq": _stack(qb), "bk": _stack(kb), "bv": _stack(vb),
            "wo": _stack([t(g(f"h.{i}.attn.c_proj.weight")) for i in range(L)]),
            "bo": _stack([g(f"h.{i}.attn.c_proj.bias") for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"h.{i}.mlp.c_fc.weight")) for i in range(L)]),
            "b_up": _stack([g(f"h.{i}.mlp.c_fc.bias") for i in range(L)]),
            "w_down": _stack([t(g(f"h.{i}.mlp.c_proj.weight")) for i in range(L)]),
            "b_down": _stack([g(f"h.{i}.mlp.c_proj.bias") for i in range(L)]),
        },
    }
    out = {
        "tok_embed": g("wte.weight"),
        "pos_embed": g("wpe.weight"),
        "layers": layers,
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }
    if not cfg.tie_embeddings:
        lm = state.get("lm_head.weight")
        out["lm_head"] = (
            t(lm) if lm is not None
            else np.ascontiguousarray(g("wte.weight").T)
        )
    return out


def _convert_phi(state, cfg: ModelConfig) -> dict:
    """HF phi-2 names → our layout (microsoft/phi-2: parallel blocks with
    one input_layernorm, q/k/v/dense + fc1/fc2 all biased, untied
    lm_head with bias, final_layernorm). HF linear is [out, in] → ours
    [in, out]."""
    pre = "model." if any(k.startswith("model.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L = cfg.n_layers
    layers = {
        "ln1": {
            "scale": _stack([g(f"layers.{i}.input_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"layers.{i}.input_layernorm.bias") for i in range(L)]),
        },
        "attn": {
            "wq": _stack([t(g(f"layers.{i}.self_attn.q_proj.weight")) for i in range(L)]),
            "wk": _stack([t(g(f"layers.{i}.self_attn.k_proj.weight")) for i in range(L)]),
            "wv": _stack([t(g(f"layers.{i}.self_attn.v_proj.weight")) for i in range(L)]),
            "wo": _stack([t(g(f"layers.{i}.self_attn.dense.weight")) for i in range(L)]),
            "bq": _stack([g(f"layers.{i}.self_attn.q_proj.bias") for i in range(L)]),
            "bk": _stack([g(f"layers.{i}.self_attn.k_proj.bias") for i in range(L)]),
            "bv": _stack([g(f"layers.{i}.self_attn.v_proj.bias") for i in range(L)]),
            "bo": _stack([g(f"layers.{i}.self_attn.dense.bias") for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"layers.{i}.mlp.fc1.weight")) for i in range(L)]),
            "b_up": _stack([g(f"layers.{i}.mlp.fc1.bias") for i in range(L)]),
            "w_down": _stack([t(g(f"layers.{i}.mlp.fc2.weight")) for i in range(L)]),
            "b_down": _stack([g(f"layers.{i}.mlp.fc2.bias") for i in range(L)]),
        },
    }
    return {
        "tok_embed": g("embed_tokens.weight"),
        "layers": layers,
        "final_norm": {
            "scale": g("final_layernorm.weight"),
            "bias": g("final_layernorm.bias"),
        },
        "lm_head": t(state["lm_head.weight"]),
        "lm_head_bias": state["lm_head.bias"],
    }


def _convert_gptj(state, cfg: ModelConfig) -> dict:
    """HF GPT-J names → our layout (transformer.h.N.{ln_1, attn.{q,k,v,
    out}_proj bias-free, mlp.{fc_in,fc_out} biased}, untied lm_head WITH
    bias). HF linear is [out, in] → ours [in, out]."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L = cfg.n_layers
    layers = {
        "ln1": {
            "scale": _stack([g(f"h.{i}.ln_1.weight") for i in range(L)]),
            "bias": _stack([g(f"h.{i}.ln_1.bias") for i in range(L)]),
        },
        "attn": {
            "wq": _stack([t(g(f"h.{i}.attn.q_proj.weight")) for i in range(L)]),
            "wk": _stack([t(g(f"h.{i}.attn.k_proj.weight")) for i in range(L)]),
            "wv": _stack([t(g(f"h.{i}.attn.v_proj.weight")) for i in range(L)]),
            "wo": _stack([t(g(f"h.{i}.attn.out_proj.weight")) for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"h.{i}.mlp.fc_in.weight")) for i in range(L)]),
            "b_up": _stack([g(f"h.{i}.mlp.fc_in.bias") for i in range(L)]),
            "w_down": _stack([t(g(f"h.{i}.mlp.fc_out.weight")) for i in range(L)]),
            "b_down": _stack([g(f"h.{i}.mlp.fc_out.bias") for i in range(L)]),
        },
    }
    return {
        "tok_embed": g("wte.weight"),
        "layers": layers,
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "lm_head": t(state["lm_head.weight"]),
        "lm_head_bias": state["lm_head.bias"],
    }


def _convert_mpt(state, cfg: ModelConfig) -> dict:
    """HF MPT names → our layout: transformer.blocks.N.{norm_1, attn.Wqkv
    (sequential q|k|v thirds), attn.out_proj, norm_2, ffn.{up,down}_proj},
    weight-only norms, zero biases, tied head, ALiBi."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L, D = cfg.n_layers, cfg.d_model
    qw, kw, vw = [], [], []
    for i in range(L):
        w = g(f"blocks.{i}.attn.Wqkv.weight")  # [3D, D], plain thirds
        qw.append(t(w[:D])); kw.append(t(w[D:2 * D])); vw.append(t(w[2 * D:]))
    layers = {
        "ln1": {"scale": _stack([g(f"blocks.{i}.norm_1.weight") for i in range(L)])},
        "ln2": {"scale": _stack([g(f"blocks.{i}.norm_2.weight") for i in range(L)])},
        "attn": {
            "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
            "wo": _stack([t(g(f"blocks.{i}.attn.out_proj.weight")) for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"blocks.{i}.ffn.up_proj.weight")) for i in range(L)]),
            "w_down": _stack([t(g(f"blocks.{i}.ffn.down_proj.weight")) for i in range(L)]),
        },
    }
    out = {
        "tok_embed": g("wte.weight"),
        "layers": layers,
        "final_norm": {"scale": g("norm_f.weight")},
    }
    if not cfg.tie_embeddings:
        lm = state.get("lm_head.weight")
        out["lm_head"] = (
            t(lm) if lm is not None
            else np.ascontiguousarray(g("wte.weight").T)
        )
    return out


def _convert_bloom(state, cfg: ModelConfig) -> dict:
    """HF BLOOM names → our layout: word_embeddings + its LayerNorm,
    per-head [H, 3, hd] interleaved fused QKV WITH biases (same packing
    as gpt-neox), biased dense/mlp, sequential pre-norm blocks, ALiBi
    (no positional tensors at all)."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L, D = cfg.n_layers, cfg.d_model
    H, hd = cfg.n_heads, cfg.head_dim
    qw, kw, vw, qb, kb, vb = [], [], [], [], [], []
    for i in range(L):
        w = g(f"h.{i}.self_attention.query_key_value.weight")  # [3D, D]
        b = g(f"h.{i}.self_attention.query_key_value.bias")
        wr = w.reshape(H, 3, hd, D)
        br = b.reshape(H, 3, hd)
        for dst, bst, j in ((qw, qb, 0), (kw, kb, 1), (vw, vb, 2)):
            dst.append(np.ascontiguousarray(wr[:, j].reshape(H * hd, D).T))
            bst.append(np.ascontiguousarray(br[:, j].reshape(H * hd)))
    layers = {
        "ln1": {
            "scale": _stack([g(f"h.{i}.input_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"h.{i}.input_layernorm.bias") for i in range(L)]),
        },
        "ln2": {
            "scale": _stack([g(f"h.{i}.post_attention_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"h.{i}.post_attention_layernorm.bias") for i in range(L)]),
        },
        "attn": {
            "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
            "bq": _stack(qb), "bk": _stack(kb), "bv": _stack(vb),
            "wo": _stack([t(g(f"h.{i}.self_attention.dense.weight")) for i in range(L)]),
            "bo": _stack([g(f"h.{i}.self_attention.dense.bias") for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"h.{i}.mlp.dense_h_to_4h.weight")) for i in range(L)]),
            "b_up": _stack([g(f"h.{i}.mlp.dense_h_to_4h.bias") for i in range(L)]),
            "w_down": _stack([t(g(f"h.{i}.mlp.dense_4h_to_h.weight")) for i in range(L)]),
            "b_down": _stack([g(f"h.{i}.mlp.dense_4h_to_h.bias") for i in range(L)]),
        },
    }
    out = {
        "tok_embed": g("word_embeddings.weight"),
        "embed_norm": {
            "scale": g("word_embeddings_layernorm.weight"),
            "bias": g("word_embeddings_layernorm.bias"),
        },
        "layers": layers,
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }
    if not cfg.tie_embeddings:
        lm = state.get("lm_head.weight")
        out["lm_head"] = (
            t(lm) if lm is not None
            else np.ascontiguousarray(g("word_embeddings.weight").T)
        )
    return out


def _convert_falcon(state, cfg: ModelConfig) -> dict:
    """HF Falcon names → our layout. falcon-7b fuses q/k/v as
    [(H + 2)*hd, D] with ALL query heads first, then one k head, then one
    v head (multi_query — HF _split_heads' else branch); falcon-rw-style
    checkpoints (multi_query=False) use the per-head [H, 3, hd]
    interleave instead. Parallel attn+mlp share input_layernorm; no
    linear biases; layernorms keep theirs."""
    pre = "transformer." if any(k.startswith("transformer.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L, D = cfg.n_layers, cfg.d_model
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qw, kw, vw = [], [], []
    for i in range(L):
        w = g(f"h.{i}.self_attention.query_key_value.weight")
        if K == 1:  # multi_query: q block, then single k + v heads
            qw.append(t(w[: H * hd]))
            kw.append(t(w[H * hd: (H + 1) * hd]))
            vw.append(t(w[(H + 1) * hd:]))
        elif K == H:  # falcon-rw: [H, 3, hd] on the out dim
            wr = w.reshape(H, 3, hd, D)
            for dst, j in ((qw, 0), (kw, 1), (vw, 2)):
                dst.append(np.ascontiguousarray(wr[:, j].reshape(H * hd, D).T))
        else:
            raise ValueError(
                "falcon grouped-KV (new_decoder_architecture) checkpoints "
                "are not supported by the native loader"
            )
    layers = {
        "ln1": {
            "scale": _stack([g(f"h.{i}.input_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"h.{i}.input_layernorm.bias") for i in range(L)]),
        },
        "attn": {
            "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
            "wo": _stack([t(g(f"h.{i}.self_attention.dense.weight")) for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"h.{i}.mlp.dense_h_to_4h.weight")) for i in range(L)]),
            "w_down": _stack([t(g(f"h.{i}.mlp.dense_4h_to_h.weight")) for i in range(L)]),
        },
    }
    params = {
        "tok_embed": g("word_embeddings.weight"),
        "layers": layers,
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = t(state["lm_head.weight"])
    return params


def _convert_neox(state, cfg: ModelConfig) -> dict:
    """HF GPT-NeoX/Pythia names → our layout. The fused query_key_value
    weight is [3*D, D] with rows ordered HEAD-MAJOR and q/k/v INTERLEAVED
    per head ([H, 3, hd] on the out dim — HF splits it after a
    view(B, T, H, 3*hd)); a naive thirds split would scramble heads."""
    pre = "gpt_neox." if any(k.startswith("gpt_neox.") for k in state) else ""
    g = lambda k: state[pre + k]
    t = lambda a: np.ascontiguousarray(a.T)
    L, D = cfg.n_layers, cfg.d_model
    H, hd = cfg.n_heads, cfg.head_dim

    def split_qkv(w, b):
        # w [3D, D] -> [H, 3, hd, D]; b [3D] -> [H, 3, hd]
        wr = w.reshape(H, 3, hd, D)
        br = b.reshape(H, 3, hd)
        ws = [np.ascontiguousarray(wr[:, i].reshape(H * hd, D).T) for i in range(3)]
        bs = [np.ascontiguousarray(br[:, i].reshape(H * hd)) for i in range(3)]
        return ws, bs

    qw, kw, vw, qb, kb, vb = [], [], [], [], [], []
    for i in range(L):
        ws, bs = split_qkv(
            g(f"layers.{i}.attention.query_key_value.weight"),
            g(f"layers.{i}.attention.query_key_value.bias"),
        )
        qw.append(ws[0]); kw.append(ws[1]); vw.append(ws[2])
        qb.append(bs[0]); kb.append(bs[1]); vb.append(bs[2])
    layers = {
        "ln1": {
            "scale": _stack([g(f"layers.{i}.input_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"layers.{i}.input_layernorm.bias") for i in range(L)]),
        },
        "ln2": {
            "scale": _stack([g(f"layers.{i}.post_attention_layernorm.weight") for i in range(L)]),
            "bias": _stack([g(f"layers.{i}.post_attention_layernorm.bias") for i in range(L)]),
        },
        "attn": {
            "wq": _stack(qw), "wk": _stack(kw), "wv": _stack(vw),
            "bq": _stack(qb), "bk": _stack(kb), "bv": _stack(vb),
            "wo": _stack([t(g(f"layers.{i}.attention.dense.weight")) for i in range(L)]),
            "bo": _stack([g(f"layers.{i}.attention.dense.bias") for i in range(L)]),
        },
        "mlp": {
            "w_up": _stack([t(g(f"layers.{i}.mlp.dense_h_to_4h.weight")) for i in range(L)]),
            "b_up": _stack([g(f"layers.{i}.mlp.dense_h_to_4h.bias") for i in range(L)]),
            "w_down": _stack([t(g(f"layers.{i}.mlp.dense_4h_to_h.weight")) for i in range(L)]),
            "b_down": _stack([g(f"layers.{i}.mlp.dense_4h_to_h.bias") for i in range(L)]),
        },
    }
    return {
        "tok_embed": g("embed_in.weight"),
        "layers": layers,
        "final_norm": {
            "scale": g("final_layer_norm.weight"),
            "bias": g("final_layer_norm.bias"),
        },
        "lm_head": t(state["embed_out.weight"]),
    }


def _convert_phi3(state, cfg: ModelConfig) -> dict:
    """HF Phi-3 names → our layout. Architecturally phi-3 IS a llama-
    style model (rmsnorm, gated silu, GQA, rope) — only the tensor
    packing differs: qkv_proj fuses [q | k | v] on the out dim and
    gate_up_proj fuses [gate | up]. Un-fuse into llama key names and
    DELEGATE to _convert_llama, so every llama-branch behavior (norm
    folds, biases, future fixes) applies identically."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F = cfg.d_ff
    unfused: dict[str, np.ndarray] = {}
    for k, v in state.items():
        if k.endswith(".self_attn.qkv_proj.weight"):
            base = k.replace("qkv_proj", "{}")
            unfused[base.format("q_proj")] = v[: H * hd]
            unfused[base.format("k_proj")] = v[H * hd: (H + K) * hd]
            unfused[base.format("v_proj")] = v[(H + K) * hd:]
        elif k.endswith(".mlp.gate_up_proj.weight"):
            unfused[k.replace("gate_up_proj", "gate_proj")] = v[:F]
            unfused[k.replace("gate_up_proj", "up_proj")] = v[F:]
        else:
            unfused[k] = v
    return _convert_llama(unfused, cfg)


def _convert_falcon_h1(state, cfg: ModelConfig) -> dict:
    """HF falcon_h1 names -> our layout. The attention, MLP and norms are a
    llama block under other names (feed_forward.*, pre_ff_layernorm,
    final_layernorm): rename and DELEGATE to _convert_llama; the Mamba-2
    mixer's tensors (mamba.*) stack into layers/ssm. conv1d.weight is
    [C, 1, K] (depthwise) -> [C, K]; linears transpose as everywhere."""
    renamed = {
        k.replace(".feed_forward.", ".mlp.")
         .replace(".pre_ff_layernorm.", ".post_attention_layernorm.")
         .replace("final_layernorm.", "norm."): v
        for k, v in state.items() if ".mamba." not in k
    }
    params = _convert_llama(renamed, cfg)
    pre = "model." if any(k.startswith("model.") for k in state) else ""
    t = lambda a: np.ascontiguousarray(a.T)
    m = lambda i, k: state[f"{pre}layers.{i}.mamba.{k}"]
    L = range(cfg.n_layers)
    params["layers"]["ssm"] = {
        "w_in": _stack([t(m(i, "in_proj.weight")) for i in L]),
        "conv_w": _stack([m(i, "conv1d.weight")[:, 0, :] for i in L]),
        "conv_b": _stack([m(i, "conv1d.bias") for i in L]),
        "dt_bias": _stack([m(i, "dt_bias") for i in L]),
        "A_log": _stack([m(i, "A_log") for i in L]),
        "D": _stack([m(i, "D") for i in L]),
        "norm": _stack([m(i, "norm.weight") for i in L]),
        "w_out": _stack([t(m(i, "out_proj.weight")) for i in L]),
    }
    return params


def _convert_joyai(state, cfg: ModelConfig) -> dict:
    """HF joyai_llm_flash (DeepSeek-V3 layout) names -> our layout: the latent
    attention's seven tensors, the leading dense layers (``dense_layers``),
    then the expert layers (``layers``) with mlp.gate.{weight,
    e_score_correction_bias}, mlp.experts.N.* stacked over N, and
    mlp.shared_experts.*. Tensors of layers past cfg.n_layers — the next-n
    (multi-token prediction) layer, which is not built — are ignored."""
    pre = "model." if any(k.startswith("model.") for k in state) else ""
    t = lambda a: np.ascontiguousarray(a.T)  # noqa: E731  HF linear is [out, in]
    w = lambda i, k: state[f"{pre}layers.{i}.{k}"]  # noqa: E731
    proj = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))

    def group(idx, moe: bool) -> dict:
        attn = {
            "wq_a": _stack([t(w(i, "self_attn.q_a_proj.weight")) for i in idx]),
            "q_a_norm": _stack([w(i, "self_attn.q_a_layernorm.weight") for i in idx]),
            "wq_b": _stack([t(w(i, "self_attn.q_b_proj.weight")) for i in idx]),
            "wkv_a": _stack([t(w(i, "self_attn.kv_a_proj_with_mqa.weight")) for i in idx]),
            "kv_a_norm": _stack([w(i, "self_attn.kv_a_layernorm.weight") for i in idx]),
            "wkv_b": _stack([t(w(i, "self_attn.kv_b_proj.weight")) for i in idx]),
            "wo": _stack([t(w(i, "self_attn.o_proj.weight")) for i in idx]),
        }
        out = {
            "attn": attn,
            "ln1": {"scale": _stack([w(i, "input_layernorm.weight") for i in idx])},
            "ln2": {"scale": _stack(
                [w(i, "post_attention_layernorm.weight") for i in idx])},
        }
        swiglu = lambda at: {  # noqa: E731
            ours: _stack([t(w(i, f"{at}.{theirs}.weight")) for i in idx])
            for ours, theirs in proj
        }
        if not moe:
            out["mlp"] = swiglu("mlp")
            return out
        E = range(cfg.n_experts)
        out["moe"] = {
            "router": _stack([t(w(i, "mlp.gate.weight")) for i in idx]),
            "router_bias": _stack(
                [w(i, "mlp.gate.e_score_correction_bias") for i in idx]
            ).astype(np.float32),
            **{
                ours: _stack([
                    _stack([t(w(i, f"mlp.experts.{e}.{theirs}.weight")) for e in E])
                    for i in idx])
                for ours, theirs in proj
            },
        }
        if cfg.n_shared_experts:
            out["moe"]["shared"] = swiglu("mlp.shared_experts")
        return out

    k = cfg.first_k_dense
    params = {
        "tok_embed": state[f"{pre}embed_tokens.weight"],
        "layers": group(range(k, cfg.n_layers), True),
        "final_norm": {"scale": state[f"{pre}norm.weight"]},
    }
    if k:
        params["dense_layers"] = group(range(k), False)
    if not cfg.tie_embeddings:
        params["lm_head"] = t(state["lm_head.weight"])
    return params


def _convert_exaone_moe(state, cfg: ModelConfig) -> dict:
    """HF exaone_moe (LGAI-EXAONE/K-EXAONE-*) names -> our layout. The names
    are EXAONE 4.0's for a block (self_attn.{q,k,v,o}_proj, q_norm / k_norm,
    post_attention_layernorm / post_feedforward_layernorm on the branches'
    OUTPUTS) and DeepSeek-V3's, whose config keys the model uses, for the
    expert layer (mlp.gate.weight, mlp.experts.N.*, mlp.shared_experts.*) and
    for the multi-token-prediction layer, which is layer ``num_hidden_layers``
    of the file (enorm, hnorm, eh_proj beside a block's own names; its
    embed_tokens and shared_head repeat the trunk's and are not read):
    its tensors are NOT skipped. A cut configuration (cfg.n_experts_held,
    cfg.vocab_published) takes its share of every layer's experts and the
    rows it holds of the embedding and the head out of the whole."""
    pre = "model." if any(k.startswith("model.") for k in state) else ""
    t = lambda a: np.ascontiguousarray(a.T)  # noqa: E731  HF linear is [out, in]
    w = lambda i, k: state[f"{pre}layers.{i}.{k}"]  # noqa: E731
    proj = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    held = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)

    def group(idx, moe: bool) -> dict:
        out = {
            "attn": {
                **{ours: _stack([t(w(i, f"self_attn.{theirs}.weight")) for i in idx])
                   for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                        ("wv", "v_proj"), ("wo", "o_proj"))},
                "q_norm": _stack([w(i, "self_attn.q_norm.weight") for i in idx]),
                "k_norm": _stack([w(i, "self_attn.k_norm.weight") for i in idx]),
            },
            "ln1_post": {"scale": _stack(
                [w(i, "post_attention_layernorm.weight") for i in idx])},
            "ln2_post": {"scale": _stack(
                [w(i, "post_feedforward_layernorm.weight") for i in idx])},
        }
        swiglu = lambda at: {  # noqa: E731
            ours: _stack([t(w(i, f"{at}.{theirs}.weight")) for i in idx])
            for ours, theirs in proj
        }
        if not moe:
            out["mlp"] = swiglu("mlp")
            return out
        out["moe"] = {
            "router": _stack([t(w(i, "mlp.gate.weight")) for i in idx]),
            **{
                ours: _stack([
                    _stack([t(w(i, f"mlp.experts.{e}.{theirs}.weight")) for e in held])
                    for i in idx])
                for ours, theirs in proj
            },
            "shared": swiglu("mlp.shared_experts"),
        }
        return out

    k, L, V = cfg.first_k_dense, cfg.n_layers, cfg.vocab_size
    params = {
        "tok_embed": state[f"{pre}embed_tokens.weight"][:V],
        "layers": group(range(k, L), True),
        "final_norm": {"scale": state[f"{pre}norm.weight"]},
        "lm_head": t(state["lm_head.weight"][:V]),
    }
    if k:
        params["dense_layers"] = group(range(k), False)
    if cfg.mtp_layers:
        params["mtp"] = {
            "enorm": {"scale": w(L, "enorm.weight")},
            "hnorm": {"scale": w(L, "hnorm.weight")},
            "eh_proj": t(w(L, "eh_proj.weight")),
            "block": group(range(L, L + cfg.mtp_layers), True),
        }
    return params


def _convert_llama(state, cfg: ModelConfig) -> dict:
    """HF Llama/Mistral names → our layout (weights transpose: HF linear is
    [out, in]; ours is [in, out])."""
    pre = "model." if any(k.startswith("model.") for k in state) else ""
    L = cfg.n_layers
    t = lambda a: np.ascontiguousarray(a.T)
    # gemma stores rmsnorm weights in the (1 + w) convention; our _norm
    # multiplies by scale directly, so fold the +1 in here
    norm_off = 1.0 if cfg.norm_plus_one else 0.0
    raw = lambda k: state[pre + k]
    g = lambda k: (raw(k) + norm_off) if "layernorm.weight" in k or k == "norm.weight" else raw(k)
    if cfg.post_norms and cfg.no_pre_norms:
        # olmo2: ONLY output norms — no input/pre_feedforward norms exist
        layers = {
            "ln1_post": {"scale": _stack([g(f"layers.{i}.post_attention_layernorm.weight") for i in range(L)])},
            "ln2_post": {"scale": _stack([g(f"layers.{i}.post_feedforward_layernorm.weight") for i in range(L)])},
        }
    elif cfg.post_norms:
        # gemma-2 names: post_attention_layernorm is the POST-attn output
        # norm (ours ln1_post); the pre-mlp norm is pre_feedforward_…
        layers = {
            "ln1": {"scale": _stack([g(f"layers.{i}.input_layernorm.weight") for i in range(L)])},
            "ln1_post": {"scale": _stack([g(f"layers.{i}.post_attention_layernorm.weight") for i in range(L)])},
            "ln2": {"scale": _stack([g(f"layers.{i}.pre_feedforward_layernorm.weight") for i in range(L)])},
            "ln2_post": {"scale": _stack([g(f"layers.{i}.post_feedforward_layernorm.weight") for i in range(L)])},
        }
    else:
        layers = {
            "ln1": {"scale": _stack([g(f"layers.{i}.input_layernorm.weight") for i in range(L)])},
            "ln2": {"scale": _stack([g(f"layers.{i}.post_attention_layernorm.weight") for i in range(L)])},
        }
        if cfg.norm == "layernorm" and cfg.norm_bias:  # stablelm: biased LNs
            layers["ln1"]["bias"] = _stack(
                [raw(f"layers.{i}.input_layernorm.bias") for i in range(L)])
            layers["ln2"]["bias"] = _stack(
                [raw(f"layers.{i}.post_attention_layernorm.bias") for i in range(L)])
    layers["attn"] = {
        "wq": _stack([t(g(f"layers.{i}.self_attn.q_proj.weight")) for i in range(L)]),
        "wk": _stack([t(g(f"layers.{i}.self_attn.k_proj.weight")) for i in range(L)]),
        "wv": _stack([t(g(f"layers.{i}.self_attn.v_proj.weight")) for i in range(L)]),
        "wo": _stack([t(g(f"layers.{i}.self_attn.o_proj.weight")) for i in range(L)]),
    }
    if pre + "layers.0.self_attn.q_proj.bias" in state:  # qwen2: q/k/v-only bias
        for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
            layers["attn"][ours] = _stack(
                [g(f"layers.{i}.self_attn.{theirs}.bias") for i in range(L)]
            )
    if pre + "layers.0.self_attn.q_norm.weight" in state:  # qwen3/gemma3
        # gemma-3's qk norms are zero-centered like its other norms —
        # fold the +1 here too (qwen3: norm_off is 0)
        for ours, theirs in (("q_norm", "q_norm"), ("k_norm", "k_norm")):
            layers["attn"][ours] = _stack(
                [raw(f"layers.{i}.self_attn.{theirs}.weight") + norm_off
                 for i in range(L)]
            )
    if cfg.is_moe:
        E = cfg.n_experts
        if pre + "layers.0.block_sparse_moe.gate.weight" in state:
            # mixtral names: block_sparse_moe.{gate, experts.N.w1/w2/w3}
            mb, gate_k, up_k, down_k = "block_sparse_moe", "w1", "w3", "w2"
            router_k = f"{mb}.gate"
            ek = lambda i, e, w: f"layers.{i}.{mb}.experts.{e}.{w}.weight"
        else:
            # qwen3_moe names: mlp.{gate, experts.N.gate/up/down_proj}
            gate_k, up_k, down_k = "gate_proj", "up_proj", "down_proj"
            router_k = "mlp.gate"
            ek = lambda i, e, w: f"layers.{i}.mlp.experts.{e}.{w}.weight"
        layers["moe"] = {
            "router": _stack([t(g(f"layers.{i}.{router_k}.weight")) for i in range(L)]),
            "w_gate": _stack([
                _stack([t(g(ek(i, e, gate_k))) for e in range(E)])
                for i in range(L)
            ]),
            "w_down": _stack([
                _stack([t(g(ek(i, e, down_k))) for e in range(E)])
                for i in range(L)
            ]),
            "w_up": _stack([
                _stack([t(g(ek(i, e, up_k))) for e in range(E)])
                for i in range(L)
            ]),
        }
    else:
        layers["mlp"] = {
            "w_gate": _stack([t(g(f"layers.{i}.mlp.gate_proj.weight")) for i in range(L)]),
            "w_up": _stack([t(g(f"layers.{i}.mlp.up_proj.weight")) for i in range(L)]),
            "w_down": _stack([t(g(f"layers.{i}.mlp.down_proj.weight")) for i in range(L)]),
        }
    params = {
        "tok_embed": g("embed_tokens.weight"),
        "layers": layers,
        "final_norm": {"scale": g("norm.weight")},
    }
    if cfg.norm == "layernorm" and cfg.norm_bias:
        params["final_norm"]["bias"] = raw("norm.bias")
    if not cfg.tie_embeddings:
        lm = state.get("lm_head.weight")
        params["lm_head"] = t(lm) if lm is not None else np.ascontiguousarray(g("embed_tokens.weight").T)
    return params


def _materialize(params, dtype, host: bool):
    """Cast the tree to `dtype` — on DEVICE normally, or as HOST numpy
    arrays (ml_dtypes handles bf16) when the caller wants to transform
    weights before the upload (e.g. int8 quantization: materializing the
    dense model in HBM first would double the load-time peak)."""
    # the mixer's per-head decay vectors stay float32 whatever the dtype
    # (core.init_params keeps them so too)
    # (and so does the sigmoid router's selection bias)
    keep = lambda path: (  # noqa: E731
        len(path) >= 2 and (
            getattr(path[-2], "key", None) == "ssm"
            and getattr(path[-1], "key", None) in ("A_log", "D", "dt_bias")
            or getattr(path[-1], "key", None) == "router_bias"))
    if host:
        return jax.tree_util.tree_map_with_path(
            lambda path, a: np.asarray(a).astype(
                np.float32 if keep(path) else np.dtype(dtype)), params)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(a, jnp.float32 if keep(path) else dtype),
        params)


def load_checkpoint(
    path: str | Path, cfg: ModelConfig, dtype=jnp.bfloat16, host: bool = False
) -> dict:
    """Load a LOCAL checkpoint directory into our param pytree.

    Accepts: a dir with *.safetensors / pytorch_model*.bin (HF layout), or a
    dir produced by save_native(). host=True keeps the tree in host memory
    (see _materialize).
    """
    path = Path(path)
    if (path / "bee2bee_manifest.json").exists():
        return load_native(path, dtype=dtype, host=host)
    state = _load_hf_state(path)
    if any(".c_attn." in k for k in state):
        # gpt2 stores Conv1D [D, 3D]; gpt-bigcode stores Linear
        # [D + 2*kv_dim, D] — MQA configs and/or the transposed shape
        # identify the bigcode layout
        w0 = next(v for k, v in state.items() if k.endswith("attn.c_attn.weight"))
        if cfg.n_kv_heads != cfg.n_heads or w0.shape[0] != cfg.d_model:
            params = _convert_bigcode(state, cfg)
        else:
            params = _convert_gpt2(state, cfg)
    elif any(".mlp.fc1." in k for k in state):
        params = _convert_phi(state, cfg)
    elif any("word_embeddings_layernorm" in k for k in state):
        params = _convert_bloom(state, cfg)  # bloom's unique embed-LN key
    elif any(".attn.Wqkv." in k for k in state):  # mpt's unique fused name
        params = _convert_mpt(state, cfg)
    elif any(".self_attention.query_key_value." in k for k in state):
        # MUST precede the neox check: ".attention.query_key_value." is a
        # substring of falcon's ".self_attention.query_key_value."
        params = _convert_falcon(state, cfg)
    elif any(".attention.query_key_value." in k for k in state):
        params = _convert_neox(state, cfg)
    elif any(".self_attn.qkv_proj." in k for k in state):  # phi-3's fused
        params = _convert_phi3(state, cfg)
    elif any(".mlp.fc_in." in k for k in state):  # gpt-j's unique mlp names
        params = _convert_gptj(state, cfg)
    elif any(".mamba.in_proj." in k for k in state):  # falcon_h1's mixer
        params = _convert_falcon_h1(state, cfg)
    elif any(".self_attn.kv_a_proj_with_mqa." in k for k in state):
        params = _convert_joyai(state, cfg)  # latent attention's unique name
    elif any(".eh_proj." in k for k in state) or (
            cfg.moe_router == "sigmoid" and not cfg.moe_select_bias):
        params = _convert_exaone_moe(state, cfg)  # MTP tensors and all
    else:
        params = _convert_llama(state, cfg)
    return _materialize(params, dtype, host)


# ---- native format: content-addressed pieces + manifest ---------------------
# save_native/load_native double as the checkpoint/resume story AND the piece
# source for mesh weight distribution: the manifest is a pieces.ShardManifest.


def save_native(params, cfg: ModelConfig, path: str | Path, mesh_axes: dict[str, int] | None = None):
    from ..pieces import build_shard_manifest, save_pieces
    from .partition import flat_partition_specs

    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    flat = _flatten(params)
    specs = (
        flat_partition_specs(params, mesh_axes, cfg=cfg)
        if mesh_axes
        else {k: () for k in flat}
    )
    manifest, blobs = build_shard_manifest(cfg.name, flat, specs, mesh_axes or {})
    save_pieces(list(blobs.values()), path / "pieces")
    (path / "bee2bee_manifest.json").write_text(manifest.to_json())
    (path / "model_config.json").write_text(json.dumps(cfg.__dict__, default=str))
    return manifest


def load_native(path: str | Path, dtype=jnp.bfloat16, host: bool = False) -> dict:
    from ..pieces import ShardManifest, load_piece

    path = Path(path)
    manifest = ShardManifest.from_json((path / "bee2bee_manifest.json").read_text())
    flat: dict[str, np.ndarray] = {}
    for piece in manifest.pieces:
        data = load_piece(path / "pieces", piece.sha256)
        arr = np.frombuffer(data, dtype=piece.dtype).reshape(piece.shape)
        if piece.shard_count > 1:
            flat.setdefault(piece.param, [None] * piece.shard_count)[piece.shard_index] = arr
        else:
            flat[piece.param] = arr
    for k, v in list(flat.items()):
        if isinstance(v, list):
            shard = next(p for p in manifest.pieces if p.param == k)
            flat[k] = np.concatenate(v, axis=shard.axis)
    params = _unflatten(flat)
    return _materialize(params, dtype, host)


def _flatten(params, prefix="") -> dict[str, np.ndarray]:
    out = {}
    for k, v in params.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "/"))
        else:
            out[key] = np.asarray(v)
    return out


def _unflatten(flat: dict[str, np.ndarray]) -> dict:
    out: dict = {}
    for k, v in flat.items():
        parts = k.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out
