"""What a DENSE block's weight products must stream and compute, from the
configuration file's PUBLISHED keys alone.

The yardstick for ``phi3.weights.stream_roofline`` / ``h1.weights.stream_roofline``:
the matrices that the program's ``attn.qkv``, ``attn.out``, ``mlp.*`` and
``head.*`` scopes cover, and no other (falcon-h1's mixer projections run under
``ssm.*``: on neither side of the share). A layer: ``W_q`` and ``W_o``
``hidden_size x heads x head_dim`` each, ``W_k`` and ``W_v`` ``hidden_size x
kv heads x head_dim`` each, and ``MLP_MATRICES`` (gate, up, down: a gated MLP,
as both configurations') of ``hidden_size x intermediate_size``. The
head: ``vocab_size x hidden_size`` (the embedding is a lookup of a few rows).
The layers RUN: the file's ``layers`` where it cuts the published
``num_hidden_layers``. ``loop_of`` hands them to ``loop_bytes.py`` as a stack of
ONE pass.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
MLP_MATRICES = 3


def dtype_bytes(conf: dict) -> int:
    """Bytes a weight: the served dtype (``server.config_json.dtype``)."""
    return DTYPE_BYTES[conf["server"]["config_json"]["dtype"]]


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def attention_bytes(conf: dict) -> int:
    """One layer's W_q, W_k, W_v, W_o."""
    q = conf["num_attention_heads"] * head_dim(conf)
    kv = conf.get("num_key_value_heads", conf["num_attention_heads"]) * head_dim(conf)
    return conf["hidden_size"] * (2 * q + 2 * kv) * dtype_bytes(conf)


def mlp_bytes(conf: dict) -> int:
    """One layer's MLP matrices."""
    return MLP_MATRICES * conf["hidden_size"] * conf["intermediate_size"] * dtype_bytes(conf)


def layer_bytes(conf: dict) -> int:
    return attention_bytes(conf) + mlp_bytes(conf)


def head_bytes(conf: dict) -> int:
    return conf["vocab_size"] * conf["hidden_size"] * dtype_bytes(conf)


def layers_run(conf: dict) -> int:
    return conf.get("layers") or conf["num_hidden_layers"]


def loop_of(conf: dict, head: bool = True) -> dict:
    """``loop_bytes.py``'s ``loop`` section for a plain stack: one pass."""
    return {"passes": 1, "layers": layers_run(conf),
            "layer_bytes": layer_bytes(conf),
            "head_bytes": head_bytes(conf) if head else 0,
            "dtype_bytes": dtype_bytes(conf)}
