"""The plain reference and the comparison that decides ``correct``.

A child process of its own, started only after the server child has exited (a
chip belongs to one process). It is the llama-branch forward pass (RMSNorm,
rotary halves, grouped-query causal attention with an optional sliding window,
SwiGLU, untied head) written straight from the published description in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernel, no cache, no batching tricks, one layer at a time. Its sizes come from
the configuration FILE; only the seeded random weights come from the program
(``core.init_params`` under the program's own shardings, the same call the
server makes), upcast to float32 one layer at a time.

What is compared. The gateway returns TEXT: the byte tokenizer maps token t to
byte (t - 3) % 256 and decodes with ``errors="replace"``, so a served token id
is known only up to its byte, and not at all once a byte fails to decode. So
each probe (a prompt of ASCII bytes, a few greedy tokens) is walked from its
first served character up to the first U+FFFD: at each position the reference
computes the logits over the context so far, and the best reference logit
among the tokens OF THE SERVED BYTE must lie within ``tolerance`` of the
reference's maximum. That token then extends the context (teacher forcing).
Position 0 checks the prefill, the later ones decode steps through the paged
cache.

The tolerance (in the job file, from the configuration file) and its reason:
seeded weights give logits of std ~1; the served path rounds to bf16 through
32 layers, which moves a logit by a few hundredths (PR 21 saw 0.0625 between
two bf16 layouts at 8 layers), so greedy may pick a runner-up that close to
the maximum. A wrong page, mask or head mapping serves an unrelated token:
its byte class (~125 of ~32000 tokens) tops out ~1.5 below the maximum.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BOS, OFFSET = 1, 3  # the program's byte tokenizer: ids 0..2 special, byte b -> b + 3
MIN_CHECKED, MIN_DECODE_CHECKED = 8, 4


def known_bytes(text: str) -> list[int]:
    """The served bytes that the text still shows, up to the first loss."""
    out: list[int] = []
    for ch in text:
        if ch == "\ufffd":
            break
        out.extend(ch.encode("utf-8"))
    return out


def byte_class(byte: int, vocab: int):
    import numpy as np

    return np.arange(OFFSET + byte, vocab, 256)


def build_forward(dims: dict):
    """jit-compiled pieces of the plain forward pass; ``dims`` holds the
    published numbers under the names of the model's own config.json."""
    import jax
    import jax.numpy as jnp

    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    window = dims.get("sliding_window")

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale

    def rope(x, positions):  # x [B, T, heads, hd]; rotate (first, second) halves
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = positions[..., None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    @jax.jit
    def embed(tok_embed, tokens):
        return jnp.take(tok_embed.astype(jnp.float32), tokens, axis=0)

    @jax.jit
    def layer(x, layers, index):
        # one layer's weights out of the stacked [L, ...] arrays, upcast here
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(jnp.float32),
            layers)
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        h = rms(x, lp["ln1"]["scale"])
        q = rope((h @ lp["attn"]["wq"]).reshape(B, T, H, hd), pos)
        k = rope((h @ lp["attn"]["wk"]).reshape(B, T, Hkv, hd), pos)
        v = (h @ lp["attn"]["wv"]).reshape(B, T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)  # query head i reads kv head i // group
        v = jnp.repeat(v, H // Hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
        qi, ki = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        visible = ki <= qi
        if window:
            visible = visible & (qi - ki < window)
        scores = jnp.where(visible[None, None], scores, -1e30)
        attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(B, T, H * hd) @ lp["attn"]["wo"]
        h2 = rms(x, lp["ln2"]["scale"])
        mlp = (jax.nn.silu(h2 @ lp["mlp"]["w_gate"]) * (h2 @ lp["mlp"]["w_up"]))
        return x + mlp @ lp["mlp"]["w_down"]

    @jax.jit
    def head(x, final_scale, lm_head):
        return rms(x, final_scale.astype(jnp.float32)) @ lm_head.astype(jnp.float32)

    return embed, layer, head


def main(job_path: str) -> int:
    job = json.loads(Path(job_path).read_text())
    sys.path.insert(0, str(ROOT))
    from bee2bee_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.config import parse_mesh_shape
    from bee2bee_tpu.models import core, partition
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.parallel import MeshSpec, build_mesh, local_mesh

    conf = json.loads((ROOT / job["config_file"]).read_text())
    dims = conf
    srv = conf["server"]
    devs = jax.devices()
    if devs[0].platform != job["platform"] or len(devs) < conf["chips"]:
        print(json.dumps({"ok": False, "error": f"jax found {len(devs)} x "
                          f"{devs[0].platform}, need {conf['chips']} x {job['platform']}"}))
        return 1

    # the program's seeded weights, made the way the server makes them
    mcfg = get_config(srv["model"])
    want = {"d_model": dims["hidden_size"], "n_layers": dims["num_hidden_layers"],
            "n_heads": dims["num_attention_heads"], "n_kv_heads": dims["num_key_value_heads"],
            "d_ff": dims["intermediate_size"], "vocab_size": dims["vocab_size"]}
    differs = {k: (getattr(mcfg, k), v) for k, v in want.items() if getattr(mcfg, k) != v}
    if differs:
        print(json.dumps({"ok": False, "error": f"the program's preset {srv['model']!r} "
                          f"differs from the configuration file: {differs}"}))
        return 1
    mesh = (build_mesh(MeshSpec.from_dict(parse_mesh_shape(srv["mesh_shape"])))
            if srv.get("mesh_shape") else local_mesh())
    dtype = jnp.dtype(srv.get("config_json", {}).get("dtype", "bfloat16"))
    key = jax.random.key(0)  # EngineConfig.rng_seed: the node config cannot set it
    shapes = jax.eval_shape(lambda: core.init_params(mcfg, key, dtype=dtype))
    params = core.init_params(
        mcfg, key, dtype=dtype,
        out_shardings=partition.param_shardings(shapes, mesh, mcfg))
    dev0 = devs[0]

    embed, layer, head = build_forward(dims)
    L, V = dims["num_hidden_layers"], dims["vocab_size"]
    probes = job["probes"]
    P = max(len(p["prompt"].encode()) for p in probes) + 1
    n_new = int(job["output_tokens"])  # the walk's depth and the one compiled shape
    T = P + n_new
    tokens = np.zeros((len(probes), T), np.int32)
    for i, p in enumerate(probes):
        raw = p["prompt"].encode()
        if len(raw) + 1 != P:
            print(json.dumps({"ok": False, "error": "probe prompts differ in length"}))
            return 1
        tokens[i, 0] = BOS
        tokens[i, 1:P] = np.frombuffer(raw, np.uint8).astype(np.int32) + OFFSET
    served = [known_bytes(p["text"])[:n_new] for p in probes]

    def logits_at(step: int):
        """Reference logits [N, V] at position P - 1 + step over the context so far.
        The weights stay where the program's shardings put them (under model:N
        the compiler splits each float32 product over the chips; gathering a
        layer at a time to one chip cost 22 s a pass on four chips)."""
        with jax.default_matmul_precision("highest"):
            x = embed(params["tok_embed"], tokens)
            for index in range(L):
                x = layer(x, params["layers"], np.int32(index))
            lm_head = params["tok_embed"].T if dims["tie_word_embeddings"] else params["lm_head"]
            out = head(x[:, P - 1 + step], params["final_norm"]["scale"], lm_head)
        return np.asarray(out)

    margins: list[tuple[int, int, float]] = []  # (probe, step, margin)
    stds = []
    for step in range(n_new):
        live = [i for i, b in enumerate(served) if len(b) > step]
        if not live:
            break
        logits = logits_at(step)
        stds.append(float(np.std(logits[live])))
        for i in live:
            cls = byte_class(served[i][step], V)
            best = cls[int(np.argmax(logits[i, cls]))]
            margins.append((i, step, float(np.max(logits[i]) - logits[i, best])))
            tokens[i, P + step] = best
    tol = float(job["tolerance"])
    checked = len(margins)
    decode_checked = sum(1 for _, s, _ in margins if s > 0)
    worst = max((m for _, _, m in margins), default=None)
    enough = checked >= MIN_CHECKED and decode_checked >= MIN_DECODE_CHECKED
    ok = bool(enough and worst is not None and worst <= tol
              and all(math.isfinite(m) for _, _, m in margins))
    print(json.dumps({
        "ok": ok, "probes": len(probes), "checked": checked,
        "decode_checked": decode_checked, "enough_positions": enough,
        "worst_margin": worst, "tolerance": tol,
        "margins_over_0.02": [m for m in margins if m[2] > 0.02][:20],
        "logits_std": stds[0] if stds else None,
        "device": {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(devs)},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
