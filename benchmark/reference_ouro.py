"""The plain reference for ``ouro`` configurations (looped language models) and
the comparison that decides ``correct`` in their cells. Same job file in, same
result line out as ``reference.py``; a configuration file names it under
``reference.module``.

The forward pass is Ouro's, written from the published config and the paper
(arXiv:2510.25741; what the config does not carry is listed under the
configuration file's ``assumed``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no pool, no batching, a
Python loop over ``total_ut_steps`` passes x ``num_hidden_layers`` layers with
each layer's served bf16 weights upcast as it is used, the full causal forward
over prompt + served tokens. With ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``,
pass ``t``, layer ``l``, the weights of layer ``l`` THE SAME in every pass:

    x  = E[token]                                  at pass 0, layer 0
    a  = RMS(x; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l   (no bias)
    q, k = RoPE(q, k; position, theta, the whole head, halves rotated together)
    attention: causal, full, 1 / sqrt(head size), over the K and V that THIS
               pass computed in THIS layer (a served cache holds them apart:
               cache layer t * layers + l)
    h  = x + RMS(attn Wo_l; g1'_l)                 (a norm on each branch's OUTPUT too)
    x' = h + RMS((silu(b Wgate_l) * (b Wup_l)) Wdown_l; g2'_l),   b = RMS(h; g2_l)
    after the last layer of EVERY pass:  x <- RMS(x; g_f), the model's one
               final norm; its output is what layer 0 of the next pass reads
    logits = (the last pass's normed output) W_head

Its sizes come from the configuration FILE (the model's own ``config.json``
names); only the seeded weights come from the program. It shares no block or
loop code with ``bee2bee_tpu/models/core.py``.

What is compared: the forking byte-class walk of ``reference_falcon_h1.py``
(imported, not copied: served text -> bytes -> the best reference logit among
the tokens of the served byte must lie within ``tolerance`` of the reference's
maximum; every same-byte candidate within the tolerance opens a context of its
own). What decides is the MEAN over the compared positions of the best margin
any context gave, against the configuration's ``mean_margin_limit``: bf16
through 192 layer passes throws ONE position of a run's ~80 far out now and
then (a served prefill's top token 0.35 under the float32 maximum, six seeds on
the chip) and leaves the others at 0, so the worst of a run is an extreme of
that noise, while a fault of the model moves every position. ``tolerance`` is
the walk's own (which same-byte candidates open a context, where a context is
abandoned); its verdict is reported as ``walk_ok`` and does not decide.

``job["perturb"]`` (the builder's proof that the limits discriminate, never set
by ``run.py``), each ONE thing wrong: ``{"passes": n}`` runs ``n`` passes;
``{"no_norm_between_passes": true}`` norms after the last pass only;
``{"pass_reads_previous_cache": true}``: pass ``t > 0`` attends over pass
``t - 1``'s K and V (what a wrong cache index serves);
``{"no_post_norms": true}`` adds each branch's output un-normed;
``{"activation_dtype": "float8_e4m3fn"}`` rounds the residual stream after the
embedding and after every block (the nearest precision below bf16).
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(Path(__file__).resolve().parent))

from reference import BOS, OFFSET, byte_class, known_bytes  # noqa: E402
from reference_falcon_h1 import SPARE_ROWS, walk  # noqa: E402

PERTURBS = ("passes", "no_norm_between_passes", "pass_reads_previous_cache",
            "no_post_norms", "activation_dtype")


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, layer, norm,
    head). ``layer(x, layers, index, kv)`` returns (x', (k, v)); ``kv`` None =
    attend over this call's own K and V."""
    import jax
    import jax.numpy as jnp

    perturb = perturb or {}
    unknown = sorted(set(perturb) - set(PERTURBS))
    if unknown:
        raise KeyError(f"no perturbation {unknown}; known: {list(PERTURBS)}")
    post_norms = not perturb.get("no_post_norms")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])

    def act(x):
        """The residual stream at the perturbed activation type's precision, still
        float32 (``lax.reduce_precision``: the TPU compiler may elide a convert
        pair as allowed excess precision, PR 28)."""
        if act_dtype == jnp.float32:
            return x
        info = jnp.finfo(act_dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale

    def rope(x, positions):  # x [R, T, heads, hd]; rotate (first, second) halves
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = positions[..., None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed, tokens, axis=0).astype(jnp.float32))

    @jax.jit
    def layer(x, layers, index, kv):
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(jnp.float32),
            layers)
        R, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (R, T))
        a = rms(x, lp["ln1"]["scale"])
        q = rope((a @ lp["attn"]["wq"]).reshape(R, T, H, hd), pos)
        k = rope((a @ lp["attn"]["wk"]).reshape(R, T, Hkv, hd), pos)
        v = (a @ lp["attn"]["wv"]).reshape(R, T, Hkv, hd)
        k_read, v_read = (k, v) if kv is None else kv
        k_read = jnp.repeat(k_read, H // Hkv, axis=2)
        v_read = jnp.repeat(v_read, H // Hkv, axis=2)
        scores = jnp.einsum("rthd,rshd->rhts", q, k_read) / math.sqrt(hd)
        visible = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        scores = jnp.where(visible[None, None], scores, -1e30)
        attn = jnp.einsum("rhts,rshd->rthd", jax.nn.softmax(scores, axis=-1), v_read)
        out = attn.reshape(R, T, H * hd) @ lp["attn"]["wo"]
        h = x + (rms(out, lp["ln1_post"]["scale"]) if post_norms else out)
        b = rms(h, lp["ln2"]["scale"])
        mlp = (jax.nn.silu(b @ lp["mlp"]["w_gate"]) * (b @ lp["mlp"]["w_up"])) @ lp["mlp"]["w_down"]
        return act(h + (rms(mlp, lp["ln2_post"]["scale"]) if post_norms else mlp)), (k, v)

    @jax.jit
    def norm(x, scale):
        return rms(x, scale.astype(jnp.float32))

    @jax.jit
    def head(x, lm_head):
        return x @ lm_head.astype(jnp.float32)

    return embed, layer, norm, head


def normed_output(dims: dict, params: dict, tokens, pieces, perturb: dict | None = None):
    """The last pass's normed hidden states [R, T, D] of the full causal
    forward over ``tokens`` [R, T]: passes x layers, one call a layer."""
    import jax
    import numpy as np

    perturb = perturb or {}
    embed, layer, norm, _ = pieces
    L = dims["num_hidden_layers"]
    passes = int(perturb.get("passes", dims["total_ut_steps"]))
    norm_between = not perturb.get("no_norm_between_passes")
    read_previous = bool(perturb.get("pass_reads_previous_cache"))
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        previous = None  # the pass before's (K, V) of every layer, kept only when perturbed
        for t in range(passes):
            made = []
            for index in range(L):
                x, kv = layer(x, params["layers"], np.int32(index),
                              previous[index] if previous else None)
                if read_previous:
                    made.append(kv)
            previous = made if read_previous else None
            if norm_between or t == passes - 1:
                x = norm(x, params["final_norm"]["scale"])
    return x


def full_logits(dims: dict, params: dict, tokens, perturb: dict | None = None):
    """Float32 logits [R, T, V] at every position (the tier-1 tests' entry)."""
    import jax

    pieces = build_forward(dims, perturb)
    x = normed_output(dims, params, tokens, pieces, perturb)
    with jax.default_matmul_precision("highest"):
        return pieces[3](x, _lm_head(dims, params))


def _lm_head(dims, params):
    return params["tok_embed"].T if dims["tie_word_embeddings"] else params["lm_head"]


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    return {
        "hidden_size": mcfg.d_model, "num_hidden_layers": mcfg.n_layers,
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "head_dim": mcfg.head_dim, "intermediate_size": mcfg.d_ff,
        "vocab_size": mcfg.vocab_size, "rms_norm_eps": mcfg.norm_eps,
        "rope_theta": mcfg.rope_theta, "total_ut_steps": mcfg.loop_steps,
        "tie_word_embeddings": mcfg.tie_embeddings,
    }


def compare(job: dict, conf: dict, params: dict) -> dict:
    """The comparison on ``job``'s served text with the program's seeded
    ``params``: the result line's fields (``ok`` decides ``correct``)."""
    import jax
    import numpy as np

    dims, perturb = conf, job.get("perturb")
    pieces = build_forward(dims, perturb)
    V = dims["vocab_size"]
    probes = job["probes"]
    P = max(len(p["prompt"].encode()) for p in probes) + 1
    n_new = int(job["output_tokens"])
    R = len(probes) + SPARE_ROWS
    tokens = np.zeros((R, P + n_new), np.int32)
    owner = np.full((R,), -1, np.int64)
    for i, p in enumerate(probes):
        raw = p["prompt"].encode()
        if len(raw) + 1 != P:
            return {"ok": False, "error": "probe prompts differ in length"}
        tokens[i, 0] = BOS
        tokens[i, 1:P] = np.frombuffer(raw, np.uint8).astype(np.int32) + OFFSET
        owner[i] = i
    served = [known_bytes(p["text"])[:n_new] for p in probes]
    position_margin: dict = {}  # (probe, step) -> the best margin any of its contexts gave

    def logits_at(step: int):
        """Reference logits [R, V] at position P - 1 + step over the contexts so far."""
        x = normed_output(dims, params, tokens, pieces, perturb)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(pieces[3](x[:, P - 1 + step], _lm_head(dims, params)))
        for r in np.flatnonzero(owner >= 0):
            text = served[owner[r]]
            if len(text) > step:
                at = (int(owner[r]), step)
                margin = float(logits[r].max() - logits[r, byte_class(text[step], V)].max())
                position_margin[at] = min(position_margin.get(at, math.inf), margin)
        return logits

    tol = float(job["tolerance"])
    res = walk(logits_at, tokens, owner, served, P, n_new, V, tol)
    mean_margin = (sum(position_margin.values()) / len(position_margin)
                   if position_margin else math.inf)
    mean_limit = float(conf["reference"]["mean_margin_limit"])
    return {
        **res, "ok": bool(res["enough_positions"] and mean_margin <= mean_limit),
        "walk_ok": res["ok"],
        "mean_margin": mean_margin if math.isfinite(mean_margin) else None,
        "mean_margin_limit": mean_limit, "probes": len(probes), "perturb": perturb,
        "served_bytes": sum(len(b) for b in served),
        "distinct_served_bytes": len({b for text in served for b in text}),
    }


def main(job_path: str) -> int:
    job = json.loads(Path(job_path).read_text())
    sys.path.insert(0, str(ROOT))
    from bee2bee_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from bee2bee_tpu.models import core, partition
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.parallel import local_mesh

    conf = json.loads((ROOT / job["config_file"]).read_text())
    dims, srv = conf, conf["server"]
    devs = jax.devices()
    if devs[0].platform != job["platform"] or len(devs) < conf["chips"]:
        print(json.dumps({"ok": False, "error": f"jax found {len(devs)} x "
                          f"{devs[0].platform}, need {conf['chips']} x {job['platform']}"}))
        return 1
    # the program's seeded weights, made the way the server makes them
    mcfg = get_config(srv["model"])
    want = dims_of_preset(mcfg)
    differs = {k: (v, dims.get(k)) for k, v in want.items()
               if dims.get(k) != v and not (isinstance(v, float) and dims.get(k) is not None
                                            and math.isclose(v, dims[k], rel_tol=1e-12))}
    if differs:
        print(json.dumps({"ok": False, "error": f"the program's preset {srv['model']!r} "
                          f"differs from the configuration file: {differs}"}))
        return 1
    mesh = local_mesh()
    dtype = jnp.dtype(srv.get("config_json", {}).get("dtype", "bfloat16"))
    key = jax.random.key(0)  # EngineConfig.rng_seed: the node config cannot set it
    shapes = jax.eval_shape(lambda: core.init_params(mcfg, key, dtype=dtype))
    params = core.init_params(
        mcfg, key, dtype=dtype,
        out_shardings=partition.param_shardings(shapes, mesh, mcfg))

    res = compare(job, conf, params)
    dev0 = devs[0]
    print(json.dumps({**res, "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                                        "count": len(devs)}}))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
