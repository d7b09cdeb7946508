"""The plain reference for ``falcon_h1`` configurations and the comparison that
decides ``correct`` in their cells. Same job file in, same result line out as
``reference.py``; a configuration file names it under ``reference.module``.

The forward pass is Falcon-H1's, written straight from the published
description (and checked against ``transformers``' ``FalconH1ForCausalLM`` at
tiny size, ``tests/test_falcon_h1.py``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunking,
no batching tricks, one layer at a time, the head in column blocks so that it
fits the chip beside the program's 10.5 GB of weights. With ``u`` the normed
block input:

- attention: GQA, rotary halves, ``k = (W_k u') * key_multiplier`` before the
  rotation, ``u' = u * attention_in_multiplier``, scores / sqrt(head size);
- mixer (Mamba-2): ``p = W_in (u * ssm_in_multiplier)`` times the five zone
  multipliers; a causal depthwise conv of width K with bias over [x; B; C],
  SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then THE
  TOKEN-BY-TOKEN RECURRENCE ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = h_t C_t + D x_t`` (B and C shared by the heads of a group), a scan
  of T steps with the state in float32; ``y * SiLU(z)``, an RMS norm over each
  group's channels, the learned scale, ``W_out``;
- ``x' = x + ssm_out_multiplier * mixer + attention_out_multiplier * attention``;
  ``y = x' + MLP(RMSNorm(x'))`` with the MLP's two multipliers;
- ``embed * embedding_multiplier``; ``logits = (W_head h) * lm_head_multiplier``.

Its sizes come from the configuration FILE (the model's own ``config.json``
names; the depth as run is ``layers``); only the seeded weights come from the
program. It shares no code with ``bee2bee_tpu/models/core.py``'s mixer.

What is compared: ``reference.py``'s walk (served text -> bytes -> the best
reference logit among the tokens of the served byte must lie within
``tolerance`` of the reference's maximum), with its known ambiguity cured for
this cell: with 1,020 tokens a byte class two tokens OF ONE BYTE tie within
the tolerance often, and the text does not say which one the server took. So
the walk FORKS: every same-byte candidate within the tolerance extends a
context of its own (up to ``SPARE_ROWS`` forks in all), and a probe is right
if any of its contexts stays within the tolerance to the end.

``job["perturb"]`` (the builder's proof that the tolerance discriminates, never
set by ``run.py``): ``{"state_dtype": "bfloat16"}`` rounds the recurrent state
to that type after every token; ``{"activation_dtype": "float8_e4m3fn"}``
rounds the residual stream after the embedding and after every block (the
nearest precision below the configuration's bf16); ``{"drop_multiplier":
"<config key>"}`` computes with that multiplier at 1.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(Path(__file__).resolve().parent))

from reference import BOS, MIN_CHECKED, MIN_DECODE_CHECKED, OFFSET, byte_class, known_bytes  # noqa: E402

SPARE_ROWS = 16  # contexts beyond one a probe that forks may open
HEAD_BLOCK = 32640  # columns of the head a call (261,120 = 8 blocks)
SCALARS = ("embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
           "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
           "ssm_out_multiplier")


def multipliers(dims: dict, drop: str | None = None) -> dict:
    """The fourteen multipliers by their config.json names; ``drop`` sets one
    key's value(s) to 1 (the perturbed reference)."""
    out = {k: float(dims.get(k, 1.0)) for k in SCALARS}
    out["mlp_multipliers"] = [float(v) for v in dims.get("mlp_multipliers") or (1.0, 1.0)]
    out["ssm_multipliers"] = [float(v) for v in dims.get("ssm_multipliers") or (1.0,) * 5]
    if drop is not None:
        if drop not in out:
            raise KeyError(f"no multiplier {drop!r}; known: {sorted(out)}")
        out[drop] = [1.0] * len(out[drop]) if isinstance(out[drop], list) else 1.0
    return out


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, layer, head)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    perturb = perturb or {}
    mult = multipliers(dims, perturb.get("drop_multiplier"))
    state_dtype = jnp.dtype(perturb.get("state_dtype", "float32"))
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))

    def rounded(x, dtype):
        """x at ``dtype``'s precision, still float32. ``lax.reduce_precision``,
        not a convert pair: the TPU compiler may elide float32 -> narrow ->
        float32 as allowed excess precision (seen on the chip, PR 28)."""
        if dtype == jnp.float32:
            return x
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    def act(x):  # the residual stream, rounded to the perturbed activation type
        return rounded(x, act_dtype)
    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])
    Hs, P, N = dims["mamba_n_heads"], dims["mamba_d_head"], dims["mamba_d_state"]
    G, K = dims["mamba_n_groups"], dims["mamba_d_conv"]
    inner = Hs * P
    zones = np.concatenate([np.full((w,), m, np.float32) for w, m in zip(
        (inner, inner, G * N, G * N, Hs), mult["ssm_multipliers"])])

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale

    def rope(x, positions):  # x [R, T, heads, hd]; rotate (first, second) halves
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = positions[..., None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def attention(u, p):
        R, T, _ = u.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (R, T))
        ua = u * mult["attention_in_multiplier"]
        q = rope((ua @ p["wq"]).reshape(R, T, H, hd), pos)
        k = rope((ua @ p["wk"]).reshape(R, T, Hkv, hd) * mult["key_multiplier"], pos)
        v = (ua @ p["wv"]).reshape(R, T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)  # query head i reads kv head i // group
        v = jnp.repeat(v, H // Hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        scores = jnp.where(causal[None, None], scores, -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(R, T, H * hd) @ p["wo"]

    def mixer(u, p):
        R, T, _ = u.shape
        proj = ((u * mult["ssm_in_multiplier"]) @ p["w_in"]) * zones
        z, xbc, dt = proj[..., :inner], proj[..., inner:-Hs], proj[..., -Hs:]
        # causal depthwise conv: tap K-1 multiplies the current token
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(padded[:, k:k + T] * p["conv_w"][:, k] for k in range(K)) + p["conv_b"]
        conv = jax.nn.silu(conv)
        x = conv[..., :inner].reshape(R, T, Hs, P)
        # B and C of a group, repeated over the group's heads (head h: group h // (Hs/G))
        Bh = jnp.repeat(conv[..., inner:inner + G * N].reshape(R, T, G, N), Hs // G, axis=2)
        Ch = jnp.repeat(conv[..., inner + G * N:].reshape(R, T, G, N), Hs // G, axis=2)
        dt = jax.nn.softplus(dt + p["dt_bias"])  # [R, T, Hs]
        A = -jnp.exp(p["A_log"])  # [Hs]

        def token(h, inp):  # h [R, Hs, P, N]: one token of the recurrence
            x_t, dt_t, b_t, c_t = inp
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            h = rounded(h, state_dtype)
            y_t = jnp.einsum("rhpn,rhn->rhp", h, c_t) + p["D"][:, None] * x_t
            return h, y_t

        _, y = jax.lax.scan(
            token, jnp.zeros((R, Hs, P, N), jnp.float32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
        y = jnp.moveaxis(y, 0, 1).reshape(R, T, inner) * jax.nn.silu(z)
        y = y.reshape(R, T, G, inner // G)  # gate first, then a norm a group
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return (y.reshape(R, T, inner) * p["norm"]) @ p["w_out"]

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed.astype(jnp.float32), tokens, axis=0)
                   * mult["embedding_multiplier"])

    @jax.jit
    def layer(x, layers, index):
        # one layer's weights out of the stacked [L, ...] arrays, upcast here
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(jnp.float32),
            layers)
        u = rms(x, lp["ln1"]["scale"])
        x = x + (mult["ssm_out_multiplier"] * mixer(u, lp["ssm"])
                 + mult["attention_out_multiplier"] * attention(u, lp["attn"]))
        v = rms(x, lp["ln2"]["scale"])
        gate_m, down_m = mult["mlp_multipliers"]
        mlp = (v @ lp["mlp"]["w_up"]) * jax.nn.silu((v @ lp["mlp"]["w_gate"]) * gate_m)
        return act(x + (mlp @ lp["mlp"]["w_down"]) * down_m)

    @jax.jit
    def head_block(h, lm_head, start):  # h already normed; a block of columns
        width = min(HEAD_BLOCK, lm_head.shape[1])
        w = jax.lax.dynamic_slice_in_dim(lm_head, start, width, axis=1).astype(jnp.float32)
        return (h @ w) * mult["lm_head_multiplier"]

    def head(x, final_scale, lm_head):
        """Logits [R, V] of x [R, D], the head in blocks of HEAD_BLOCK columns."""
        h = rms(x, final_scale.astype(jnp.float32))
        V = lm_head.shape[1]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)  # the last block overlaps its neighbour
        out = np.empty((x.shape[0], V), np.float32)
        for s in starts:
            out[:, s:s + width] = np.asarray(head_block(h, lm_head, np.int32(s)))
        return out

    return embed, layer, head


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    return {
        "hidden_size": mcfg.d_model, "layers": mcfg.n_layers,
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "head_dim": mcfg.head_dim, "intermediate_size": mcfg.d_ff,
        "vocab_size": mcfg.vocab_size, "rms_norm_eps": mcfg.norm_eps,
        "rope_theta": mcfg.rope_theta, "mamba_n_heads": mcfg.ssm_heads,
        "mamba_d_head": mcfg.ssm_head_dim, "mamba_d_state": mcfg.ssm_state,
        "mamba_n_groups": mcfg.ssm_groups, "mamba_d_conv": mcfg.ssm_conv,
        "mamba_chunk_size": mcfg.ssm_chunk,
        "embedding_multiplier": mcfg.embedding_multiplier,
        "lm_head_multiplier": mcfg.lm_head_multiplier,
        "attention_in_multiplier": mcfg.attention_in_multiplier,
        "attention_out_multiplier": mcfg.attention_out_multiplier,
        "key_multiplier": mcfg.key_multiplier,
        "ssm_in_multiplier": mcfg.ssm_in_multiplier,
        "ssm_out_multiplier": mcfg.ssm_out_multiplier,
        "mlp_multipliers": list(mcfg.mlp_multipliers),
        "ssm_multipliers": list(mcfg.ssm_multipliers),
        "tie_word_embeddings": mcfg.tie_embeddings,
    }


def walk(logits_at, tokens, owner, served, P: int, n_new: int, V: int, tol: float) -> dict:
    """The forking walk. ``tokens`` [R, P + n_new] holds one context a row,
    ``owner[r]`` the probe a row belongs to (-1 = free). Returns the result
    line's fields. ``logits_at(step)`` gives [R, V] over the contexts so far."""
    import numpy as np

    R = tokens.shape[0]
    row_worst = np.zeros((R,), np.float64)  # a live context's worst margin so far
    alive = owner >= 0
    ended: dict[int, list[float]] = {}  # probe -> worst margins of its ended contexts
    events, stds, forks, forks_dropped = [], [], 0, 0
    checked = decode_checked = 0
    for step in range(n_new):
        live = [r for r in range(R) if alive[r] and len(served[owner[r]]) > step]
        for r in range(R):  # a context whose probe's text ends here has ended well
            if alive[r] and r not in live:
                ended.setdefault(int(owner[r]), []).append(float(row_worst[r]))
                alive[r] = False
        if not live:
            break
        logits = logits_at(step)
        stds.append(float(np.std(logits[live])))
        seen = set()
        for r in live:
            i = int(owner[r])
            cls = byte_class(served[i][step], V)
            top = float(np.max(logits[r]))
            margins = top - logits[r, cls]
            order = np.argsort(margins)
            if i not in seen:
                seen.add(i)
                checked += 1
                decode_checked += step > 0
            best = float(margins[order[0]])
            if best > tol / 8.0:
                events.append((i, step, best))
            if best > tol:  # this context ends here, wrong
                ended.setdefault(i, []).append(max(float(row_worst[r]), best))
                alive[r] = False
                continue
            row_worst[r] = max(row_worst[r], best)
            tokens[r, P + step] = cls[order[0]]
            for j in order[1:]:  # every further same-byte candidate within the tolerance
                if margins[j] > tol:
                    break
                free = np.flatnonzero(owner < 0)
                if not len(free):
                    forks_dropped += 1
                    continue
                f = int(free[0])
                tokens[f] = tokens[r]
                tokens[f, P + step] = cls[j]
                owner[f], alive[f] = i, True
                row_worst[f] = max(row_worst[r], float(margins[j]))
                forks += 1
    for r in range(R):
        if alive[r]:
            ended.setdefault(int(owner[r]), []).append(float(row_worst[r]))
    # a probe is as good as its best context
    per_probe = {i: min(ws) for i, ws in ended.items()}
    worst = max(per_probe.values(), default=None)
    enough = checked >= MIN_CHECKED and decode_checked >= MIN_DECODE_CHECKED
    ok = bool(enough and worst is not None and math.isfinite(worst) and worst <= tol)
    return {
        "ok": ok, "checked": checked, "decode_checked": int(decode_checked),
        "enough_positions": enough, "worst_margin": worst, "tolerance": tol,
        "forks": forks, "forks_dropped": forks_dropped,
        "margins_over_tol_8th": sorted(events, key=lambda e: -e[2])[:20],
        "logits_std": stds[0] if stds else None,
        "tolerance_share_of_std": tol / stds[0] if stds and stds[0] else None,
    }


def main(job_path: str) -> int:
    job = json.loads(Path(job_path).read_text())
    sys.path.insert(0, str(ROOT))
    from bee2bee_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models import core, partition
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.parallel import local_mesh

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
    dev0 = devs[0]

    embed, layer, head = build_forward(dims, job.get("perturb"))
    L, V = dims["layers"], dims["vocab_size"]
    probes = job["probes"]
    P = max(len(p["prompt"].encode()) for p in probes) + 1
    n_new = int(job["output_tokens"])  # the walk's depth and the one compiled shape
    R = len(probes) + SPARE_ROWS
    tokens = np.zeros((R, P + n_new), np.int32)
    owner = np.full((R,), -1, np.int64)
    for i, p in enumerate(probes):
        raw = p["prompt"].encode()
        if len(raw) + 1 != P:
            print(json.dumps({"ok": False, "error": "probe prompts differ in length"}))
            return 1
        tokens[i, 0] = BOS
        tokens[i, 1:P] = np.frombuffer(raw, np.uint8).astype(np.int32) + OFFSET
        owner[i] = i
    served = [known_bytes(p["text"])[:n_new] for p in probes]

    def logits_at(step: int):
        """Reference logits [R, V] at position P - 1 + step over the contexts so far."""
        with jax.default_matmul_precision("highest"):
            x = embed(params["tok_embed"], tokens)
            for index in range(L):
                x = layer(x, params["layers"], np.int32(index))
            lm_head = params["tok_embed"].T if dims["tie_word_embeddings"] else params["lm_head"]
            return head(x[:, P - 1 + step], params["final_norm"]["scale"], lm_head)

    res = walk(logits_at, tokens, owner, served, P, n_new, V, float(job["tolerance"]))
    print(json.dumps({
        **res, "probes": len(probes), "perturb": job.get("perturb"),
        "device": {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(devs)},
    }))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
