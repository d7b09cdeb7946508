"""The plain reference for ``joyai_llm_flash`` configurations and the comparison
that decides ``correct`` in their cells. Same job file in, same result line out
as ``reference.py``; a configuration file names it under ``reference.module``.

The forward pass is JoyAI-LLM-Flash's (the DeepSeek-V3 layout its config.json
follows), written straight from the published description in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no kernel, no
cache, no batching tricks, one layer at a time, the experts and the head in
blocks so that they fit the chip beside the program's 11.1 GB of weights. With
``RMS`` the RMSNorm (eps 1e-6), every layer is ``x += Attn(RMS(x)); x +=
FFN(RMS(x))``, then the final ``RMS`` and an untied head:

- attention (MLA, EXPANDED form: per-head keys and values are built from the
  latent, which the served path never does): ``c_q = RMS(u W_qa)``; per head
  ``[q_nope | q_rope] = c_q W_qb``; ``[c_kv | k_r] = u W_kva``; ``c_kv =
  RMS(c_kv)``; ``k_rope = RoPE(k_r)``, ONE head shared by all; ``q_rope =
  RoPE(q_rope)``; RoPE rotates the pairs (2i, 2i+1); per head ``[k_nope | v] =
  c_kv W_kvb``; ``s = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``,
  causal softmax, ``o = sum p v``, ``concat(o) W_o``;
- FFN: layer 0 .. ``first_k_dense_replace`` - 1 a SwiGLU MLP; after them
  ``z = u W_r`` (float32), ``s = sigmoid(z)``, the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias`` are chosen, their weights are ``s``
  WITHOUT the bias, divided by their sum + 1e-20, times
  ``routed_scaling_factor``; ``y = sum_e w_e down_e(silu(gate_e u) * up_e u) +
  the shared expert``. EVERY expert runs on every token and the weights mask
  the sum (the dense form: dropless by construction).

Departures from ``transformers``' DeepSeek-V3 code, each without effect on the
logits: RoPE is applied to the interleaved pairs in place (transformers permutes
q and k to halves first: the same dot products); the next-n (multi-token
prediction) layer is not built (it does not enter the next-token logits).

Its sizes come from the configuration FILE (the model's own ``config.json``
names; the depth as run is ``layers``); only the seeded weights come from the
program. It shares no code with ``bee2bee_tpu/models/core.py``'s attention or
expert layer.

What is compared: ``reference_falcon_h1.py``'s forking walk (served text ->
bytes -> the best reference logit among the tokens of the served byte must lie
within ``tolerance`` of the reference's maximum; every same-byte candidate
within the tolerance extends a context of its own), with one rule more.
**Routing is discontinuous**: a bf16 rounding upstream can swap a token's 8th
and 9th expert, which moves that token's logits far more than activation
rounding does: one swapped expert of eight is ~half of the routed branch's
output at that token, ~0.1-0.9 of the logits' std in a margin, where rounding
alone gives under 0.007. So the reference reports, per compared position, the
gap between the 8th and the 9th selection score at that position in every
expert layer, and it ALSO computes the position with every nonempty SUBSET of
the expert layers swapped 8th <-> 9th there (2^4 - 1 variants a step, each
recomputing that one position: ``swapped_logits``). A subset is ADMISSIBLE
for a row where every swapped layer's gap, IN THE PASS THAT LEADS TO IT, is
under ``near_tie`` (from the configuration file): a swap in an earlier layer
moves the later layers' scores by far more than rounding does, so their gaps
are judged after it. The position passes if the served byte is within the
tolerance under the plain routing or under an admissible subset, the routing
the served path may have taken. ``near_tie_positions`` counts the compared
(probe, step) pairs with such a gap in the plain pass, ``near_tie_rescued``
those that needed a swap to pass, ``min_gap`` the smallest gap met. What the
rule cannot reach: the same swaps at EARLIER tokens of the context, which reach
the compared position through attention, and swaps of another form (the 8th
against the 10th where the 9th and 10th tie). They throw a position or two of
a run far out (the worst of ~45 read 0.05 to 0.92 of the logits' std over the
seeds on the chip) and leave the others where they were, where a fault of the
model moves every position. So what decides ``correct`` is not the worst
position but ``mean_margin``, the mean over the compared positions of the
best margin, against ``mean_margin_limit`` (the served path 0.00-0.03, a model
wrong in one thing 0.4-1.0). ``tolerance`` stays the walk's: a same-byte
candidate within it opens a context, a context whose served byte lies over it
is abandoned there and its margin counted; the walk's own verdict is
reported as ``walk_ok`` (``tolerance_why`` in the configuration file has the
readings).

``job["perturb"]`` (the builder's proof that the limit discriminates, never
set by ``run.py``), each ONE thing wrong: ``{"drop": "routed_scaling_factor"}``,
``{"drop": "shared_expert"}``, ``{"drop": "e_score_correction_bias"}`` (the
experts chosen by the score alone), ``{"bias_in_weights": true}`` (the selection
bias added to the weights too), ``{"no_k_rope": true}`` (``k_rope`` not rotated),
``{"activation_dtype": "float8_e4m3fn"}`` (the residual stream rounded after
the embedding and after every layer: the nearest precision below bf16),
``{"router_dtype": "bfloat16"}`` (the router's two operands rounded to bf16: as
published the router is never bf16; this one moves near-tie choices only, which
is the served path's own noise, so the check is NOT expected to tell it apart:
``tolerance_why`` has the reading).
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(Path(__file__).resolve().parent))

from reference import BOS, OFFSET, known_bytes  # noqa: E402
from reference_falcon_h1 import SPARE_ROWS, walk  # noqa: E402

HEAD_BLOCK = 32320  # columns of the head a call (129,280 = 4 blocks)
EXPERT_BLOCK = 8  # experts a call of the dense expert sum


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, dense_layer,
    expert_layer, head). ``expert_layer(x, layers, index, swap)`` returns (x',
    gap [R, T]): ``swap`` [R, T] bool takes the 9th selection instead of the
    8th at those positions; ``gap`` is the 8th minus the 9th selection score.
    With ``at`` and ``x_at`` it computes ONE position (swapped_logits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    perturb = perturb or {}
    drop = perturb.get("drop")
    if drop not in (None, "routed_scaling_factor", "shared_expert", "e_score_correction_bias"):
        raise KeyError(f"cannot drop {drop!r}")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    router_dtype = jnp.dtype(perturb.get("router_dtype", "float32"))
    D, H = dims["hidden_size"], dims["num_attention_heads"]
    R_kv, dn, dr, dv = (dims["kv_lora_rank"], dims["qk_nope_head_dim"],
                        dims["qk_rope_head_dim"], dims["v_head_dim"])
    E, k = dims["n_routed_experts"], dims["num_experts_per_tok"]
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])
    scale = 1.0 if drop == "routed_scaling_factor" else float(dims["routed_scaling_factor"])
    Eb = min(EXPERT_BLOCK, E)
    f32 = jnp.float32

    def rounded(x, dtype):
        """``x`` at ``dtype``'s precision, still float32 (``lax.reduce_precision``:
        the TPU compiler elides a convert pair)."""
        if dtype == jnp.float32:
            return x
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    def act(x):
        """The residual stream at the perturbed precision."""
        return rounded(x, act_dtype)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def rope(x, pos):  # x [R, T, heads, dr]; rotate the pairs (2i, 2i+1)
        freqs = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=f32) / dr))
        ang = pos[..., None].astype(f32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)

    def attention(u, p, at=None):
        """``at`` (a traced position): the queries of that position alone,
        [R, 1, D]; keys and values of every position either way."""
        R, T, _ = u.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (R, T))
        uq, qpos = (u, pos) if at is None else (
            jax.lax.dynamic_slice_in_dim(u, at, 1, axis=1), jnp.full((R, 1), at))
        c_q = rms(uq @ p["wq_a"], p["q_a_norm"])
        q = (c_q @ p["wq_b"]).reshape(R, -1, H, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], qpos)
        kv = u @ p["wkv_a"]
        c_kv = rms(kv[..., :R_kv], p["kv_a_norm"])
        k_r = kv[..., R_kv:][:, :, None, :]
        k_rope = k_r if perturb.get("no_k_rope") else rope(k_r, pos)  # one head
        kvb = (c_kv @ p["wkv_b"]).reshape(R, T, H, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
             + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope[:, :, 0])) / math.sqrt(dn + dr)
        causal = jnp.arange(T)[None, :] <= qpos[0][:, None]
        s = jnp.where(causal[None, None], s, -1e30)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(R, -1, H * dv) @ p["wo"]

    def one(tree, index):
        """One layer of the stacked [L, ...] arrays, upcast to float32."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(f32), tree)

    def swiglu(u, p):
        return (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed.astype(f32), tokens, axis=0))

    @jax.jit
    def dense_layer(x, layers, index):
        lp = one(layers, index)
        x = x + attention(rms(x, lp["ln1"]["scale"]), lp["attn"])
        return act(x + swiglu(rms(x, lp["ln2"]["scale"]), lp["mlp"]))

    def attend_and_route(x, layers, index, swap, at=None, x_at=None):
        """The attention half and the router: (x after attention, the normed
        FFN input u, dense weights [R, T, E], gap [R, T]). With ``at``: of
        that position alone (T = 1), ``x`` being the layer's input at every
        position with ``x_at`` [R, D] in that position's place."""
        small = {n: v for n, v in layers.items() if n != "moe"}
        lp = one(small, index)
        moe = layers["moe"]
        if at is not None:
            x = jax.lax.dynamic_update_slice(x, x_at[:, None], (0, at, 0))
        a = attention(rms(x, lp["ln1"]["scale"]), lp["attn"], at)
        x = (x if at is None else x_at[:, None]) + a
        u = rms(x, lp["ln2"]["scale"])
        s = jax.nn.sigmoid(
            rounded(u, router_dtype) @ rounded(one(moe["router"], index), router_dtype))
        bias = one(moe["router_bias"], index)
        if drop == "e_score_correction_bias":
            bias = jnp.zeros_like(bias)
        topv, topi = jax.lax.top_k(s + bias, k + 1)
        gap = topv[..., k - 1] - topv[..., k]
        chosen = jnp.where(
            swap[..., None],
            jnp.concatenate([topi[..., :k - 1], topi[..., k:]], axis=-1), topi[..., :k])
        w = jnp.take_along_axis(s + bias if perturb.get("bias_in_weights") else s,
                                chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
        dense_w = jnp.sum(jax.nn.one_hot(chosen, E, dtype=f32) * w[..., None], axis=-2)
        return x, u, dense_w, gap

    route_all = jax.jit(lambda x, layers, index, swap: attend_and_route(x, layers, index, swap))
    route_at = jax.jit(attend_and_route)

    @jax.jit
    def expert_block(u, dense_w, moe, index, start):
        """sum over experts [start, start + Eb) of w_e * expert_e(u)."""
        def cut(a):  # [L, E, a, b] -> [Eb, a, b] float32
            return jax.lax.dynamic_slice(
                a, (index, start, 0, 0), (1, Eb) + a.shape[2:])[0].astype(f32)

        h = jax.nn.silu(jnp.einsum("rtd,edf->rtef", u, cut(moe["w_gate"]))) * jnp.einsum(
            "rtd,edf->rtef", u, cut(moe["w_up"]))
        y = jnp.einsum("rtef,efd->rted", h, cut(moe["w_down"]))
        w = jax.lax.dynamic_slice_in_dim(dense_w, start, Eb, axis=2)
        return jnp.einsum("rted,rte->rtd", y, w)

    @jax.jit
    def finish(x, u, routed, shared, index):
        if drop != "shared_expert" and shared is not None:
            routed = routed + swiglu(u, one(shared, index))
        return act(x + routed)

    def expert_layer(x, layers, index, swap, at=None, x_at=None):
        """(x' [R, T, D], gap [R, T]); with ``at`` (and ``x_at`` [R, D], that
        position's input) the layer at that position alone: T = 1."""
        if at is None:
            x, u, dense_w, gap = route_all(x, layers, index, swap)
        else:
            x, u, dense_w, gap = route_at(x, layers, index, swap, at, x_at)
        moe = layers["moe"]
        starts = list(range(0, E - Eb + 1, Eb))
        assert starts[-1] + Eb == E, (E, Eb)
        routed = sum(expert_block(u, dense_w, moe, index, np.int32(s)) for s in starts)
        return finish(x, u, routed, moe.get("shared"), index), gap

    @jax.jit
    def head_block(h, lm_head, start):
        width = min(HEAD_BLOCK, lm_head.shape[1])
        return h @ jax.lax.dynamic_slice_in_dim(lm_head, start, width, axis=1).astype(f32)

    def head(x, final_scale, lm_head):
        """Logits [R, V] of x [R, D], the head in blocks of HEAD_BLOCK columns."""
        h = rms(x, final_scale.astype(f32))
        V = lm_head.shape[1]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)
        out = np.empty((x.shape[0], V), np.float32)
        for s in starts:
            out[:, s:s + width] = np.asarray(head_block(h, lm_head, np.int32(s)))
        return out

    return embed, dense_layer, expert_layer, head


def forward_logits(dims: dict, params: dict, tokens, position: int, swaps=None,
                   perturb: dict | None = None, pieces=None, keep: list | None = None):
    """Reference logits [R, V] at ``position`` of ``tokens`` [R, T], and the
    routing gaps [expert layers, R, T]. ``swaps`` [expert layers, R, T] bool
    (default none) are the (layer, row, position)s that take the 9th selection.
    ``keep`` (a list) receives every expert layer's input [R, T, D]."""
    import jax
    import numpy as np

    embed, dense_layer, expert_layer, head = pieces or build_forward(dims, perturb)
    kd = dims["first_k_dense_replace"]
    Lm = dims["layers"] - kd
    R, T = tokens.shape
    if swaps is None:
        swaps = np.zeros((Lm, R, T), bool)
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        for i in range(kd):
            x = dense_layer(x, params["dense_layers"], np.int32(i))
        for i in range(Lm):
            if keep is not None:
                keep.append(x)
            x, gap = expert_layer(x, params["layers"], np.int32(i), swaps[i])
            gaps.append(np.asarray(gap))
        logits = head(x[:, position], params["final_norm"]["scale"], _lm_head(dims, params))
    return logits, np.stack(gaps)


def _lm_head(dims, params):
    return params["tok_embed"].T if dims["tie_word_embeddings"] else params["lm_head"]


def swapped_logits(dims: dict, params: dict, inputs: list, position: int, swaps, pieces):
    """Logits [R, V] at ``position`` with ``swaps`` [expert layers, R] bool
    applied AT THAT POSITION, and that position's gaps [expert layers, R] in
    this pass: what forward_logits gives under the same swaps, at a fraction
    of its work. Attention is causal and the swaps sit at the compared
    position, so every other position's hidden state is the plain pass's:
    each expert layer is recomputed at that one position, its keys and values
    from the plain pass's layer inputs (``inputs``: forward_logits' ``keep``)
    with that position's replaced by the swapped one."""
    import jax
    import numpy as np

    expert_layer, head = pieces[2], pieces[3]
    at = np.int32(position)
    gaps = []
    with jax.default_matmul_precision("highest"):
        x_at = inputs[0][:, position]
        for i, x_all in enumerate(inputs):
            x, gap = expert_layer(x_all, params["layers"], np.int32(i),
                                  np.asarray(swaps[i])[:, None], at, x_at)
            x_at = x[:, 0]
            gaps.append(np.asarray(gap)[:, 0])
        logits = head(x_at, params["final_norm"]["scale"], _lm_head(dims, params))
    return logits, np.stack(gaps)


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    return {
        "hidden_size": mcfg.d_model, "layers": mcfg.n_layers,
        "num_attention_heads": mcfg.n_heads, "q_lora_rank": mcfg.mla_q_rank,
        "kv_lora_rank": mcfg.mla_kv_rank, "qk_nope_head_dim": mcfg.mla_nope_dim,
        "qk_rope_head_dim": mcfg.mla_rope_dim, "v_head_dim": mcfg.mla_v_dim,
        "intermediate_size": mcfg.d_ff, "moe_intermediate_size": mcfg.expert_ff,
        "n_routed_experts": mcfg.n_experts, "num_experts_per_tok": mcfg.n_experts_per_tok,
        "n_shared_experts": mcfg.n_shared_experts,
        "first_k_dense_replace": mcfg.first_k_dense,
        "routed_scaling_factor": mcfg.moe_scale, "rms_norm_eps": mcfg.norm_eps,
        "rope_theta": mcfg.rope_theta, "vocab_size": mcfg.vocab_size,
        "tie_word_embeddings": mcfg.tie_embeddings,
    }


def routed_logits_at(dims, params, tokens, owner, served, P: int, near_tie: float, pieces,
                     seen: dict):
    """``at(step)`` -> a list of [R, V] logits at the step's position: the
    plain routing first, then one array for every nonempty subset of the
    expert layers, computed with those layers' 8th and 9th selection swapped
    AT THE COMPARED POSITION. A row takes a subset's logits only where the
    subset is ADMISSIBLE for it: every swapped layer's gap, IN THE PASS THAT
    LEADS TO IT (a swap in an earlier layer moves the later layers' scores by
    far more than rounding does), is under ``near_tie``; elsewhere, and in
    rows whose probe has no served byte at the step, it keeps the plain
    logits. ``seen`` collects the smallest gap met and which rows had a near
    tie under the plain routing."""
    import numpy as np

    R = tokens.shape[0]

    def at(step: int):
        pos = P - 1 + step
        live = np.array([owner[r] >= 0 and len(served[owner[r]]) > step for r in range(R)])
        inputs: list = []
        base, gaps = forward_logits(dims, params, tokens, pos, pieces=pieces, keep=inputs)
        g = gaps[:, :, pos]  # [expert layers, R]: the gaps at the compared position
        near = (g < near_tie) & live[None, :]
        seen["near"].append(near.any(axis=0))
        out = [base]
        if not near.any():
            return out
        seen["min_gap"] = min(seen["min_gap"], float(g[:, live].min()))
        n_layers = g.shape[0]
        for v in range(1, 2 ** n_layers):
            layers = np.flatnonzero((v >> np.arange(n_layers)) & 1)
            swaps = np.zeros(g.shape, bool)
            swaps[layers] = live
            logits, g_v = swapped_logits(dims, params, inputs, pos, swaps, pieces)
            admissible = live & (g_v[layers] < near_tie).all(axis=0)
            out.append(np.where(admissible[:, None], logits, base))
        return out

    return at


def compare(job: dict, conf: dict, params: dict) -> dict:
    """The comparison on ``job``'s served text with the program's seeded
    ``params``: the result line's fields (``ok`` decides ``correct``)."""
    import numpy as np

    from reference import byte_class

    dims = conf
    pieces = build_forward(dims, job.get("perturb"))
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
    near_tie = float(conf["reference"]["near_tie"])
    seen = {"min_gap": math.inf, "near": []}
    variants = routed_logits_at(dims, params, tokens, owner, served, P, near_tie, pieces, seen)
    tol = float(job["tolerance"])
    rescued = 0
    position_margin: dict = {}  # (probe, step) -> the best margin any of its contexts gave

    def logits_at(step: int):
        """One [R, V] array for the walk: a row's logits under the routing
        (plain, or a subset of its near-tie layers swapped at the compared
        position) that serves its probe's byte best. The walk then applies
        its tolerance to that."""
        nonlocal rescued
        outs = variants(step)
        folded = outs[0].copy()
        for r in np.flatnonzero(owner >= 0):
            text = served[owner[r]]
            if len(text) <= step:
                continue
            cls = byte_class(text[step], V)
            margins = [float(o[r].max() - o[r, cls].max()) for o in outs]
            best = int(np.argmin(margins))
            if best:
                folded[r] = outs[best][r]
                rescued += margins[0] > tol >= margins[best]
            at = (int(owner[r]), step)
            position_margin[at] = min(position_margin.get(at, math.inf), margins[best])
        return folded

    res = walk(logits_at, tokens, owner, served, P, n_new, V, tol)
    # What decides: the MEAN over the compared positions. A routing swap at an
    # earlier token of a context (which no rule here can follow) throws ONE
    # position far out and leaves the others where they were, so the worst of a
    # run's ~45 positions is an extreme of that noise (0.05 to 0.92 over the
    # seeds); a fault of the model moves every position, and the mean tells the
    # two apart (the configuration's tolerance_why has the readings). The walk's
    # own verdict (no probe's best context over the tolerance) is reported as
    # ``walk_ok`` and does not decide.
    mean_limit = float(conf["reference"]["mean_margin_limit"])
    mean_margin = (sum(position_margin.values()) / len(position_margin)
                   if position_margin else math.inf)
    near = np.stack(seen["near"]) if seen["near"] else np.zeros((0, R), bool)
    return {
        **res, "ok": bool(res["enough_positions"] and mean_margin <= mean_limit),
        "walk_ok": res["ok"],
        "mean_margin": mean_margin if math.isfinite(mean_margin) else None,
        "mean_margin_limit": mean_limit, "probes": len(probes), "perturb": job.get("perturb"),
        "near_tie": near_tie, "near_tie_positions": int(near.sum()),
        "near_tie_rescued": int(rescued),
        "min_gap": None if math.isinf(seen["min_gap"]) else seen["min_gap"],
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
