"""The plain reference for ``exaone_moe`` configurations (K-EXAONE) and the
comparison that decides ``correct`` in their cells. Same job file in, same
result line out as ``reference.py``; a configuration file names it under
``reference.module``.

The forward pass is K-EXAONE's, written straight from its published
``config.json`` (and, for what that does not say, from the family's own
``transformers/models/exaone4/modeling_exaone4.py``: the configuration file's
``assumed`` lists each) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunking, no
batching tricks; a Python loop over the layers; one expert at a time; the head in
column blocks. With ``RMS`` the RMSNorm (eps ``rms_norm_eps``):

    x = E[token]
    every layer:  x' = x + RMS(attention(x); g1);  x'' = x' + RMS(ffn(x'); g2)
                  (the norms sit on the branches' OUTPUTS, as EXAONE 4.0's)
    attention:  q, k, v = W_q x, W_k x, W_v x; per-head RMS of q and k (learned
        scales); in a WINDOW layer RoPE (theta, half-split pairs) on q and k and
        the keys of the last ``sliding_window`` positions, in a FULL layer no
        rotation and every earlier key; scores / sqrt(head_dim); W_o
    ffn, layer < first_k_dense_replace:  W_down(SiLU(W_gate x') * W_up x')
    ffn, the others:  s = sigmoid(W_r x') (float32); the num_experts_per_tok
        largest s are chosen, w = s / sum(chosen s) * routed_scaling_factor;
        sum_e w_e expert_e(x') + shared(x')
    logits = RMS(x; g_f) W_head                     (untied, over the rows held)
    the MTP layer, position t:  u = W_eh [RMS(E[token t+1]; g_e) ; RMS(x_t; g_h)]
        with x_t the trunk's LAST hidden state (g_f's input); one block as above
        (full attention, no rotation, a sparse ffn); logits = RMS(u'; g_f) W_head:
        the model's draft of token t+2

**The shares.** The program holds ``num_experts_held`` of every layer's
``num_experts`` experts from ``expert_first`` on and rows 0 ..
``vocab_size_held`` - 1 of the embedding and the head; the rest lie on chips this
cut does not have. The reference does as the program does: every expert of the
HELD set runs on every token behind the weights' mask (dense, dropless by
construction), the part of the absent experts is LEFT OUT of the sum, and the
logits are those of the held rows. Nothing stands in for the absent chips.

Its sizes come from the configuration FILE (the model's own ``config.json``
names; the depth as run is ``layers``, the pattern the first ``layers`` entries of
``layer_types`` / ``sliding_windows``); only the seeded weights come from the
program. It shares no code with ``bee2bee_tpu/models/core.py``.

What is compared: ``reference_falcon_h1.py``'s forking walk with
``reference_joyai.py``'s ROUTING rule, as ``reference_granite.py`` has them
(served text -> bytes -> the best reference logit among the tokens of the served
byte must lie within ``tolerance`` of the reference's maximum; the compared
position ALSO computed with the k-th <-> (k+1)-th choice swapped at that position
in every layer whose gap is under ``near_tie``, a tree of at most ``MAX_PASSES``
passes a position). What decides ``correct`` is ``mean_margin`` against
``mean_margin_limit`` (``tolerance_why`` in the configuration file has the
readings); the walk's own verdict is reported as ``walk_ok``.

**The MTP layer and the window** cannot be seen in served text: a draft is never
accepted under seeded weights (about 1 in ``vocab_size_held``), and a probe is 64 +
8 tokens under a window of 128, so no layer's window binds in what is compared (an
ignored window reads the SAME margins, my chip runs, PR 54). So the comparison ALSO
drives the ENGINE's own compiled programs at the cell's shapes (``served_steps``: an
``InferenceEngine`` built as the node builds its own, a pool of its making): the
probes' prompts repeated to 2-3 windows on as many rows as the server batches (64),
prefilled by the grouped ``[n, bucket]`` program with its MTP pass (``mtp_next``),
then TWO verify steps of the ``[rows, 2]`` chunk at the same offset: the first with
the prefill's own draft (rejected, but for one in thousands), the second with the
first's verdict as the draft (accepted in every row: the path a deployment runs).
The trunk's greedy token at the prefill's last position and at both positions of
the chunk, and the MTP layer's draft made at each of the three (``mtp_draft``, as
the scheduler reads it), must stand within ``long_margin_limit`` /
``mtp_margin_limit`` of this file's best logit there, in the mean
(``engine_steps``; both DECIDE). An ignored window in an early layer, a rotation in
the full layer, a wrong table of the chunk or a wrong page of cache layer 5 moves
these logits as far as a wrong weight does; a position's margin is its best under the
ROUTING rule, as the walk's (``routed_margins``: every large single margin met on
the chip was one near-tied layer's other choice at the compared position, at gaps
up to 0.036: ``tolerance_why``). The LAST layers' windows move them less
than bf16 does (``tolerance_why``), so the ragged read itself is ALSO held to a
dense mask at the verify step's shapes with sharpened queries, every cache layer
with its own window (``window_read``, against ``window_read_limit``; it decides).

``job["perturb"]`` (the builder's proof that the limit discriminates, never set by
``run.py``), each ONE thing wrong: ``{"window_off": i}`` (layer i attends fully),
``{"rope_global": true}`` (the full layers rotate too), ``{"drop": "qk_norm" |
"routed_scaling_factor" | "norm_topk_prob" | "shared_expert"}``, ``{"expert_first":
n}`` (the held arrays taken as experts n..), ``{"dense_as_sparse": true}`` (the
leading dense layer's ffn run as the first sparse layer's), ``{"activation_dtype":
"float8_e4m3fn"}`` (the residual stream rounded after the embedding and every
layer: the nearest precision below bf16), ``{"mtp_window": w}`` (the MTP block
attends behind a window), ``{"mtp_token": "current"}`` (the MTP layer is handed
token t where it takes token t + 1).
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

HEAD_BLOCK = 19200  # columns of the head a call
STEP_WINDOWS = (2.0, 3.0)  # engine_steps: the contexts' lengths in windows, spread between
REF_ROWS = 16  # engine_steps: rows a pass of the plain forward
STEP_TABLE = 64  # window_read: pages a row's table holds (the cell's deepest context: 44)
MAX_PASSES = 8  # leaves of a compared position's tree of routing passes
VARIANT_ROWS = 8  # contexts a pass of swapped routings computes at once
PERTURBATIONS = {"activation_dtype", "drop", "expert_first", "window_off", "rope_global",
                 "dense_as_sparse", "mtp_window", "mtp_token"}
DROPS = (None, "qk_norm", "routed_scaling_factor", "norm_topk_prob", "shared_expert")


def layer_plan(dims: dict, perturb: dict | None = None) -> list[dict]:
    """One entry a layer that runs: {group ("dense_layers" | "layers"), index
    (its place in that group's stack), sparse, window (0 = full), rope}."""
    perturb = perturb or {}
    n, k = int(dims["layers"]), int(dims.get("first_k_dense_replace") or 0)
    windows = list(dims["sliding_windows"][:n])
    plan = []
    for i in range(n):
        w = 0 if perturb.get("window_off") == i else int(windows[i])
        plan.append({
            "group": "dense_layers" if i < k else "layers",
            "index": i if i < k else i - k, "sparse": i >= k, "window": w,
            "rope": bool(windows[i]) or bool(perturb.get("rope_global")),
        })
    if perturb.get("dense_as_sparse"):
        for entry in plan[:k]:
            entry["sparse_ffn_of"] = 0  # the first sparse layer's expert layer
    return plan


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, block, head,
    mtp_in). ``block(x [R, T, D], group, index, window, rope, ffn_group,
    ffn_index, sparse, swap [R], at)`` is one layer read out of ``group``'s
    stack at ``index`` whose ffn is ``ffn_group``'s at ``ffn_index``; returns
    (the layer's output, gap [R, T]: the k-th minus the (k+1)-th router logit,
    +inf for a dense ffn). ``swap`` takes the (k+1)-th expert in the k-th's place
    at position ``at``."""
    import jax
    import jax.numpy as jnp

    perturb = perturb or {}
    unknown = set(perturb) - PERTURBATIONS
    if unknown:
        raise KeyError(f"unknown perturbation {sorted(unknown)}")
    drop = perturb.get("drop")
    if drop not in DROPS:
        raise KeyError(f"unknown drop {drop!r}")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    f32 = jnp.float32
    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps = dims["rms_norm_eps"]
    theta = float((dims.get("rope_parameters") or {}).get("rope_theta", 1000000.0))
    E, k = dims["num_experts"], dims["num_experts_per_tok"]
    held = int(dims.get("num_experts_held") or E)
    first = int(perturb.get("expert_first", dims.get("expert_first") or 0))
    scale = 1.0 if drop == "routed_scaling_factor" else float(dims["routed_scaling_factor"])

    def rounded(x, dtype):
        """x at ``dtype``'s precision, still float32 (``lax.reduce_precision``:
        the TPU compiler elides a float32 -> narrow -> float32 convert pair)."""
        if dtype == f32:
            return x
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    def act(x):  # the residual stream at the perturbed activation type
        return rounded(x, act_dtype)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def one(tree, index):
        """One layer of the stacked [L, ...] arrays, upcast to float32."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(f32), tree)

    def rotate(x):  # [R, T, heads, hd], position = the token's index
        T = x.shape[1]
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
        ang = jnp.arange(T, dtype=f32)[:, None] * freqs[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    def attention(u, p, window, rope):
        R, T, _ = u.shape
        q = (u @ p["wq"]).reshape(R, T, H, hd)
        kk = (u @ p["wk"]).reshape(R, T, Hkv, hd)
        v = (u @ p["wv"]).reshape(R, T, Hkv, hd)
        if drop != "qk_norm":
            q, kk = rms(q, p["q_norm"]), rms(kk, p["k_norm"])
        q = jnp.where(rope, rotate(q), q)
        kk = jnp.where(rope, rotate(kk), kk)
        kk, v = jnp.repeat(kk, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, kk) / math.sqrt(hd)
        t_i, s_i = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        seen = (s_i <= t_i) & ((window == 0) | (t_i - s_i < window))
        scores = jnp.where(seen[None, None], scores, -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(R, T, H * hd) @ p["wo"]

    def swiglu(h, p):
        return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    def experts(h, moe, index, swap, at):
        """(routed + shared [R, T, D], gap [R, T]) of the ffn's input ``h``."""
        R, T, _ = h.shape
        z = h @ jax.lax.dynamic_index_in_dim(moe["router"], index, keepdims=False).astype(f32)
        zs, idx = jax.lax.top_k(z, k + 1)  # [R, T, k + 1], largest first
        gap = zs[..., k - 1] - zs[..., k]
        swapped = (swap[:, None] & (jnp.arange(T)[None, :] == at))[..., None]
        last = (jnp.arange(k) == k - 1)[None, None, :]
        # the k-th slot takes the (k+1)-th choice where swapped
        chosen = jnp.where(swapped & last, idx[..., k:], idx[..., :k])
        sc = jax.nn.sigmoid(jnp.where(swapped & last, zs[..., k:], zs[..., :k]))
        w = sc if drop == "norm_topk_prob" else sc / jnp.sum(sc, axis=-1, keepdims=True)
        w = w * scale
        local = chosen - first  # a held expert's weight a token: [R, T, held]
        w_held = jnp.sum(w[..., None] * (local[..., None] == jnp.arange(held)), axis=-2)

        def expert(acc, e):
            pick = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
                jax.lax.dynamic_index_in_dim(a, index, keepdims=False), e,
                keepdims=False).astype(f32)
            y = (jax.nn.silu(h @ pick(moe["w_gate"])) * (h @ pick(moe["w_up"]))
                 ) @ pick(moe["w_down"])
            return acc + y * jax.lax.dynamic_index_in_dim(w_held, e, axis=2), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
        if drop != "shared_expert":
            out = out + swiglu(h, one(moe["shared"], index))
        return out, gap

    def make_block(sparse: bool):
        @jax.jit
        def block(x, group, index, window, rope, ffn_group, ffn_index, swap, at):
            lp = one({n: group[n] for n in ("attn", "ln1_post", "ln2_post")}, index)
            x1 = x + rms(attention(x, lp["attn"], window, rope), lp["ln1_post"]["scale"])
            if sparse:
                out, gap = experts(x1, ffn_group["moe"], ffn_index, swap, at)
            else:
                out = swiglu(x1, one(ffn_group["mlp"], ffn_index))
                gap = jnp.full(x.shape[:2], jnp.inf, f32)
            return act(x1 + rms(out, lp["ln2_post"]["scale"])), gap

        return block

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed, tokens, axis=0).astype(f32))

    @jax.jit
    def mtp_in(tok_embed, mtp, hidden, next_tokens):
        e = jnp.take(tok_embed, next_tokens, axis=0).astype(f32)
        both = jnp.concatenate([rms(e, mtp["enorm"]["scale"].astype(f32)),
                                rms(hidden, mtp["hnorm"]["scale"].astype(f32))], axis=-1)
        return act(both @ mtp["eh_proj"].astype(f32))

    @jax.jit
    def head_block(h, lm_head, start):  # h already normed; a block of the head's columns
        width = min(HEAD_BLOCK, lm_head.shape[1])
        return h @ jax.lax.dynamic_slice_in_dim(lm_head, start, width, axis=1).astype(f32)

    def head(x, final_scale, lm_head):
        """Logits [..., V] of x [..., D], the head in blocks of HEAD_BLOCK columns."""
        import numpy as np

        h = rms(x, final_scale.astype(f32))
        V = lm_head.shape[1]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)  # the last block overlaps its neighbour
        out = np.empty((*x.shape[:-1], V), np.float32)
        for s in starts:
            out[..., s:s + width] = np.asarray(head_block(h, lm_head, np.int32(s)))
        return out

    return embed, {True: make_block(True), False: make_block(False)}, head, mtp_in


def _at(position):
    """``block``'s ``at``: one position for every row, or [R, 1] (one a row)."""
    import numpy as np

    at = np.asarray(position, np.int32)
    return at if at.ndim == 0 else at.reshape(-1, 1)


def _run_layers(params, x, plan, block, swaps, position):
    import numpy as np

    gaps = []
    for i, lay in enumerate(plan):
        sparse = lay["sparse"] or "sparse_ffn_of" in lay
        ffn_group, ffn_index = params[lay["group"]], lay["index"]
        if "sparse_ffn_of" in lay:
            ffn_group, ffn_index = params["layers"], lay["sparse_ffn_of"]
        x, gap = block[sparse](
            x, params[lay["group"]], np.int32(lay["index"]), np.int32(lay["window"]),
            np.bool_(lay["rope"]), ffn_group, np.int32(ffn_index),
            np.asarray(swaps[i]), _at(position))
        gaps.append(gap)
    return x, gaps


def forward_logits(dims: dict, params: dict, tokens, position, swaps=None,
                   perturb: dict | None = None, pieces=None, mtp: bool = False):
    """Reference logits [R, V] at ``position`` (one for every row, or [R]: one a
    row) of ``tokens`` [R, T] and the routing gaps there [layers, R]. ``swaps``
    [layers, R] bool (default none) are the (layer, row)s that take the (k+1)-th
    choice AT ``position``. ``mtp``: the MTP layer's logits there instead (the
    draft made at ``position`` with the token that follows it), ``swaps`` and
    the gaps one layer longer: the MTP block's is the last."""
    import jax
    import numpy as np

    perturb = perturb or {}
    embed, block, head, mtp_in = pieces or build_forward(dims, perturb)
    plan = layer_plan(dims, perturb)
    R, _ = tokens.shape
    if swaps is None:
        swaps = np.zeros((len(plan) + mtp, R), bool)
    pos = np.broadcast_to(np.asarray(position, np.int64), (R,))

    def rows_at(x):  # [R, T, D] -> [R, D] at each row's position
        return np.take_along_axis(np.asarray(x), pos[:, None, None], axis=1)[:, 0]

    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        x, gaps = _run_layers(params, x, plan, block, swaps, position)
        if mtp:
            follow = tokens[:, :-1] if perturb.get("mtp_token") == "current" else tokens[:, 1:]
            u = mtp_in(params["tok_embed"], params["mtp"], x[:, :-1], follow)
            x, gap = block[True](
                u, params["mtp"]["block"], np.int32(0),
                np.int32(perturb.get("mtp_window", 0)), np.bool_(False),
                params["mtp"]["block"], np.int32(0), np.asarray(swaps[-1]), _at(position))
            gaps = gaps + [gap]
        logits = head(rows_at(x), params["final_norm"]["scale"], params["lm_head"])
    return logits, np.stack([rows_at(g[..., None])[:, 0] for g in gaps])


def full_forward(dims: dict, params: dict, tokens, perturb: dict | None = None, pieces=None,
                 at=None, with_gaps: bool = False):
    """(trunk logits [R, T, V], MTP logits [R, T - 1, V] or None): the whole
    plain forward of ``tokens`` [R, T]; the MTP layer's row t is made with token
    t + 1 and is the draft of token t + 2. ``at`` [R, P] (positions under T - 1):
    the two heads at those positions of each row alone, [R, P, V] each;
    ``with_gaps`` then adds the routing gaps there, [layers + 1, R, P] (the MTP
    block's last)."""
    import jax
    import numpy as np

    perturb = perturb or {}
    embed, block, head, mtp_in = pieces or build_forward(dims, perturb)
    plan = layer_plan(dims, perturb)
    R, T = tokens.shape
    none = np.zeros((len(plan) + 1, R), bool)

    def picked(x):
        return x if at is None else np.take_along_axis(
            np.asarray(x), np.asarray(at)[:, :, None], axis=1)

    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        x, gaps = _run_layers(params, x, plan, block, none, 0)
        logits = head(picked(x), params["final_norm"]["scale"], params["lm_head"])
        if not dims.get("num_nextn_predict_layers"):
            return logits, None
        follow = tokens[:, :-1] if perturb.get("mtp_token") == "current" else tokens[:, 1:]
        u = mtp_in(params["tok_embed"], params["mtp"], x[:, :-1], follow)
        u, gap = block[True](
            u, params["mtp"]["block"], np.int32(0),
            np.int32(perturb.get("mtp_window", 0)), np.bool_(False),
            params["mtp"]["block"], np.int32(0), none[0], np.int32(0))
        mtp_logits = head(picked(u), params["final_norm"]["scale"], params["lm_head"])
        if with_gaps:
            return logits, mtp_logits, np.stack([picked(g[..., None])[..., 0] for g in gaps + [gap]])
        return logits, mtp_logits


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    n = mcfg.n_layers
    return {
        "hidden_size": mcfg.d_model, "layers": n,
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "head_dim": mcfg.head_dim, "intermediate_size": mcfg.d_ff,
        "moe_intermediate_size": mcfg.expert_ff, "rms_norm_eps": mcfg.norm_eps,
        "num_experts": mcfg.n_experts, "num_experts_per_tok": mcfg.n_experts_per_tok,
        "num_experts_held": mcfg.experts_held, "expert_first": mcfg.expert_first,
        "num_shared_experts": mcfg.n_shared_experts,
        "routed_scaling_factor": mcfg.moe_scale,
        "first_k_dense_replace": mcfg.first_k_dense,
        "vocab_size_held": mcfg.vocab_size,
        "vocab_size": mcfg.vocab_published or mcfg.vocab_size,
        "num_nextn_predict_layers": mcfg.mtp_layers,
        "tie_word_embeddings": mcfg.tie_embeddings,
        "sliding_windows_run": list(mcfg.layer_windows[:n]),
        "rope_theta": mcfg.rope_theta,
    }


def dims_of_file(conf: dict) -> dict:
    """The configuration file's side of dims_of_preset's comparison."""
    n = conf["layers"]
    return dict(conf, sliding_windows_run=list(conf["sliding_windows"][:n]),
                rope_theta=float((conf.get("rope_parameters") or {}).get("rope_theta", 0)))


def routed_logits_at(dims, params, tokens, owner, served, P: int, near_tie: float, pieces,
                     seen: dict, perturb=None):
    """``at(step)`` -> (plain logits [R, V], {row: [logits [V] under each
    ADMISSIBLE routing of the compared position]}): ``reference_granite.py``'s
    tree of routing passes (a routing is a set of layers swapped k-th <->
    (k+1)-th at the compared position; admissible where every swapped layer's
    gap, in the pass that leads to it, is under ``near_tie``; breadth first, at
    most MAX_PASSES a row, VARIANT_ROWS contexts a pass)."""
    import numpy as np

    R = tokens.shape[0]

    def at(step: int):
        pos = P - 1 + step
        live = np.array([owner[r] >= 0 and len(served[owner[r]]) > step for r in range(R)])
        base, gaps = forward_logits(dims, params, tokens, pos, perturb=perturb, pieces=pieces)
        L = gaps.shape[0]
        near = (gaps < near_tie) & live[None, :]
        seen["near"].append(near.any(axis=0))
        if live.any():
            seen["min_gap"] = min(seen["min_gap"], float(gaps[:, live].min()))
        variants: dict[int, list] = {}
        budget = {int(r): MAX_PASSES for r in np.flatnonzero(near.any(axis=0))}
        queue = [(int(r), (int(lyr),)) for r in budget for lyr in np.flatnonzero(near[:, r])]
        while queue:
            jobs, rest = [], []
            for job in queue:
                if budget[job[0]] > 0 and len(jobs) < VARIANT_ROWS:
                    budget[job[0]] -= 1
                    jobs.append(job)
                elif budget[job[0]] > 0:
                    rest.append(job)
            if not jobs:
                break
            rows = [r for r, _ in jobs] + [jobs[0][0]] * (VARIANT_ROWS - len(jobs))
            swaps = np.zeros((L, VARIANT_ROWS), bool)
            for j, (_, layers) in enumerate(jobs):
                swaps[list(layers), j] = True
            logits, g = forward_logits(dims, params, tokens[rows], pos, swaps, perturb=perturb,
                                       pieces=pieces)
            seen["passes"] += 1
            for j, (r, layers) in enumerate(jobs):
                variants.setdefault(r, []).append(logits[j])
                rest += [(r, layers + (int(lyr),)) for lyr in range(layers[-1] + 1, L)
                         if g[lyr, j] < near_tie]
            queue = rest
        seen["cut"] += sum(b == 0 for b in budget.values())
        return base, variants

    return at


def step_rows(conf: dict) -> int:
    """The rows of a verify step: the server's batch (the cell's 64)."""
    return int(conf["server"]["config_json"]["max_batch_size"])


def step_contexts(dims: dict, prompts):
    """(contexts [R, T] right-padded, their lengths [R]) for the R rows of a
    verify step: the probes' prompts ``prompts`` [n, P] (BOS first) repeated to
    between STEP_WINDOWS windows, row r of the n-th pass over the probes
    starting n tokens into its prompt, the lengths spread over the range."""
    import numpy as np

    window = max(int(w) for w in dims["sliding_windows"][:dims["layers"]])
    lo, hi = (int(f * window) for f in STEP_WINDOWS)
    R = step_rows(dims)
    lengths = lo + 1 + (np.arange(R) * 37) % (hi - lo)
    ctx = np.zeros((R, int(lengths.max())), prompts.dtype)
    for r, n in enumerate(lengths):
        body = np.roll(prompts[r % len(prompts), 1:], -(r // len(prompts)))
        ctx[r, 0] = prompts[r % len(prompts), 0]
        ctx[r, 1:n] = np.tile(body, -(-n // len(body)))[:n - 1]
    return ctx, lengths.astype(np.int32)


def routed_margins(dims: dict, params: dict, tokens, positions, picks, margins, gaps, near_tie,
                   perturb, pieces, mtp: bool):
    """The ROUTING rule (module docstring) at one compared position a row:
    ``margins`` [R] (how far ``picks[r]`` stands below the best logit at
    ``positions[r]`` of ``tokens[r]``) and ``gaps`` [layers, R] are the plain
    pass's; a row that disagrees is ALSO computed with every admissible set of
    its near-tied layers swapped at that position (a tree of at most MAX_PASSES
    passes a row, VARIANT_ROWS rows a pass) and keeps its best margin.
    -> (margins [R], rows rescued to agreement, passes)."""
    import numpy as np

    best, L = margins.copy(), gaps.shape[0]
    budget = {int(r): MAX_PASSES for r in np.flatnonzero(margins > 0)
              if (gaps[:, r] < near_tie).any()}
    queue = [(r, (int(lyr),)) for r in budget for lyr in np.flatnonzero(gaps[:, r] < near_tie)]
    passes = 0
    while queue:
        jobs, rest = [], []
        for job in queue:
            if budget[job[0]] > 0 and best[job[0]] > 0 and len(jobs) < VARIANT_ROWS:
                budget[job[0]] -= 1
                jobs.append(job)
            elif budget[job[0]] > 0 and best[job[0]] > 0:
                rest.append(job)
        if not jobs:
            break
        rows = [r for r, _ in jobs] + [jobs[0][0]] * (VARIANT_ROWS - len(jobs))
        swaps = np.zeros((L, VARIANT_ROWS), bool)
        for j, (_, layers) in enumerate(jobs):
            swaps[list(layers), j] = True
        logits, g = forward_logits(dims, params, tokens[rows], positions[rows], swaps, perturb,
                                   pieces, mtp)
        passes += 1
        for j, (r, layers) in enumerate(jobs):
            best[r] = min(best[r], float(logits[j].max() - logits[j, picks[r]]))
            rest += [(r, layers + (int(lyr),)) for lyr in range(layers[-1] + 1, L)
                     if g[lyr, j] < near_tie]
        queue = rest
    return best, int(((margins > 0) & (best <= 0)).sum()), passes


def engine_steps(dims: dict, params: dict, prompts, program_steps, perturb, pieces) -> dict:
    """The ENGINE's own prefill and verify programs against this file, on
    contexts past the window (module docstring) -> {"long": the trunk's
    positions, "mtp": the MTP layer's}. ``program_steps(contexts [R, T], lengths
    [R])`` is ``served_steps``'s: a prefill (first logits [R, V], ``draft0`` [R]),
    a verify step of [first | draft0] (``next1``, ``accepted1``, ``draft1``) and
    the SAME step again with the program's own verdict as the draft (``next2``,
    ``accepted2``, ``draft2``: the accepted path). With N a row's length, the
    trunk's greedy tokens at N - 1 (the prefill's), N (the first step's verdict)
    and N + 1 (the second step's, behind an accepted draft), and the MTP layer's
    drafts made at the same three positions, must each stand within its limit of
    this file's best logit there, in the mean over rows and positions; a
    position's margin is its best under the ROUTING rule, as the walk's."""
    import numpy as np

    ctx, lengths = step_contexts(dims, prompts)
    got = {k: np.asarray(v) for k, v in program_steps(ctx, lengths).items()}
    R, rows = len(lengths), np.arange(len(lengths))
    first = got["first_logits"].argmax(axis=-1)
    took1, took2 = got["accepted1"] > 0, got["accepted2"] > 0
    verdict = np.where(took1, got["draft0"], got["next1"])  # the token at N + 1
    tokens = np.zeros((R, ctx.shape[1] + 3), ctx.dtype)
    tokens[:, :ctx.shape[1]] = ctx
    for k, col in enumerate((first, verdict, got["next2"])):
        tokens[rows, lengths + k] = col
    at = lengths[:, None] - 1 + np.arange(3)[None, :]
    # (REF_ROWS rows a pass: a row's float32 scores are 64 heads x T x T)
    parts = [full_forward(dims, params, tokens[i:i + REF_ROWS], perturb, pieces,
                          at=at[i:i + REF_ROWS], with_gaps=True) for i in range(0, R, REF_ROWS)]
    trunk, mtp = (np.concatenate([p[n] for p in parts]) for n in (0, 1))
    gaps = np.concatenate([p[2] for p in parts], axis=1)  # [layers + 1, R, 3]
    near_tie = float(dims["reference"]["near_tie"])
    every = np.ones((R,), bool)
    # (a row whose SECOND step did not take the program's own verdict says its
    # next2 / draft2 of position N again: counted under own_rejected, not compared)
    heads = {"long": (trunk, gaps[:-1], False,
                      [(first, every), (verdict, every), (got["next2"], took2)]),
             "mtp": (mtp, gaps, True,
                     [(got["draft0"], every), (got["draft1"], ~took1), (got["draft2"], took2)])}
    out = {}
    for name, (ref, head_gaps, is_mtp, picks) in heads.items():
        plain, best, agrees, rescued, passes = [], [], [], 0, 0
        for k, (tok, use) in enumerate(picks):
            use = np.flatnonzero(use)
            m = ref[use, k].max(axis=-1) - ref[use, k, tok[use]]
            b, saved, n = routed_margins(dims, params, tokens[use], at[use, k], tok[use], m,
                                         head_gaps[:, use, k], near_tie, perturb, pieces, is_mtp)
            plain.append(m), best.append(b), agrees.append(ref[use, k].argmax(axis=-1) == tok[use])
            rescued, passes = rescued + saved, passes + n
        plain, best = np.concatenate(plain), np.concatenate(best)
        out[name] = {"positions": int(best.size), "mean_margin": float(best.mean()),
                     "worst_margin": float(best.max()), "plain_mean_margin": float(plain.mean()),
                     "plain_worst_margin": float(plain.max()),
                     "agrees": float(np.concatenate(agrees).mean()),
                     "near_tie_rescued": rescued, "routing_passes": passes}
    out["long"].update(
        rows=R, tokens=[int(lengths.min()), int(lengths.max())],
        max_abs_diff=float(np.abs(trunk[:, 0] - got["first_logits"]).max()),
        accepted_first=int(took1.sum()), own_rejected=int((~took2).sum()))
    return out


def window_read(conf: dict, perturb: dict | None = None) -> dict:
    """The program's ragged read against this file's dense mask at the verify
    step's shapes, cache layer by cache layer (``reference_smallthinker.py``'s
    check, here for the verify step's [rows, 2] queries): the window that
    ``core.make_layer_window`` gives each of the program's SIX cache layers (the
    MTP block's the last) against ``sliding_windows`` + ``mtp_sliding_windows``
    of the configuration file, rows 1 to 5 windows deep and around one window's
    edge, a table of STEP_TABLE pages. Seeded weights spread attention nearly
    evenly, so the keys behind a window move a logit less than bf16 does in the
    LAST layers (``tolerance_why``); here q is drawn four times as wide as k, a
    query leans on a handful of keys, and a read that sees one key too many or
    skips a page is off by a whole value row. ``window_read_err`` is the largest
    |read - dense| of any output (the values' rms is 1) against
    ``window_read_limit``. Shares with the served path:
    ``ops/ragged.make_ragged_attn_fn`` and the preset's per-layer window."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.ops.ragged import make_ragged_attn_fn

    perturb = perturb or {}
    srv = conf["server"]["config_json"]
    mcfg = get_config(conf["server"]["model"])
    BS = int(srv.get("kv_block_size", 16))
    S = STEP_TABLE * BS
    H, Hkv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    windows = [int(w) for w in conf["sliding_windows"][:conf["layers"]]] + [
        int(w) for w in conf.get("mtp_sliding_windows", [])[:conf["num_nextn_predict_layers"]]]
    if "window_off" in perturb:
        windows[perturb["window_off"]] = 0
    if "mtp_window" in perturb:
        windows[-1] = int(perturb["mtp_window"])
    W, L, T, R = max(windows), len(windows), 2, step_rows(conf)
    dtype = jnp.dtype(srv.get("dtype", "bfloat16"))
    # the first query's position a row: around one window's edge, then 1 - 5 windows
    edge = [W - 2, W - 1, W, W + 1]
    at = np.asarray((edge + [int(W * (1 + 4 * r / R)) for r in range(R)])[:R], np.int32)
    pages = -(-(at + T) // BS)
    rng = np.random.default_rng(0)
    ids = rng.permutation(int(pages.sum())) + 1  # block 0 is the null block
    tables = np.zeros((R, STEP_TABLE), np.int32)
    for r, n in enumerate(pages):
        tables[r, :n] = ids[pages[:r].sum():pages[:r].sum() + n]
    kq, kk = jax.random.split(jax.random.key(0))
    pool = jax.random.normal(kk, (L, len(ids) + 1, 2, Hkv, BS, hd), jnp.float32).astype(dtype)
    q = (4.0 * jax.random.normal(kq, (R, T, H, hd), jnp.float32)).astype(dtype)
    positions = at[:, None] + np.arange(T, dtype=np.int32)[None]
    attn = make_ragged_attn_fn(None)
    layer_window = core.make_layer_window(mcfg)

    @jax.jit
    def read(pool, q, layer):
        return attn(q, pool, None, layer_window(layer), mcfg, positions=positions,
                    block_tables=tables, layer=layer)

    @jax.jit
    def dense(pool, q, layer, window):
        with jax.default_matmul_precision("highest"):
            kv = jnp.take(pool[layer], tables, axis=0).astype(jnp.float32)  # [R, MB, 2, Hkv, BS, hd]
            kv = kv.transpose(2, 0, 1, 4, 3, 5).reshape(2, R, S, Hkv, hd)
            pos = jnp.arange(S)[None, None, :]
            seen = (pos <= positions[:, :, None]) & (
                (window == 0) | (pos > positions[:, :, None] - window))
            s = jnp.einsum("rtgjd,rsgd->rgjts",
                           q.astype(jnp.float32).reshape(R, T, Hkv, H // Hkv, hd),
                           kv[0]) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), axis=-1)
            return jnp.einsum("rgjts,rsgd->rtgjd", p, kv[1]).reshape(R, T, H * hd)

    worst = 0.0
    for layer in range(L):
        got = np.asarray(read(pool, q, np.int32(layer)), np.float32).reshape(R, T, -1)
        want = np.asarray(dense(pool, q, np.int32(layer), np.int32(windows[layer])))
        worst = max(worst, float(np.abs(got - want).max()))
    limit = float(conf["reference"]["window_read_limit"])
    return {"window_read_err": worst, "window_read_limit": limit,
            "window_read_ok": bool(worst <= limit), "window_read_layers": windows}


def compare(job: dict, conf: dict, params: dict, program_steps=None) -> dict:
    """The comparison on ``job``'s served text with the program's seeded
    ``params``: the result line's fields (``ok`` decides ``correct``).
    ``program_steps`` (``served_steps``: the engine's own prefill and verify
    programs) adds ``long`` and ``mtp`` (``engine_steps``), which DECIDE with
    ``long_margin_limit`` and ``mtp_margin_limit``."""
    import numpy as np

    dims = conf
    perturb = job.get("perturb")
    pieces = build_forward(dims, perturb)
    V = int(dims.get("vocab_size_held") or dims["vocab_size"])
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
    seen = {"min_gap": math.inf, "near": [], "passes": 0, "cut": 0}
    routed = routed_logits_at(dims, params, tokens, owner, served, P, near_tie, pieces, seen,
                              perturb)
    tol = float(job["tolerance"])
    rescued = 0
    position_margin: dict = {}  # (probe, step) -> the best margin any of its contexts gave

    def logits_at(step: int):
        """One [R, V] array for the walk: a row's logits under the routing
        (plain, or an admissible set of its near-tie layers swapped at the
        compared position) that serves its probe's byte best."""
        nonlocal rescued
        base, variants = routed(step)
        folded = base.copy()
        for r in np.flatnonzero(owner >= 0):
            text = served[owner[r]]
            if len(text) <= step:
                continue
            cls = byte_class(text[step], V)
            outs = [base[r]] + variants.get(int(r), [])
            margins = [float(o.max() - o[cls].max()) for o in outs]
            best = int(np.argmin(margins))
            if best:
                folded[r] = outs[best]
                rescued += margins[0] > tol >= margins[best]
            at = (int(owner[r]), step)
            position_margin[at] = min(position_margin.get(at, math.inf), margins[best])
        return folded

    res = walk(logits_at, tokens, owner, served, P, n_new, V, tol)
    mean_limit = float(conf["reference"]["mean_margin_limit"])
    mean_margin = (sum(position_margin.values()) / len(position_margin)
                   if position_margin else math.inf)
    near = np.stack(seen["near"]) if seen["near"] else np.zeros((0, R), bool)
    out = {
        **res, "ok": bool(res["enough_positions"] and mean_margin <= mean_limit),
        "walk_ok": res["ok"],
        "mean_margin": mean_margin if math.isfinite(mean_margin) else None,
        "mean_margin_limit": mean_limit, "probes": len(probes), "perturb": perturb,
        "near_tie": near_tie, "near_tie_positions": int(near.sum()),
        "near_tie_rescued": int(rescued), "routing_passes": seen["passes"],
        "routing_trees_cut": seen["cut"],
        "min_gap": None if math.isinf(seen["min_gap"]) else seen["min_gap"],
    }
    if program_steps is not None:
        out.update(engine_steps(dims, params, tokens[:len(probes), :P].copy(), program_steps,
                                perturb, pieces))
        for name in ("long", "mtp"):
            limit = float(conf["reference"][f"{name}_margin_limit"])
            out[name].update(limit=limit, ok=bool(out[name]["mean_margin"] <= limit))
            out["ok"] = bool(out["ok"] and out[name]["ok"])
    return out


def served_steps(params, mcfg, server: dict, mesh):
    """``program_steps`` of ``engine_steps``: an ``InferenceEngine`` built from
    the configuration's ``server`` section as the node builds its own
    (``NodeConfig.engine_config``), and ITS compiled programs at the cell's
    shapes over a pool of its own making: the grouped ``[n, bucket]`` prefill
    with ``mtp_next`` and the ``[rows, 2]`` verify step."""
    import dataclasses

    import jax
    import numpy as np

    from bee2bee_tpu.config import NodeConfig
    from bee2bee_tpu.engine import InferenceEngine

    flags = server.get("flags", [])
    known = {f.name for f in dataclasses.fields(NodeConfig)}
    node = NodeConfig(**{k: v for k, v in server.get("config_json", {}).items() if k in known},
                      **({"attention": flags[flags.index("--attention") + 1]}
                         if "--attention" in flags else {}))
    eng = InferenceEngine(mcfg, params=params, mesh=mesh, engine_config=node.engine_config())
    BS = eng.engine_cfg.kv_block_size

    def program_steps(ctx, lengths):
        R, _ = ctx.shape
        bucket = eng._bucket_for(int(lengths.max()))
        n = max(g for g in eng.prefill_group_rows(bucket) if R % g == 0)
        pages = -(-(int(lengths.max()) + 2) // BS)
        width = 1 << (pages - 1).bit_length()
        tables = np.zeros((R, width), np.int32)
        tables[:, :pages] = 1 + np.random.default_rng(0).permutation(R * pages).reshape(R, pages)
        tok = np.zeros((R, bucket), np.int32)
        tok[:, :ctx.shape[1]] = ctx
        zero = np.zeros((n,), np.int32)
        pool, first, draft0 = eng.new_pool(), [], []
        for at in range(0, R, n):
            rows = slice(at, at + n)
            pool, logits, extras = eng._prefill(
                eng.params, tok[rows], pool, lengths[rows], zero, tables[rows], zero,
                lengths[rows], mtp_next=np.full((n,), -1, np.int32))
            first.append(logits)
            draft0.append(extras["mtp_draft"])
        first, draft0 = (np.concatenate(jax.device_get(a)) for a in (first, draft0))
        out = {"first_logits": first, "draft0": draft0}
        cur = first.argmax(axis=-1).astype(np.int32)
        ones = np.ones((R,), np.int32)
        greedy = (np.zeros((R,), np.float32), np.zeros((R,), np.int32), np.ones((R,), np.float32))
        for step, draft in (("1", draft0), ("2", None)):
            if draft is None:  # the program's own verdict of position N + 1
                draft = np.where(out["accepted1"] > 0, draft0, out["next1"])
            nxt, pool, acc, own = eng._spec_verify(
                eng.params, cur, draft.astype(np.int32)[:, None], ones, pool, lengths, *greedy,
                None, eng._next_key(), tables)
            nxt, acc, drafted = jax.device_get((nxt, acc, own["mtp_draft"]))
            out.update({f"next{step}": nxt, f"accepted{step}": acc, f"draft{step}": drafted})
        return out

    return program_steps


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
    srv = conf["server"]
    devs = jax.devices()
    if devs[0].platform != job["platform"] or len(devs) < conf["chips"]:
        print(json.dumps({"ok": False, "error": f"jax found {len(devs)} x "
                          f"{devs[0].platform}, need {conf['chips']} x {job['platform']}"}))
        return 1

    # the program's seeded weights, made the way the server makes them
    mcfg = get_config(srv["model"])
    have = dims_of_file(conf)
    differs = {k: (v, have.get(k)) for k, v in dims_of_preset(mcfg).items()
               if have.get(k) != v and not (isinstance(v, float) and have.get(k) is not None
                                            and math.isclose(v, have[k], rel_tol=1e-12))}
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

    res = compare(job, conf, params, served_steps(params, mcfg, srv, mesh))
    seen = window_read(conf, job.get("perturb"))
    res.update(seen)
    res["ok"] = bool(res["ok"] and seen["window_read_ok"])
    print(json.dumps({
        **res,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
    }))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
