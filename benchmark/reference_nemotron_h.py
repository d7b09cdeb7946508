"""The plain reference for ``nemotron_h`` configurations and the comparison that
decides ``correct`` in their cells. Same job file in, same result line out as
``reference.py``; a configuration file names it under ``reference.module``.

The forward pass is Nemotron 3's, written straight from its published
``config.json`` in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no chunking,
no batching tricks; a Python loop over the layers by the characters of
``hybrid_override_pattern``; one expert at a time; the head in column blocks.
With ``RMS`` the RMSNorm (eps ``layer_norm_epsilon``):

    x = E[token]
    every layer:  x' = x + branch(RMS(x; g))       ONE branch, ONE norm, ONE add
    ``M`` (Mamba-2):  [z | xBC | dt] = W_in u; a causal depthwise conv of width
        K with bias over xBC, SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log);
        THE TOKEN-BY-TOKEN RECURRENCE h_t = exp(dt_t A) h_{t-1} + dt_t x_t
        (outer) B_t, y_t = h_t C_t + D x_t (B and C shared by the heads of one
        of ``n_groups`` groups), a scan of T steps, state float32; y * SiLU(z),
        an RMS norm over each group's channels, the learned scale, W_out
    ``*`` (attention):  GQA, NO rotation, scores / sqrt(head_dim), causal, full
    ``E`` (LatentMoE):  s = sigmoid(W_r u) over all ``n_routed_experts``
        (a float32 product; ``u`` as the router READS it, below); the ``num_experts_per_tok`` largest of s + b are chosen (b
        the selection bias); w_j = routed_scaling_factor s_j / sum of the
        chosen s; z = u W_in (hidden -> ``moe_latent_size``);
        y = (sum_j w_j relu(z W1_j)^2 W2_j) W_out + relu(u Ws1)^2 Ws2
        (two matrices an expert, no gate; router, shared expert and both latent
        projections at the model's width)
    logits = RMS(x; g_f) W_head                    (the head is untied)

**The shares.** The program holds ``n_routed_experts_held`` of every expert
layer's experts from ``expert_first`` on and rows 0 .. ``vocab_size_held`` - 1 of
the embedding and the head; the rest lie on the three other chips of its stage,
which this cut does not have. The reference does as the program does: every
expert of the HELD set runs on every token behind the weights' mask (dense,
dropless by construction), the part of the absent experts is LEFT OUT of the
sum (then through ``W_out``, which is linear and has no bias), and the logits are
over the held rows. Nothing stands in for what is absent.

**The one place where the reference rounds: what the router reads.** The choice
of 22 of 512 is a DISCRETE function of the router's input, and the configuration
states that input in bfloat16 (``server.config_json.dtype``: the program's
activations; its router's product is float32 at the highest precision, as here).
At 22 of 512 the 22nd and the 23rd score lie ~0.003 apart, so an input that
differs in its 9th bit picks another expert at nearly every token of a context,
and each such pick moves the logits by more than all the rounding of eleven
layers: with the router's input left in float32 the served text reads a mean
margin of 0.017-0.145 of a logit std of 0.99 over twelve seeds, with it rounded
to the served dtype 30 x less (``reference.tolerance_why`` has both columns). So
``dims["router_input_dtype"]`` (``main`` sets it to the served dtype; absent =
float32, as the tier-1 tests at float32 run it) rounds ``u`` for the ROUTER
alone: scores, weights, experts, shared expert, mixers, attention, norms,
residual stream and head stay float32.

Its sizes come from the configuration FILE (the model's own ``config.json``
names; the layers that run are ``hybrid_override_pattern[layer_first :
layer_first + layers]``); only the seeded weights come from the program. It
shares no code with ``bee2bee_tpu/models/core.py``.

What is compared: ``reference_falcon_h1.py``'s forking walk (served text ->
bytes -> the best reference logit among the tokens of the served byte must lie
within ``tolerance`` of the reference's maximum; every same-byte candidate
within the tolerance extends a context of its own), with ``reference_granite
.py``'s ROUTING rule: a bf16 rounding upstream can swap a token's k-th and
(k+1)-th expert, which moves that token's logits far more than rounding does.
So the compared position is ALSO computed with the k-th <-> (k+1)-th choice
swapped AT THAT POSITION in every expert layer whose gap (k-th minus (k+1)-th
of s + b), IN THE PASS THAT LEADS TO IT, is under ``near_tie``: a tree of passes
that forks at each such layer (at most ``MAX_PASSES`` leaves a position,
breadth first), and the position's margin is its best under any of them. At 22
of 512 the 22nd and the 23rd score lie ~0.003 apart on average (granite's 10th
and 11th logit of 72: ~0.05): ``near_tie`` is calibrated on the flips the chip
showed (the configuration file's ``reference.tolerance_why``). What decides
``correct`` is ``mean_margin``, the mean over the compared positions of the best
margin, against ``mean_margin_limit``; the walk's own verdict is ``walk_ok``.

``job["perturb"]`` (the builder's proof that the limit discriminates, never set
by ``run.py``), each ONE thing wrong: ``{"activation_dtype": "float8_e4m3fn"}``
(the residual stream rounded after the embedding and after every layer: the
nearest precision below bf16), ``{"router_dtype": "bfloat16"}`` (a bf16 ROUTER:
its operands and its logits rounded, where the configuration states a float32
product), ``{"activation": "relu"}`` (relu for relu^2, experts and
shared expert), ``{"activation": "gated"}`` (a gate added: silu(a) * a of the
one up product), ``{"drop": "shared_expert"}``, ``{"drop":
"routed_scaling_factor"}``, ``{"expert_first": n}`` (the held arrays taken as
experts n.. of the router's outputs), ``{"n_groups": 1}`` (every head reads
group 0's B and C, one norm over all channels), ``{"rope": true}`` (the
attention layer rotated, theta ``rope_theta``), ``{"w_out": "before_weighting"}``
(W_out applied to every expert's output before the weighted sum: the SAME
function, for W_out is linear and has no bias; kept to show its reading equals
the plain one's).
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

HEAD_BLOCK = 32768  # columns of the head a call
MAX_PASSES = 8  # leaves of a compared position's tree of routing passes
VARIANT_ROWS = 8  # contexts a pass of swapped routings computes at once
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
STACKS = {"mamba": "ssm", "attention": "attn", "moe": "moe"}
PERTURBATIONS = {"activation_dtype", "router_dtype", "activation", "drop", "expert_first",
                 "n_groups", "rope", "w_out"}


def layer_plan(dims: dict) -> list[tuple[str, int]]:
    """[(kind, the layer's slot among the layers of its kind)] for the layers
    that run: the stacked ``ssm`` / ``attn`` / ``moe`` arrays are as deep as
    their kinds, in layer order."""
    first = int(dims.get("layer_first") or 0)
    pattern = dims["hybrid_override_pattern"][first:first + int(dims["layers"])]
    seen = {"mamba": 0, "attention": 0, "moe": 0}
    plan = []
    for c in pattern:
        plan.append((KINDS[c], seen[KINDS[c]]))
        seen[KINDS[c]] += 1
    return plan


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, layer, head).
    ``layer[kind](x [R, T, D], norm scales [L, D], the kind's stack, index,
    slot, swap [R], at)`` is layer ``index`` (its branch at ``slot`` of the
    stack); returns (the layer's output, gap [R, T]: the k-th minus the
    (k+1)-th of s + b; +inf for a layer that routes nothing). ``swap`` takes the
    (k+1)-th expert in the k-th's place at position ``at``."""
    import jax
    import jax.numpy as jnp

    perturb = perturb or {}
    unknown = set(perturb) - PERTURBATIONS
    if unknown:
        raise KeyError(f"unknown perturbation {sorted(unknown)}")
    drop = perturb.get("drop")
    if drop not in (None, "shared_expert", "routed_scaling_factor"):
        raise KeyError(f"unknown drop {drop!r}")
    activation = perturb.get("activation", "relu2")
    if activation not in ("relu2", "relu", "gated"):
        raise KeyError(f"unknown activation {activation!r}")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    router_dtype = jnp.dtype(perturb.get("router_dtype", "float32"))
    router_in = jnp.dtype(dims.get("router_input_dtype") or "float32")
    f32 = jnp.float32
    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps = dims["layer_norm_epsilon"]
    Hs, P, N = dims["mamba_num_heads"], dims["mamba_head_dim"], dims["ssm_state_size"]
    G, K = dims["n_groups"], dims["conv_kernel"]
    one_group = int(perturb.get("n_groups", G)) == 1 and G > 1
    inner = Hs * P
    E, k = dims["n_routed_experts"], dims["num_experts_per_tok"]
    held = int(dims.get("n_routed_experts_held") or E)
    first = int(perturb.get("expert_first", dims.get("expert_first") or 0))
    scale = 1.0 if drop == "routed_scaling_factor" else float(dims["routed_scaling_factor"])
    theta = float(dims.get("rope_theta", 10000.0))

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

    def fn(a):  # an expert's (and the shared expert's) activation of its up product
        if activation == "relu":
            return jax.nn.relu(a)
        if activation == "gated":
            return jax.nn.silu(a) * a
        return jnp.square(jax.nn.relu(a))

    def rotate(x):  # [R, T, heads, hd], the whole head, halves as a block
        T = x.shape[1]
        inv = theta ** (-jnp.arange(0, hd, 2, dtype=f32) / hd)
        ang = jnp.arange(T, dtype=f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    def attention(u, p):
        R, T, _ = u.shape
        q = (u @ p["wq"]).reshape(R, T, H, hd)
        kk = (u @ p["wk"]).reshape(R, T, Hkv, hd)
        if perturb.get("rope"):
            q, kk = rotate(q), rotate(kk)
        kk = jnp.repeat(kk, H // Hkv, axis=2)
        v = jnp.repeat((u @ p["wv"]).reshape(R, T, Hkv, hd), H // Hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, kk) / math.sqrt(hd)  # no rotation
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        scores = jnp.where(causal[None, None], scores, -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(R, T, H * hd) @ p["wo"]

    def mixer(u, p):
        R, T, _ = u.shape
        proj = u @ p["w_in"]
        z, xbc, dt = proj[..., :inner], proj[..., inner:-Hs], proj[..., -Hs:]
        # causal depthwise conv: tap K-1 multiplies the current token
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + T] * p["conv_w"][:, j] for j in range(K)) + p["conv_b"]
        conv = jax.nn.silu(conv)
        x = conv[..., :inner].reshape(R, T, Hs, P)
        # B and C of a group, repeated over the group's heads
        Bg = conv[..., inner:inner + G * N].reshape(R, T, G, N)
        Cg = conv[..., inner + G * N:].reshape(R, T, G, N)
        if one_group:  # (perturbed: every head reads group 0's)
            Bg, Cg = (jnp.repeat(a[:, :, :1], G, axis=2) for a in (Bg, Cg))
        Bh, Ch = (jnp.repeat(a, Hs // G, axis=2) for a in (Bg, Cg))
        dt = jax.nn.softplus(dt + p["dt_bias"])  # [R, T, Hs]
        A = -jnp.exp(p["A_log"])  # [Hs]

        def token(h, inp):  # h [R, Hs, P, N]: one token of the recurrence
            x_t, dt_t, b_t, c_t = inp
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return h, jnp.einsum("rhpn,rhn->rhp", h, c_t) + p["D"][:, None] * x_t

        _, y = jax.lax.scan(
            token, jnp.zeros((R, Hs, P, N), f32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
        y = jnp.moveaxis(y, 0, 1).reshape(R, T, inner) * jax.nn.silu(z)
        groups = 1 if one_group else G
        y = y.reshape(R, T, groups, inner // groups)  # gate first, then a norm a group
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return (y.reshape(R, T, inner) * p["norm"]) @ p["w_out"]

    def experts(h, moe, slot, swap, at):
        """(routed + shared [R, T, D], gap [R, T]) of the normed ``h``."""
        R, T, _ = h.shape
        w_r = jax.lax.dynamic_index_in_dim(moe["router"], slot, keepdims=False).astype(f32)
        # (the router READS its input at the served activation dtype: the
        # module's docstring; a perturbed router also rounds its logits)
        logits = rounded(rounded(h, router_in), router_dtype) @ rounded(w_r, router_dtype)
        s = jax.nn.sigmoid(rounded(logits, router_dtype))
        bias = jax.lax.dynamic_index_in_dim(moe["router_bias"], slot, keepdims=False)
        picked, idx = jax.lax.top_k(s + bias, k + 1)  # [R, T, k + 1], largest first
        gap = picked[..., k - 1] - picked[..., k]
        swapped = (swap[:, None] & (jnp.arange(T)[None, :] == at))[..., None]
        last = jnp.arange(k)[None, None, :] == k - 1
        # the k-th slot takes the (k+1)-th choice where swapped
        chosen = jnp.where(swapped & last, idx[..., k:], idx[..., :k])
        sc = jnp.take_along_axis(s, chosen, axis=-1)  # the scores WITHOUT the bias
        w = sc / (jnp.sum(sc, axis=-1, keepdims=True) + 1e-20) * scale
        # a held expert's weight a token: [R, T, held]
        local = chosen - first
        w_held = jnp.sum(
            w[..., None] * (local[..., None] == jnp.arange(held)), axis=-2)
        mp = one({n: moe[n] for n in ("latent_in", "latent_out")}, slot)
        z = h @ mp["latent_in"]  # [R, T, Dl]
        each = perturb.get("w_out") == "before_weighting"

        def expert(acc, e):
            pick = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
                jax.lax.dynamic_index_in_dim(a, slot, keepdims=False), e,
                keepdims=False).astype(f32)
            y = fn(z @ pick(moe["w_up"])) @ pick(moe["w_down"])
            if each:
                y = y @ mp["latent_out"]
            return acc + y * jax.lax.dynamic_index_in_dim(w_held, e, axis=2), None

        out, _ = jax.lax.scan(
            expert, jnp.zeros_like(h if each else z), jnp.arange(held))
        if not each:
            out = out @ mp["latent_out"]
        if drop != "shared_expert":
            sp = one(moe["shared"], slot)
            out = out + fn(h @ sp["w_up"]) @ sp["w_down"]
        return out, gap

    def make_layer(kind: str):
        @jax.jit
        def layer(x, norms, stack, index, slot, swap, at):
            u = rms(x, jax.lax.dynamic_index_in_dim(norms, index, keepdims=False).astype(f32))
            if kind == "moe":
                out, gap = experts(u, stack, slot, swap, at)
            else:
                out = (mixer if kind == "mamba" else attention)(u, one(stack, slot))
                gap = jnp.full(x.shape[:2], jnp.inf, f32)
            return act(x + out), gap

        return layer

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed, tokens, axis=0).astype(f32))

    @jax.jit
    def head_block(h, lm_head, start):  # h already normed; a block of the head's columns
        width = min(HEAD_BLOCK, lm_head.shape[1])
        return h @ jax.lax.dynamic_slice_in_dim(lm_head, start, width, axis=1).astype(f32)

    def head(x, final_scale, lm_head):
        """Logits [R, V] of x [R, D], the head in blocks of HEAD_BLOCK columns."""
        import numpy as np

        h = rms(x, final_scale.astype(f32))
        V = lm_head.shape[1]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)  # the last block overlaps its neighbour
        out = np.empty((x.shape[0], V), np.float32)
        for s in starts:
            out[:, s:s + width] = np.asarray(head_block(h, lm_head, np.int32(s)))
        return out

    return embed, {kind: make_layer(kind) for kind in STACKS}, head


def forward_logits(dims: dict, params: dict, tokens, position: int, swaps=None,
                   perturb: dict | None = None, pieces=None):
    """Reference logits [R, V] at ``position`` of ``tokens`` [R, T] and the
    routing gaps there [layers, R] (+inf for a layer that routes nothing).
    ``swaps`` [layers, R] bool (default none) are the (layer, row)s that take
    the (k+1)-th choice AT ``position``."""
    import jax
    import numpy as np

    embed, layer, head = pieces or build_forward(dims, perturb)
    plan = layer_plan(dims)
    R, _ = tokens.shape
    if swaps is None:
        swaps = np.zeros((len(plan), R), bool)
    layers = params["layers"]
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        for i, (kind, slot) in enumerate(plan):
            x, gap = layer[kind](x, layers["ln1"]["scale"], layers[STACKS[kind]], np.int32(i),
                                 np.int32(slot), np.asarray(swaps[i]), np.int32(position))
            gaps.append(np.asarray(gap[:, position]))
        logits = head(x[:, position], params["final_norm"]["scale"], params["lm_head"])
    return logits, np.stack(gaps)


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    chars = {v: c for c, v in KINDS.items()}
    return {
        "hidden_size": mcfg.d_model, "layers": mcfg.n_layers,
        "pattern_run": "".join(chars[t] for t in mcfg.layer_types),
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "head_dim": mcfg.head_dim,
        "moe_intermediate_size": mcfg.expert_ff, "moe_latent_size": mcfg.moe_latent,
        "moe_shared_expert_intermediate_size": mcfg.shared_ff,
        "vocab_size_held": mcfg.vocab_size, "vocab_size": mcfg.vocab_published or mcfg.vocab_size,
        "layer_norm_epsilon": mcfg.norm_eps,
        "mamba_num_heads": mcfg.ssm_heads, "mamba_head_dim": mcfg.ssm_head_dim,
        "ssm_state_size": mcfg.ssm_state, "n_groups": mcfg.ssm_groups,
        "conv_kernel": mcfg.ssm_conv, "chunk_size": mcfg.ssm_chunk,
        "n_routed_experts": mcfg.n_experts, "num_experts_per_tok": mcfg.n_experts_per_tok,
        "n_routed_experts_held": mcfg.experts_held, "expert_first": mcfg.expert_first,
        "routed_scaling_factor": mcfg.moe_scale, "tie_word_embeddings": mcfg.tie_embeddings,
    }


def dims_of_file(conf: dict) -> dict:
    """The configuration file's numbers beside the slice of the pattern that
    runs and the rows of the vocabulary held (what dims_of_preset names)."""
    first = int(conf.get("layer_first") or 0)
    return dict(conf, pattern_run=conf["hybrid_override_pattern"][first:first + conf["layers"]],
                vocab_size_held=conf.get("vocab_size_held") or conf["vocab_size"])


def routed_logits_at(dims, params, tokens, owner, served, P: int, near_tie: float, pieces,
                     seen: dict):
    """``at(step)`` -> (plain logits [R, V], {row: [logits [V] under each
    ADMISSIBLE routing of the compared position]}). A routing is a set of
    expert layers swapped k-th <-> (k+1)-th there; it is admissible where every
    swapped layer's gap, in the pass that leads to it (a swap in an earlier
    layer moves the later layers' scores by far more than rounding does), is
    under ``near_tie``. The tree is walked breadth first, at most MAX_PASSES
    routings a row, VARIANT_ROWS contexts a pass. ``seen`` collects the
    smallest gap met, the near ties of the plain pass and the passes run."""
    import numpy as np

    R = tokens.shape[0]

    def at(step: int):
        pos = P - 1 + step
        live = np.array([owner[r] >= 0 and len(served[owner[r]]) > step for r in range(R)])
        base, gaps = forward_logits(dims, params, tokens, pos, pieces=pieces)
        L = gaps.shape[0]
        near = (gaps < near_tie) & live[None, :]
        seen["near"].append(near.any(axis=0))
        if live.any():
            seen["min_gap"] = min(seen["min_gap"], float(gaps[:, live].min()))
        variants: dict[int, list] = {}
        budget = {int(r): MAX_PASSES for r in np.flatnonzero(near.any(axis=0))}
        # (row, the layers swapped): children fork at a LATER layer's near tie
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
            logits, g = forward_logits(dims, params, tokens[rows], pos, swaps, pieces=pieces)
            seen["passes"] += 1
            for j, (r, layers) in enumerate(jobs):
                variants.setdefault(r, []).append(logits[j])
                rest += [(r, layers + (int(lyr),)) for lyr in range(layers[-1] + 1, L)
                         if g[lyr, j] < near_tie]
            queue = rest
        seen["cut"] += sum(b == 0 for b in budget.values())
        return base, variants

    return at


def compare(job: dict, conf: dict, params: dict) -> dict:
    """The comparison on ``job``'s served text with the program's seeded
    ``params``: the result line's fields (``ok`` decides ``correct``)."""
    import numpy as np

    dims = conf
    pieces = build_forward(dims, job.get("perturb"))
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
    routed = routed_logits_at(dims, params, tokens, owner, served, P, near_tie, pieces, seen)
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
    # What decides: the MEAN over the compared positions (reference_joyai.py:
    # a routing swap at an EARLIER token, which no rule here follows, throws
    # one position far out; a fault of the model moves every position).
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
        "near_tie_rescued": int(rescued), "routing_passes": seen["passes"],
        "routing_trees_cut": seen["cut"],
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
    srv = conf["server"]
    devs = jax.devices()
    if devs[0].platform != job["platform"] or len(devs) < conf["chips"]:
        print(json.dumps({"ok": False, "error": f"jax found {len(devs)} x "
                          f"{devs[0].platform}, need {conf['chips']} x {job['platform']}"}))
        return 1

    # the program's seeded weights, made the way the server makes them
    mcfg = get_config(srv["model"])
    want, have = dims_of_preset(mcfg), dims_of_file(conf)
    differs = {k: (v, have.get(k)) for k, v in want.items()
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
    res = compare(job, dict(dims_of_file(conf), router_input_dtype=str(dtype)), params)
    print(json.dumps({
        **res,
        "router_input_dtype": str(dtype),
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
    }))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
