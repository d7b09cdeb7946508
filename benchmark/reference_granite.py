"""The plain reference for ``granitemoehybrid`` configurations and the comparison
that decides ``correct`` in their cells. Same job file in, same result line out
as ``reference.py``; a configuration file names it under ``reference.module``.

The forward pass is granite-4.0-h's, written straight from its published
``config.json`` (and checked against ``transformers``'
``GraniteMoeHybridForCausalLM`` at tiny size, ``tests/test_granite.py``) in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernel, no cache, no chunking, no batching tricks; a Python loop over the
layers by ``layer_types``; one expert at a time; the head in column blocks.
With ``RMS`` the RMSNorm (eps 1e-5) and ``m = residual_multiplier``:

    x = E[token] * embedding_multiplier
    every layer:  x' = x + m * mixer(RMS(x; g1));  h = RMS(x'; g2)
                  x'' = x' + m * (routed(h) + shared(h))
    "mamba" layer (Mamba-2):  [z | xBC | dt] = W_in u; a causal depthwise conv
        of width K with bias over xBC, SiLU; dt = softplus(dt + dt_bias);
        A = -exp(A_log); THE TOKEN-BY-TOKEN RECURRENCE h_t = exp(dt_t A) h_{t-1}
        + dt_t x_t (outer) B_t, y_t = h_t C_t + D x_t (B and C shared by the
        heads of a group), a scan of T steps, state float32; y * SiLU(z), an
        RMS norm over each group's channels, the learned scale, W_out
    "attention" layer:  GQA, NO rotation anywhere, scores * attention_multiplier,
        causal, full
    routed:  z = W_r h (float32); the num_experts_per_tok largest z are chosen,
        w = softmax over those; sum_e w_e W_down_e(SiLU(a_e) * b_e),
        [a_e | b_e] = W_in_e h
    shared:  one SwiGLU MLP shared_intermediate_size wide, added unweighted
    logits = (E RMS(x; g_f)) / logits_scaling          (the head is tied)

**The expert share.** The program holds ``num_local_experts_held`` of every
layer's ``num_local_experts`` experts from ``expert_first`` on; the rest lie
on a partner chip that this cut does not have. The reference does as the
program does: every expert of the HELD set runs on every token behind the
weights' mask (dense, dropless by construction), and the part of the absent
experts is LEFT OUT of the sum. Nothing stands in for it.

Departures from ``transformers``' code, each without effect on the logits: the
mixer is the recurrence, not its chunked form; the experts run dense behind a
mask, not gathered. Its sizes come from the configuration FILE (the model's own
``config.json`` names; the depth as run is ``layers``, the pattern the first
``layers`` entries of ``layer_types``); only the seeded weights come from the
program. It shares no code with ``bee2bee_tpu/models/core.py``.

What is compared: ``reference_falcon_h1.py``'s forking walk (served text ->
bytes -> the best reference logit among the tokens of the served byte must lie
within ``tolerance`` of the reference's maximum; every same-byte candidate
within the tolerance extends a context of its own), with
``reference_joyai.py``'s ROUTING rule: a bf16 rounding upstream can swap a
token's k-th and (k+1)-th expert, which moves that token's logits far more than
rounding does. So the compared position is ALSO computed with the k-th <->
(k+1)-th choice swapped AT THAT POSITION in every layer whose gap (k-th minus
(k+1)-th router logit), IN THE PASS THAT LEADS TO IT, is under ``near_tie``: a
tree of passes that forks at each such layer (at most ``MAX_PASSES`` leaves a
position, breadth first), and the position's margin is its best under any of
them. A swap that takes an expert held ELSEWHERE in or out changes the held
part like any other. What decides ``correct`` is ``mean_margin``, the mean over
the compared positions of the best margin, against ``mean_margin_limit``
(``tolerance_why`` in the configuration file has the readings); the walk's own
verdict is reported as ``walk_ok``.

``job["perturb"]`` (the builder's proof that the limit discriminates, never
set by ``run.py``), each ONE thing wrong: ``{"activation_dtype":
"float8_e4m3fn"}`` (the residual stream rounded after the embedding and after
every layer: the nearest precision below bf16), ``{"drop":
"residual_multiplier"}``, ``{"drop": "shared_expert"}``, ``{"expert_first": n}``
(the held arrays taken as experts n.. of the router's outputs),
``{"attention_at": i}`` (the one attention layer of the first period moved to
index i), ``{"state_dtype": "bfloat16"}`` (the recurrent state rounded after
every token: served text cannot fail it, PERF.md section 7).
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

HEAD_BLOCK = 25088  # columns of the head a call (100,352 = 4 blocks)
MAX_PASSES = 8  # leaves of a compared position's tree of routing passes
VARIANT_ROWS = 8  # contexts a pass of swapped routings computes at once
PERTURBATIONS = {"activation_dtype", "drop", "expert_first", "attention_at", "state_dtype"}


def layer_plan(dims: dict, perturb: dict | None = None) -> list[tuple[str, int]]:
    """[(kind, the layer's slot among the layers of its kind)] for the
    ``layers`` that run: the stacked ``ssm`` / ``attn`` arrays are as deep as
    their kinds, in layer order."""
    types = list(dims["layer_types"][:dims["layers"]])
    at = (perturb or {}).get("attention_at")
    if at is not None:  # the first attention layer moved to index ``at``
        types.insert(int(at), types.pop(types.index("attention")))
    seen = {"mamba": 0, "attention": 0}
    plan = []
    for t in types:
        plan.append((t, seen[t]))
        seen[t] += 1
    return plan


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, layer, head).
    ``layer[kind](x [R, T, D], common, mixer, index, slot, swap [R], at)`` is
    layer ``index`` (its mixer at ``slot`` of ``mixer``'s stack); returns (the
    layer's output, gap [R, T]: the k-th minus the (k+1)-th router logit).
    ``swap`` takes the (k+1)-th expert in the k-th's place at position ``at``."""
    import jax
    import jax.numpy as jnp

    perturb = perturb or {}
    unknown = set(perturb) - PERTURBATIONS
    if unknown:
        raise KeyError(f"unknown perturbation {sorted(unknown)}")
    drop = perturb.get("drop")
    if drop not in (None, "residual_multiplier", "shared_expert"):
        raise KeyError(f"unknown drop {drop!r}")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    state_dtype = jnp.dtype(perturb.get("state_dtype", "float32"))
    f32 = jnp.float32
    D = dims["hidden_size"]
    H, Hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims.get("head_dim") or D // H
    eps = dims["rms_norm_eps"]
    Hs, P, N = dims["mamba_n_heads"], dims["mamba_d_head"], dims["mamba_d_state"]
    G, K = dims["mamba_n_groups"], dims["mamba_d_conv"]
    inner = Hs * P
    E, k = dims["num_local_experts"], dims["num_experts_per_tok"]
    held = int(dims.get("num_local_experts_held") or E)
    first = int(perturb.get("expert_first", dims.get("expert_first") or 0))
    rm = 1.0 if drop == "residual_multiplier" else float(dims["residual_multiplier"])
    attn_mult = float(dims["attention_multiplier"])
    embed_mult = float(dims["embedding_multiplier"])
    logits_scaling = float(dims["logits_scaling"])

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

    def attention(u, p):
        R, T, _ = u.shape
        q = (u @ p["wq"]).reshape(R, T, H, hd)
        kk = jnp.repeat((u @ p["wk"]).reshape(R, T, Hkv, hd), H // Hkv, axis=2)
        v = jnp.repeat((u @ p["wv"]).reshape(R, T, Hkv, hd), H // Hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, kk) * attn_mult  # no rotation
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
        Bh = jnp.repeat(conv[..., inner:inner + G * N].reshape(R, T, G, N), Hs // G, axis=2)
        Ch = jnp.repeat(conv[..., inner + G * N:].reshape(R, T, G, N), Hs // G, axis=2)
        dt = jax.nn.softplus(dt + p["dt_bias"])  # [R, T, Hs]
        A = -jnp.exp(p["A_log"])  # [Hs]

        def token(h, inp):  # h [R, Hs, P, N]: one token of the recurrence
            x_t, dt_t, b_t, c_t = inp
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            h = rounded(h, state_dtype)
            return h, jnp.einsum("rhpn,rhn->rhp", h, c_t) + p["D"][:, None] * x_t

        _, y = jax.lax.scan(
            token, jnp.zeros((R, Hs, P, N), f32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
        y = jnp.moveaxis(y, 0, 1).reshape(R, T, inner) * jax.nn.silu(z)
        y = y.reshape(R, T, G, inner // G)  # gate first, then a norm a group
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return (y.reshape(R, T, inner) * p["norm"]) @ p["w_out"]

    def experts(h, moe, index, swap, at):
        """(routed + shared [R, T, D], gap [R, T]) of the normed ``h``."""
        R, T, _ = h.shape
        z = h @ jax.lax.dynamic_index_in_dim(moe["router"], index, keepdims=False).astype(f32)
        zs, idx = jax.lax.top_k(z, k + 1)  # [R, T, k + 1], largest first
        gap = zs[..., k - 1] - zs[..., k]
        swapped = (swap[:, None] & (jnp.arange(T)[None, :] == at))[..., None]
        last = jnp.arange(k + 1)[None, None, :] == k - 1
        # the k-th slot takes the (k+1)-th choice where swapped
        chosen = jnp.where(swapped & last[..., :k], idx[..., k:], idx[..., :k])
        zc = jnp.where(swapped & last[..., :k], zs[..., k:], zs[..., :k])
        w = jax.nn.softmax(zc, axis=-1)  # over the chosen k alone
        # a held expert's weight a token: [R, T, held]
        local = chosen - first
        w_held = jnp.sum(
            w[..., None] * (local[..., None] == jnp.arange(held)), axis=-2)

        def expert(acc, e):
            pick = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
                jax.lax.dynamic_index_in_dim(a, index, keepdims=False), e,
                keepdims=False).astype(f32)
            y = (jax.nn.silu(h @ pick(moe["w_gate"])) * (h @ pick(moe["w_up"]))
                 ) @ pick(moe["w_down"])
            return acc + y * jax.lax.dynamic_index_in_dim(w_held, e, axis=2), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
        if drop != "shared_expert":
            sp = one(moe["shared"], index)
            out = out + (jax.nn.silu(h @ sp["w_gate"]) * (h @ sp["w_up"])) @ sp["w_down"]
        return out, gap

    def make_layer(kind: str):
        name, mix = ("ssm", mixer) if kind == "mamba" else ("attn", attention)

        @jax.jit
        def layer(x, common, stack, index, slot, swap, at):
            x1 = x + rm * mix(rms(x, one(common["ln1"], index)["scale"]), one(stack, slot))
            out, gap = experts(rms(x1, one(common["ln2"], index)["scale"]),
                               common["moe"], index, swap, at)
            return act(x1 + rm * out), gap

        return name, layer

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed, tokens, axis=0).astype(f32) * embed_mult)

    @jax.jit
    def head_block(h, tok_embed, start):  # h already normed; a block of the tied head
        width = min(HEAD_BLOCK, tok_embed.shape[0])
        w = jax.lax.dynamic_slice_in_dim(tok_embed, start, width, axis=0).astype(f32)
        return (h @ w.T) / logits_scaling

    def head(x, final_scale, tok_embed):
        """Logits [R, V] of x [R, D], the tied head in blocks of HEAD_BLOCK rows."""
        import numpy as np

        h = rms(x, final_scale.astype(f32))
        V = tok_embed.shape[0]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)  # the last block overlaps its neighbour
        out = np.empty((x.shape[0], V), np.float32)
        for s in starts:
            out[:, s:s + width] = np.asarray(head_block(h, tok_embed, np.int32(s)))
        return out

    return embed, dict(make_layer(kind) for kind in ("mamba", "attention")), head


def forward_logits(dims: dict, params: dict, tokens, position: int, swaps=None,
                   perturb: dict | None = None, pieces=None):
    """Reference logits [R, V] at ``position`` of ``tokens`` [R, T] and the
    routing gaps there [layers, R]. ``swaps`` [layers, R] bool (default none)
    are the (layer, row)s that take the (k+1)-th choice AT ``position``."""
    import jax
    import numpy as np

    embed, layer, head = pieces or build_forward(dims, perturb)
    plan = layer_plan(dims, perturb)
    R, _ = tokens.shape
    if swaps is None:
        swaps = np.zeros((len(plan), R), bool)
    layers = params["layers"]
    common = {n: a for n, a in layers.items() if n not in ("ssm", "attn")}
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], tokens)
        for i, (kind, slot) in enumerate(plan):
            name = "ssm" if kind == "mamba" else "attn"
            x, gap = layer[name](x, common, layers[name], np.int32(i), np.int32(slot),
                                 np.asarray(swaps[i]), np.int32(position))
            gaps.append(np.asarray(gap[:, position]))
        logits = head(x[:, position], params["final_norm"]["scale"], params["tok_embed"])
    return logits, np.stack(gaps)


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    return {
        "hidden_size": mcfg.d_model, "layers": mcfg.n_layers,
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "intermediate_size": mcfg.d_ff, "shared_intermediate_size": mcfg.shared_ff,
        "vocab_size": mcfg.vocab_size, "rms_norm_eps": mcfg.norm_eps,
        "mamba_n_heads": mcfg.ssm_heads, "mamba_d_head": mcfg.ssm_head_dim,
        "mamba_d_state": mcfg.ssm_state, "mamba_n_groups": mcfg.ssm_groups,
        "mamba_d_conv": mcfg.ssm_conv, "mamba_chunk_size": mcfg.ssm_chunk,
        "num_local_experts": mcfg.n_experts, "num_experts_per_tok": mcfg.n_experts_per_tok,
        "num_local_experts_held": mcfg.experts_held, "expert_first": mcfg.expert_first,
        "embedding_multiplier": mcfg.embedding_multiplier,
        "logits_scaling": 1.0 / mcfg.lm_head_multiplier,
        "residual_multiplier": mcfg.residual_multiplier,
        "attention_multiplier": 1.0 / math.sqrt(mcfg.attn_scale or mcfg.head_dim),
        "tie_word_embeddings": mcfg.tie_embeddings,
    }


def routed_logits_at(dims, params, tokens, owner, served, P: int, near_tie: float, pieces,
                     seen: dict):
    """``at(step)`` -> (plain logits [R, V], {row: [logits [V] under each
    ADMISSIBLE routing of the compared position]}). A routing is a set of
    layers swapped k-th <-> (k+1)-th there; it is admissible where every
    swapped layer's gap, in the pass that leads to it (a swap in an earlier
    layer moves the later layers' logits by far more than rounding does), is
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
    if job.get("perturb"):  # the plan follows the perturbation too
        dims = dict(dims, layer_types=[kind for kind, _ in layer_plan(dims, job["perturb"])])
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
    want = dict(dims_of_preset(mcfg), layer_types=list(mcfg.layer_types))
    have = dict(conf, layer_types=list(conf["layer_types"][:conf["layers"]]))
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
    res = compare(job, conf, params)
    print(json.dumps({
        **res,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
    }))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
