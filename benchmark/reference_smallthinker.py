"""The plain reference for ``smallthinker`` configurations and the comparison
that decides ``correct`` in their cells. Same job file in, same result line out
as ``reference.py``; a configuration file names it under ``reference.module``.

The forward pass is SmallThinker-21BA3B-Instruct's, written straight from its
published ``config.json`` and description in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no paged pool, no
batching; one context at a time, the queries in blocks of ``Q_BLOCK``
positions against every key (so a 6k-token context's scores fit beside the
program's 7.9 GB of weights), the experts and the head in blocks. With ``RMS``
the RMSNorm (eps 1e-6), layer ``l`` (``window`` where
``sliding_window_layout[l]`` is 1, else full; ``rope_layout`` is the same list):

    a = RMS(x; g1)                        # also the ROUTER's input
    q, k, v = a Wq [28 x 128], a Wk [4 x 128], a Wv [4 x 128]      (no bias)
    window layer: q, k = RoPE(q, k; position, theta, the whole head, halves
                  rotated together);  full layer: NO rotation at all
    attention: causal, 1 / sqrt(128), 7 query heads a KV head; window layer:
               key s seen by query t iff t - window < s <= t
    h = x + attn Wo;  b = RMS(h; g2)
    z = a Wr (float32);  the 6 largest z are chosen, w = softmax over those 6
    out = h + sum_e w_e (relu(b Wgate_e) * (b Wup_e)) Wdown_e

then the final ``RMS`` and an untied head. EVERY expert runs on every token and
the weights mask the sum (the dense form: dropless by construction). Its sizes
come from the configuration FILE (the model's own ``config.json`` names; the
depth as run is ``layers``); only the seeded weights come from the program. It
shares no code with ``bee2bee_tpu/models/core.py``'s attention or expert layer.

**How a 6k-token probe is walked.** A context's prompt runs ONCE, layer after
layer, and every layer's INPUT at every position is kept (float32, on the
host). The position compared at a step is then computed from those: its keys
and values from the kept inputs of the positions before it, nothing else
carried over (attention is causal, so what lies before a position does not
depend on it). That is ``reference_joyai.swapped_logits``' scheme, used for
the plain pass too: recomputing 6,144 positions through 64 experts for each of
8 steps would take the run's time limit several times over.

What is compared: ``reference_falcon_h1.py``'s forking walk (served text ->
bytes -> the best reference logit among the tokens of the served byte must lie
within ``tolerance`` of the reference's maximum; every same-byte candidate
within the tolerance extends a context of its own), with
``reference_joyai.py``'s ROUTING rule: a bf16 rounding upstream can swap a
token's 6th and 7th expert, so the compared position is ALSO computed with the
6th <-> 7th choice swapped in every layer whose gap (6th minus 7th logit), in
the pass that leads to it, is under ``near_tie``: a tree of passes that forks
at each such layer (at most ``MAX_PASSES`` leaves), and the position's margin
is its best under any of them. What decides ``correct`` is ``mean_margin``, the
mean over the compared positions of the best margin, against
``mean_margin_limit`` (``tolerance_why`` in the configuration file has the
readings); the walk's own verdict is reported as ``walk_ok``.

``job["perturb"]`` (the builder's proof that the limit discriminates, never
set by ``run.py``), each ONE thing wrong: ``{"no_window": true}`` (window layers
attend fully), ``{"rope_full_layers": true}`` (full layers rotated too),
``{"router_input": "ffn_norm"}`` (the router fed ``b``), ``{"activation":
"silu"}``, ``{"router_weights": "sigmoid"}`` (sigmoid of the chosen logits,
divided by their sum), ``{"activation_dtype": "float8_e4m3fn"}`` (the residual
stream rounded after the embedding and after every layer: the nearest
precision below bf16).
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

HEAD_BLOCK = 37984  # columns of the head a call (151,936 = 4 blocks)
EXPERT_BLOCK = 8  # experts a step of the dense expert sum
Q_BLOCK = 256  # query positions a call of the prompt pass
MAX_PASSES = 32  # leaves of a compared position's tree of routing passes


def build_forward(dims: dict, perturb: dict | None = None):
    """jit-compiled pieces of the plain forward pass: (embed, layer, head).
    ``layer(x_all [T, D], x_q [Q, D], q_start, layers, index, swap [Q])`` is
    layer ``index`` at the positions [q_start, q_start + Q): ``x_all`` holds
    the layer's input at every position (rows past the queries are never
    seen), ``x_q`` the queries' own (it replaces those rows of ``x_all``).
    Returns (the layer's output [Q, D], gap [Q]: the 6th minus the 7th router
    logit); ``swap`` takes the 7th expert in the 6th's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    perturb = perturb or {}
    unknown = set(perturb) - {"no_window", "rope_full_layers", "router_input", "activation",
                             "router_weights", "activation_dtype"}
    if unknown:
        raise KeyError(f"unknown perturbation {sorted(unknown)}")
    act_dtype = jnp.dtype(perturb.get("activation_dtype", "float32"))
    H, Hkv, hd = dims["num_attention_heads"], dims["num_key_value_heads"], dims["head_dim"]
    E, k = dims["moe_num_primary_experts"], dims["moe_num_active_primary_experts"]
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])
    window = int(dims["sliding_window_size"])
    layout = jnp.asarray(dims["sliding_window_layout"][:dims["layers"]], jnp.int32)
    gate_act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[perturb.get("activation", "relu")]
    Eb = min(EXPERT_BLOCK, E)
    assert E % Eb == 0, (E, Eb)
    f32 = jnp.float32

    def act(x):
        """The residual stream at the perturbed precision, still float32
        (``lax.reduce_precision``: the TPU compiler elides a convert pair)."""
        if act_dtype == jnp.float32:
            return x
        info = jnp.finfo(act_dtype)
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def rope(x, pos):  # x [T, heads, hd]; the (first, second) halves rotate together
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
        ang = pos[:, None].astype(f32) * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def one(tree, index):
        """One layer of the stacked [L, ...] arrays, upcast to float32."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False).astype(f32), tree)

    @jax.jit
    def embed(tok_embed, tokens):
        return act(jnp.take(tok_embed, tokens, axis=0).astype(f32))

    @jax.jit
    def layer(x_all, x_q, q_start, layers, index, swap):
        T, Q = x_all.shape[0], x_q.shape[0]
        lp = one({n: v for n, v in layers.items() if n != "moe"}, index)
        moe = layers["moe"]
        windowed = layout[index] > 0
        x_all = jax.lax.dynamic_update_slice(x_all, x_q, (q_start, 0))
        pos, qpos = jnp.arange(T), q_start + jnp.arange(Q)
        a_all, a = rms(x_all, lp["ln1"]["scale"]), rms(x_q, lp["ln1"]["scale"])
        q = (a @ lp["attn"]["wq"]).reshape(Q, H, hd)
        key = (a_all @ lp["attn"]["wk"]).reshape(T, Hkv, hd)
        v = (a_all @ lp["attn"]["wv"]).reshape(T, Hkv, hd)
        rotate = windowed | bool(perturb.get("rope_full_layers"))
        q = jnp.where(rotate, rope(q, qpos), q)
        key = jnp.where(rotate, rope(key, pos), key)
        s = jnp.einsum("qgjd,tgd->gjqt", q.reshape(Q, Hkv, H // Hkv, hd), key) / math.sqrt(hd)
        seen = pos[None, :] <= qpos[:, None]
        if not perturb.get("no_window"):
            seen &= ~windowed | (pos[None, :] > qpos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("gjqt,tgd->qgjd", p, v).reshape(Q, H * hd)
        h = x_q + o @ lp["attn"]["wo"]
        b = rms(h, lp["ln2"]["scale"])
        z = (b if perturb.get("router_input") == "ffn_norm" else a) @ one(moe["router"], index)
        topv, topi = jax.lax.top_k(z, k + 1)
        gap = topv[:, k - 1] - topv[:, k]
        chosen = jnp.where(swap[:, None],
                           jnp.concatenate([topi[:, :k - 1], topi[:, k:]], axis=-1), topi[:, :k])
        zc = jnp.take_along_axis(z, chosen, axis=-1)
        if perturb.get("router_weights") == "sigmoid":
            w = jax.nn.sigmoid(zc)
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        else:
            w = jax.nn.softmax(zc, axis=-1)
        dense_w = jnp.sum(jax.nn.one_hot(chosen, E, dtype=f32) * w[..., None], axis=-2)  # [Q, E]

        def expert_block(i, y):
            def cut(m):  # [L, E, a, b] -> [Eb, a, b] float32
                return jax.lax.dynamic_slice(
                    m, (index, i * Eb, 0, 0), (1, Eb) + m.shape[2:])[0].astype(f32)

            g = gate_act(jnp.einsum("qd,edf->qef", b, cut(moe["w_gate"])))
            u = jnp.einsum("qd,edf->qef", b, cut(moe["w_up"]))
            out = jnp.einsum("qef,efd->qed", g * u, cut(moe["w_down"]))
            return y + jnp.einsum(
                "qed,qe->qd", out, jax.lax.dynamic_slice_in_dim(dense_w, i * Eb, Eb, axis=1))

        routed = jax.lax.fori_loop(0, E // Eb, expert_block, jnp.zeros_like(h))
        return act(h + routed), gap

    @jax.jit
    def head_block(hid, lm_head, start):
        width = min(HEAD_BLOCK, lm_head.shape[1])
        return hid @ jax.lax.dynamic_slice_in_dim(lm_head, start, width, axis=1).astype(f32)

    def head(x, final_scale, lm_head):
        """Logits [R, V] of x [R, D], the head in blocks of HEAD_BLOCK columns."""
        hid = rms(x, final_scale.astype(f32))
        V = lm_head.shape[1]
        width = min(HEAD_BLOCK, V)
        starts = list(range(0, V - width + 1, width))
        if starts[-1] + width < V:
            starts.append(V - width)
        out = np.empty((x.shape[0], V), np.float32)
        for s in starts:
            out[:, s:s + width] = np.asarray(head_block(hid, lm_head, np.int32(s)))
        return out

    return embed, layer, head


def _lm_head(dims, params):
    return params["tok_embed"].T if dims["tie_word_embeddings"] else params["lm_head"]


def _padded(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def prompt_pass(dims: dict, params: dict, tokens, n: int, pieces) -> list:
    """Every layer's input [T, D] (numpy float32; T = ``tokens`` padded to whole
    query blocks) for the first ``n`` positions of ONE context ``tokens`` [T]:
    the context's state, which ``position_logits`` extends a position at a time.
    Rows at and past ``n`` hold whatever the pad tokens gave: no later read
    sees a position it has not itself written."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    embed, layer, _ = pieces
    state = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["tok_embed"], jnp.asarray(tokens))
        no_swap = np.zeros((Q_BLOCK,), bool)
        for i in range(dims["layers"]):
            state.append(np.array(x))  # a copy the walk may write to
            x = jnp.concatenate([
                layer(x, x[s:s + Q_BLOCK], np.int32(s), params["layers"], np.int32(i),
                      no_swap)[0]
                for s in range(0, _padded(n), Q_BLOCK)
            ] + ([x[_padded(n):]] if _padded(n) < len(tokens) else []))
    return state


def position_logits(dims: dict, params: dict, state: list, token: int, pos: int,
                    near_tie: float, pieces):
    """Reference logits at position ``pos`` of the context whose layer inputs
    before ``pos`` are ``state``, with ``token`` standing there: a list of [V]
    arrays, the plain routing first, then one for every other leaf of the tree
    of passes that forks (6th <-> 7th expert AT THIS POSITION) at each layer
    whose gap, in the pass that leads to it, is under ``near_tie``. Also the
    plain pass's gaps [layers]. The plain pass's layer inputs at ``pos`` are
    written into ``state``: the next position's keys and values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    embed, layer, head = pieces
    at = np.int32(pos)
    with jax.default_matmul_precision("highest"):
        passes = [embed(params["tok_embed"], jnp.asarray([token], jnp.int32))]  # plain first
        gaps = []
        for i in range(dims["layers"]):
            state[i][pos] = np.asarray(passes[0][0])
            x_all = jnp.asarray(state[i])
            out = []
            for n, x_at in enumerate(passes):
                y, gap = layer(x_all, x_at, at, params["layers"], np.int32(i), np.zeros((1,), bool))
                out.append(y)
                if n == 0:
                    gaps.append(float(gap[0]))
                if float(gap[0]) < near_tie and len(passes) + len(out) - n - 1 < MAX_PASSES:
                    out.append(layer(x_all, x_at, at, params["layers"], np.int32(i),
                                     np.ones((1,), bool))[0])
            # the plain pass stays first: its fork, if any, was appended right after it
            passes = out
        logits = head(jnp.concatenate(passes), params["final_norm"]["scale"],
                      _lm_head(dims, params))
    return list(logits), gaps


def dims_of_preset(mcfg) -> dict:
    """The program's preset under config.json's names: what the file must say."""
    layout = [int(w > 0) for w in mcfg.layer_windows]
    return {
        "hidden_size": mcfg.d_model, "layers": mcfg.n_layers,
        "num_attention_heads": mcfg.n_heads, "num_key_value_heads": mcfg.n_kv_heads,
        "head_dim": mcfg.head_dim, "moe_ffn_hidden_size": mcfg.expert_ff,
        "moe_num_primary_experts": mcfg.n_experts,
        "moe_num_active_primary_experts": mcfg.n_experts_per_tok,
        "rms_norm_eps": mcfg.norm_eps, "rope_theta": mcfg.rope_theta,
        "sliding_window_size": mcfg.sliding_window, "vocab_size": mcfg.vocab_size,
        "tie_word_embeddings": mcfg.tie_embeddings,
        "sliding_window_layout": layout, "rope_layout": layout,
    }


def context_logits(dims: dict, params: dict, tokens, positions, pieces=None,
                   near_tie: float = 0.0) -> list:
    """Teacher-forced reference logits [V] at each of the ascending
    ``positions`` of ONE context ``tokens`` (1-D): a prompt pass up to the
    first, then a position at a time (the tests' entry point)."""
    import numpy as np

    pieces = pieces or build_forward(dims)
    padded = np.zeros((_padded(len(tokens)),), np.int32)
    padded[:len(tokens)] = tokens
    state = prompt_pass(dims, params, padded, positions[0], pieces)
    out = []
    for pos in range(positions[0], positions[-1] + 1):
        logits, _ = position_logits(dims, params, state, int(tokens[pos]), pos, near_tie, pieces)
        if pos in positions:
            out.append(logits[0])
    return out


def compare(job: dict, conf: dict, params: dict) -> dict:
    """The comparison on ``job``'s served text with the program's seeded
    ``params``: the result line's fields (``ok`` decides ``correct``)."""
    import numpy as np

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
    tol = float(job["tolerance"])
    T = _padded(P + n_new)
    states: dict[int, list] = {}  # row -> its context's layer inputs
    seen = {"min_gap": math.inf, "near": 0, "rescued": 0}
    position_margin: dict = {}  # (probe, step) -> the best margin any of its contexts gave

    def state_of(r: int, pos: int) -> list:
        """Row r's state up to ``pos``: its own, a copy of the context it was
        forked from (same probe, same tokens before ``pos``), or a prompt pass."""
        if r not in states:
            twin = next((q for q in states if owner[q] == owner[r]
                         and (tokens[q, :pos] == tokens[r, :pos]).all()), None)
            if twin is not None:
                states[r] = [a.copy() for a in states[twin]]
            else:
                padded = np.zeros((T,), np.int32)
                padded[:P] = tokens[r, :P]
                states[r] = prompt_pass(dims, params, padded, P - 1, pieces)
        return states[r]

    def logits_at(step: int):
        """[R, V] for the walk: a live row's logits under the routing pass
        (plain, or its near-tie layers swapped at the compared position) that
        serves its probe's byte best. The walk then applies its tolerance."""
        pos = P - 1 + step
        folded = np.zeros((R, V), np.float32)
        for r in np.flatnonzero(owner >= 0):
            text = served[owner[r]]
            if len(text) <= step:
                states.pop(int(r), None)
                continue
            outs, gaps = position_logits(
                dims, params, state_of(int(r), pos), int(tokens[r, pos]), pos, near_tie, pieces)
            cls = byte_class(text[step], V)
            margins = [float(o.max() - o[cls].max()) for o in outs]
            best = int(np.argmin(margins))
            folded[r] = outs[best]
            seen["min_gap"] = min(seen["min_gap"], min(gaps))
            seen["near"] += min(gaps) < near_tie
            seen["rescued"] += margins[0] > tol >= margins[best]
            at = (int(owner[r]), step)
            position_margin[at] = min(position_margin.get(at, math.inf), margins[best])
        return folded

    res = walk(logits_at, tokens, owner, served, P, n_new, V, tol)
    # what decides: the MEAN over the compared positions (reference_joyai.py: a
    # routing swap at an EARLIER token throws one position far out and leaves
    # the others where they were; a fault of the model moves every position)
    mean_limit = float(conf["reference"]["mean_margin_limit"])
    mean_margin = (sum(position_margin.values()) / len(position_margin)
                   if position_margin else math.inf)
    return {
        **res, "ok": bool(res["enough_positions"] and mean_margin <= mean_limit),
        "walk_ok": res["ok"],
        "mean_margin": mean_margin if math.isfinite(mean_margin) else None,
        "mean_margin_limit": mean_limit, "probes": len(probes), "perturb": job.get("perturb"),
        "prompt_tokens": P, "near_tie": near_tie, "near_tie_positions": int(seen["near"]),
        "near_tie_rescued": int(seen["rescued"]),
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
    # the file holds the published 52-layer lists: the layers as run are their head
    have = dict(dims, **{n: dims[n][:dims["layers"]]
                         for n in ("sliding_window_layout", "rope_layout")})
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
    dev0 = devs[0]
    print(json.dumps({**res, "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                                        "count": len(devs)}}))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
