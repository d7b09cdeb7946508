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
within the tolerance extends a context of its own), walked THROUGH the bytes
the text lost (``served_bytes``, ``walk``: half of a seeded model's greedy
bytes at this vocabulary, so 8 probes of 8 tokens cut at the first loss show
1-14 positions), with ``reference_joyai.py``'s ROUTING rule: a bf16 rounding
upstream can swap a token's 6th and 7th expert, so the compared position is
ALSO computed with the
6th <-> 7th choice swapped in every layer whose gap (6th minus 7th logit), in
the pass that leads to it, is under ``near_tie``: a tree of passes that forks
at each such layer (at most ``MAX_PASSES`` leaves), and the position's margin
is its best under any of them. What decides ``correct`` is ``mean_margin``, the
mean over the compared positions of the best margin, against
``mean_margin_limit`` (``tolerance_why`` in the configuration file has the
readings); the walk's own verdict is reported as ``walk_ok``. AND the program's
ragged read must equal this file's dense mask at the cell's shapes
(``window_read``, against ``window_read_limit``): served text cannot show
whether a window binds.

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

from reference import BOS, MIN_CHECKED, MIN_DECODE_CHECKED, OFFSET, byte_class  # noqa: E402

HEAD_BLOCK = 37984  # columns of the head a call (151,936 = 4 blocks)
EXPERT_BLOCK = 8  # experts a step of the dense expert sum
Q_BLOCK = 256  # query positions a call of the prompt pass
MAX_PASSES = 32  # leaves of a compared position's tree of routing passes
LOST = -1  # a served position whose byte the text no longer shows (one U+FFFD)


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


def served_bytes(text: str, n_new: int) -> list[int]:
    """The served bytes a probe's text shows, a position each, ``LOST`` where
    it shows U+FFFD. The byte tokenizer decodes with ``errors="replace"``: a
    U+FFFD stands for ONE byte of 0x80-0xFF, or for a truncated sequence of two
    or three. Every other character gives its own bytes, so the positions add
    up to ``n_new`` exactly when every U+FFFD is one byte (a longer loss, a
    special token or an early end leaves them short: nothing can make them
    long). Then the whole text is walked; else only up to the first loss, as
    ``reference.known_bytes`` has it. At this vocabulary a seeded model's
    greedy byte is lost every other position, and 8 probes of 8 tokens cut at
    the first loss showed 1-14 positions (PR 43)."""
    out: list[int] = []
    for ch in text:
        out.extend([LOST] if ch == "\ufffd" else ch.encode("utf-8"))
    if len(out) != n_new and LOST in out:
        out = out[:out.index(LOST)]
    return out[:n_new]


def token_class(byte: int, V: int):
    """The tokens a served position may have been: those of its byte, or of
    any byte that cannot stand alone (0x80-0xFF) where the byte is lost."""
    import numpy as np

    if byte != LOST:
        return byte_class(byte, V)
    ids = np.arange(OFFSET, V)
    return ids[(ids - OFFSET) % 256 >= 0x80]


LOST_ROWS = 32  # contexts beyond one a probe that the lost bytes' runners-up may open


def walk(logits_at, tokens, owner, served, P: int, n_new: int, V: int, tol: float) -> dict:
    """``reference_falcon_h1.walk`` with LOST positions walked through, not
    ended at. ``logits_at(step, rows)`` gives [R, V] with the ``rows`` filled.

    At a known byte: as there. The best reference logit of the byte's tokens
    must lie within ``tol`` of the maximum, else the context ends, wrong; every
    further candidate within ``tol`` is a context of its own, and a probe is as
    good as its best context. At a lost byte nothing is compared: the context
    goes on with the lost class's best token, and every further one within
    ``tol / 4`` of the maximum opens a context (the served path rounds to bf16
    and may route a near-tie the other way: it picked a runner-up 0.06-0.17
    off at 1 position in 100, PR 43). A context that followed the wrong token
    reads far out at its next known byte. That end says nothing of the served
    path, so it is a GUESS's end: not counted as wrong, not compared. A margin
    within ``tol`` there is compared like any other, so a fault that moves
    every position still moves the mean. ``margins`` holds, a compared
    position, the best margin any of its contexts gave; ``checked`` counts
    those."""
    import numpy as np

    R = tokens.shape[0]
    row_worst = np.zeros((R,), np.float64)  # a live context's worst margin so far
    guessed = np.zeros((R,), bool)  # lost bytes since the context's last compared one
    alive = owner >= 0
    ended: dict[int, list[float]] = {}  # probe -> worst margins of its ended contexts
    margins_at: dict = {}  # (probe, step) -> the best margin of a compared position
    events, stds, forks, forks_dropped, lost, guesses_ended = [], [], 0, 0, 0, 0
    for step in range(n_new):
        live = [r for r in range(R) if alive[r] and len(served[owner[r]]) > step]
        for r in range(R):  # a context whose probe's text ends here has ended well
            if alive[r] and r not in live:
                ended.setdefault(int(owner[r]), []).append(float(row_worst[r]))
                alive[r] = False
        if not live:
            break
        logits = logits_at(step, live)
        stds.append(float(np.std(logits[live])))
        for r in live:
            i = int(owner[r])
            known = served[i][step] != LOST
            cls = token_class(served[i][step], V)
            margins = float(np.max(logits[r])) - logits[r, cls]
            order = np.argsort(margins)
            best = float(margins[order[0]])
            if known:
                if best > tol and guessed[r]:  # a wrong guess before it: this context ends here
                    guesses_ended += 1
                    alive[r] = False
                    continue
                if best > tol / 8.0:
                    events.append((i, step, best))
                margins_at[(i, step)] = min(margins_at.get((i, step), math.inf), best)
                if best > tol:  # this context ends here, wrong
                    ended.setdefault(i, []).append(max(float(row_worst[r]), best))
                    alive[r] = False
                    continue
                row_worst[r] = max(row_worst[r], best)
                guessed[r] = False
            else:
                lost += 1
                guessed[r] = True
            tokens[r, P + step] = cls[order[0]]
            for j in order[1:]:  # every further candidate of the class within the width
                if margins[j] > (tol if known else tol / 4.0):
                    break
                free = np.flatnonzero(owner < 0)
                if not len(free):
                    forks_dropped += 1
                    continue
                f = int(free[0])
                tokens[f] = tokens[r]
                tokens[f, P + step] = cls[j]
                owner[f], alive[f], guessed[f] = i, True, guessed[r]
                row_worst[f] = max(row_worst[r], float(margins[j])) if known else row_worst[r]
                forks += 1
    for r in range(R):
        if alive[r]:
            ended.setdefault(int(owner[r]), []).append(float(row_worst[r]))
    per_probe = {i: min(ws) for i, ws in ended.items()}  # a probe is as good as its best context
    worst = max(per_probe.values(), default=None)
    checked = len(margins_at)
    decode_checked = sum(step > 0 for _, step in margins_at)
    enough = checked >= MIN_CHECKED and decode_checked >= MIN_DECODE_CHECKED
    ok = bool(enough and worst is not None and math.isfinite(worst) and worst <= tol)
    return {
        "ok": ok, "checked": checked, "decode_checked": decode_checked,
        "lost_walked": lost, "guesses_ended": guesses_ended, "enough_positions": enough,
        "worst_margin": worst, "tolerance": tol, "forks": forks, "forks_dropped": forks_dropped,
        "margins_over_tol_8th": sorted(events, key=lambda e: -e[2])[:20],
        "logits_std": stds[0] if stds else None,
        "tolerance_share_of_std": tol / stds[0] if stds and stds[0] else None,
        "margins": margins_at,
    }


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
    R = len(probes) + LOST_ROWS
    tokens = np.zeros((R, P + n_new), np.int32)
    owner = np.full((R,), -1, np.int64)
    for i, p in enumerate(probes):
        raw = p["prompt"].encode()
        if len(raw) + 1 != P:
            return {"ok": False, "error": "probe prompts differ in length"}
        tokens[i, 0] = BOS
        tokens[i, 1:P] = np.frombuffer(raw, np.uint8).astype(np.int32) + OFFSET
        owner[i] = i
    served = [served_bytes(p["text"], n_new) for p in probes]
    near_tie = float(conf["reference"]["near_tie"])
    tol = float(job["tolerance"])
    T = _padded(P + n_new)
    states: dict[int, list] = {}  # row -> its context's layer inputs
    seen = {"min_gap": math.inf, "near": 0, "rescued": 0}

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

    def logits_at(step: int, rows: list):
        """[R, V] for the walk: a live row's logits under the routing pass
        (plain, or its near-tie layers swapped at the compared position) that
        serves its probe's byte best. The walk then applies its tolerance."""
        pos = P - 1 + step
        folded = np.zeros((R, V), np.float32)
        for r in set(states) - set(rows):  # an ended context's state: half a GB of host memory
            del states[r]
        for r in rows:
            text = served[owner[r]]
            outs, gaps = position_logits(
                dims, params, state_of(int(r), pos), int(tokens[r, pos]), pos, near_tie, pieces)
            cls = token_class(text[step], V)
            margins = [float(o.max() - o[cls].max()) for o in outs]
            best = int(np.argmin(margins))
            folded[r] = outs[best]
            seen["min_gap"] = min(seen["min_gap"], min(gaps))
            seen["near"] += min(gaps) < near_tie
            seen["rescued"] += margins[0] > tol >= margins[best]
        return folded

    res = walk(logits_at, tokens, owner, served, P, n_new, V, tol)
    # what decides: the MEAN over the compared positions (reference_joyai.py: a
    # routing swap at an EARLIER token throws one position far out and leaves
    # the others where they were; a fault of the model moves every position)
    mean_limit = float(conf["reference"]["mean_margin_limit"])
    position_margin = res.pop("margins")
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


def window_read(conf: dict, prompt_tokens: int, output_tokens: int,
                perturb: dict | None = None) -> dict:
    """The program's ragged read against this file's dense mask, layer by
    layer, at the cell's own shapes: the window that ``core.make_layer_window``
    gives each layer of the program's preset, the page-table width of
    ``max_seq_len`` (1,024 pages), decode rows at the probes' depths and just
    past and just inside one window, and one prefill chunk of ``prefill_chunk``
    queries that ends at the probes' prompt. Served text cannot show a window:
    seeded weights spread attention over 6k keys nearly evenly, so the keys
    behind a window change a logit by less than bf16 does (``tolerance_why``).
    Here q is drawn four times as wide as k, so a score's std is 4 and a query
    leans on a handful of keys, a third of them behind the window of a row 6k
    deep: a read that sees one of them, or skips a page it should see, is off
    by a whole value row. ``window_read_err`` is the largest |read - dense| of
    any output, in units of the values' rms, against ``window_read_limit``.
    Shares with the served path: ``ops/ragged.make_ragged_attn_fn`` and the
    preset's per-layer window; nothing of the model's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee2bee_tpu.models import core
    from bee2bee_tpu.models.config import get_config
    from bee2bee_tpu.ops.ragged import make_ragged_attn_fn

    srv = conf["server"]["config_json"]
    mcfg = get_config(conf["server"]["model"])
    S, BS, C = int(srv["max_seq_len"]), int(srv.get("kv_block_size", 16)), int(srv["prefill_chunk"])
    W, L = int(conf["sliding_window_size"]), int(conf["layers"])
    H, Hkv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    layout = conf["sliding_window_layout"][:L]
    dtype = jnp.dtype(srv.get("dtype", "bfloat16"))
    P = int(prompt_tokens)
    decode = [min(n, S) for n in (P + int(output_tokens), P, W + 1, W - 1)]  # tokens cached a row
    chunk_at = (P - 1) // C * C  # the prompt's last chunk
    rows = [(n - 1, 1) for n in decode] + [(chunk_at, C)]  # (the first query's position, queries)
    pages = [-(-(at + T) // BS) for at, T in rows]
    rng = np.random.default_rng(0)
    ids = rng.permutation(sum(pages)) + 1  # block 0 is the null block
    tables = np.zeros((len(rows), S // BS), np.int32)
    for r, n in enumerate(pages):
        tables[r, :n] = ids[sum(pages[:r]):sum(pages[:r]) + n]
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    pool_shape = (L, Hkv, len(ids) + 1, BS, hd)
    k_pool = jax.random.normal(kk, pool_shape, jnp.float32).astype(dtype)
    v_pool = jax.random.normal(kv, pool_shape, jnp.float32).astype(dtype)
    attn = make_ragged_attn_fn(None)
    layer_window = core.make_layer_window(mcfg)

    @jax.jit
    def read(k_pool, v_pool, q, table, positions, layer):
        return attn(q, k_pool, v_pool, layer_window(layer), mcfg, positions=positions,
                    block_tables=table, layer=layer)

    @jax.jit
    def dense(k_pool, v_pool, q, table, positions, layer):  # q [T, H, hd] of ONE row, its table [MB]
        with jax.default_matmul_precision("highest"):
            def keys(pool):  # [S, Hkv, hd] as the row's table maps them
                return jnp.take(pool[layer], table, axis=1).transpose(1, 2, 0, 3).reshape(
                    S, Hkv, hd).astype(jnp.float32)

            pos = jnp.arange(S)
            seen = pos[None, :] <= positions[:, None]
            if not (perturb or {}).get("no_window"):
                seen &= (jnp.asarray(layout)[layer] == 0) | (pos[None, :] > positions[:, None] - W)
            s = jnp.einsum("tgjd,sgd->gjts", q.astype(jnp.float32).reshape(-1, Hkv, H // Hkv, hd),
                           keys(k_pool)) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
            return jnp.einsum("gjts,sgd->tgjd", p, keys(v_pool)).reshape(-1, H * hd)

    worst, Tq = 0.0, min(C, 512)  # the dense side in blocks of Tq queries
    for T, which in ((1, range(len(decode))), (C, [len(decode)])):
        at = np.asarray([rows[r][0] for r in which], np.int32)
        positions = at[:, None] + np.arange(T, dtype=np.int32)[None]
        q = (4.0 * jax.random.normal(jax.random.fold_in(kq, T), (len(at), T, H, hd),
                                     jnp.float32)).astype(dtype)
        for layer in range(L):
            got = np.asarray(read(k_pool, v_pool, q, tables[list(which)], positions,
                                  np.int32(layer)), np.float32)
            for n, r in enumerate(which):
                for t in range(0, T, Tq):
                    want = np.asarray(dense(k_pool, v_pool, q[n, t:t + Tq], tables[r],
                                            positions[n, t:t + Tq], np.int32(layer)))
                    worst = max(worst, float(np.abs(got[n, t:t + Tq] - want).max()))
    limit = float(conf["reference"]["window_read_limit"])
    return {"window_read_err": worst, "window_read_limit": limit, "window_read_ok": worst <= limit,
            "window_read_rows": [list(r) for r in rows]}


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
    # before the weights are made: the dense side of a 2,048-query chunk wants the room
    seen = window_read(conf, len(job["probes"][0]["prompt"].encode()) + 1, job["output_tokens"],
                       job.get("perturb"))
    mesh = local_mesh()
    dtype = jnp.dtype(srv.get("config_json", {}).get("dtype", "bfloat16"))
    key = jax.random.key(0)  # EngineConfig.rng_seed: the node config cannot set it
    shapes = jax.eval_shape(lambda: core.init_params(mcfg, key, dtype=dtype))
    params = core.init_params(
        mcfg, key, dtype=dtype,
        out_shardings=partition.param_shardings(shapes, mesh, mcfg))

    res = compare(job, conf, params)
    res["ok"] = bool(res["ok"] and seen["window_read_ok"])
    dev0 = devs[0]
    print(json.dumps({**res, **seen, "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                                        "count": len(devs)}}))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
