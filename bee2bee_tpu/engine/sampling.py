"""On-device token sampling: greedy / temperature / top-k / top-p / min-p.

Replaces the sampling knobs the reference forwards to torch generate
(reference services.py:44-59: temperature, max_new_tokens). Everything is
shape-static and branchless via masking, so it lives inside the jit'd
decode step — no host round-trip between logits and token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample(
    logits,  # [B, V] float32
    key,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    min_p: float = 0.0,
):
    """Sample next tokens [B]. temperature<=0 → greedy (argmax).

    Static Python values for the knobs keep the jitted step monomorphic —
    the engine compiles one step per (temperature==0?) variant, which is
    the right trade: sampling params rarely change within a request.
    """
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)

    logits = logits / jnp.asarray(max(temperature, 1e-6), logits.dtype)

    if min_p and min_p > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        floor = min_p * jnp.max(probs, axis=-1, keepdims=True)
        logits = jnp.where(probs >= floor, logits, -jnp.inf)

    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)

    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p; the top
        # token is always kept (top_p=0 degrades to greedy, not to garbage)
        keep = (cum - probs < top_p).at[:, 0].set(True)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)

    return jax.random.categorical(key, logits, axis=-1)


def apply_penalties(
    logits,  # [B, V] float32
    counts,  # [B, 2, V] int32: [:, 0] prompt occurrences, [:, 1] generated
    repetition,  # [B] float32; 1.0 = off (HF-style multiplicative)
    presence,  # [B] float32; 0.0 = off (flat tax on any generated token)
    frequency,  # [B] float32; 0.0 = off (per-generated-occurrence tax)
):
    """Occurrence penalties, applied BEFORE temperature/argmax so greedy
    decoding benefits too (greedy + repetition_penalty is the classic
    'stop the loop' config). The two count channels carry the two
    conventions faithfully: repetition follows HF's
    RepetitionPenaltyLogitsProcessor (divide positive logits, multiply
    negative ones, over PROMPT + generated tokens); presence/frequency
    follow OpenAI (generated tokens ONLY — taxing prompt words would
    make a summarizer avoid its own article's subject)."""
    gen = counts[:, 1]
    seen_any = (counts[:, 0] > 0) | (gen > 0)
    rep = repetition[:, None]
    logits = jnp.where(
        seen_any, jnp.where(logits > 0, logits / rep, logits * rep), logits
    )
    logits = logits - presence[:, None] * (gen > 0).astype(logits.dtype)
    logits = logits - frequency[:, None] * gen.astype(logits.dtype)
    return logits


@jax.named_scope("sample.draw")  # tracing.DEVICE_PARTS: the sampler's ops carry the part
def sample_batched(
    logits,  # [B, V] float32
    key,
    temperature,  # [B] float32; <= 0 → greedy for that row
    top_k,  # [B] int32; <= 0 → no top-k restriction
    top_p,  # [B] float32; >= 1 → no nucleus restriction
    min_p=None,  # [B] float32; <= 0 → off. Keeps tokens whose prob (after
    # temperature) is >= min_p * max prob — a relative floor that adapts
    # to the distribution's confidence where top_p's absolute mass cut
    # does not (the "min-p sampling" recipe)
    counts=None,  # optional [B, 2, V] int32 (see apply_penalties) → penalties first
    repetition=None,  # [B] float32 (with counts)
    presence=None,  # [B] float32 (with counts)
    frequency=None,  # [B] float32 (with counts)
):
    """Per-row sampling for continuous batching: every knob is a traced
    [B] array, so ONE compiled decode step serves any mix of concurrent
    requests' sampling settings (the scalar `sample` compiles one variant
    per signature — fine for a single stream, wrong for a shared batch).

    This is the sampling stage of the FUSED decode root (scheduler
    ._decode_fn and engine._spec_verify_fn call it inside their jit
    graphs, threading ``counts`` through the scan carry): logits never
    leave the device between the forward and the token, and a penalized
    row rides the same compiled window as its greedy neighbors instead
    of parking the whole batch on a split counts graph. ``counts=None``
    lowers to a counts-free graph — the pre-fusion trace, bit-for-bit —
    which is what an all-plain batch compiles and runs.

    Semantics per row match `sample`: [penalties →] temperature scale →
    top-k mask → nucleus mask over the already-masked logits →
    categorical; greedy rows short-circuit to argmax via a final where.
    """
    if counts is not None:
        logits = apply_penalties(logits, counts, repetition, presence, frequency)
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)

    def sampled_path(_):
        l = logits / jnp.maximum(temperature, 1e-6)[:, None]

        if min_p is not None:
            probs0 = jax.nn.softmax(l, axis=-1)
            floor = min_p[:, None] * jnp.max(probs0, axis=-1, keepdims=True)
            # the top token always survives (probs0 >= floor there)
            l = jnp.where(probs0 >= floor, l, -jnp.inf)

        sorted_l = jnp.sort(l, axis=-1)[:, ::-1]
        k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
        kth = jnp.take_along_axis(sorted_l, (k_eff - 1)[:, None], axis=-1)
        l = jnp.where(l < kth, -jnp.inf, l)

        sorted_m = jnp.sort(l, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_m, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs < top_p[:, None]).at[:, 0].set(True)
        cutoff = jnp.min(
            jnp.where(keep, sorted_m, jnp.inf), axis=-1, keepdims=True
        )
        l = jnp.where(l < cutoff, -jnp.inf, l)

        sampled = jax.random.categorical(key, l, axis=-1)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    # the sorts/cumsum above cost real time at vocab scale (two bitonic
    # sorts of [B, V] per token on TPU); an all-greedy batch — the common
    # serving default — must pay argmax only. lax.cond executes one branch.
    return jax.lax.cond(
        jnp.any(temperature > 0.0), sampled_path, lambda _: greedy, None
    )
