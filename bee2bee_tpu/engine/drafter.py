"""The model-tier drafter: a real small model resident beside the target.

``DraftModel`` implements spec.Drafter tier "model": it holds its own
tiny weights and a RECTANGULAR KV cache ([L, B, S, Hkv, hd] —
core.init_cache; drafter contexts are short-lived and tiny, so the paged
pool machinery would be pure overhead) and drafts K tokens per eligible
row in ONE batched autoregressive pass: a [B, 2] chunk forward that
catches the cache up to the row's context tail and yields draft token 0,
then a K-1 step lax.scan of [B, 1] decode steps — one jit root, one
shape, all rows together.

KV state algebra (the whole file hangs on this): ``consumed[slot]`` is
the number of context positions with VALID cache content — every token
ctx[0..consumed) has been fed at its position. Feeds are always
CONTIGUOUS from ``consumed``, which buys a universal safety invariant:
any cache position >= a row's frontier is rewritten by the chunk that
first covers it BEFORE any query at or beyond it runs (core.forward
writes K/V before attention; causal masking hides higher positions until
then). So rejected-draft K/V, padded prime chunks, and idle-row parking
writes are all garbage-above-frontier — never observed. The per-step
bookkeeping:

- propose: feed ctx[consumed:] (1 or 2 tokens in steady state), draft K,
  set consumed = len(ctx). The scan also wrote K/V for drafts[0..K-2].
- observe(accepted=a): the target kept drafts[:a] + a bonus token, so
  consumed += min(a, K-1) — accepted drafts' K/V is already valid; the
  bonus (and a full-accept's draft K-1) gets fed next propose. The gap
  len(ctx) - consumed stays in {1, 2} while the row drafts every step.
- a row that skipped drafting for some steps (eligibility flapped) or a
  fresh/re-primed row catches up through batched [B, W] prime chunks.
- rejection-heavy rows (consecutive zero-accept streak) re-prime from
  scratch — the typed escape hatch for any host/device state drift.

Idle rows in a batched call park at ``_idle_off`` — a fixed offset past
every reachable real frontier — so one fixed-shape root serves any
active subset without touching inactive rows' live state.

Loaded beside the target in engine/engine.py (BEE2BEE_DRAFTER /
--drafter), which runs the tokenizer compatibility gate below first: a
drafter whose token ids mean different strings than the target's would
be a silent garbage-draft loop (acceptance ~0, all verify FLOPs wasted),
so vocab-size or tokenizer-fingerprint mismatch is a typed
``DrafterLoadError`` at boot.
"""

from __future__ import annotations

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import config as model_config
from ..models import core, support
from .spec import Drafter


class DrafterLoadError(RuntimeError):
    """Drafter/target incompatibility detected at boot (never at serve
    time): vocab-size mismatch, tokenizer-fingerprint mismatch, or a
    drafter spec that cannot resolve to a model."""


def tokenizer_fingerprint(tok) -> str:
    """Stable identity hash for a tokenizer: two tokenizers with the same
    fingerprint map ids to the same strings. HF tokenizers hash their
    full vocab table; the byte fallback is fully determined by its type
    and vocab size."""
    inner = getattr(tok, "_tok", None)
    if inner is not None and hasattr(inner, "get_vocab"):
        blob = json.dumps(sorted(inner.get_vocab().items()), ensure_ascii=True)
        return "vocab:" + hashlib.sha256(blob.encode()).hexdigest()
    return f"{type(tok).__name__}:{getattr(tok, 'vocab_size', 0)}"


def validate_drafter_compat(target_cfg, target_tok, draft_cfg, draft_tok):
    """The boot-time gate: draft token ids must BE target token ids."""
    if draft_cfg.vocab_size != target_cfg.vocab_size:
        raise DrafterLoadError(
            f"drafter vocab_size {draft_cfg.vocab_size} != target "
            f"vocab_size {target_cfg.vocab_size}: draft ids would be "
            f"garbage to the verify path"
        )
    tf, df = tokenizer_fingerprint(target_tok), tokenizer_fingerprint(draft_tok)
    if tf != df:
        raise DrafterLoadError(
            f"drafter tokenizer {df} != target tokenizer {tf}: same vocab "
            f"size but different id->string maps"
        )


class _Slot:
    __slots__ = ("idx", "consumed", "zero_streak")

    def __init__(self, idx: int):
        self.idx = idx
        self.consumed = 0
        self.zero_streak = 0


class DraftModel(Drafter):
    """Tier "model": batched K-token drafting with a resident small model.

    One instance per engine, sized to the engine's max_batch; per-request
    cache rows are slot-assigned on first propose and released by
    forget() at retirement. All jax work happens on the scheduler thread
    (same discipline as the verify root)."""

    tier = "model"

    # consecutive all-rejected verify verdicts before a full re-prime —
    # the drift escape hatch; cheap because re-priming is W tokens/step
    REPRIME_AFTER = 4
    PRIME_WIDTH = 64

    def __init__(
        self,
        model,
        spec_tokens: int,
        batch: int,
        target_max_seq_len: int,
        dtype="float32",
        seed: int = 0,
        checkpoint_path: str | None = None,
        params=None,
        sentinel=None,
    ):
        if spec_tokens < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {spec_tokens}")
        try:
            self.cfg = model_config.resolve_model_config(model, checkpoint_path)
        except KeyError as e:
            raise DrafterLoadError(f"unknown drafter model {model!r}") from e
        support.require(self.cfg, "spec_model_drafter")
        self.spec_tokens = K = spec_tokens
        self.batch = batch
        self.dtype = jnp.dtype(dtype)
        # the longest context we draft at: the drafter's own positional
        # capacity caps it (gpt2-class drafters have learned positions);
        # rows beyond this miss instead of indexing garbage embeddings
        self.cap = min(target_max_seq_len, self.cfg.max_seq_len - K - 1)
        self.prime_width = W = min(self.PRIME_WIDTH, max(self.cap, 8))
        # idle rows park past every reachable real frontier (a real row's
        # writes reach at most cap + K - 2), so a batched call never
        # clobbers an inactive row's valid prefix
        self._idle_off = self.cap + K - 1
        S = self._idle_off + max(W, K) + 1
        self.seq_len = S

        if params is None:
            params = core.init_params(
                self.cfg, jax.random.key(seed), dtype=self.dtype
            )
        if (
            jax.default_backend() == "cpu"
            and not isinstance(params.get("layers"), (list, tuple))
        ):
            # same CPU GEMM-packing fast path the target engine uses
            params = core.unstack_layers(jax.device_get(params))
        self.params = params
        self.cache = core.init_cache(self.cfg, batch, S, dtype=self.dtype)
        self.tokenizer = None
        if checkpoint_path:
            from .tokenizer import load_tokenizer

            self.tokenizer = load_tokenizer(
                checkpoint_path, self.cfg.vocab_size
            )

        self._slots: dict[int, _Slot] = {}      # id(req) -> slot state
        self._free = list(range(batch))

        draft = jax.jit(self._draft_fn, donate_argnums=(1,))
        prime = jax.jit(self._prime_fn, donate_argnums=(1,))
        if sentinel is not None:
            # one declared shape each ([B,2] / [B,W]): any other trace
            # through these roots is a genuine storm
            draft = sentinel.watch(
                "draft", draft,
                key_fn=lambda p, c, t, *a: tuple(t.shape),
                allowed=lambda key: key == (batch, 2),
            )
            prime = sentinel.watch(
                "draft_prime", prime,
                key_fn=lambda p, c, t, *a: tuple(t.shape),
                allowed=lambda key: key == (batch, W),
            )
        self._draft = draft
        self._prime = prime

    # --------------------------------------------------------- jit roots
    def _prime_fn(self, params, cache, tokens, offsets):
        """Catch-up chunk: write K/V for tokens at [offset, offset+W) per
        row; logits discarded. Padded tails and idle rows write garbage
        above their frontiers — safe by the contiguity invariant."""
        _, cache = core.forward(params, self.cfg, tokens, cache, offsets)
        return cache

    def _draft_fn(self, params, cache, tokens, tlen, offsets):
        """The draft root: one [B, 2] chunk + a K-1 step scan of [B, 1]
        decode steps = K greedy draft tokens per row.

        tokens[b] = ctx[consumed:] right-padded to 2; tlen[b] in {1, 2};
        offsets[b] = consumed (where tokens[b, 0] is written). Draft 0 is
        the argmax at chunk index tlen-1 (the context's last token);
        drafts 1..K-1 come from feeding each draft back at position
        offset + tlen + j. The pad slot of a tlen=1 row is overwritten by
        draft 0's own feed one step later."""
        B = tokens.shape[0]
        K = self.spec_tokens
        logits, cache = core.forward(params, self.cfg, tokens, cache, offsets)
        b_idx = jnp.arange(B)
        tok0 = jnp.argmax(logits[b_idx, tlen - 1], axis=-1).astype(jnp.int32)
        if K == 1:
            return tok0[:, None], cache

        def step(carry, j):
            cache, cur = carry
            lg, cache = core.forward(
                params, self.cfg, cur[:, None], cache, offsets + tlen + j
            )
            nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt

        (cache, _), rest = lax.scan(
            step, (cache, tok0), jnp.arange(K - 1, dtype=jnp.int32)
        )
        drafts = jnp.concatenate([tok0[:, None], rest.T], axis=1)
        return drafts, cache

    # --------------------------------------------------- Drafter interface
    def _slot(self, req) -> _Slot | None:
        st = self._slots.get(id(req))
        if st is None:
            if not self._free:
                return None
            st = _Slot(self._free.pop())
            self._slots[id(req)] = st
        return st

    def propose_batch(self, rows):
        out = {}
        active = []  # (b, req, st, ctx)
        for b, req in rows:
            ctx = list(req.ids) + list(req.out_ids)
            if len(ctx) > self.cap:
                out[b] = []              # past drafter capacity: a miss
                continue
            st = self._slot(req)
            if st is None:
                out[b] = []              # no cache row free (shouldn't
                continue                 # happen: batch == max_batch)
            if st.consumed > len(ctx) - 1 or st.consumed < 0:
                # context moved under us (stop-string truncation, slot
                # reuse): recompute from scratch — rewriting from 0 is
                # always sound, it re-establishes the contiguous frontier
                st.consumed = 0
            active.append((b, req, st, ctx))
        if not active:
            return out

        # -- catch-up: prime rows whose frontier trails the context tail.
        # Target frontier is len(ctx) - 1 (the last token feeds in the
        # draft chunk itself so its logits yield draft 0).
        while any(len(ctx) - 1 - st.consumed > 1 for _, _, st, ctx in active):
            tokens = np.zeros((self.batch, self.prime_width), np.int32)
            offsets = np.full((self.batch,), self._idle_off, np.int32)
            for _, _, st, ctx in active:
                n = min(self.prime_width, len(ctx) - 1 - st.consumed)
                if n <= 1:
                    continue
                chunk = ctx[st.consumed:st.consumed + self.prime_width]
                tokens[st.idx, :len(chunk)] = chunk
                offsets[st.idx] = st.consumed
                st.consumed += n
            self.cache = self._prime(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(offsets),
            )

        # -- the draft step proper: one [B, 2] root call for all rows
        tokens = np.zeros((self.batch, 2), np.int32)
        tlen = np.ones((self.batch,), np.int32)
        offsets = np.full((self.batch,), self._idle_off, np.int32)
        for _, _, st, ctx in active:
            tail = ctx[st.consumed:]
            tokens[st.idx, :len(tail)] = tail
            tlen[st.idx] = len(tail)
            offsets[st.idx] = st.consumed
            st.consumed = len(ctx)
        drafts_d, self.cache = self._draft(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(tlen), jnp.asarray(offsets),
        )
        # meshlint: ignore[ML-J003] -- drafts feed the verify dispatch on
        # this same scheduler step; the readback IS the product
        drafts = np.asarray(jax.device_get(drafts_d))
        for b, _, st, _ in active:
            out[b] = [int(t) for t in drafts[st.idx]]
        return out

    def observe(self, req, accepted: int) -> None:
        st = self._slots.get(id(req))
        if st is None:
            return
        # drafts[0..accepted-1] were fed during the scan, so their K/V is
        # already valid context; a full accept's last draft (K-1) and the
        # bonus token were never fed — they arrive in the next chunk
        st.consumed += min(int(accepted), self.spec_tokens - 1)
        if accepted == 0:
            st.zero_streak += 1
            if st.zero_streak >= self.REPRIME_AFTER:
                st.consumed = 0          # full re-prime from prompt+accepted
                st.zero_streak = 0
        else:
            st.zero_streak = 0

    def forget(self, req) -> None:
        st = self._slots.pop(id(req), None)
        if st is not None:
            self._free.append(st.idx)

    def close(self) -> None:
        self._slots.clear()
        self._free = list(range(self.batch))
        self.params = None
        self.cache = None

    def hbm_source(self):
        """HBM ledger hook: the drafter's resident footprint."""
        return {"params": self.params, "cache": self.cache}
