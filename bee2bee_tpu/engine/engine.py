"""InferenceEngine: the serving core.

TPU-first structure (SURVEY §7 step 2, hard part 1):

- **Bucketed prefill**: prompts pad up to a power-of-two bucket; each bucket
  shape compiles once, bounding the recompile space. Pad K/V written past the
  true length is overwritten by decode exactly when it would enter the
  causal window, so no separate validity mask is needed.
- **Continuous batching** (engine/scheduler.py): concurrent requests share
  ONE paged KV block pool + per-row block tables (engine/paged.py),
  donated through every decode step so XLA updates it in place in HBM;
  rows admit/retire between chunks, a request stops paying compute at
  EOS, per-step cache traffic follows live tokens, and prompt prefixes
  are shared block-level copy-on-write. The old rectangular
  [max_batch, max_seq_len] cache is gone: dense attention serves the
  gathered block view, ``attention="flash"`` runs the ragged
  paged-attention kernel (ops/ragged.py) straight off the pool, and
  ``attention="sp"`` shards the pool's slot dim over the `seq` axis.
- **On-device sampling** inside the jit'd step: one fused
  forward+sample+cache-update program per token; the only host transfer per
  chunk is the sampled token ids (needed for streaming/stop anyway).
- **Mesh-agnostic**: params and cache carry NamedShardings from
  models.partition; the same engine serves a 1-chip node or a v5e-8 TP
  group — jit inserts the collectives.

The generate() contract mirrors what the reference's streaming path provides
(reference hf.py:46-136: max_new_tokens, temperature, stop handling, chunk
callback) minus the transcript parsing, which lives in the service layer.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..metrics import get_registry
from ..models import config as model_config
from ..models import core, partition, support
from ..parallel.mesh import local_mesh
from ..tracing import current_timing, prog_scope
from ..utils import MetricsAggregator
from .programs import StoredPrograms
from .tokenizer import load_tokenizer

logger = logging.getLogger("bee2bee_tpu.engine")

# per-request serving distributions, observed at retirement (scheduler
# thread). TTFT and inter-token (TPOT) are the ROADMAP's "as fast as the
# hardware allows" yardsticks; /metrics exposes their histograms.
_H_TTFT = get_registry().histogram(
    "engine.ttft_ms", "time to first token per request (ms)"
)
_H_INTER_TOKEN = get_registry().histogram(
    "engine.inter_token_ms", "mean inter-token latency per request (ms)"
)
_H_E2E = get_registry().histogram(
    "engine.e2e_latency_ms", "submit-to-done latency per request (ms)"
)
_C_TOKENS_OUT = get_registry().counter(
    "engine.tokens_generated", "tokens generated across all requests"
)

DEFAULT_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096, 8192)
# the prefill root's rows: an admission burst's requests of one bucket run
# as ONE [n, bucket] program (scheduler._admit), n from this ladder, while
# the call stays inside the token budget — which bounds the call's
# temporaries and the compile space ([2|4|8, 64], [2|4|8, 128], [2|4, 256],
# [2, 512] beside the [1, bucket] programs). Derived from nothing a user sets.
PREFILL_GROUP_ROWS = (1, 2, 4, 8)
PREFILL_GROUP_TOKENS = 1024
PREFILL_GROUP_MAX_BUCKET = 512


@dataclass
class EngineConfig:
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"
    prefill_buckets: tuple = DEFAULT_BUCKETS
    rng_seed: int = 0
    # the MOST tokens one call of the decode program runs, and the width of
    # its token buffer: the step count itself is an operand of that one
    # program, chosen by the scheduler at every dispatch (scheduler
    # ._window_size: up to this many while a row streams, fewer where a
    # row's end with the queue waiting is worth a stop). So this is the
    # coarsest a stream's cadence gets, the most a row can hold its slot
    # past its end, and the cache's overshoot margin (blocks_per_row); it
    # is no longer how long a window IS. The value 32 was chosen on a
    # set-up that is gone; since PR 49 the scheduler shortens windows from
    # what it observes, so the cap binds only where long windows pay.
    decode_chunk: int = 32
    # continuous-batching rows: concurrent requests share one [max_batch]
    # KV cache and decode together (engine/scheduler.py). Decode is
    # HBM-bound on the weights, so extra rows are nearly free throughput.
    max_batch: int = 8
    # readback window: up to this many chunks are dispatched per host sync
    # when no active request is streaming (the cost of a sync is not
    # measured on the current machine). The window is also
    # capped by the tightest active row budget, so worst-case post-EOS
    # waste is max_inflight_chunks * decode_chunk tokens.
    max_inflight_chunks: int = 8
    # "dense": einsum attention (models/core._attention) over the
    # gathered block view — covers every score variant incl. ALiBi;
    # "flash": the ragged paged-attention pallas kernel (ops/ragged.py)
    # reading K/V straight from the block pool — no gathered view, no
    # [T,S] score materialization, VMEM-resident online softmax; serves
    # decode, spec-verify and ragged prefill chunks from one kernel and
    # carries sliding windows / logit softcap / the gemma score scale
    # via the dense path's own mask + scalar params;
    # "sp": sequence-parallel serving (parallel/sp_serving.py) — the
    # pool's slot dim is sharded over the mesh's `seq` axis
    # (partition.paged_cache_spec) and attention merges per-shard
    # online-softmax partials via psum over the gathered view; pool HBM
    # and the quadratic prefill term scale 1/seq. Needs seq > 1.
    # "auto": flash when on TPU and the head layout supports the kernel
    # (ops.ragged.validate_ragged_mesh), dense otherwise — resolved once
    # at engine build (interpret-mode pallas off-TPU would be far slower
    # than XLA's fused dense path).
    attention: str = "dense"
    # chunked prefill: process the prompt in fixed chunks of this many
    # tokens instead of one whole-prompt bucket. Bounds dense-attention
    # prefill score memory to [H, chunk, S] (a whole 8k prompt at once is
    # [H, 8k, 8k] — gigabytes), and ONE compiled shape serves every
    # prompt length. None = whole-prompt power-of-two buckets.
    prefill_chunk: int | None = None
    # weight-only quantization: "none" | "int8" (models/quant.py). Decode
    # streams every weight per step, so int8 halves that HBM traffic;
    # activations/KV stay in `dtype`. Applied after checkpoint load,
    # before sharding.
    quantize: str = "none"
    # prompt prefix cache: keep up to this many prompt K/V entries and
    # admit new requests from the longest cached prefix, prefilling only
    # the remainder. Chat transcripts resend the whole history every turn
    # (the reference rebuilds full context per message — its hf.py
    # transcript path), so turn N+1 pays only the delta. Cost depends on
    # the cache layout: rectangular entries each snapshot a full batch-1
    # row cache in HBM; paged entries cost NO extra HBM — they pin the
    # prompt's existing pool blocks (refcounted), and a hit shares those
    # blocks copy-on-write, device-copying at most the final partial
    # block. Pinned blocks are reclaimed LRU-first under pool pressure.
    # 0 = disabled.
    prefix_cache_entries: int = 0
    # tokens per pool block. Smaller blocks track live length tighter
    # (less over-allocation, finer sharing granularity); larger blocks
    # shrink the table/gather overhead. 16 matches the TPU second-minor
    # tile and means a 64-token prompt costs 4 blocks, not a max_seq row.
    kv_block_size: int = 16
    # total pool blocks (incl. the reserved null block 0). None sizes the
    # pool so exhaustion is impossible: max_batch full rows (plus decode-
    # chunk overshoot) + worst-case pinned prefix entries. Set explicitly
    # to trade HBM for admission backpressure (the scheduler queues, and
    # reclaims prefix pins, when the free list runs dry).
    kv_pool_blocks: int | None = None
    # self-speculative decoding (engine/spec.py): draft up to this many
    # tokens per step by n-gram lookup against the row's own
    # prompt+output, verify them all in ONE [B, K+1] forward, accept the
    # longest exact prefix. Greedy non-penalized rows only (token-for-
    # token parity with plain greedy decode); sampled/penalized rows in
    # the same batch keep the normal decode windows. 0 = off. Composes
    # with attention="dense" AND "flash" — the verify chunk rides the
    # paged write path and the ragged kernel serves the [B, K+1] shape
    # natively; only "sp" lacks the capability (the scheduler detects it
    # off the active attn path and logs once).
    spec_tokens: int = 0
    # suffix n-gram lengths the drafter tries, longest first. A longer
    # match predicts the continuation better; min_match=2 keeps single
    # high-frequency tokens (spaces, newlines) from drafting noise.
    spec_min_match: int = 2
    spec_max_match: int = 8
    # per-row adaptive disable: after spec_probe_tokens drafted tokens,
    # a row whose acceptance rate sits below spec_min_accept stops
    # speculating (the draft lookup + wider verify buy nothing on
    # non-repetitive content).
    spec_min_accept: float = 0.25
    spec_probe_tokens: int = 64
    # model-tier drafter (engine/drafter.py): "" / None = n-gram only;
    # "mesh" = a BEE2BEE_DISAGG=draft peer hosts the model and streams
    # drafts over draft_request/draft_result frames; anything else is a
    # registry name or checkpoint path for a small model loaded RESIDENT
    # beside the target (vocab/tokenizer-compat gated at boot — a
    # mismatch is a typed DrafterLoadError, never a silent garbage-draft
    # loop). Rows where n-gram fails its probe escalate to this tier
    # instead of dropping to plain decode. Requires spec_tokens > 0.
    # None = resolve from BEE2BEE_DRAFTER at construction.
    drafter: str | None = None
    # rng seed for a random-init (registry-name, no checkpoint) drafter.
    # None = the engine's rng_seed — which makes a same-name drafter
    # WEIGHT-IDENTICAL to a random-init target (the bench's CPU proxy
    # for a well-distilled drafter: greedy acceptance ~1).
    drafter_seed: int | None = None
    # batched multi-LoRA serving (adapters/pool.py): slots for hot-
    # swappable adapters over the one resident base model — per-row
    # adapter selection inside the SAME decode step (a mixed batch
    # serves N tenants in one forward; adapter-less batches skip the
    # lora arguments entirely). 0 = off. Adapters page in/out at runtime
    # (engine.load_adapter / the mesh's DHT fetch) without a restart.
    max_adapters: int = 0

    def __post_init__(self):
        # <= 0 means "disabled" (NodeConfig uses 0 as its sentinel); a raw
        # 0 reaching the admission loop would make an empty chunk that
        # never advances
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            self.prefill_chunk = None
        if self.kv_block_size < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {self.kv_block_size}")
        if self.spec_tokens < 0:  # NodeConfig's 0-means-disabled sentinel
            self.spec_tokens = 0
        if self.max_adapters < 0:
            self.max_adapters = 0
        if self.spec_tokens and not (
            1 <= self.spec_min_match <= self.spec_max_match
        ):
            raise ValueError(
                f"need 1 <= spec_min_match <= spec_max_match, got "
                f"{self.spec_min_match}..{self.spec_max_match}"
            )
        if self.drafter is None:
            self.drafter = (os.environ.get("BEE2BEE_DRAFTER") or "").strip()
        if self.drafter_seed is None:
            self.drafter_seed = self.rng_seed
        if self.drafter and not self.spec_tokens:
            raise ValueError(
                "drafter set but spec_tokens is 0: the drafter feeds the "
                "speculative verify path — set spec_tokens (--spec) too"
            )


@dataclass
class GenerationResult:
    text: str
    token_ids: list[int]
    prompt_tokens: int
    new_tokens: int
    ttft_s: float  # time to first token
    latency_s: float
    tokens_per_sec: float
    finish_reason: str  # "stop" | "length" | "eos"
    timings: dict = field(default_factory=dict)


class InferenceEngine:
    def __init__(
        self,
        model: str | model_config.ModelConfig,
        params=None,
        mesh=None,
        engine_config: EngineConfig | None = None,
        tokenizer=None,
        checkpoint_path: str | None = None,
        lora_path: str | None = None,
    ):
        # registry name, 'auto' sentinel, or checkpoint-config fallback —
        # one shared rule (models/config.resolve_model_config; the
        # reference's AutoModel any-checkpoint capability,
        # reference services.py:39-52)
        self.model_cfg = model_config.resolve_model_config(model, checkpoint_path)
        self.engine_cfg = engine_config or EngineConfig()
        # default to the degenerate 1-device mesh; multi-chip serving passes
        # an explicit mesh (the model must divide its axes — validated below)
        self.mesh = mesh if mesh is not None else local_mesh()
        # effective context BEFORE attention validation: _window_binds
        # and the validation error message both read it
        self.max_seq_len = min(self.engine_cfg.max_seq_len, self.model_cfg.max_seq_len)
        partition.validate_divisibility(self.model_cfg, self.mesh)
        # what this kind of model cannot run yet (models/support.py)
        support.require(
            self.model_cfg,
            *self._features_in_use(self.engine_cfg, self.mesh,
                                   self.max_seq_len, self.model_cfg),
            prefill_chunk=self.engine_cfg.prefill_chunk,
            max_seq_len=self.max_seq_len,
        )
        if self.model_cfg.mtp_layers and self.engine_cfg.spec_tokens > 1:
            raise ValueError(
                f"spec_tokens={self.engine_cfg.spec_tokens}: {self.model_cfg.name!r} "
                f"drafts with its {self.model_cfg.mtp_layers} multi-token-"
                "prediction layer, one token a step (spec_tokens 1, or 0 = off)"
            )
        if self.engine_cfg.attention == "auto":
            # replace, don't mutate: the caller may share one EngineConfig
            # across engines on different backends/meshes
            self.engine_cfg = dataclasses.replace(
                self.engine_cfg, attention=self._resolve_auto_attention()
            )
        self._validate_attention_impl()
        if self.engine_cfg.quantize not in ("none", "int8", "", None):
            # fail BEFORE the (multi-GB) checkpoint load, like the other
            # config validation above
            raise ValueError(
                f"quantize={self.engine_cfg.quantize!r}: only 'int8' or 'none'"
            )
        self.dtype = jnp.dtype(self.engine_cfg.dtype)
        self.metrics = MetricsAggregator()

        quantized = self.engine_cfg.quantize == "int8"
        if params is None and checkpoint_path:
            from ..models.loader import load_checkpoint

            # HOST-side: shard_params below then uploads each device only
            # its own shard (a device-side load would land the whole model
            # on device 0 first), and a quantizing engine never
            # materializes the dense model in HBM
            params = load_checkpoint(
                checkpoint_path, self.model_cfg, dtype=self.dtype, host=True
            )
        if params is None:
            params = self._init_random_params()
        if lora_path:
            # base + trained low-rank deltas, merged BEFORE quantization so
            # int8 scales see the finetuned weights (train/lora.py)
            from ..train.lora import load_adapters, merge_lora

            adapters, lcfg = load_adapters(lora_path)
            params = merge_lora(params, adapters, lcfg)
        if quantized:
            from ..models.quant import quantize_params

            # device_get is a no-op for the host-loaded checkpoint path;
            # random-init params (tests/demos) do round-trip, but anything
            # that fit dense at init fits trivially
            params = quantize_params(jax.device_get(params))
        if (
            jax.default_backend() == "cpu"
            and all(n == 1 for n in self.mesh.shape.values())
        ):
            # CPU fallback serving: unstack [L, ...] layers into per-layer
            # contiguous arrays. XLA:CPU can't pre-pack a GEMM operand it
            # must slice out of the stacked array inside the graph — every
            # layer dot drops to a naive kernel (measured 20x per block on
            # distilgpt2 decode). Unrolled layers compile O(L) but CPU
            # compiles fast; TPU keeps the stacked lax.scan (core.forward).
            params = core.unstack_layers(jax.device_get(params), self.model_cfg)
        self.params = partition.shard_params(params, self.mesh, cfg=self.model_cfg)
        self.tokenizer = tokenizer or load_tokenizer(checkpoint_path, self.model_cfg.vocab_size)

        self._replicated = NamedSharding(self.mesh, P())
        # engine economics plane (engine/introspect.py, ISSUE 15): the
        # retrace sentinel every jit root below registers with, the HBM
        # ledger, and the MFU/goodput meter the scheduler feeds. Built
        # BEFORE the jits so their compiles count from call one.
        from .introspect import EngineIntrospection

        self.introspect = EngineIntrospection(self.model_cfg, self.mesh)
        self.introspect.ledger.register("weights", lambda: self.params)
        # the declared compile space — THE warm-up/bucket-growth contract
        # the sentinel enforces: prefill widths are the configured buckets
        # (clipped to context) + the chunked-prefill width, batch sizes
        # the scheduler's pow2 grow ladder. A shape outside these through
        # a registered root is a steady-state retrace (typed incident).
        prefill_widths = {
            b for b in self.engine_cfg.prefill_buckets if b <= self.max_seq_len
        } | {self.max_seq_len}
        if self.engine_cfg.prefill_chunk:
            prefill_widths.add(self.engine_cfg.prefill_chunk)
        # ... by the rows of a grouped prefill: [1, width] for every width,
        # [n, bucket] on the group ladder
        self._declared_prefill_shapes = frozenset(
            (n, w) for w in prefill_widths for n in self.prefill_group_rows(w)
        )
        # batch buckets: the CLOSURE of {1} under the scheduler's actual
        # resize ops — grow min(2b, max_batch), shrink max(1, b//2) — so
        # a non-pow2 max_batch's shrink ladder (6 -> 3 -> 1) is declared
        # warm-up, not a false storm
        mb = self.engine_cfg.max_batch
        sizes: set[int] = set()
        frontier = {1, mb}
        while frontier:
            b = frontier.pop()
            if b in sizes:
                continue
            sizes.add(b)
            frontier.add(min(2 * b, mb))
            frontier.add(max(1, b // 2))
        self._declared_batch_sizes = frozenset(sizes)
        # stored programs of this engine's roots (engine/programs.py): what
        # their text depends on beside the call's own signature and the
        # build, and the devices they run on
        self.stored_programs = functools.partial(
            StoredPrograms,
            salt=repr((self.model_cfg, self.engine_cfg, dict(self.mesh.shape))),
            devices=list(self.mesh.devices.flat),
        )
        # one jit object; it specializes per tokens shape (= rows x bucket).
        # The programs the boot warm-up makes resident are loaded executables
        # (scheduler.warm_prefill); every other shape compiles on first use
        self._prefill = self.stored_programs(
            "prefill",
            self.introspect.sentinel.watch(
                "prefill",
                jax.jit(self._prefill_fn, donate_argnums=(2,),
                        donate_argnames=("state",)),
                key_fn=self._prefill_key,
                allowed=lambda key: key[:2] in self._declared_prefill_shapes,
            ),
            self._prefill_key,
        )
        # speculative-decode verify step: [B, K+1] forward through the
        # same cache write paths, donated like the decode cache
        self._spec_verify = self.introspect.sentinel.watch(
            "spec_verify",
            jax.jit(self._spec_verify_fn, donate_argnums=(4,)),
            key_fn=self._spec_verify_key,
            allowed=lambda key: (
                key[0] in self._declared_batch_sizes
                and key[1] == self.engine_cfg.spec_tokens
            ),
        )
        # the verify WINDOW of an engine that drafts for itself (mtp_on): up
        # to decode_chunk verify steps in one dispatch, the draft fed back on
        # the chip; such a node compiles this root INSTEAD of the one above
        self._spec_window = self.introspect.sentinel.watch(
            "spec_verify",
            jax.jit(self._spec_window_fn, donate_argnums=(5,)),
            key_fn=self._spec_window_key,
        )
        self._state_zeros = jax.jit(
            functools.partial(prog_scope("prog.pool")(core.init_ssm_state),
                              self.model_cfg, dtype=self.dtype),
            static_argnums=0,
        )
        self._rng = jax.random.key(self.engine_cfg.rng_seed)
        # jitted split: an eager jax.random.split is a dispatch of its
        # own, and _next_key runs on every admission/window (the cost of
        # an eager op is not measured on the current machine)
        self._split_key = jax.jit(
            prog_scope("prog.sample")(lambda k: tuple(jax.random.split(k))))
        # gateways run execute() on a thread pool: guard the rng stream and
        # lazy scheduler creation (jax itself is safe for concurrent dispatch)
        self._mutex = threading.Lock()
        self._scheduler = None  # created on first generate (allocates the
        # shared [max_batch] cache — engines built only for score()/info
        # never pay for it)
        # batched multi-LoRA serving: the hot-swap pool (adapters/pool.py).
        # Construction is cheap — device factors allocate at the first
        # load_adapter, whose rank/targets fix the pool geometry.
        self.adapter_pool = None
        if self.engine_cfg.max_adapters > 0:
            from ..adapters.pool import AdapterPool

            self.adapter_pool = AdapterPool(
                self.model_cfg, self.engine_cfg.max_adapters
            )
            # HBM ledger: the stacked A/B factors + scales are the
            # "adapter pool vs KV pool" squeeze the ledger exists to
            # show ((None, None) before the first load reads as 0)
            self.introspect.ledger.register(
                "adapter_pool", lambda: self.adapter_pool.device_args()
            )
        # model-tier drafter (engine/drafter.py): loaded RESIDENT beside
        # the target, tokenizer-compat gated (typed DrafterLoadError at
        # boot — never a silent garbage-draft loop at serve time).
        # "mesh" loads nothing here: the scheduler builds the MeshDrafter
        # client and meshnet/draft.py attaches the transport.
        self.drafter_model = None
        if self.engine_cfg.drafter and self.engine_cfg.drafter != "mesh":
            from .drafter import DraftModel, validate_drafter_compat

            spec = self.engine_cfg.drafter
            ckpt = spec if os.path.exists(spec) else None
            self.drafter_model = DraftModel(
                "auto" if ckpt else spec,
                spec_tokens=self.engine_cfg.spec_tokens,
                batch=self.engine_cfg.max_batch,
                target_max_seq_len=self.max_seq_len,
                dtype=self.dtype,
                seed=self.engine_cfg.drafter_seed,
                checkpoint_path=ckpt,
                sentinel=self.introspect.sentinel,
            )
            validate_drafter_compat(
                self.model_cfg, self.tokenizer, self.drafter_model.cfg,
                self.drafter_model.tokenizer or self.tokenizer,
            )
            self.introspect.ledger.register(
                "drafter", lambda: self.drafter_model.hbm_source()
                if self.drafter_model is not None else None
            )

    def _init_random_params(self):
        """Seeded random weights, generated ALREADY SHARDED over the mesh
        (core.init_params's out_shardings): each device creates only its
        own shard, so a model that needs the whole mesh to fit never
        lands on one device first."""
        key = jax.random.key(self.engine_cfg.rng_seed)
        shapes = jax.eval_shape(
            lambda: core.init_params(self.model_cfg, key, dtype=self.dtype)
        )
        return core.init_params(
            self.model_cfg, key, dtype=self.dtype,
            out_shardings=partition.param_shardings(
                shapes, self.mesh, self.model_cfg
            ),
        )

    # ------------------------------------------------------------ compiled fns

    @staticmethod
    def _prefill_key(params, tokens, cache, true_len, offset,
                     block_tables=None, write_floor=None, write_ceil=None,
                     adapters=None, aids=None, ascales=None, state=None,
                     mtp_next=None):
        """Sentinel shape key for the prefill root: the dims that select
        a compiled variant — batch rows, the padded token width (the
        bucket), the block-table width bucket, and the None-flags of the
        optional operands (each flag is a distinct legitimate trace: a
        recurrent row's carried state among them)."""
        return (
            int(tokens.shape[0]), int(tokens.shape[1]),
            None if block_tables is None else int(block_tables.shape[1]),
            write_floor is not None, write_ceil is not None,
            adapters is not None, state is not None,
        ) + ((True,) if mtp_next is not None else ())

    @staticmethod
    def _spec_verify_key(params, cur, drafts, draft_lens, cache, offsets,
                         temps, topks, topps, minps=None, key=None,
                         tables=None, adapters=None, aids=None, ascales=None,
                         counts=None, reps=None, press=None, freqs=None):
        """Sentinel shape key for the spec-verify root: batch bucket,
        draft width K, and the optional-operand flags (counts rides along
        when the batch holds penalized rows — the fused-root discipline,
        docs/PERF.md "Decode hot loop")."""
        return (
            int(cur.shape[0]), int(drafts.shape[1]),
            minps is not None,
            None if tables is None else int(tables.shape[1]),
            adapters is not None,
            counts is not None,
        )

    @staticmethod
    def _spec_window_key(params, cur, draft, drafting, budget, cache, offsets,
                         temps, topks, topps, minps=None, key=None,
                         tables=None, adapters=None, aids=None, ascales=None,
                         counts=None, reps=None, press=None, freqs=None,
                         steps=None):
        """Sentinel shape key for the verify-window root: _spec_verify_key's
        (``steps`` is an operand's VALUE, as the decode root's: not keyed)."""
        return InferenceEngine._spec_verify_key(
            params, cur, draft, drafting, cache, offsets, temps, topks, topps,
            minps, key, tables, adapters, aids, ascales, counts)

    def _attn_fn(self):
        """attn_fn for core.forward per the engine's attention setting.
        "flash" is the ragged paged kernel (ops/ragged.py) — it reads the
        block pool directly (core.forward detects the `ragged` marker and
        skips the gathered-view build). Under a non-trivial mesh the
        pallas kernel runs per-shard via shard_map — pallas_call has no
        SPMD partitioning rule, so sharding propagation would all-gather
        it."""
        if self.engine_cfg.attention == "flash":
            from ..ops.ragged import make_ragged_attn_fn

            return make_ragged_attn_fn(self.mesh)
        if self.engine_cfg.attention == "sp":
            from ..parallel.sp_serving import make_sp_attn_fn

            return make_sp_attn_fn(self.mesh)
        return None

    def _resolve_auto_attention(self) -> str:
        """attention='auto' → 'flash' (the ragged paged kernel) when THIS
        engine's mesh devices are TPU and the head layout supports it,
        'sp' on a seq-sharded mesh, else 'dense'. Measured rationale
        (docs/PERF.md r4): flash's whole-graph compile is ~2x faster than
        dense's, and the ragged kernel never materializes the gathered
        block view or [T, S] scores. On non-TPU devices the kernel runs
        in pallas interpret mode — orders of magnitude slower than XLA's
        fused dense einsum — so those resolve to dense. The platform
        comes from the mesh, not jax.devices(): an explicit CPU mesh on
        a TPU-default host must not pick flash. Sliding windows and the
        gemma-2 score math ride the ragged kernel (mask + scalar params);
        only ALiBi stays dense-only."""
        from ..ops.ragged import validate_ragged_mesh

        if self.mesh.shape.get("seq", 1) > 1:
            # a seq axis exists for exactly one reason: sequence-parallel
            # pool sharding. flash/dense would leave the pool replicated
            # across the seq group (paged_cache_spec seq-shards only
            # under "sp") — silent 1/seq HBM-scaling loss
            if self.model_cfg.pos_embedding == "alibi":
                raise ValueError(
                    "no attention impl supports ALiBi on a seq-sharded "
                    "mesh; drop the seq axis"
                )
            if self._gemma2_score_math():
                raise ValueError(
                    "no attention impl supports gemma-2 score math "
                    "(softcap / attn_scale / alternating windows) on a "
                    "seq-sharded mesh; drop the seq axis"
                )
            if self._window_binds():
                raise ValueError(
                    f"no attention impl supports sliding_window="
                    f"{self.model_cfg.sliding_window} on a seq-sharded mesh; "
                    "drop the seq axis or serve full-causal"
                )
            logger.info("attention=auto -> sp (mesh has a seq axis)")
            return "sp"
        if self.model_cfg.pos_embedding == "alibi":
            logger.info("attention=auto -> dense (ALiBi bias: only the "
                        "dense path implements it)")
            return "dense"
        if self.mesh.devices.flat[0].platform != "tpu":
            logger.info("attention=auto -> dense (mesh devices are not TPU)")
            return "dense"
        try:
            validate_ragged_mesh(self.model_cfg, self.mesh)
        except ValueError as e:  # unsupported head layout
            logger.info("attention=auto -> dense (%s)", e)
            return "dense"
        logger.info("attention=auto -> flash (ragged paged kernel)")
        return "flash"

    def _gemma2_score_math(self) -> bool:
        """True when the model needs score math only the dense path
        implements: attention-logit softcap, a non-head_dim score scale,
        or per-layer window alternation (gemma-2)."""
        cfg = self.model_cfg
        return bool(
            cfg.attn_logit_softcap
            or (cfg.attn_scale and cfg.attn_scale != cfg.head_dim)
            or (cfg.sliding_window and cfg.sliding_window_every > 1)
        )

    def _window_binds(self) -> bool:
        """True iff the model's sliding window can actually mask a cache
        position at THIS engine's context length. zephyr/mistral ship
        window == max context (4096): with cache capacity <= 4096 the
        window clause is always true and full-causal kernels are exact —
        rejecting flash/sp there would be a pure perf regression."""
        w = self.model_cfg.sliding_window
        return bool(w) and w < self.max_seq_len

    def _validate_attention_impl(self):
        if (self.engine_cfg.attention in ("flash", "sp")
                and self.model_cfg.pos_embedding == "alibi"):
            raise ValueError(
                f"attention={self.engine_cfg.attention!r} does not implement "
                f"the ALiBi score bias ({self.model_cfg.name!r}); use "
                "attention='dense' (the kernels would silently drop the "
                "per-head position bias)"
            )
        if self.engine_cfg.attention == "sp" and self._gemma2_score_math():
            # the RAGGED kernel (flash) carries softcap/attn_scale as
            # scalar params and the window alternation via the dense
            # path's mask; sp's partial-merge math hardcodes 1/sqrt(hd)
            raise ValueError(
                f"attention='sp' does not implement gemma-2's score math "
                f"({self.model_cfg.name!r}: attention softcap / "
                "query_pre_attn_scalar / alternating windows); use "
                "attention='dense' or 'flash' — the sp partials hardcode "
                "1/sqrt(hd) and no tanh cap, so logits would silently "
                "diverge"
            )
        if self.engine_cfg.attention == "sp" and self._window_binds():
            raise ValueError(
                f"attention='sp' does not implement sliding_window="
                f"{self.model_cfg.sliding_window} at context "
                f"{self.max_seq_len} ({self.model_cfg.name!r}); use "
                "attention='dense' or 'flash' (sp would silently attend "
                "beyond the window)"
            )
        if (self.engine_cfg.attention in ("dense", "flash")
                and self.mesh.shape.get("seq", 1) > 1):
            # a seq axis shards the pool's slot dim only under 'sp';
            # dense/flash would silently serve a pool REPLICATED across
            # the whole seq group — the exact 1/seq HBM loss the axis
            # exists to avoid (the pre-round-8 paged guard, re-anchored)
            raise ValueError(
                f"attention={self.engine_cfg.attention!r} does not shard "
                "the paged pool over a seq axis; use attention='sp' or "
                "drop the seq axis"
            )
        if self.engine_cfg.attention == "flash":
            from ..ops.ragged import validate_ragged_mesh

            validate_ragged_mesh(self.model_cfg, self.mesh)
        elif self.engine_cfg.attention == "sp":
            from ..parallel.sp_serving import validate_sp_mesh

            validate_sp_mesh(self.model_cfg, self.engine_cfg, self.mesh)

    @staticmethod
    def _features_in_use(ec: EngineConfig, mesh, max_seq_len: int,
                         cfg=None) -> set[str]:
        """The features a configuration turns on, by the names
        models/support.REFUSED knows them under: ONE condition a feature.
        (pipeline_stages, kv_export and the paths that walk the layers
        themselves are asked about where they are built.) A model with a
        multi-token-prediction layer (``cfg``) speculates with THAT
        (``spec_mtp``, which no kind refuses), not with the n-gram floor."""
        axis = mesh.shape.get
        own = bool(cfg is not None and cfg.mtp_layers)
        on = {
            "kv_int8": jnp.dtype(ec.cache_dtype) == jnp.int8,
            "weight_int8": ec.quantize == "int8",
            "spec_ngram": ec.spec_tokens > 0 and not own,
            "spec_mtp": ec.spec_tokens > 0 and own,
            "spec_model_drafter": bool(ec.drafter),
            "spec_mesh_drafter": ec.drafter == "mesh",
            "seq_attention": axis("seq", 1) > 1 or ec.attention == "sp",
            "mesh_model": axis("model", 1) > 1,
            "mesh_expert": axis("expert", 1) > 1,
            "multi_lora": ec.max_adapters > 0,
            "prefix_cache": ec.prefix_cache_entries > 0,
            # a chunk that does not divide the context re-anchors its last
            # window over tokens already fed
            "prefill_chunk": bool(ec.prefill_chunk
                                  and max_seq_len % ec.prefill_chunk),
        }
        return {feature for feature, used in on.items() if used}

    @property
    def mtp_on(self) -> bool:
        """Does this engine draft with the model's own multi-token-prediction
        layer (the ``mtp`` tier)? Its prefill and verify programs then run
        that layer behind the trunk."""
        return bool(self.model_cfg.mtp_layers
                    and self.engine_cfg.spec_tokens == 1)

    @property
    def state_info(self) -> dict | None:
        """The recurrent state's identity for the boot record (/providers,
        the ``boot`` line): shapes a row and dtypes — config arithmetic,
        never allocates. None for models without one."""
        cfg = self.model_cfg
        if not cfg.has_ssm:
            return None
        # as deep as the layers that HOLD a mixer (cfg.state_layers)
        ssm = (cfg.state_layers, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        conv = (cfg.state_layers, cfg.ssm_conv - 1, cfg.ssm_conv_dim)
        row = int(np.prod(ssm)) * 4 + int(np.prod(conv)) * self.dtype.itemsize
        return {
            "layers": int(cfg.state_layers),
            "ssm_row_shape": list(ssm), "ssm_dtype": "float32",
            "conv_row_shape": list(conv), "conv_dtype": str(self.dtype),
            "bytes_per_row": row,
            "max_rows": int(self.engine_cfg.max_batch),
            "bytes_at_max_rows": row * int(self.engine_cfg.max_batch),
        }

    def new_state(self, rows: int):
        """``rows`` slots of zeroed recurrent state (core.init_ssm_state),
        made under jit so nothing lands eagerly; None for models without."""
        if not self.model_cfg.has_ssm:
            return None
        return self._state_zeros(rows)

    @prog_scope("prog.prefill")
    def _prefill_fn(self, params, tokens, cache, true_len, offset,
                    block_tables=None, write_floor=None, write_ceil=None,
                    adapters=None, aids=None, ascales=None, state=None,
                    mtp_next=None):
        """tokens [B, Tb] padded; returns (cache, last_logits [B, V]). The
        rows are the requests of one admission group (scheduler._admit:
        B on PREFILL_GROUP_ROWS), each with its own `true_len`, `offset`,
        table row, `write_floor` and `write_ceil` ([B]; a scalar holds
        for every row); a DEAD row (true_len 0, write_ceil 0, a null table
        row) writes nothing and leaves its state slot as it was.
        `offset` is the global cache position of tokens[:, 0] — 0 for a
        whole-prompt prefill, the running position for chunked prefill.
        `true_len` is the valid length WITHIN this chunk. With
        `block_tables`, `cache` is the paged pool and the chunk scatters
        into the row's mapped blocks (core.forward's paged path);
        `write_floor` keeps re-fed positions below a CoW share point from
        rewriting shared donor blocks, `write_ceil` drops the padded tail
        so short prompts only claim blocks covering their real length.
        `adapters`/`aids`/`ascales` (adapters/pool.py): the row's LoRA
        factors apply to the PROMPT too — an adapted wk/wv writes
        adapter-specific K/V, which is exactly why adapter rows never
        share the base model's prefix cache (scheduler guard).
        ``state`` (recurrent models): the prefilling ROWS' state slots
        ([L, B, ...]: the previous chunk's output, donated; None for fresh
        rows, whose zero state — "no token seen" — is made here); the state
        after the chunk is returned third, in a dict that also holds an
        expert model's ``moe_stats``. The bucket's padded tail
        leaves it untouched (``valid_len``), and only the last real
        position's logits are computed.
        ``mtp_next`` [B] (an engine on the ``mtp`` tier: mtp_on): the token
        that follows the chunk's last real position where the PROMPT says it
        (a chunk that is not the walk's last), else -1: the greedy token of
        the last position's logits, which is a greedy row's first token.
        The model's multi-token-prediction layer then runs over the chunk
        behind the trunk (position t with h_t and token t+1: its K/V rows
        for the prompt) and its greedy token at the last position, the
        row's first DRAFT, rides the extras as ``mtp_draft`` [B]."""
        recurrent = self.model_cfg.has_ssm
        if recurrent and state is None:
            state = core.init_ssm_state(
                self.model_cfg, tokens.shape[0], dtype=self.dtype)
        moe = self.model_cfg.moe_dropless
        stats = len(core.moe_stats_names(self.model_cfg))
        # the head for ONE position a row (recurrent models since PR 28, and
        # latent-attention ones: at a 129,280-token vocabulary the full
        # [1, 512, V] logits are 0.27 GB; smallthinker's [1, 2048, 151,936]
        # would be 1.24 GB); phi-3's programs stay as they were
        one_logit = (recurrent or self.model_cfg.has_mla or moe
                     or mtp_next is not None)
        last = jnp.maximum(jnp.asarray(true_len, jnp.int32) - 1, 0)  # dead row: 0
        if moe:  # the forward's expert-layer counters: extras["moe_stats"]
            cache = dict(cache, moe_stats=jnp.zeros((stats,), jnp.int32))
        logits, cache, *hidden = core.forward(
            params, self.model_cfg, tokens,
            dict(cache, **state) if recurrent else cache, offset,
            attn_fn=self._attn_fn(), block_tables=block_tables,
            paged_write_floor=write_floor, paged_write_ceil=write_ceil,
            adapters=adapters, adapter_ids=aids, adapter_scales=ascales,
            valid_len=true_len if recurrent else None,
            last_index=last if one_logit else None,
            **({"return_hidden": True} if mtp_next is not None else {}),
        )
        draft = None
        if mtp_next is not None:
            # the tokens that follow the chunk's positions: the prompt's own,
            # and at the last real one the prompt's next or the greedy token
            rows = jnp.arange(tokens.shape[0])
            follow = jnp.where(mtp_next >= 0, mtp_next,
                               jnp.argmax(logits[:, 0, :], axis=-1))
            nxt = jnp.roll(tokens, -1, axis=1).at[rows, last].set(
                follow.astype(tokens.dtype))
            mtp_logits, cache = core.mtp_forward(
                params, self.model_cfg, hidden[0], nxt, cache, offset,
                attn_fn=self._attn_fn(), block_tables=block_tables,
                paged_write_floor=write_floor, paged_write_ceil=write_ceil,
                last_index=last,
            )
            draft = jnp.argmax(mtp_logits[:, 0, :], axis=-1).astype(jnp.int32)
        # what the chunk hands back beside the pool, by NAME: the row's
        # recurrent state and / or the expert layers' counters
        extras = {k: cache.pop(k) for k in
                  tuple(state or ()) + (("moe_stats",) if moe else ())}
        if draft is not None:
            extras["mtp_draft"] = draft
        if not one_logit:
            idx = last.reshape(-1, 1, 1)  # [B,1,1]
            with jax.named_scope("head.logits"):  # the head's one position a row
                logits = jnp.take_along_axis(logits, jnp.broadcast_to(idx, (logits.shape[0], 1, logits.shape[2])), axis=1)
        if extras:
            return cache, logits[:, 0, :], extras
        return cache, logits[:, 0, :]

    @prog_scope("prog.verify")
    def _spec_verify_fn(self, params, cur, drafts, draft_lens, cache, offsets,
                        temps, topks, topps, minps, key, tables=None,
                        adapters=None, aids=None, ascales=None,
                        counts=None, reps=None, press=None, freqs=None):
        """Speculative-decode verify: one [B, K+1] forward checks a whole
        draft. Returns (next_tok [B], cache, accepted [B]) — plus the
        updated ``counts`` when penalty bookkeeping rides along.

        ``cur`` [B] is each row's last accepted token, ``drafts`` [B, K]
        the proposed continuations (padded with zeros past
        ``draft_lens`` [B]). The chunk [cur | drafts] runs through the
        SAME cache write path as decode (rectangular vmapped
        dynamic-update or paged block scatter via ``tables``) at each
        row's offset. Position j's logits predict token j+1, so a draft
        token is correct iff it equals the greedy argmax one position
        earlier; ``accepted`` is the longest such prefix (capped at
        draft_lens — pad positions never count). The returned token is
        sampled from the logits AT the accept position: for greedy rows
        that is exactly the argmax plain decode would have produced
        (token-for-token parity), for non-drafting sampled rows
        (draft_lens == 0) it is their normal one-token sample from
        position 0. Rejected positions hold stale K/V but sit at/past
        the row's new offset (offset + accepted + 1), where the causal
        invariant masks or overwrites them — rollback costs nothing.

        An engine on the ``mtp`` tier (mtp_on) runs the model's multi-token-
        prediction layer in the SAME program, behind the verdict: over the
        chunk's positions with the tokens that follow them (the accepted
        drafts, then the token just chosen), so its K/V rows of every
        accepted position are final and a rejected position's is rewritten
        when that position comes round again (the trunk's own invariant);
        its greedy token at the last ACCEPTED position is the next step's
        draft. A dict {``mtp_draft`` [B], ``moe_stats``} is then returned
        fourth, before the counts: no draft dispatch, no hidden state on
        the host.
        """
        own = self.mtp_on
        if own and self.model_cfg.moe_dropless:
            cache = dict(cache, moe_stats=jnp.zeros(
                (len(core.moe_stats_names(self.model_cfg)),), jnp.int32))
        nxt, cache, accepted, draft, counts = self._verify_step(
            params, cur, drafts, draft_lens, cache, offsets, temps, topks,
            topps, minps, key, tables, adapters, aids, ascales,
            counts, reps, press, freqs)
        extras = {"mtp_draft": draft} if own else None
        if own and "moe_stats" in cache:
            extras["moe_stats"] = cache.pop("moe_stats")
        return tuple(x for x in (nxt, cache, accepted, extras, counts)
                     if x is not None)

    def _verify_step(self, params, cur, drafts, draft_lens, cache, offsets,
                     temps, topks, topps, minps, key, tables, adapters, aids,
                     ascales, counts, reps, press, freqs):
        """ONE verify step, the body both verify roots run (_spec_verify_fn:
        a step a call; _spec_window_fn: a step a turn of its loop) ->
        (next_tok [B], cache, accepted [B], the MTP layer's next draft [B] |
        None, counts | None). An expert model's ``moe_stats`` ride ``cache``
        through it and are the root's to zero and to pop."""
        from .sampling import sample_batched

        B, K = drafts.shape
        own = self.mtp_on
        tokens = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, K+1]
        with jax.named_scope("spec.verify"):
            logits, cache, *hidden = core.forward(
                params, self.model_cfg, tokens, cache, offsets,
                attn_fn=self._attn_fn(), block_tables=tables,
                adapters=adapters, adapter_ids=aids, adapter_scales=ascales,
                **({"return_hidden": True} if own else {}),
            )
        with jax.named_scope("spec.accept"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
            pos = jnp.arange(K, dtype=jnp.int32)[None, :]
            match = (drafts == greedy[:, :-1]) & (pos < draft_lens[:, None])
            # longest all-match prefix: cumprod zeroes everything after the
            # first mismatch, the sum counts the survivors
            accepted = jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            # (a select over K + 1: no element-wise gather a step)
            last = core.take_position(logits, accepted)[:, 0, :]
        if counts is not None:
            # fused penalty bookkeeping (docs/PERF.md "Decode hot loop"): a
            # penalized row never drafts (scheduler._spec_eligible), so its
            # accepted is 0 and the draft bump below is a masked no-op for it;
            # non-drafting rows still need their ACCEPTED drafts counted so
            # the shared [B,2,V] gen-counts stay coherent across the batch.
            gain = (pos < accepted[:, None]).astype(counts.dtype)  # [B, K]
            counts = counts.at[jnp.arange(B)[:, None], 1, drafts].add(gain)
        nxt = sample_batched(last, key, temps, topks, topps, minps,
                             counts, reps, press, freqs).astype(jnp.int32)
        if counts is not None:
            counts = counts.at[jnp.arange(B), 1, nxt].add(1)
        if not own:
            return nxt, cache, accepted, None, counts
        # the MTP layer behind the verdict: position j of the chunk with the
        # token that follows it
        follow = jnp.where(
            jnp.arange(K + 1, dtype=jnp.int32)[None, :] < accepted[:, None],
            jnp.concatenate([drafts, nxt[:, None]], axis=1), nxt[:, None])
        mtp_logits, cache = core.mtp_forward(
            params, self.model_cfg, hidden[0], follow, cache, offsets,
            attn_fn=self._attn_fn(), block_tables=tables,
            last_index=accepted,
        )
        draft = jnp.argmax(mtp_logits[:, 0, :], axis=-1).astype(jnp.int32)
        return nxt, cache, accepted, draft, counts

    @prog_scope("prog.verify")
    def _spec_window_fn(self, params, cur, draft, drafting, budget, cache,
                        offsets, temps, topks, topps, minps, key, tables=None,
                        adapters=None, aids=None, ascales=None, counts=None,
                        reps=None, press=None, freqs=None, *, steps):
        """The verify WINDOW of an engine that drafts for itself (mtp_on):
        ``steps`` verify steps (an int32 scalar OPERAND, 1 <= steps <= N =
        decode_chunk; the decode root's pattern, scheduler._decode_fn) in ONE
        program, each _verify_step on what the step before left: ``cur`` <-
        its token, ``draft`` [B, K] <- its MTP layer's draft, ``offsets`` <-
        offsets + accepted + 1. Step i draws with key i of the N split from
        ``key`` and computes what the i-th of ``steps`` chained _spec_verify
        calls would: same positions, same pool rows (a rejected position's
        K/V and MTP row are rewritten when the position comes round), same
        keys at the same steps.

        A step's draft lengths are made here: ``drafting`` [B] marks the rows
        that draft at all (greedy, unpenalised, on the tier: fixed for the
        window; the others ride with length 0, one token a step) and
        ``budget`` [B] is each row's remaining tokens, less what the window
        has given it so far: a row at its last token does not draft.

        Returns (cur', cache', offsets', counts', toks [N, B, K+1], accepted
        [N, B], draft' [B, K], extras): row b's tokens of step i are
        toks[i, b, :accepted[i, b]] (the drafts kept) and toks[i, b, K] (the
        step's own); only the first ``steps`` entries hold a step. ``extras``
        is an expert model's ``moe_stats`` summed over the steps, else None."""
        B, K = draft.shape
        N = self.engine_cfg.decode_chunk
        if self.model_cfg.moe_dropless:
            cache = dict(cache, moe_stats=jnp.zeros(
                (len(core.moe_stats_names(self.model_cfg)),), jnp.int32))
        keys = jax.random.split(key, N)
        start = offsets

        def step(i, carry):
            cur, draft, cache, off, cnt, toks, accs = carry
            lens = jnp.where(drafting > 0,
                             jnp.clip(budget - (off - start) - 1, 0, K), 0)
            nxt, cache, accepted, made, cnt = self._verify_step(
                params, cur, draft, lens, cache, off, temps, topks, topps,
                minps, keys[i], tables, adapters, aids, ascales,
                cnt, reps, press, freqs)
            toks = jax.lax.dynamic_update_index_in_dim(
                toks, jnp.concatenate([draft, nxt[:, None]], axis=1), i, 0)
            accs = jax.lax.dynamic_update_index_in_dim(accs, accepted, i, 0)
            return (nxt, jnp.broadcast_to(made[:, None], draft.shape), cache,
                    off + accepted + 1, cnt, toks, accs)

        cur, draft, cache, offsets, counts, toks, accs = jax.lax.fori_loop(
            0, steps, step,
            (cur, draft, cache, offsets, counts,
             jnp.zeros((N, B, K + 1), jnp.int32), jnp.zeros((N, B), jnp.int32)),
        )
        extras = ({"moe_stats": cache.pop("moe_stats")}
                  if "moe_stats" in cache else None)
        return cur, cache, offsets, counts, toks, accs, draft, extras

    # ------------------------------------------------------------ helpers

    def prefill_group_rows(self, bucket: int) -> tuple[int, ...]:
        """The row counts a prefill program of this padded width may have,
        ascending: the group ladder inside the token budget, 1 alone for a
        width over the ladder's widest bucket."""
        if bucket > PREFILL_GROUP_MAX_BUCKET:
            return (1,)
        return tuple(n for n in PREFILL_GROUP_ROWS
                     if n == 1 or n * bucket <= PREFILL_GROUP_TOKENS)

    def _bucket_for(self, n: int) -> int:
        for b in self.engine_cfg.prefill_buckets:
            if b >= n and b <= self.max_seq_len:
                return b
        return self.max_seq_len

    def _fit_spec(self, spec: P, shape: tuple[int, ...]) -> P:
        """Fall back axis-by-axis when a dim doesn't divide its mesh axis
        (e.g. batch=1 on a data=2 mesh) instead of crashing device_put."""
        return P(*[
            e if e is None or shape[i] % self.mesh.shape.get(e, 1) == 0 else None
            for i, e in enumerate(spec)
        ])

    # ---- paged-pool geometry (engine/paged.py holds the allocator) ----

    @property
    def blocks_per_row(self) -> int:
        """Max pool blocks one row can map: capacity plus the decode-chunk
        overshoot (a readback window may write up to decode_chunk - 2
        positions past capacity before the host sees the stop; the row
        owns real blocks for that overshoot — an out-of-table position
        would otherwise depend on jax's OOB gather/scatter defaults
        instead of landing in a block the row owns)."""
        from .paged import ceil_div

        return ceil_div(
            self.max_seq_len + self.engine_cfg.decode_chunk,
            self.engine_cfg.kv_block_size,
        )

    @property
    def pool_blocks(self) -> int:
        """Total pool blocks: explicit kv_pool_blocks, or sized so the
        free list cannot run dry (null block + max_batch full rows +
        worst-case pinned prefix entries)."""
        from .paged import ceil_div

        if self.engine_cfg.kv_pool_blocks is not None:
            return self.engine_cfg.kv_pool_blocks
        pin = ceil_div(self.max_seq_len, self.engine_cfg.kv_block_size)
        return (
            1
            + self.engine_cfg.max_batch * self.blocks_per_row
            + self.engine_cfg.prefix_cache_entries * pin
        )

    @property
    def kv_info(self) -> dict:
        """KV-pool identity (ISSUE 12 drive-by): which cache layout this
        engine runs and its effective capacity — rides engine.info AND
        the telemetry digest, so /mesh/health and the router see which
        peers serve the doubled int8 pool, not just a raw block count
        whose bytes-per-block they can't know. Pure config arithmetic —
        never allocates the pool or the scheduler."""
        return {
            "cache_dtype": str(jnp.dtype(self.engine_cfg.cache_dtype)),
            "block_size": int(self.engine_cfg.kv_block_size),
            "pool_blocks": int(self.pool_blocks),
            # usable tokens (block 0 is the reserved null block)
            "capacity_tokens": int(
                (self.pool_blocks - 1) * self.engine_cfg.kv_block_size
            ),
            # what a token stores (core.pool_layout), as published: a
            # lane-aligned pool's arrays hold more (engine.hbm_bytes)
            "layout": {n: list(hw) for n, hw in
                       core.pool_layout(self.model_cfg).items()},
            # layers of cache a token holds (a looped stack: passes x layers)
            "cache_layers": int(self.model_cfg.cache_layers),
            "bytes_per_token": core.pool_bytes_per_token(
                self.model_cfg,
                jnp.dtype(self.engine_cfg.cache_dtype).itemsize),
        }

    @property
    def kv_quantized(self) -> bool:
        """True when the pool stores int8 pages + per-page-per-head
        scales (EngineConfig.cache_dtype='int8' / --kv-quant)."""
        return jnp.dtype(self.engine_cfg.cache_dtype) == jnp.int8

    @property
    def kv_in_place(self) -> bool:
        """True where core.forward writes and reads the pool in place with
        the ragged kernels alone (ops/ragged.py "Layouts"): the ragged
        reader over a float pool."""
        return self.engine_cfg.attention == "flash" and not self.kv_quantized

    def new_pool(self):
        """The paged KV block pool, placed with the kv-head `model` spec
        (partition.paged_cache_spec) so TP serving gathers stay local;
        under attention='sp' the slot dim additionally shards over `seq`
        (per-device pool memory 1/seq — the long-context scaling). An
        int8 pool (cache_dtype='int8') carries its kv_scale array,
        sharded like the pool's kv-head dim (partition.paged_scale_spec).
        The in-place path's pool is lane-aligned (below)."""
        from ..ops.flash import interpret_off_tpu

        make = functools.partial(
            core.init_paged_pool,
            self.model_cfg, self.pool_blocks, self.engine_cfg.kv_block_size,
            jnp.dtype(self.engine_cfg.cache_dtype),
            # on a TPU the in-place path's pool is stored in the kernels'
            # layout (core.init_paged_pool)
            lane_aligned=(
                self.kv_in_place and not interpret_off_tpu(self.mesh)
            ),
        )
        spec = partition.paged_cache_spec(
            self.model_cfg, self.mesh,
            seq_sharded=self.engine_cfg.attention == "sp",
        )
        sspec = partition.paged_scale_spec(self.model_cfg, self.mesh)
        shardings = {
            name: NamedSharding(
                self.mesh,
                self._fit_spec(
                    sspec if name.endswith("_scale") else spec, arr.shape),
            )
            for name, arr in jax.eval_shape(make).items()
        }
        # created under jit ALREADY SHARDED, like the weights: zeros made
        # eagerly land whole on the default device first (on model:4 that
        # was a multi-GB transient on chip 0 beside its share of the model)
        return jax.jit(make, out_shardings=shardings)()

    def _next_key(self):
        with self._mutex:
            self._rng, sub = self._split_key(self._rng)
            return sub

    # ---------------------------------------------- multi-adapter serving

    def load_adapter(self, name: str, adapters: dict | None = None,
                     lcfg=None, path: str | None = None) -> int:
        """Pin one LoRA adapter into the hot-swap pool (fresh load,
        in-place refresh, or LRU-evicting a cold adapter) WITHOUT
        restarting the engine — in-flight generations keep the factors
        they were dispatched with. Pass (adapters, lcfg) directly (the
        DHT fetch path) or ``path`` to an adapter .npz, whose versioned
        sha256 manifest is verified on read. Typed AdapterLoadError on a
        corrupt/mismatched adapter; returns the pool slot."""
        if self.adapter_pool is None:
            raise RuntimeError(
                "multi-adapter serving is off (EngineConfig.max_adapters=0)"
            )
        if path is not None:
            from ..train.lora import load_adapters

            adapters, lcfg = load_adapters(path, model_cfg=self.model_cfg)
        if adapters is None or lcfg is None:
            raise ValueError("load_adapter needs (adapters, lcfg) or path")
        return self.adapter_pool.load(name, adapters, lcfg)

    def unload_adapter(self, name: str) -> bool:
        """Evict a resident adapter; AdapterPoolBusy while rows are in
        flight on it (the refcount hot-swap guard)."""
        if self.adapter_pool is None:
            return False
        return self.adapter_pool.evict(name)

    def has_adapter(self, name: str) -> bool:
        return self.adapter_pool is not None and self.adapter_pool.has(name)

    def resident_adapters(self) -> list[str]:
        return self.adapter_pool.resident() if self.adapter_pool else []

    # ------------------------------------------------------------ public API

    @property
    def scheduler(self):
        """The continuous-batching scheduler (lazy: allocates the shared
        [max_batch] KV cache on first use)."""
        if self._scheduler is None:
            from .scheduler import BatchScheduler

            with self._mutex:
                if self._scheduler is None:
                    self._scheduler = BatchScheduler(
                        self, max_batch=self.engine_cfg.max_batch
                    )
        return self._scheduler

    def close(self):
        """Stop the scheduler thread (idempotent). The swap happens under
        _mutex (so a concurrent lazy creation can't be missed) but
        shutdown() runs outside it — the scheduler thread takes _mutex in
        _next_key, so joining while holding it would stall."""
        with self._mutex:
            sch, self._scheduler = self._scheduler, None
        if sch is not None:
            sch.shutdown()
        if self.drafter_model is not None:
            self.drafter_model.close()
            self.drafter_model = None
        # drop out of the economics digest (a closed engine must not keep
        # its params pinned through the ledger, nor report stale gauges)
        self.introspect.close()

    @staticmethod
    def _event_error(ev: dict) -> Exception:
        """Typed exception for a failed-generation event: an admission-
        race unknown_adapter keeps its type across the event queue (the
        serving surfaces map it to 404 / a typed gen_error) — everything
        else stays the generic RuntimeError."""
        if ev.get("error_kind") == "unknown_adapter":
            from ..adapters.pool import UnknownAdapter

            return UnknownAdapter(ev.get("error", "unknown adapter"))
        return RuntimeError(ev.get("error", "generation failed"))

    def _stop_set(self, stop_tokens):
        stop = set(int(t) for t in (stop_tokens or []))
        eos = self.tokenizer.eos_token_id
        if eos is not None and eos >= 0:
            stop.add(int(eos))
        return stop, eos

    def _make_request(
        self, prompt, max_new_tokens, temperature, top_k, top_p, stop_tokens,
        stream: bool = False, repetition_penalty: float = 1.0,
        presence_penalty: float = 0.0, frequency_penalty: float = 0.0,
        min_p: float = 0.0, tenant: str = "default",
        adapter: str | None = None,
    ):
        from .scheduler import Request

        ids = self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        # clamp generation to what the cache can hold while keeping at least
        # a small prompt window (callers may pass max_new_tokens == cache
        # size; clamping, not erroring, is the serving behavior)
        min_prompt = max(1, min(len(ids), 16))
        max_gen = self.max_seq_len - 1 - min_prompt
        if max_gen < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves no room in "
                f"max_seq_len={self.max_seq_len}"
            )
        max_new_tokens = max(0, min(max_new_tokens, max_gen))
        # left-truncate so prompt + generation fits the cache (the reference
        # simply OOMs/errors here; we keep the most recent context)
        budget = self.max_seq_len - 1 - max(max_new_tokens, 1)
        if len(ids) > budget:
            ids = ids[-budget:]
        if repetition_penalty is not None and repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {repetition_penalty}"
            )
        if min_p is not None and not (0.0 <= min_p <= 1.0):
            # min_p > 1 would mask EVERY token (floor above the max prob)
            # and degenerate to token 0 — reject, don't silently garble
            raise ValueError(f"min_p must be in [0, 1], got {min_p}")
        if adapter:
            # typed BEFORE submission (UnknownAdapter → /v1 404, p2p
            # unknown_adapter): serving is off, or the adapter is not
            # resident and nothing upstream (node.ensure_adapter) paged
            # it in. The admission-time acquire still re-checks — an
            # eviction can race a queued request.
            from ..adapters.pool import UnknownAdapter

            if self.adapter_pool is None:
                raise UnknownAdapter(
                    f"adapter {adapter!r}: multi-adapter serving is off "
                    "(EngineConfig.max_adapters=0)"
                )
            if not self.adapter_pool.has(adapter):
                raise UnknownAdapter(f"adapter {adapter!r} is not resident")
        stop, eos = self._stop_set(stop_tokens)
        # the gateway's timeline record, when this call runs under one
        # that no engine request has taken yet (one record, one request)
        timing = current_timing()
        if timing is not None and timing.t_submit:
            timing = None
        return Request(
            ids, max_new_tokens, temperature, top_k, top_p, stop, eos,
            self.tokenizer, stream=stream,
            repetition_penalty=repetition_penalty,
            presence_penalty=presence_penalty,
            frequency_penalty=frequency_penalty,
            min_p=min_p,
            tenant=tenant,
            adapter=adapter,
            timing=timing,
        )

    def _build_result(self, req) -> GenerationResult:
        t = req.timing
        t_first = t.t_first or t.t_done
        latency = t.t_done - t.t_submit
        decode_time = t.t_done - t_first
        n_out = len(req.out_ids)
        tps = n_out / decode_time if decode_time > 0 and n_out else 0.0
        self.metrics.record(n_out, latency)
        ttft_ms = (t_first - t.t_submit) * 1000.0
        if n_out or req.finish != "cancelled":
            # a request cancelled while still queued never produced a
            # token: its "ttft" would be the client's abandon wait, which
            # would skew the serving distributions under cancel bursts
            _H_TTFT.observe(ttft_ms)
            _H_E2E.observe(latency * 1000.0)
            if n_out > 1:
                _H_INTER_TOKEN.observe(decode_time * 1000.0 / (n_out - 1))
            _C_TOKENS_OUT.inc(n_out)
        # the client-facing latency breakdown (ISSUE 5): rides the result
        # through the service layer onto gen_success frames, so the caller
        # sees WHERE its latency went without scraping any node.
        # prefill_ms includes the first-token sample+readback (the device
        # sync that makes the token observable — the client-visible cost).
        # t_admit == 0 marks requests that never entered admission
        # (cancelled in queue / zero budget): no queue/prefill split exists.
        timings = {
            "prefill_bucket": req.bucket,
            "decode_s": round(decode_time, 4),
            "chunks": req.chunks_decoded,
            "queue_wait_ms": (
                round((t.t_admit - t.t_submit) * 1000.0, 3) if t.t_admit else None
            ),
            "prefill_ms": (
                round((t_first - t.t_admit) * 1000.0, 3) if t.t_admit else None
            ),
            "ttft_ms": round(ttft_ms, 3),
            # every stamp reached so far, ms after the earliest; a gateway
            # that streams the done line completes it (tracing.RequestTiming)
            "timeline_ms": t.timeline_ms(),
            "decode_tokens": n_out,
            "tokens_per_s": round(tps, 2),
            "spec_acceptance": (
                round(req.spec_accepted / req.spec_drafted, 4)
                if req.spec_drafted else None
            ),
        }
        return GenerationResult(
            text=self.tokenizer.decode(req.out_ids),
            token_ids=list(req.out_ids),
            prompt_tokens=req.prompt_tokens,
            new_tokens=n_out,
            ttft_s=round(t_first - t.t_submit, 4),
            latency_s=round(latency, 4),
            tokens_per_sec=round(tps, 2),
            finish_reason=req.finish or "length",
            timings=timings,
        )

    def generate_stream(
        self,
        prompt: str | list[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        stop_tokens: list[int] | None = None,
        repetition_penalty: float = 1.0,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        min_p: float = 0.0,
        tenant: str = "default",
        adapter: str | None = None,
    ) -> Iterator[dict]:
        """Yield {"token": last_id, "tokens": ids, "text": piece} per decode
        window, then {"done": True, "result": GenerationResult}. Streaming
        granularity is one decode window: at most engine_cfg.decode_chunk
        tokens. Requests from
        concurrent callers share the scheduler's batch — submission order
        is admission order; rows decode together (including rows on
        DIFFERENT adapters: per-row selection inside one decode step)."""
        req = self._make_request(
            prompt, max_new_tokens, temperature, top_k, top_p, stop_tokens,
            stream=True, repetition_penalty=repetition_penalty,
            presence_penalty=presence_penalty,
            frequency_penalty=frequency_penalty,
            min_p=min_p,
            tenant=tenant,
            adapter=adapter,
        )
        if req.max_new_tokens <= 0:
            req.timing.t_first = req.timing.t_done = time.perf_counter()
            yield {"done": True, "result": self._build_result(req)}
            return
        self.scheduler.submit(req)
        try:
            while True:
                ev = req.events.get()
                if ev.get("done") and ev.get("result") is None:
                    raise self._event_error(ev)
                yield ev
                if ev.get("done"):
                    return
        finally:
            # consumer closed the generator early (e.g. a stop marker
            # completed in the service layer): release the batch row
            # instead of decoding to the token budget for nobody
            if req.finish is None:
                req.cancelled = True

    def generate(self, prompt, **kw) -> GenerationResult:
        """Non-streaming generation via the same scheduler path; blocks
        until the request retires (EOS / stop / budget)."""
        stop_tokens = kw.pop("stop_tokens", None)
        req = self._make_request(
            prompt,
            kw.get("max_new_tokens", 128),
            kw.get("temperature", 0.0),
            kw.get("top_k", 0),
            kw.get("top_p", 1.0),
            stop_tokens,
            repetition_penalty=kw.get("repetition_penalty", 1.0),
            presence_penalty=kw.get("presence_penalty", 0.0),
            frequency_penalty=kw.get("frequency_penalty", 0.0),
            min_p=kw.get("min_p", 0.0),
            tenant=kw.get("tenant", "default"),
            adapter=kw.get("adapter"),
        )
        if req.max_new_tokens <= 0:
            req.timing.t_first = req.timing.t_done = time.perf_counter()
            return self._build_result(req)
        self.scheduler.submit(req)
        while True:
            ev = req.events.get()
            if ev.get("done"):
                if ev.get("result") is None:
                    raise self._event_error(ev)
                return ev["result"]

    # ---------------------------------------------------- live migration

    def migration_signature(self) -> dict:
        """Pool-compat fingerprint a KV import is validated against: two
        engines whose signatures match have bit-compatible pool block
        layouts (same per-layer K/V geometry, block size and storage
        dtype), so exported blocks scatter straight in."""
        cfg = self.model_cfg
        return {
            "model": cfg.name,
            "n_layers": cfg.n_layers,
            # what a block's tensors are deep: a looped stack's passes too
            "cache_layers": cfg.cache_layers,
            "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim,
            "pool_layout": {n: list(hw) for n, hw in
                            core.pool_layout(cfg).items()},
            "block_size": self.engine_cfg.kv_block_size,
            "cache_dtype": str(jnp.dtype(self.engine_cfg.cache_dtype)),
        }

    def import_generation(self, snap: dict, kv: dict | None = None):
        """Resume a migrated generation (scheduler.checkpoint's snapshot):
        rebuild the Request, prime its accepted output, and submit it on
        the import path — with ``kv`` (host {"k","v"} block arrays) the
        scheduler scatters the shipped blocks and decodes on with ZERO
        prefill; without, it re-prefills prompt + accepted (the fallback
        rung). Returns the live Request; its events queue carries
        {"imported": True} on success, then the usual token/done events.
        Raises ValueError on a snapshot this engine cannot host."""
        from .scheduler import Request

        ids = [int(t) for t in snap.get("ids") or []]
        out = [int(t) for t in snap.get("out") or []]
        if not ids:
            raise ValueError("import: empty prompt")
        if snap.get("model") and snap["model"] != self.model_cfg.name:
            raise ValueError(
                f"import: snapshot is for model {snap['model']!r}, "
                f"this engine serves {self.model_cfg.name!r}"
            )
        adapter = snap.get("adapter") or None
        if adapter and not self.has_adapter(adapter):
            # the row's KV was computed (and its decode continues) under
            # THIS adapter's wk/wv deltas — resuming without it would be
            # silent corruption, and the re-prefill rung would recompute
            # the wrong K/V too. Typed refusal; the exporter's ladder
            # tries another target (migrate.py types this 'incompatible').
            raise ValueError(
                f"import: adapter {adapter!r} is not resident on this engine"
            )
        req = Request(
            ids,
            int(snap.get("max_new_tokens") or 0),
            snap.get("temperature", 0.0),
            int(snap.get("top_k") or 0),
            float(snap.get("top_p") if snap.get("top_p") is not None else 1.0),
            set(int(t) for t in snap.get("stop") or []),
            None if snap.get("eos") is None else int(snap["eos"]),
            self.tokenizer,
            stream=True,  # the migration bridge reads token events
            repetition_penalty=float(snap.get("repetition_penalty") or 1.0),
            presence_penalty=float(snap.get("presence_penalty") or 0.0),
            frequency_penalty=float(snap.get("frequency_penalty") or 0.0),
            min_p=float(snap.get("min_p") or 0.0),
            tenant=str(snap.get("tenant") or "default"),
            adapter=adapter,
        )
        req.out_ids = out
        # the already-streamed text was emitted at the SOURCE; the local
        # delta decoder must start past it or the first resumed chunk
        # would replay the whole output
        req._flushed_text = self.tokenizer.decode(out) if out else ""
        if kv is not None:
            if not out:
                raise ValueError("import: KV snapshot without accepted tokens")
            offset = int(snap.get("offset") or 0)
            if offset != len(ids) + len(out) - 1:
                raise ValueError(
                    f"import: offset {offset} breaks the live-row invariant "
                    f"(prompt {len(ids)} + out {len(out)} - 1)"
                )
            if offset + 1 >= self.max_seq_len:
                raise ValueError(
                    f"import: offset {offset} leaves no room in "
                    f"max_seq_len={self.max_seq_len}"
                )
            if int(snap.get("block_size") or 0) != self.engine_cfg.kv_block_size:
                raise ValueError(
                    f"import: block_size {snap.get('block_size')} != "
                    f"{self.engine_cfg.kv_block_size}"
                )
            # the block arrays must match the pool geometry EXACTLY —
            # a malformed/mismatched export must reject typed here, not
            # raise on the scheduler thread (whose catch-all would fail
            # every in-flight request on this node). An int8 pool demands
            # the scale tensors too (and ONLY then): dequantizing shipped
            # pages with absent/mismatched scales is silent corruption.
            from .paged import ceil_div

            cfg = self.model_cfg
            nb = ceil_div(offset, self.engine_cfg.kv_block_size)
            cache_dt = jnp.dtype(self.engine_cfg.cache_dtype)
            want = {
                name: ((cfg.cache_layers, heads, nb,
                        self.engine_cfg.kv_block_size, width), cache_dt)
                for name, (heads, width) in core.pool_layout(cfg).items()
            }
            if self.kv_quantized:
                sshape = (cfg.cache_layers, cfg.n_kv_heads, nb)
                want["k_scale"] = (sshape, jnp.dtype(jnp.float32))
                want["v_scale"] = (sshape, jnp.dtype(jnp.float32))
            got_names = set(kv) if isinstance(kv, dict) else set()
            if got_names != set(want):
                raise ValueError(
                    f"import: kv tensors {sorted(got_names)} != pool "
                    f"layout {sorted(want)} (cache_dtype {cache_dt})"
                )
            for name, (wshape, wdt) in want.items():
                arr = kv.get(name)
                shape = tuple(getattr(arr, "shape", ()))
                if shape != wshape:
                    raise ValueError(
                        f"import: kv[{name!r}] shape {shape} != pool "
                        f"geometry {wshape}"
                    )
                if jnp.dtype(getattr(arr, "dtype", None)) != wdt:
                    # wrong-dtype bytes pass the sha256 (it hashes what
                    # was sent) but would scatter garbage bit patterns
                    raise ValueError(
                        f"import: kv[{name!r}] dtype {arr.dtype} != pool "
                        f"dtype {wdt}"
                    )
            req.import_state = {
                "offset": offset, "cur": int(snap["cur"]), "kv": kv,
            }
        elif out:
            # re-prefill rung: the KV for prompt + out[:-1] is recomputed
            # locally; out[-1] is the resume token (its K/V is written by
            # the first decode forward, same as any freshly sampled token)
            seq = ids + out[:-1]
            if len(seq) + 1 >= self.max_seq_len:
                raise ValueError(
                    f"import: {len(seq)} accepted positions leave no room "
                    f"in max_seq_len={self.max_seq_len}"
                )
            req.import_state = {"seq": seq, "cur": out[-1], "kv": None}
        # else: nothing was ever decoded — a plain fresh admission
        self.scheduler.submit(req)
        return req

    def score(self, token_ids: list[int]):
        """Per-token logprobs of a sequence (no cache, full forward) — the
        scoring/training-parity path."""
        ids = jnp.asarray([token_ids], jnp.int32)
        logits, _ = core.forward(self.params, self.model_cfg, ids, None, jnp.int32(0))
        logprobs = jax.nn.log_softmax(logits[0, :-1], axis=-1)
        tgt = ids[0, 1:]
        return jax.device_get(jnp.take_along_axis(logprobs, tgt[:, None], axis=1)[:, 0])

    @property
    def info(self) -> dict:
        out = {
            "model": self.model_cfg.name,
            "n_params": int(
                sum(np.prod(x.shape) for x in jax.tree.leaves(self.params))
            ),
            "mesh": dict(self.mesh.shape),
            "dtype": str(self.dtype),
            "max_seq_len": self.max_seq_len,
            # the devices THIS engine's mesh runs on, as jax reports them
            "platform": self.introspect.platform,
            "device_kind": self.introspect.device_kind,
            "device_count": self.introspect.device_count,
            "attention": self.engine_cfg.attention,  # 'auto' resolved
        }
        out["kv"] = self.kv_info
        if self.model_cfg.has_ssm:
            out["state"] = self.state_info
        # speculative-decode observability (dashboards read acceptance to
        # judge whether the workload repeats enough to keep K up). Read
        # _scheduler directly — info() must not allocate the batch cache.
        sch = self._scheduler
        st = sch.stats if sch is not None else None
        drafted = st.spec_drafted if st else 0
        out["spec"] = {
            "spec_tokens": self.engine_cfg.spec_tokens,
            "drafted": drafted,
            "accepted": st.spec_accepted if st else 0,
            "acceptance": (
                round(st.spec_accepted / drafted, 4) if drafted else 0.0
            ),
        }
        # tiered drafting: per-tier split only when a drafter is
        # configured (the base dict shape above is pinned by tests and
        # the dashboards' scrape schema)
        if self.engine_cfg.drafter:
            out["spec"]["drafter"] = self.engine_cfg.drafter
            out["spec"]["tiers"] = dict(st.spec_tiers) if st else {}
        # multi-adapter serving: residency + pool churn (dashboards, the
        # mesh hello's service metadata, and the router's placement input
        # all read this through TPUService.get_metadata)
        if self.adapter_pool is not None:
            out["adapters"] = self.adapter_pool.info
        # engine economics plane (ISSUE 15): per-root compile counts,
        # MFU/goodput over the trailing window, and the HBM ledger —
        # refresh() also brings the engine.* economics gauges current
        out["introspect"] = self.introspect.refresh()
        return out
