"""Continuous-batching scheduler: shared-cache decode with rolling admission.

The round-1 engine dispatched every decode chunk of a request up front and
truncated host-side afterwards — a request stopping at 10 tokens with
max_new_tokens=2048 still paid ~2048 decode steps, and concurrent requests
were independent batch-1 programs contending for the chip. This scheduler
replaces both (the reference's torch path stops at EOS per request but has
no batching at all — reference hf.py:84-108):

- **One shared paged KV pool** plus per-row device state (current token,
  write offset). All rows decode together in one compiled program per
  chunk; on TPU, decode is HBM-bandwidth-bound on the weights, so batched
  rows ride along nearly free — this is the route to the BASELINE
  throughput ladder, not bigger single streams. The ONE cache layout is
  the block pool ``[L, num_blocks, 2, Hkv, block_size, hd]`` + per-row block
  tables (engine/paged.py): blocks are allocated lazily, attention
  touches only live blocks — per-step cache HBM traffic scales with live
  tokens instead of ``bsz * max_seq`` (the deleted rectangular layout's
  measured 4x idle-row tax) — and batch resize/compaction are host table
  moves, zero device copies. The rect/paged mode split is GONE: dense
  attention serves the gathered block view, ``attention="flash"`` runs
  the ragged paged kernel (ops/ragged.py) straight off the pool, and
  ``attention="sp"`` shards the pool's slot dim over `seq`
  (partition.paged_cache_spec) and merges per-shard softmax partials
  over the gathered view — every combination, plus speculative decode,
  composes in a single batch.
- **Adaptive batch bucketing**: ``bsz`` tracks the active row count in
  power-of-two buckets (grow on admission, shrink on retirement, capped at
  max_batch). Each bucket size compiles the decode program once; active
  rows are kept compacted in [0, active) by host table moves into
  retirement holes.
- **Rolling admission**: new requests prefill into a private row cache
  (bucketed, compile-bounded) and are spliced into a free batch row via one
  donated dynamic_update_slice program. Admission happens between decode
  chunks; nothing waits for the batch to drain.
- **EOS early-exit**: tokens are read back every chunk; a row whose request
  hit a stop token or its token budget retires immediately and frees the
  row for the next queued request. A window's length is chosen at every
  dispatch (_window_size: at most decode_chunk steps while a row streams,
  fewer where a row's end with the queue waiting is worth the host's turn
  and the admission that follow), so a request holds its row for about the
  tokens it generated, not for ceil(tokens / decode_chunk) chunks.
- **Per-row sampling** (sampling.sample_batched): temperature/top-k/top-p
  ride as [B] arrays inside the one compiled step, so mixed sampling
  settings never force a recompile.

Threading model: one daemon scheduler thread owns all device state; public
submit() only appends to a queue under a condition variable. Stream
consumers read per-request event queues (queue.Queue), so gateway threads
never touch jax state — the single-owner rule that keeps this race-free.
"""

from __future__ import annotations

import functools
import logging
import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..health import get_recorder
from ..metrics import get_registry
from ..models import core
from ..models.support import FeatureUnsupported
from ..router.fairness import WdrrQueue
from ..router.tenants import load_tenant_config
from ..tracing import RequestTiming, annotate, get_tracer, prog_scope
from .introspect import _C_HOST_SYNCS, _C_SYNC_STALLS, _G_OVERLAP
from .engine import PREFILL_GROUP_MAX_BUCKET
from .paged import (
    PoolExhausted,
    RowCache,
    best_prefix_key,
    prefill_chunk_positions,
)
from .sampling import sample_batched
from .spec import (
    TIER_OFF,
    DrafterStack,
    MeshDrafter,
    MtpDrafter,
    NgramDrafter,
    should_disable,
)

logger = logging.getLogger("bee2bee_tpu.scheduler")

# serving histograms/gauges (metrics.py): the load-bearing latency
# distributions the ROADMAP north star is judged by. Observed on the
# scheduler thread (single producer), scraped by /metrics.
_REG = get_registry()
_H_QUEUE_WAIT = _REG.histogram(
    "engine.queue_wait_ms", "submit-to-admission wait per request (ms)"
)
_H_PREFILL = _REG.histogram(
    "engine.prefill_ms",
    "admission prefill through first-token readback per request (ms)",
)
_H_STEP = _REG.histogram(
    "engine.step_ms",
    "one decode window / verify window / serialized verify step wall time (ms)"
)
_H_WINDOW_STEPS = _REG.histogram(
    "engine.window_steps",
    "decode steps of each dispatched window, verify steps of each verify "
    "window (chosen at dispatch: choose_window_steps); engine.step_ms / this "
    "= ms a step",
    buckets=(1, 2, 4, 8, 12, 16, 24, 32, 64, 128, 256),
)
_C_WINDOWS = _REG.counter(
    "engine.windows",
    "decode and verify windows dispatched (cut label: full = ran to the cap "
    "| budget = shortened for a row's end with the queue waiting | drain = "
    "nobody queued and every row ends sooner | sync = pinned for a spec "
    "draft | room = a verify window cut to what a row's context still holds)",
)
_H_BURST = _REG.histogram(
    "engine.admit_burst_requests",
    "requests placed by one admission call that placed any: the burst whose "
    "first tokens come back in ONE gather (observed once a burst)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
_C_PREFILL_CALLS = _REG.counter(
    "engine.prefill_calls",
    "prefill PROGRAMS dispatched: one a chunk of an admission's walk, the "
    "re-prefill import rung included (bucket label: the chunk's padded "
    "width in tokens)",
)
_C_LOOP_PASSES = _REG.counter(
    "engine.loop_passes",
    "passes of the layer stack dispatched: device calls x steps a call x "
    "the model's loop_steps (1 for a plain stack, ouro 4), counted on the "
    "host at dispatch (kind label: prefill programs | decode and verify "
    "steps)",
)
_C_PREFILL_CHUNKS = _REG.counter(
    "engine.prefill_chunks",
    "of those programs, the chunks of a CHUNKED prompt (one that walks more "
    "than one window: EngineConfig.prefill_chunk)",
)
_C_PREFILL_TOKENS = _REG.counter(
    "engine.prefill_tokens",
    "positions those programs ran over (kind label: real prompt tokens | "
    "pad of the bucket and a group's dead rows), every model",
)
_C_PREFILL_ROWS = _REG.counter(
    "engine.prefill_rows",
    "rows of those programs (kind label: live = a request's | dead = a row "
    "that only fills the group's declared shape); live / engine.prefill_calls "
    "= requests a program",
)
_G_BATCH_FILL = _REG.gauge(
    "engine.batch_fill", "active rows / current batch bucket (0..1)"
)
_G_ACTIVE_ROWS = _REG.gauge("engine.active_rows", "rows decoding this step")
_C_SPEC_DRAFTED = _REG.counter(
    "engine.spec_drafted", "speculative tokens proposed (tier label)"
)
_C_SPEC_ACCEPTED = _REG.counter(
    "engine.spec_accepted", "speculative tokens accepted (tier label)"
)
_C_SPEC_STEPS = _REG.counter(
    "engine.spec_steps",
    "speculative verify steps: one [B, K+1] forward of every live row (a "
    "verify window counts its steps)",
)
_C_SPEC_DEGRADED = _REG.counter(
    "engine.spec_mesh_degraded",
    "rows degraded off the mesh draft tier (reason label)",
)
_C_KV_PAGES_VISITED = _REG.counter(
    "engine.kv_pages_visited",
    "block-table entries handed to attention: batch rows x table width x "
    "the dispatched window's attention calls a layer",
)
_C_WINDOW_DELIVERIES = _REG.counter(
    "engine.window_deliveries",
    "settled windows whose tokens were delivered to their requests (kind "
    "label: under_device_work = an admission burst or a decode window was "
    "in flight meanwhile | exposed = the chip had nothing to run)",
)
_C_KV_PAGES_LIVE = _REG.counter(
    "engine.kv_pages_live",
    "of engine.kv_pages_visited, the entries that map a row's own block "
    "(live / visited = the share of the table the ragged kernel fetches)",
)



def _phase(name: str):
    """Run a BatchScheduler method as loop phase `name` (tracing.PhaseClock:
    a `sched.<name>` annotation on the profiler's host plane + exclusive
    seconds on engine.phase_seconds)."""

    def deco(fn):
        @functools.wraps(fn)
        def run(self, *args, **kwargs):
            with self._phases.phase(name):
                return fn(self, *args, **kwargs)

        return run

    return deco
_C_SSM_STEP_ROWS = _REG.counter(
    "engine.ssm_step_rows",
    "one-step recurrent updates dispatched: rows x decode steps x layers "
    "that hold a mixer (kind label: live | dead rows of the batch bucket)",
)
_C_SSM_STEP_KERNEL_CALLS = _REG.counter(
    "engine.ssm_step_kernel_calls",
    "calls of the one-step state kernel (ops/ssm_step.py) dispatched: "
    "decode steps x layers that hold a mixer, of every decode window of a "
    "recurrent model",
)
_C_SSM_SCAN_TOKENS = _REG.counter(
    "engine.ssm_scan_tokens",
    "positions a prefill's chunked scan ran over, a layer counted once "
    "(kind label: real | pad of the prefill bucket)",
)
_C_MOE_ASSIGNMENTS = _REG.counter(
    "engine.moe_assignments",
    "(token, expert) assignments of the dropless expert layer dispatched: "
    "positions x experts a token x expert layers of every forward (kind "
    "label: live | dead = dead rows of the batch bucket and a prefill "
    "bucket's padded tail, which reach no expert | elsewhere = live "
    "assignments whose expert another chip holds, under an expert share: "
    "no product here; live then counts the assignments computed HERE)",
)
_C_MOE_LAYER_CALLS = _REG.counter(
    "engine.moe_layer_calls",
    "expert-layer calls dispatched: forwards x expert layers (x the "
    "experts a layer = the denominator of engine.moe_experts_hit)",
)
_C_MOE_EXPERTS_HIT = _REG.counter(
    "engine.moe_experts_hit",
    "distinct experts (of those held here) with at least one live "
    "assignment, summed over "
    "expert-layer calls: counted on the device, fetched with the window's "
    "tokens (x an expert's bytes = the weights the grouped products read)",
)
_G_MOE_LOAD = _REG.gauge(
    "engine.moe_expert_load_max",
    "the busiest expert's assignments over the mean assignments an expert, "
    "averaged over the expert-layer calls of the last fetched decode window "
    "(1 = perfectly balanced)",
)
_C_LATENT_TOKENS_READ = _REG.counter(
    "engine.latent_tokens_read",
    "latent rows a dispatched decode window's reads cover: the live rows' "
    "context lengths summed over its steps, x layers (x a row's bytes = "
    "what the latent read must fetch; prefill chunks are not in it)",
)


def choose_window_steps(budgets, queued: bool, cap: int, step, turn,
                        admit) -> tuple[int, str]:
    """How many steps the next decode window runs, and why: (n, cut).

    ``budgets``: every live row's remaining tokens (an upper bound on its
    end); ``queued``: is anyone waiting for a row; ``cap``: the most steps
    the window may run; ``step`` / ``turn`` / ``admit``: what a decode step,
    the host's turn between two windows and the part of an admission burst
    that does not grow with its requests have been observed to cost (one
    unit; None = not observed yet).

    Never past the longest budget: those steps make no token. With nobody
    queued a dead slot costs nothing, so the window runs to there. With the
    queue waiting, the steps to the cap are planned as the STOPS THAT LOSE
    THE FEWEST TOKENS, and the window runs to the first of them:
    - a row that has ended loses a token a step until the stop that hands
      its slot to the queue;
    - a stop loses what the live rows would have made in ``turn + admit``.
      What a burst costs a REQUEST is not the stop's: a freed row's request
      is prefilled sooner or later, and parks the batch as long either way.
    A stop is worth making only where a row ends, so the candidates are the
    rows' ends and the cap (every plan's last stop); ties go to the later
    stop. Before the costs are observed the window runs to the cap."""
    budgets = np.sort(np.maximum(np.asarray(budgets, np.int64), 1))
    top = int(min(cap, budgets[-1]))
    if not queued:
        return top, "full" if top == cap else "drain"
    if step is None or turn is None or admit is None:
        return top, "full" if top == cap else "budget"
    stop = (turn + admit) * len(budgets) / max(step, 1e-9)
    ends = np.unique(np.minimum(budgets, top))  # ascending; top is its last
    last = len(ends) - 1
    done = budgets[budgets <= top]
    upto = np.searchsorted(done, ends, side="right")  # rows ended by a stop
    when = np.concatenate([[0], np.cumsum(done)])[upto]  # ... their ends, summed

    def lost(i, j):
        """Tokens lost between a stop at ends[i] (-1: now) and the next at
        each of ends[j]: the rows that end between them wait for the
        second, which (unless it is the last) costs a stop itself."""
        rows, at = (upto[i], when[i]) if i >= 0 else (0, 0)
        return (upto[j] - rows) * ends[j] - (when[j] - at) + stop * (j < last)

    after = np.zeros(len(ends))  # least loss from a stop there to the last
    for i in range(last - 1, -1, -1):
        j = np.arange(i + 1, len(ends))
        after[i] = np.min(lost(i, j) + after[j])
    j = np.arange(len(ends))
    first = lost(-1, j) + after
    best = int(ends[last - int(np.argmin(first[::-1]))])  # the LAST minimum
    return best, "full" if best == cap else "budget"


# how many readings the observed costs keep. A step and a turn: odd, so the
# median IS a reading, and a new batch width is the median after five
# windows. Bursts: their sizes must DIFFER before the fixed part can be told
# from the per-request part, and a closed loop's bursts repeat a few sizes
_LAST_COSTS = 9
_LAST_BURSTS = 16


class _Observed:
    """One cost as the scheduler has observed it: the median of its last
    few readings, so that a window that compiled (seconds where the rest
    read milliseconds) moves it by nothing and a new batch width by all
    within a handful of windows."""

    def __init__(self):
        self._seen: deque = deque(maxlen=_LAST_COSTS)

    def note(self, seconds: float) -> None:
        self._seen.append(max(0.0, seconds))

    @property
    def value(self):
        if not self._seen:
            return None
        return float(np.median(self._seen))


class _ObservedBursts:
    """What the last few admission bursts cost, told apart into what grows
    with a burst's requests and what does not (choose_window_steps weighs
    the second): a request costs what the CHEAPEST burst cost a request,
    and the fixed part is the median of what the bursts took beyond that
    (a burst that compiled moves neither)."""

    def __init__(self):
        self._seen: deque = deque(maxlen=_LAST_BURSTS)

    def note(self, requests: int, seconds: float) -> None:
        self._seen.append((max(1, requests), max(0.0, seconds)))

    @property
    def fixed(self):
        if not self._seen:
            return None
        k, s = np.asarray(self._seen, np.float64).T
        return float(np.median(s - (s / k).min() * k))


class Request:
    """One in-flight generation. Consumers read .events until a done event;
    the scheduler thread is the only producer."""

    def __init__(
        self,
        ids: list[int],
        max_new_tokens: int,
        temperature: float,
        top_k: int,
        top_p: float,
        stop: set[int],
        eos: int | None,
        tokenizer,
        stream: bool = False,
        repetition_penalty: float = 1.0,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        min_p: float = 0.0,
        tenant: str = "default",
        adapter: str | None = None,
        timing: RequestTiming | None = None,
    ):
        self.stream = stream
        # fairness identity (router/tenants.py): keys the scheduler's WDRR
        # submit queue, so one tenant's burst can't starve another past
        # its configured weight even below the admission layer
        self.tenant = str(tenant or "default")
        # multi-adapter serving (adapters/pool.py): which LoRA adapter
        # this row decodes under (None = the plain base model). The slot
        # resolves at ADMISSION — an adapter may page in/out while the
        # request is queued — and the acquired flag makes the pool
        # refcount release idempotent across the several retirement paths
        self.adapter = adapter or None
        self.adapter_slot = 0
        self._adapter_acquired = False
        # set by an abandoning consumer (generate_stream closed early);
        # plain bool write cross-thread — the scheduler thread reads it at
        # chunk boundaries and retires the row
        self.cancelled = False
        # live-migration import state (engine.import_generation): admission
        # takes the import path instead of prefill when set — either
        # {"offset","cur","kv"} (shipped pool blocks scatter in) or
        # {"seq","cur","kv":None} (re-prefill prompt+accepted locally)
        self.import_state: dict | None = None
        self.ids = ids
        self.max_new_tokens = max_new_tokens
        self.temperature = float(temperature if temperature is not None else 0.0)
        self.top_k = int(top_k or 0)
        self.top_p = float(top_p if top_p is not None else 1.0)
        self.min_p = float(min_p or 0.0)
        self.stop = stop
        self.eos = eos
        self.repetition_penalty = float(repetition_penalty or 1.0)
        self.presence_penalty = float(presence_penalty or 0.0)
        self.frequency_penalty = float(frequency_penalty or 0.0)
        self.tokenizer = tokenizer
        self.events: queue.Queue = queue.Queue()
        self.out_ids: list[int] = []
        self.finish: str | None = None
        # the gateway's record when the request came through it (one
        # timeline from its accept to its first written byte), else fresh
        self.timing = timing if timing is not None else RequestTiming()
        self.timing.t_submit = time.perf_counter()
        self.prompt_tokens = len(ids)
        self.bucket = 0
        self.chunks_decoded = 0  # observability: early-exit is visible here
        self._flushed_text = ""
        # speculative-decoding bookkeeping (engine/spec.py): lifetime
        # drafted/accepted/miss totals feed stats/info; the spec_tier_*
        # triple is the CURRENT tier's probe ledger — it resets on every
        # tier transition so each tier gets its own probe budget. A row
        # starts on the stack's cheapest tier (lazily, at its first
        # draft attempt) and moves through the ladder instead of dying:
        # a tier that fails its probe joins spec_tiers_failed (never
        # retried) and the row demotes/escalates via DrafterStack
        # .next_tier until the ladder is exhausted (spec_tier == "off").
        # spec_misses counts eligible steps where the tier proposed
        # nothing; each weighs like a fully-rejected K-token draft in
        # the probe math, so a tier blind to this row's content fails
        # its probe without ever drafting.
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_misses = 0
        self.spec_tier: str | None = None  # None = not yet assigned
        self.spec_tiers_failed: set = set()
        self.spec_tier_drafted = 0
        self.spec_tier_accepted = 0
        self.spec_tier_misses = 0
        # the ``mtp`` tier's draft for this row, made on the device by the
        # last prefill or verify program: (context length it was made at,
        # token), spec.MtpDrafter hands it over
        self.mtp_draft: tuple | None = None

    # ---- token accounting (runs on the scheduler thread) ----

    def accept(self, tok: int) -> bool:
        """Feed one sampled token; returns False when the request is done
        (budget reached / stop token) — the token is NOT kept then."""
        if self.finish is not None:
            return False
        if len(self.out_ids) >= self.max_new_tokens:
            self.finish = "length"
            return False
        if tok in self.stop:
            self.finish = "eos" if tok == self.eos else "stop"
            return False
        self.out_ids.append(tok)
        if len(self.out_ids) >= self.max_new_tokens:
            self.finish = "length"  # budget exhausted by this token
        return True

    def text_delta(self, final: bool = False) -> str:
        """Cumulative-decode → UTF-8-safe incremental text (holds back a
        trailing replacement char until the multi-byte token completes)."""
        full = self.tokenizer.decode(self.out_ids)
        if not final:
            full = full.rstrip("�")
        delta = full[len(self._flushed_text):]
        self._flushed_text = full
        return delta

    def emit(self, tokens: list[int]) -> None:
        """Queue one stream event for `tokens` (just accepted). The text
        may be empty — a trailing U+FFFD is held back — so the timeline's
        first-text stamp waits for the first event that carries some."""
        text = self.text_delta(final=self.done)
        if text and not self.timing.t_first_text:
            self.timing.t_first_text = time.perf_counter()
        self.events.put({"token": tokens[-1], "tokens": tokens, "text": text})

    @property
    def done(self) -> bool:
        return self.finish is not None

    @property
    def penalized(self) -> bool:
        """True when any occurrence penalty is active — such rows route
        through the scheduler's counts-carrying decode variant."""
        return (
            self.repetition_penalty != 1.0
            or self.presence_penalty != 0.0
            or self.frequency_penalty != 0.0
        )


@dataclass
class SchedulerStats:
    # the scheduler's RowCache: pool occupancy is read off its allocator
    # when asked for, never mirrored
    cache: RowCache = field(repr=False)
    admitted: int = 0
    retired: int = 0
    chunks: int = 0  # batched decode chunks dispatched
    peak_active: int = 0
    prefix_hits: int = 0
    prefix_tokens_saved: int = 0
    # paged-pool observability. blocks_read_last_step is what the decode
    # step actually touches per layer (bsz * table-width bucket);
    # live_blocks is the sum of blocks mapped by active rows — the two
    # tracking each other is the "cache HBM reads scale with live tokens"
    # property. The deleted rectangular layout's equivalent was
    # bsz * ceil(max_seq / block_size) regardless of occupancy.
    paged_blocks_copied: int = 0  # CoW copies (<= 1 per prefix hit)
    paged_blocks_read_last_step: int = 0
    paged_live_blocks: int = 0
    paged_alloc_waits: int = 0  # admissions deferred on an exhausted pool
    # self-speculative decoding (engine/spec.py): one spec step = one
    # [B, K+1] verify forward replacing up to K+1 sequential decode
    # steps. acceptance (accepted/drafted) near 1 means the workload
    # repeats enough that almost every draft token was a free step;
    # near 0 means rows are paying the wider forward for nothing (the
    # per-row adaptive disable then kicks in).
    spec_steps: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # per-tier split of the two totals above: tier name ("ngram"/"model"/
    # "mesh") -> {"drafted": n, "accepted": n}. Dashboards judge EACH
    # tier's acceptance — the model tier earning 0.6 while n-gram sits
    # at 0.05 is exactly the signal the tier ladder acts on.
    spec_tiers: dict = field(default_factory=dict)
    # decode hot loop (docs/PERF.md): windows (and spec verifies) whose
    # dispatch carried the [B, 2, V] penalty counts
    counts_windows: int = 0
    # sticky-width growth attempts the HBM ledger's headroom gate denied
    # (the request requeues at the front and retries after retirements)
    width_grow_denials: int = 0
    # live generation migration (meshnet/migrate.py). The acceptance
    # contract of the drain path pins on these: a happy-path migration is
    # migrated_out on the source + migrated_in on the target with
    # import_reprefills UNCHANGED — zero re-prefill forwards; the
    # fallback ladder's re-prefill rung is exactly import_reprefills.
    migrated_out: int = 0       # rows checkpointed + released for export
    migrated_in: int = 0        # rows imported (KV or re-prefill)
    import_reprefills: int = 0  # imports that had to re-prefill (no KV)
    prefill_handoffs: int = 0   # disagg: rows handed off after prefill
    history: deque = field(default_factory=lambda: deque(maxlen=64))

    @property
    def paged_blocks_in_use(self) -> int:
        return self.cache.alloc.used_count

    @property
    def paged_blocks_hwm(self) -> int:
        return self.cache.alloc.hwm

    @property
    def spec_acceptance(self) -> float:
        return self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0


@dataclass
class _Admission:
    """A popped request with its batch row and its pages (RowCache.adopt /
    cover): planned, not prefilled yet."""

    req: Request
    row: int
    seq: list  # the tokens to prefill: the prompt (+ accepted, re-prefill rung)
    start: int  # [0, start) came from the prefix cache: the write floor
    windows: list  # where each chunk of its walk starts
    recompute: bool = False  # the re-prefill rung: no useful token in it


# Decode windows in flight at most (the readback ring): with two, a fetched
# window's tokens are settled while the next already runs, so the chip never
# waits for the host between windows; every run on file used 2.
RING_DEPTH = 2


class BatchScheduler:
    """Decides when rows are admitted, stepped, moved and retired; where a
    row's cache lives is engine/paged.RowCache's. See module docstring."""

    def __init__(self, engine, max_batch: int):
        self.engine = engine
        self.max_batch = max_batch
        # submit queue with per-tenant weighted-deficit fairness
        # (router/fairness.py): deque-compatible, FIFO within a tenant,
        # WDRR across tenants — cost is the request's token budget, so a
        # 4:1-weighted tenant pair drains at ~4:1 in TOKENS under
        # saturation. Weights come from the same BEE2BEE_TENANTS config
        # the admission controller reads; with no tenants configured every
        # request shares the default queue and order stays pure FIFO.
        self._queue: WdrrQueue = WdrrQueue(
            weights={
                name: spec.weight
                for name, spec in load_tenant_config().items()
            }
        )
        self._cond = threading.Condition()
        self._shutdown = False
        # live-migration plumbing (meshnet/migrate.py). checkpoint() posts
        # (req, reply queue) pairs here; the scheduler thread services them
        # at chunk boundaries — the only moment row state is consistent.
        self._checkpoints: list[tuple[Request, queue.Queue]] = []
        # node-side hook: migrate_cb(req, snapshot, reason) -> bool, called
        # ON THE SCHEDULER THREAD when a row wants to leave (disagg
        # prefill handoff, mid-decode pool exhaustion). Returning True
        # transfers ownership of req (and its events queue) to the hook —
        # the row is released and the scheduler never touches req again.
        # The hook must be fast and thread-safe (it schedules async work).
        self.migrate_cb = None
        # disagg prefill role: freshly prefilled rows are offered to
        # migrate_cb instead of decoding locally (reason "prefill_handoff")
        self.handoff_after_prefill = False

        e = engine
        # where every row's cache lives (engine/paged.py): the block pool,
        # the tables, the prefix pins and, for recurrent models, the state
        # — donated through every decode window and prefill chunk as
        # self.cache.pool / self.cache.state
        self.cache = RowCache(engine, max_batch)
        self.stats = SchedulerStats(self.cache)
        self._temps = self._topps = self._topks = self._minps = None
        self._reps = self._press = self._freqs = None
        self._reset_rows()
        self._vocab = e.model_cfg.vocab_size

        # counts live [B, 2, V] (batch leading; channel 0 = prompt
        # occurrences, 1 = generated), so they get their own row helpers
        V = self._vocab

        # (each of these small programs, like every jit root of the serving
        # path, runs its body under one root scope: tracing.prog_scope)
        pool_prog = prog_scope("prog.pool")

        def c_insert(c, row, b):
            return jax.lax.dynamic_update_slice(c, row, (b, 0, 0))

        def c_move(c, src, dst):
            row = jax.lax.dynamic_slice(c, (src, 0, 0), (1, 2, V))
            return jax.lax.dynamic_update_slice(c, row, (dst, 0, 0))

        self._counts_zeros = jax.jit(
            pool_prog(lambda b: jnp.zeros((b, 2, V), jnp.int32)),
            static_argnums=0,
        )
        self._counts_grow = jax.jit(
            pool_prog(lambda d, s: jax.lax.dynamic_update_slice(d, s, (0, 0, 0))),
            donate_argnums=(0,),
        )
        self._counts_insert = jax.jit(pool_prog(c_insert), donate_argnums=(0,))
        self._counts_move = jax.jit(pool_prog(c_move), donate_argnums=(0,))
        self._counts_bump = jax.jit(
            pool_prog(lambda c, b, t: c.at[b, 1, t].add(1)), donate_argnums=(0,)
        )
        self._counts_shrink = jax.jit(
            pool_prog(lambda c, n: c[:n]), static_argnums=(1,)
        )
        # engine economics plane (engine/introspect.py): the decode roots
        # register with the engine's retrace sentinel under the declared
        # compile space — batch sizes on the pow2 grow ladder, block-table
        # widths on the cache's width buckets.
        ic = engine.introspect
        self._meter = ic.meter
        tw_ok = self.cache.declared_table_width
        bs_ok = engine._declared_batch_sizes
        # idle release of the batch bucket: an all-idle batch holds its
        # width this long after the last dispatch before dropping to 1 (an
        # instance attr so tests can collapse the hysteresis window)
        self._sticky_idle_s = 5.0
        self._last_dispatch_t = 0.0
        # readback ring: dispatched-but-unread decode windows. Each entry
        # carries the chained device cur/offsets, the per-chunk token
        # buffers, and its own (row, request) map — row bookkeeping may
        # drift (retirement nulls _rows[b]) between dispatch and fetch.
        self._inflight: deque = deque()
        # what the window policy weighs (choose_window_steps), in seconds,
        # as observed on THIS machine for THIS model: a decode step (a
        # fetched window's time over its steps), the host's turn between a
        # fetch and the next dispatch, the admission bursts (each _admit
        # call that placed one: its requests and its seconds). _t_fetched:
        # when the last fetch returned (None after an idle wait);
        # _admit_since: the bursts' seconds since then
        self._step_s, self._turn_s, self._bursts = (
            _Observed(), _Observed(), _ObservedBursts())
        self._t_fetched: float | None = None
        self._admit_since = 0.0
        # tokens a row made a verify step, over the last verify windows (a
        # row's budget in STEPS is its tokens over this: _verify_window_size)
        self._spec_rate = _Observed()
        # settled, not yet delivered: one deque of (request, accepted
        # tokens, ended) a fetched window, oldest first. _settle_row has
        # already done what the SCHEDULER needs of those tokens (out_ids,
        # finish, the row freed); what the CALLERS need (stream events, the
        # done event) waits here until the chip has its next work
        self._undelivered: deque = deque()
        # an expert model's prefill counters (device arrays) waiting for the
        # next window's fetch
        self._moe_pending: list = []
        # the ``mtp`` tier: the last prefill program's drafts [n], on the device
        self._mtp_first = None
        # (cur, offsets) shardings of the decode root's outputs, captured
        # at the first dispatch. Ring-empty dispatches re-enter the chain
        # from the numpy host mirrors, which must be committed to these
        # before the call — see the sharding note in _dispatch_window.
        self._chain_sharding: tuple | None = None
        self._decode = ic.sentinel.watch(
            "decode",
            jax.jit(self._decode_fn, donate_argnums=(2,),
                    donate_argnames=("state",)),
            key_fn=self._decode_key,
            allowed=lambda key: key[0] in bs_ok and tw_ok(key[1]),
        )
        # jitted: sample_batched run eagerly is ~15 tiny ops = ~15
        # dispatches per admission
        self._sample_first = e.stored_programs(
            "sample", jax.jit(prog_scope("prog.sample")(sample_batched)),
            lambda logits, key, temps, topks, topps, minps=None, counts=None, *_:
                (logits.shape[0], minps is None, counts is None),
        )

        # self-speculative decoding (engine/spec.py): greedy rows draft
        # from their own prompt+output and one [B, K+1] verify call
        # replaces up to K+1 sequential decode steps. Capability is
        # detected off the ACTIVE attention path, not the config string:
        # the verify chunk is a [B, K+1] forward through the paged write
        # path, served by dense attention over the gathered view and by
        # the ragged paged kernel alike (attn fns carrying the `ragged`
        # marker). Only 'sp' remains out — its partial-merge shard_map
        # hardcodes 1/sqrt(hd) full-causal scoring and has no paged
        # capability marker — and only then does the log fire.
        self._spec = None
        if e.engine_cfg.spec_tokens > 0:
            attn_fn = e._attn_fn()
            if not (attn_fn is None or getattr(attn_fn, "ragged", False)):
                logger.info(
                    "speculative decoding disabled: attention=%r has no "
                    "paged [B, K+1] verify capability",
                    e.engine_cfg.attention,
                )
            elif e.engine_cfg.spec_tokens + 1 >= e.max_seq_len:
                # no prompt could ever leave K+1 positions of headroom —
                # rows would never be spec-eligible; say so instead of
                # silently decoding plain forever
                logger.warning(
                    "speculative decoding disabled: spec_tokens=%d leaves "
                    "no room in max_seq_len=%d",
                    e.engine_cfg.spec_tokens, e.max_seq_len,
                )
            else:
                # the tiered drafter stack (engine/spec.py): n-gram is
                # always present as the zero-cost floor; the resident
                # model tier joins when the engine loaded one
                # (--drafter <model>); the mesh tier joins when the
                # drafter is remote (--drafter mesh) — meshnet wires its
                # transport via attach_drafter_transport. Per-row tier
                # choice + probe-driven transitions live in _spec_drafts.
                # a model with a multi-token-prediction layer drafts with
                # THAT and nothing else (models/support.py refuses it the
                # other tiers)
                tiers = {"mtp": MtpDrafter()} if e.mtp_on else {
                    "ngram": NgramDrafter(
                        e.engine_cfg.spec_tokens,
                        e.engine_cfg.spec_min_match,
                        e.engine_cfg.spec_max_match,
                    )
                }
                if getattr(e, "drafter_model", None) is not None:
                    tiers["model"] = e.drafter_model
                if e.engine_cfg.drafter == "mesh":
                    tiers["mesh"] = MeshDrafter(
                        e.engine_cfg.spec_tokens,
                        model=getattr(e.model_cfg, "name", "") or "",
                    )
                self._spec = DrafterStack(tiers, e.engine_cfg.spec_tokens)
        self.mesh_drafter = (
            self._spec.tiers.get("mesh") if self._spec is not None else None
        )
        self._draft_tier: dict[int, str] = {}  # row -> tier that drafted

        # the loop's phases (only the scheduler thread enters them)
        self._phases = e.introspect.phases
        self._thread = threading.Thread(
            target=self._loop, name="bee2bee-batch-scheduler", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------ public

    def set_tenant_weights(self, weights: dict) -> None:
        """Adopt the owning node's resolved tenant weights (P2PNode
        .add_service pushes its TenantRegistry here), so a registry
        replaced at runtime can't drift from the env-seeded defaults."""
        with self._cond:
            self._queue.set_weights(weights)

    def submit(self, req: Request) -> Request:
        with self._cond:
            if self._shutdown:
                raise RuntimeError("scheduler is shut down")
            self._queue.append(
                req,
                tenant=req.tenant,
                cost=max(1.0, float(req.max_new_tokens)),
            )
            self._cond.notify()
        return req

    def checkpoint(self, req: Request, timeout: float = 30.0) -> dict | None:
        """Thread-safe: ask the scheduler thread to snapshot `req`'s state
        (prompt/output ids, sampling params, write offset, last token, and
        the referenced pool blocks as host arrays under "_kv") and RELEASE
        its row at the next chunk boundary. A still-queued request is
        pulled out of the submit queue instead (snapshot without KV).
        Returns the snapshot, or None when the request already finished —
        on a snapshot the caller owns req and its events queue from here
        (the scheduler will never emit on it again)."""
        done: queue.Queue = queue.Queue()
        with self._cond:
            if self._shutdown:
                return None
            self._checkpoints.append((req, done))
            self._cond.notify()
        try:
            return done.get(timeout=timeout)
        except queue.Empty:
            return None

    def live_requests(self) -> list[Request]:
        """Admitted + queued requests (drain enumerates these). Best-effort
        snapshot: a request may retire between this read and a
        checkpoint() — checkpoint then returns None."""
        with self._cond:
            queued = list(self._queue)
        return [r for r in self._rows if r is not None] + queued

    def shutdown(self):
        with self._cond:
            self._shutdown = True
            self._cond.notify()
        self._thread.join(timeout=5)
        ms = [None if v is None else round(v * 1000.0, 2) for v in (
            self._step_s.value, self._turn_s.value, self._bursts.fixed)]
        logger.info("window policy at close: a step %s ms, a turn %s ms, a "
                    "burst's fixed part %s ms (as last observed)", *ms)
        if self.mesh_drafter is not None:
            # drop the transport; the resident model tier (if any) is
            # owned by the engine and closed there
            self.mesh_drafter.close()

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._rows)

    # ------------------------------------------------------------ device fns

    @staticmethod
    def _decode_key(params, cur, cache, offsets, temps, topks, topps,
                    minps, key, tables=None, adapters=None, aids=None,
                    ascales=None, counts=None, reps=None, press=None,
                    freqs=None, state=None, steps=None):
        """Sentinel shape key for the decode root: batch bucket, table
        width bucket, and the optional-operand None-flags (min_p, the
        adapter factors, and the fused penalty counts each select a
        distinct legitimate trace). ``steps`` is an operand's VALUE, not a
        shape: it is taken (every dispatch passes it) and not keyed."""
        return (
            int(cur.shape[0]),
            None if tables is None else int(tables.shape[1]),
            minps is not None, adapters is not None,
            counts is not None,
        )

    @prog_scope("prog.decode")
    def _decode_fn(self, params, cur, cache, offsets, temps, topks, topps,
                   minps, key, tables=None, adapters=None, aids=None,
                   ascales=None, counts=None, reps=None, press=None,
                   freqs=None, state=None, *, steps):
        """One chunk: decode ``steps`` tokens for ALL rows. Returns
        (cur', cache', offsets', counts', toks [B, K], state').
        ``steps`` (an int32 scalar OPERAND, 1 <= steps <= K =
        decode_chunk) is how many steps this call runs: the ONE
        program of a (batch width, table width) stops where the host says
        (_window_size), and only the first ``steps`` columns of ``toks``
        hold tokens. Step i draws with key i of the K split from ``key``,
        so a call of a + b steps samples what calls of a and of b would
        have with the same keys at the same steps.
        ``state`` (recurrent models; None otherwise) rides the loop carry
        INSIDE the cache dict — core.forward reads and writes each
        layer's slice in place — and is split off again on the way out. `tables` [B, MBb]
        selects the paged-pool path: attention gathers only the mapped
        blocks. `adapters`/`aids`/`ascales` (adapters/pool.py) select
        per-row LoRA deltas inside the same step; None keeps the base
        trace. When ``counts`` [B, 2, V] rides along, penalty application +
        the per-token occurrence bump run inside this same loop — penalized
        rows cost one extra trace (the counts None-flag in _decode_key),
        never a separate root, and rep=1/pres=0/freq=0 rows pass through
        apply_penalties unchanged, so every row of a mixed batch samples
        what it would alone. counts=None keeps the counts-free graph (None
        is a valid loop-carry pytree leaf)."""
        e = self.engine
        B = cur.shape[0]
        K = e.engine_cfg.decode_chunk
        if state is not None:
            cache = dict(cache, **state)
        cache = self._with_moe_stats(cache)
        keys = jax.random.split(key, K)

        def step(i, carry):
            cur, cache, off, cnt, toks = carry
            logits, cache = core.forward(
                params, e.model_cfg, cur[:, None], cache, off,
                attn_fn=e._attn_fn(), block_tables=tables,
                adapters=adapters, adapter_ids=aids, adapter_scales=ascales,
            )
            nxt = sample_batched(
                logits[:, -1, :], keys[i], temps, topks, topps, minps,
                cnt, reps, press, freqs,
            )
            if cnt is not None:
                cnt = cnt.at[jnp.arange(B), 1, nxt].add(1)
            toks = jax.lax.dynamic_update_index_in_dim(toks, nxt, i, 0)
            return nxt, cache, off + 1, cnt, toks

        cur, cache, offsets, counts, toks = jax.lax.fori_loop(
            0, steps, step,
            (cur, cache, offsets, counts, jnp.zeros((K, B), cur.dtype)),
        )
        return (cur, cache, offsets, counts, jnp.moveaxis(toks, 0, 1),
                self._chunk_extras(cache, state))

    @staticmethod
    def _chunk_extras(cache, state):
        """What a decode chunk hands back beside the pool, popped from the
        cache dict by NAME: the recurrent state's leaves and / or an expert
        model's ``moe_stats`` in one dict (None for a model with neither)."""
        names = tuple(state or ()) + (("moe_stats",) if "moe_stats" in cache else ())
        return {k: cache.pop(k) for k in names} or None

    def _with_moe_stats(self, cache):
        """The chunk's expert-layer counters ride the cache dict through
        core.forward (its ``moe_stats``): zeroed here, popped on the way
        out. Models without a dropless expert layer keep their trace."""
        if not self.engine.model_cfg.moe_dropless:
            return cache
        return dict(cache, moe_stats=jnp.zeros(
            (len(core.moe_stats_names(self.engine.model_cfg)),), jnp.int32))

    # ------------------------------------------------------------ loop

    def _loop(self):
        while True:
            with self._cond:
                while (not self._queue and self.active == 0
                       and not self._checkpoints and not self._undelivered
                       and not self._shutdown):
                    # nothing to do, so no phase; named on a capture all the
                    # same: a device gap that waits for the callers' next
                    # requests (a closed loop whose rows end together)
                    with annotate("sched.idle"):
                        self._cond.wait()
                    self._t_fetched = None  # a wait is no turn of the host
                if self._shutdown:
                    self._fail_all("engine shut down")
                    return
            try:
                self._turn()
            except Exception as e:  # noqa: BLE001 — the thread must survive:
                # a dead scheduler thread would hang every blocked caller
                logger.exception("scheduler step failed; failing active requests")
                try:
                    with self._cond:
                        self._fail_all(f"scheduler error: {e!r}")
                    # an empty bucket-1 batch over a cache rebuilt whole
                    # (_fail_all dropped the ring and released every row)
                    self.cache.rebuild()
                    self._reset_rows()
                except Exception:
                    # recovery itself failed (dead device): stop accepting
                    # work so submit() raises instead of queueing forever
                    logger.exception("scheduler recovery failed; shutting down")
                    with self._cond:
                        self._shutdown = True
                        try:
                            self._fail_all("scheduler dead: device unrecoverable")
                        except Exception:
                            pass
                    return

    @_phase("turn")
    def _turn(self):
        """One turn of the loop. Its own lines (and the checkpoints' and
        _step's between their phases) are the phase `turn`, so that the
        phases sum to the loop's busy time with nothing left over."""
        if self._inflight and (self._checkpoints or self._queue):
            # admission and checkpoints need settled row state —
            # drain the readback ring before touching either
            if self._drain_inflight():
                self._compact_and_shrink()
        self._service_checkpoints()
        # _admit delivers the last settled window itself, whenever
        # it has nobody to admit; what it left (it may stop early)
        # is delivered here, before the next dispatch. Again while
        # it places a burst: who arrived while the firsts were
        # gathered (the callers of the rows just delivered) gets a
        # free row now, not a window later
        t = time.perf_counter()
        while placed := self._admit():
            now = time.perf_counter()
            self._bursts.note(placed, now - t)
            self._admit_since += now - t
            t = now
        self._deliver_pending()
        if self.active or self._inflight:
            self._step()

    def _fail_all(self, reason: str):
        """Error-terminate every queued AND admitted request (callers are
        blocked on their event queues and must always get a done event).
        Caller must hold self._cond — submit() appends under it."""
        # abandon the readback ring outright: its device futures may be
        # poisoned, and with every row released below nobody needs them
        self._inflight.clear()
        self._moe_pending = []
        _G_OVERLAP.set(0)
        self.cache.flush_deferred()
        # a row that settle ended is in neither _queue nor _rows: its tokens
        # and its done event are still owed (a delivery that raises fails
        # its own request, so every pass makes progress)
        while self._undelivered:
            try:
                self._deliver_pending()
            except Exception:  # noqa: BLE001
                logger.exception("delivery failed while failing all requests")
        for req in list(self._queue) + [r for r in self._rows if r is not None]:
            self._fail(req, reason)
        self._queue.clear()
        # blocked checkpoint() callers get their None verdict too — a
        # dead scheduler must not make a drain wait out its timeout
        for _req, done in self._checkpoints:
            done.put(None)
        self._checkpoints.clear()
        for b, r in enumerate(self._rows):
            if r is not None:
                self._release_row(b)

    def _reset_rows(self):
        """An empty batch at bucket 1: the host side of every row."""
        self._bsz = 1  # current batch bucket (pow2-ish, <= max_batch)
        # cur/offsets live as HOST numpy mirrors: every eager device op is
        # a dispatch and a possible sync of its own (cost not measured on
        # the current machine),
        # so the scheduler never runs eager jnp — host state goes in as
        # jit arguments (a cheap [B] transfer) and comes back with the
        # token readback it needed anyway
        self._cur = np.zeros((1,), np.int32)
        self._offsets = np.zeros((1,), np.int32)
        # per-row adapter slots (adapters/pool.py; 0 = base model). A host
        # mirror like _cur/_offsets: rides into the jitted step as a [B]
        # argument only when some row actually holds an adapter — the
        # all-base batch keeps the adapter-free trace (per-row gating
        # discipline, same as the penalized-counts split)
        self._aids = np.zeros((1,), np.int32)
        self._rows: list[Request | None] = [None]
        self._row_params_dirty = True
        # occurrence counts [bsz, V] int32 for penalty sampling — allocated
        # lazily on the first penalized admission so the common (bench)
        # path never allocates or threads it. Rows of non-penalized
        # requests may hold stale counts; they are never read (rep=1/
        # pres=0/freq=0 rows pass through apply_penalties unchanged) and
        # every admission overwrites its row with a fresh prompt bincount.
        self._counts = None

    def _release_row(self, b: int):
        """Row b is free again: its cache goes back (deferred while windows
        are in flight: they still dead-row-scatter into its blocks)."""
        self._rows[b] = None
        self.cache.release(b, in_flight=bool(self._inflight))
        self._aids[b] = 0  # dead rows gather the null adapter (zeros)
        self._row_params_dirty = True

    def _fail(self, req: Request, error: str, kind: str | None = None):
        """Error-terminate one request: its adapter refcount goes back (a
        lease nobody returns pins the slot until restart) and its blocked
        caller gets the done event, typed with ``error_kind`` where the
        serving surfaces map it (404 unknown_adapter, pool_exhausted)."""
        self._release_adapter(req)
        req.finish = "error"
        typed = {"error_kind": kind} if kind else {}
        req.events.put({"done": True, "result": None, "error": error, **typed})

    def _release_adapter(self, req: Request):
        """Return req's adapter-pool refcount (idempotent — retirement,
        migration-out and fail_all paths may all reach a request). A zero
        refcount is what lets the LRU hot-swap recycle the slot."""
        if getattr(req, "_adapter_acquired", False):
            req._adapter_acquired = False
            self.engine.adapter_pool.release(req.adapter_slot)

    # ------------------------------------------------------------ migration

    def _service_checkpoints(self):
        """Serve pending checkpoint() calls (scheduler thread, between
        windows — the only point rows/offsets/pool agree)."""
        with self._cond:
            if not self._checkpoints:
                return
            pending, self._checkpoints = self._checkpoints, []
        self._deliver_pending()  # a snapshot reads delivered state
        for req, done in pending:
            snap = None
            try:
                snap = self._checkpoint_one(req)
            except Exception:  # noqa: BLE001 — a failed snapshot must
                # still answer the blocked checkpoint() caller
                logger.exception("checkpoint failed")
            done.put(snap)

    def _checkpoint_one(self, req: Request) -> dict | None:
        b = next((i for i, r in enumerate(self._rows) if r is req), None)
        if b is not None:
            snap = self._snapshot_row(b, req)
            self._release_row(b)
            self._release_adapter(req)  # the target re-acquires its own pin
            self.stats.migrated_out += 1
            self._compact_and_shrink()
            return snap
        with self._cond:
            removed = self._queue.remove(req)
        if not removed:
            return None  # already retired (or unknown): nothing to move
        # still queued: no device state exists — the snapshot is metadata
        # only and imports as a plain fresh admission on the target
        return self._snapshot_meta(req)

    def _snapshot_meta(self, req: Request) -> dict:
        """The wire-portable half of a snapshot (meshnet/migrate.py ships
        it as the KV_EXPORT `gen` field; engine.import_generation rebuilds
        a Request from it). Occurrence counts are NOT here — they rebuild
        exactly from ids+out at import."""
        return {
            "v": 1,
            "model": self.engine.model_cfg.name,
            "ids": [int(t) for t in req.ids],
            "out": [int(t) for t in req.out_ids],
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": req.temperature,
            "top_k": req.top_k,
            "top_p": req.top_p,
            "min_p": req.min_p,
            "repetition_penalty": req.repetition_penalty,
            "presence_penalty": req.presence_penalty,
            "frequency_penalty": req.frequency_penalty,
            "stop": sorted(int(t) for t in req.stop),
            "eos": None if req.eos is None else int(req.eos),
            "tenant": req.tenant,
            # multi-adapter serving: the target must hold (or fetch) this
            # adapter before it can resume the row — KV AND future decode
            # both depend on the adapted projections
            "adapter": req.adapter,
            "block_size": self.cache.block_size,
            "offset": 0,
            "cur": None,
            "kv_blocks": 0,
        }

    def _snapshot_row(self, b: int, req: Request) -> dict:
        """Snapshot an ADMITTED row: metadata plus the pool blocks holding
        its live KV, read back as host arrays under "_kv" (the caller
        splits that off before the metadata rides the wire). Pure read —
        releasing the row is the caller's move. Live-row invariant:
        offset == len(ids) + len(out) - 1 and cur == out[-1] (the last
        sampled token's K/V is written by the NEXT forward), so the
        blocks covering [0, offset) are the complete recoverable state."""
        snap = self._snapshot_meta(req)
        offset = int(self._offsets[b])
        try:
            nb, kv = self.cache.export_row(b, offset)
        except FeatureUnsupported:
            # the recurrent state has no export format yet: ship the
            # metadata alone, so the importer takes the re-prefill rung
            # (prompt + accepted tokens rebuild K/V AND state; next
            # tokens equal)
            return snap
        snap.update(offset=offset, cur=int(self._cur[b]), kv_blocks=nb)
        if kv is not None:
            snap["_kv"] = kv
        return snap

    def _paged_import(self, req: Request, b: int, st: dict):
        """Admit an IMPORTED request onto row b (engine.import_generation
        built it): either scatter its shipped KV blocks into freshly
        allocated pool slots (the happy path — zero prefill compute, the
        decode that follows is token-for-token the unmigrated rollout) or,
        KV-less, re-prefill prompt+accepted through the normal chunk walk
        (the fallback rung, counted in import_reprefills). Raises
        PoolExhausted with the row released — imports never requeue: the
        exporting node needs a fast typed verdict to try its next rung."""
        kv = st.get("kv")
        try:
            if kv is not None:
                offset = int(st["offset"])
                self.cache.import_row(b, offset, kv)
                self._offsets[b] = offset
                # prefix pins travel WITH the generation: the imported
                # prompt K/V is exactly what a local prefill would have
                # pinned, so repeat prompts hit CoW on the target too
                if offset >= len(req.ids) and not req.adapter:
                    self.cache.publish_prefix(b, req.ids)
            else:
                seq = [int(t) for t in st["seq"]]
                # a group of one; its last_logits discarded: the next token
                # is already known (cur = out[-1]); decode resumes from it
                planned = self._plan_row(req, b, seq, recompute=True)
                self._prefill_group(req.bucket, [planned])
                self._offsets[b] = len(seq)
                self.stats.import_reprefills += 1
            self._cur[b] = int(st["cur"])
            if req.penalized:
                self._seed_counts(req, b)
            self.stats.migrated_in += 1
        except PoolExhausted:
            self._release_row(b)
            raise

    def _seed_counts(self, req: Request, b: int) -> np.ndarray:
        """Row b's occurrence counts [1, 2, V], built host-side (bincount is
        O(n+V) in numpy — no device round trip) and shipped as the row's
        fresh counts. Channel 0: prompt (repetition's "seen"); channel 1:
        generated (presence/frequency) — empty at admission, the accepted
        output of an imported row. Returns what it inserted."""
        if self._counts is None:
            self._counts = self._counts_zeros(self._bsz)
        V = self._vocab
        row = np.stack([
            np.bincount(np.asarray(ids, np.int64), minlength=V)[:V]
            for ids in (req.ids, req.out_ids)
        ]).astype(np.int32)[None]
        self._counts = self._counts_insert(self._counts, row, np.int32(b))
        return row

    # ------------------------------------------------------- batch resizing

    # minimum HBM ledger headroom fraction required to grow the batch
    # bucket (sticky widths make growth ~permanent, so a grow near the
    # memory ceiling is a standing OOM invitation, not a transient)
    _GROW_HEADROOM_MIN = 0.02

    def _growth_headroom(self) -> bool:
        """May the batch bucket grow? Gated on the HBM ledger's live
        headroom fraction (engine/introspect.py). An unknown limit
        (headroom_frac absent — e.g. CPU without BEE2BEE_HBM_BYTES)
        always allows: the gate exists to stop growth into a KNOWN
        ceiling, never to guess one."""
        try:
            frac = self.engine.introspect.ledger.snapshot().get(
                "headroom_frac"
            )
        except Exception:  # noqa: BLE001 — telemetry never blocks admission
            return True
        return frac is None or frac > self._GROW_HEADROOM_MIN

    @_phase("compact")
    def _resize(self, new_bsz: int):
        """Move to a new batch bucket. The pool is batch-bucket-
        independent (row identity lives in the block table), so only the
        host mirrors, the counts and a recurrent model's state resize —
        zero cache copies. Active rows live in [0, active); the copy of
        min(old, new) leading rows carries them all."""
        old = self._bsz
        if new_bsz == old:
            return
        if self._counts is not None:
            if new_bsz > old:
                self._counts = self._counts_grow(
                    self._counts_zeros(new_bsz), self._counts
                )
            else:
                self._counts = self._counts_shrink(self._counts, new_bsz)
        self.cache.resize(new_bsz)
        keep = min(old, new_bsz)

        def fit(mirror):
            out = np.zeros((new_bsz,), np.int32)
            out[:keep] = mirror[:keep]
            return out

        self._cur, self._offsets = fit(self._cur), fit(self._offsets)
        self._aids = fit(self._aids)
        self._rows = self._rows[:keep] + [None] * (new_bsz - keep)
        self._bsz = new_bsz
        self._row_params_dirty = True

    @_phase("compact")
    def _compact_and_shrink(self):
        """Close retirement holes by moving the highest active row down,
        then release the bucket of a batch that has stood idle."""
        while True:
            hole = next(
                (i for i, r in enumerate(self._rows) if r is None), None
            )
            last = next(
                (i for i in range(self._bsz - 1, -1, -1) if self._rows[i] is not None),
                None,
            )
            if hole is None or last is None or last < hole:
                break
            # a host table move for the pages — zero device copies
            self.cache.move(last, hole)
            if self._counts is not None:
                self._counts = self._counts_move(
                    self._counts, np.int32(last), np.int32(hole)
                )
            self._cur[hole] = self._cur[last]
            self._offsets[hole] = self._offsets[last]
            self._aids[hole] = self._aids[last]
            self._aids[last] = 0
            self._rows[hole] = self._rows[last]
            self._rows[last] = None
            self._row_params_dirty = True
        # the batch bucket is GROW-ONLY while work flows: each bucket size
        # is a distinct decode trace, and a bucket that shrank with every
        # retirement would retrace at every burst. A fully idle batch
        # releases it only after the hysteresis window, so a burst arriving
        # right after a drain reuses the width already compiled. The pool
        # and prefix pins persist across idle — only the host bucket shrinks
        if (self.active == 0 and self._bsz > 1
                and time.perf_counter() - self._last_dispatch_t
                > self._sticky_idle_s):
            self._resize(1)

    def _plan_prefill(self, req: Request, seq: list):
        """-> (start, cached blocks | None), and req.bucket: the longest
        cached prefix of ``seq`` — admit from there and prefill only the
        remainder (chat transcripts grow by appending) — and the prefill
        bucket for that remainder. Adapter rows skip the cache both ways:
        their K/V diverges from the base model's under the adapted
        projections."""
        e = self.engine
        start, cached = (0, None) if req.adapter else self.cache.match_prefix(seq)
        C = e.engine_cfg.prefill_chunk
        remaining = len(seq) - start
        if C is not None and remaining > C:
            req.bucket = C  # chunked: one compiled shape for all lengths
        else:
            req.bucket = e._bucket_for(remaining)
        return start, cached

    def _plan_row(self, req: Request, b: int, seq: list,
                  recompute: bool = False) -> _Admission:
        """Give one request its pages on row b, host work but for a prefix
        hit's one CoW copy: row b adopts the matched prefix (RowCache.adopt:
        shared full blocks, at most one copied) and is covered to the end of
        ``seq`` (the prompt; prompt + accepted-so-far on the re-prefill
        import rung, ``recompute``). On PoolExhausted every reference taken
        is released and the table row nulled, so the caller can requeue the
        request cleanly — and the raise comes BEFORE any prefill (block
        sufficiency is prechecked), so a requeue-retry cycle under pool
        pressure never redoes prefill chunks nor double-counts prefix
        stats."""
        start, cached = self._plan_prefill(req, seq)
        n = len(seq)
        try:
            copied = self.cache.adopt(b, n, start, cached)
            # the write ceil (n) turns the bucket's padded-tail scatters
            # into null-block writes, so the row only ever claims blocks
            # covering real positions (adopt's precheck counted these)
            self.cache.cover(b, n)
        except PoolExhausted:
            self._release_row(b)
            raise
        if cached is not None:
            self.stats.paged_blocks_copied += copied
            self.stats.prefix_hits += 1
            self.stats.prefix_tokens_saved += start
        return _Admission(
            req, b, seq, start,
            prefill_chunk_positions(n, start, req.bucket, self.engine.max_seq_len),
            recompute,
        )

    def _waits_for_a_planned_prefix(self, burst: list, req: Request) -> bool:
        """Would ``req`` share at least a block more with a request of this
        burst, planned and not prefilled yet, than the prefix cache gives it
        now? A prompt is published with its prefill's dispatch, so such a
        request is planned a round later and adopts those blocks, as it did
        when the burst ran one request at a time."""
        if self.cache.prefix is None or req.adapter or not burst:
            return False
        _, m = best_prefix_key(
            (a.seq for a in burst if not a.req.adapter), req.ids)
        return (m >= self.cache.block_size
                and m > self.cache.match_prefix(req.ids)[0])

    def _cut_groups(self, burst: list) -> list:
        """An admission burst as the prefill programs that run it: the
        requests of one bucket share calls, each the widest the engine
        declares ([n, bucket] on its group ladder) that their count fills —
        five are 4 + 1 — so a group has no dead row and no request rides in
        a wider bucket: the positions of one call a request, and fewer
        reads of the weights. Alone goes who cannot share a call: a prompt
        that walks more than one chunk, an adapter's row (its factors are a
        row's argument), a penalized row (its counts seed its own sample).
        The largest group first: the chip is fed soonest with the most work."""
        groups: list[list[_Admission]] = []
        by_bucket: dict[int, list[_Admission]] = {}
        for a in burst:
            if len(a.windows) > 1 or a.req.adapter or a.req.penalized:
                groups.append([a])
            else:
                by_bucket.setdefault(a.req.bucket, []).append(a)
        for bucket, rows in by_bucket.items():
            sizes = self.engine.prefill_group_rows(bucket)
            while rows:
                n = max(k for k in sizes if k <= len(rows))
                groups.append(rows[:n])
                rows = rows[n:]
        groups.sort(key=len, reverse=True)
        return groups

    def _prefill_group(self, bucket: int, group: list, dead: int = 0):
        """Prefill one group straight into the pool: ONE program a chunk
        over [n, bucket] tokens, row i of it the request ``group[i]`` at its
        own offset, length, table row, write floor and write ceil; ``dead``
        more rows fill the shape and write nothing (the boot warm-up's).
        A group of several is one chunk each (_cut_groups); a group of one
        walks its windows (paged.prefill_chunk_positions — adopt's precheck
        simulated exactly these). Then the rows' recurrent state joins the
        batch's in one insert and the prompts are pinned in the prefix
        cache. Returns last_logits [n, V].

        The capacity re-anchor can re-feed tokens BELOW a row's ``start``;
        recomputed K/V under a different chunk geometry is not guaranteed
        bit-identical, so the write floor keeps shared donor blocks
        read-only (attention still reads the donor's values there)."""
        e = self.engine
        n = len(group) + dead
        rows = [a.row for a in group] + [-1] * dead
        floors = np.asarray([a.start for a in group] + [0] * dead, np.int32)
        ceils = np.asarray([len(a.seq) for a in group] + [0] * dead, np.int32)
        # a recurrent row's state is carried from chunk to chunk in a slot
        # of the group's own ([L, n, ...]; the first chunk's program starts
        # it at zero, "no token seen") and joins the batch's state once the
        # walk is over
        recurrent, state = e.model_cfg.has_ssm, None
        fed = floors.copy()
        # goodput accounting: a re-prefill (migration/failover import)
        # recomputes K/V the fleet already paid for once; its positions are
        # scheduled work that produces zero USEFUL tokens
        useful = [i for i, a in enumerate(group) if not a.recompute]
        last_logits = None
        for w in range(len(group[0].windows) if group else 1):
            tokens = np.zeros((n, bucket), np.int32)
            true_len = np.zeros((n,), np.int32)
            offset = np.zeros((n,), np.int32)
            for i, a in enumerate(group):
                pos = a.windows[w]
                chunk = a.seq[pos:pos + bucket]
                tokens[i, :len(chunk)] = chunk
                true_len[i], offset[i] = len(chunk), pos
            if recurrent and (offset != fed).any():
                # the prefill_chunk refusal (models/support.py) makes the
                # walk monotone; a re-fed token would be absorbed twice, so
                # never run past this
                raise RuntimeError(
                    f"recurrent prefill walk re-anchored: windows at "
                    f"{offset.tolist()}, states hold {fed.tolist()} tokens"
                )
            fed = offset + true_len
            more = {}
            if e.mtp_on:
                # the token that follows a chunk in the PROMPT (-1: none, the
                # walk's last chunk: the program takes its own greedy token)
                more["mtp_next"] = np.asarray(
                    [a.seq[a.windows[w] + bucket]
                     if a.windows[w] + bucket < len(a.seq) else -1
                     for a in group] + [-1] * dead, np.int32)
            out = e._prefill(
                e.params, tokens, self.cache.pool, true_len, offset,
                self.cache.rows_table(rows, bucket), floors, ceils,
                **({"state": state} if state is not None
                   else self._lora_args_row(group[0].req) if group else {}),
                **more,
            )
            self.cache.count_pages_written(n, bucket)
            self.cache.pool, last_logits, *extras = out
            extras = dict(extras[0]) if extras else {}
            real = int(true_len.sum())
            pad = n * bucket - real  # the buckets' tails and the dead rows
            # (the walk's last chunk's is the rows' first draft: _first_tokens)
            self._mtp_first = extras.pop("mtp_draft", None)
            if "moe_stats" in extras:  # an expert model's counters
                self._moe_pending.append(extras.pop("moe_stats"))
                self._count_moe(real, pad, 1, mtp=e.mtp_on)
            _C_PREFILL_CALLS.inc(bucket=str(bucket))
            _C_LOOP_PASSES.inc(e.model_cfg.loop_steps, kind="prefill")
            if group and len(group[0].windows) > 1:
                _C_PREFILL_CHUNKS.inc()
            _C_PREFILL_ROWS.inc(len(group), kind="live")
            _C_PREFILL_ROWS.inc(dead, kind="dead")
            _C_PREFILL_TOKENS.inc(real, kind="real")
            _C_PREFILL_TOKENS.inc(pad, kind="pad")
            if recurrent:
                state = extras
                _C_SSM_SCAN_TOKENS.inc(real, kind="real")
                _C_SSM_SCAN_TOKENS.inc(pad, kind="pad")
            # economics: the padded width is what the chip ran; only the
            # real prompt tokens were useful (none on the re-prefill rung)
            self._meter.record_dispatch(
                n * bucket, float(offset.mean()) + bucket / 2.0,
                scheduled=n * bucket,
            )
            self._meter.note_useful(int(true_len[useful].sum()))
        if recurrent:
            self.cache.put_state(rows, state)
        for a in group:
            # adapter rows NEVER enter the prefix cache: an adapted wk/wv
            # writes adapter-specific K/V, so sharing those blocks with a
            # base-model (or other-adapter) prompt would serve silently
            # wrong attention — sharing stays base-model-only
            if not a.req.adapter:
                self.cache.publish_prefix(a.row, a.seq)
        return last_logits

    def _first_tokens(self, group: list):
        """One group of a burst on the chip: its prefill (_prefill_group)
        and ONE sample of its rows' first tokens -> [n] int32, not fetched."""
        e, reqs = self.engine, [a.req for a in group]
        with get_tracer().span(
            "engine.admit", rows=len(group), bucket=reqs[0].bucket,
            prompt_tokens=sum(len(a.seq) for a in group),
            prefix=sum(a.start for a in group),
        ):
            # np arguments throughout: jit converts them on entry (one
            # small transfer), no eager ops, no blocking
            last_logits = self._prefill_group(reqs[0].bucket, group)
            # one arg tuple for plain and penalized rows: a marshalling
            # change must hit both identically
            sample_args = [
                last_logits,
                e._next_key(),
                np.asarray([r.temperature for r in reqs], np.float32),
                np.asarray([r.top_k for r in reqs], np.int32),
                np.asarray([r.top_p for r in reqs], np.float32),
                (np.asarray([r.min_p for r in reqs], np.float32)
                 if any(r.min_p > 0 for r in reqs) else None),
            ]
            if reqs[0].penalized:  # alone in its group (_cut_groups)
                (req,) = reqs
                # the first sample sees the row's fresh counts
                sample_args += [
                    self._seed_counts(req, group[0].row),
                    np.asarray([req.repetition_penalty], np.float32),
                    np.asarray([req.presence_penalty], np.float32),
                    np.asarray([req.frequency_penalty], np.float32),
                ]
            first = self._sample_first(*sample_args)
            # (the ``mtp`` tier: the rows' first drafts ride the same fetch)
            return (first, self._mtp_first) if e.mtp_on else first

    def warm_prefill(self):
        """Make every prefill program a burst can ask for resident, before
        the node says that it serves: which [n, bucket] an admission meets
        depends on who arrives together, so no warm-up traffic can promise
        to meet them all, and a shape first met under load stalls every row
        for its trace and compile. The declared shapes up to the group
        ladder's widest bucket are loaded from the program store — or, on a
        build's first boot, compiled side by side (XLA releases the GIL) and
        stored (engine/programs.py) — then each runs once on dead rows
        (nothing is written outside the null block), with its group's
        sample program (stored too) and state insert, through the calls
        that serve. A node that CHUNKS its prefill (EngineConfig.prefill_chunk)
        states that long prompts are its traffic: each walks the one
        [1, chunk] program, which its first prompt compiles, and the group
        programs of short prompts are left to their first meeting (13 of them,
        106 s of a first boot at smallthinker-21b-a3b-8l's widths, PR 43).
        The caller's thread runs it, under the lock: the loop is asleep and
        nothing is queued."""
        e, t0 = self.engine, time.perf_counter()
        if e.engine_cfg.prefill_chunk:
            return
        with self._cond:
            if self._queue or self.active or self._inflight:
                raise RuntimeError("warm_prefill needs an idle scheduler")
            shapes = sorted(
                (w, n) for n, w in e._declared_prefill_shapes
                if w <= PREFILL_GROUP_MAX_BUCKET
            )

            def like(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

            def ints(*shape):
                return jax.ShapeDtypeStruct(shape, np.int32)

            params, pool = jax.tree.map(like, (e.params, self.cache.pool))

            def resident(shape) -> bool:  # the served call's arguments, as shapes
                bucket, n = shape
                table = self.cache.rows_table([-1] * n, bucket)
                return e._prefill.warm(
                    params, ints(n, bucket), pool, ints(n), ints(n),
                    ints(*table.shape), ints(n), ints(n),
                    **({"mtp_next": ints(n)} if e.mtp_on else {}))

            with ThreadPoolExecutor(max_workers=4) as side_by_side:
                loaded = list(side_by_side.map(resident, shapes))
            # whatever this boot has traced so far is forgotten. A kernel's
            # text (the Mosaic call inside a program) holds where each of its
            # parts was FIRST traced in the process, and the compile cache
            # keys on it: a boot that compiled these programs has traced the
            # model, one that loaded them has not, and the decode programs
            # traced next would differ between the two and compile again
            jax.clear_caches()
            if self.cache.recurrent:
                # at the batch bucket a loaded node holds (sticky widths):
                # the state insert is a jit program a bucket and group size
                self.cache.resize(self.max_batch)
            for bucket, n in shapes:
                logits = self._prefill_group(bucket, [], dead=n)
                sample_args = (
                    logits, e._next_key(), np.zeros((n,), np.float32),
                    np.zeros((n,), np.int32), np.ones((n,), np.float32), None)
                self._sample_first.warm(*sample_args)  # a group size's first: stored too
                self._sample_first(*sample_args).block_until_ready()
            if self.cache.recurrent:
                self.cache.resize(1)
        logger.info("prefill programs resident: %d of %d from the store, %.1f s",
                    sum(loaded), len(shapes), time.perf_counter() - t0)

    def _refund(self, req: Request):
        """A popped request that will never decode: the pop charged its
        tenant's WDRR deficit for its token budget — give it back, same
        as admission does for abandoned waiters."""
        with self._cond:
            self._queue.refund(req.tenant, max(1.0, float(req.max_new_tokens)))

    def _requeue_front(self, req: Request):
        """This admission attempt is over, the request is not: back to the
        FRONT of the queue (which refunds the WDRR cost charged at the pop,
        so the retry isn't double-billed), its adapter refcount returned (the
        retry re-acquires)."""
        self._release_adapter(req)
        with self._cond:
            self._queue.appendleft(
                req, tenant=req.tenant,
                cost=max(1.0, float(req.max_new_tokens)),
            )

    def _plan_burst(self, placed: bool) -> tuple[list, bool]:
        """Pop every request the free rows allow and give each its row and
        its pages (_plan_row) — host work, so that the whole burst is known
        before its first program is cut. -> (the planned admissions, may
        the call go on admitting). ``placed``: this call dispatched a group
        already. A cancelled request, an unknown adapter and a prompt the
        pool can never hold cost that request alone; an import is placed
        here, whole; pool backpressure and a denied growth requeue their
        request and end the call's admissions behind what is planned."""
        e = self.engine
        burst: list[_Admission] = []
        while True:
            with self._cond:
                req = (self._queue.popleft()
                       if self._queue and self.active < self.max_batch
                       else None)
            if req is None:
                return burst, True
            # someone to admit: from here the call runs as admit's part
            # `dispatch` (engine.admit_seconds{part}; until then as "none")
            self._phases.part("dispatch")
            if req.cancelled:
                req.finish = "cancelled"
                req.timing.t_first = req.timing.t_done = time.perf_counter()
                req.events.put({"done": True, "result": e._build_result(req)})
                self._refund(req)
                continue
            if self._waits_for_a_planned_prefix(burst, req):
                self._requeue_front(req)
                return burst, True
            req.timing.t_admit = time.perf_counter()
            if req.adapter:
                # slot resolution happens at ADMISSION, not submit — the
                # adapter may page out while the request queues. The
                # acquire bumps the pool refcount, so a hot-swap can
                # never evict the factors under this row mid-decode.
                try:
                    req.adapter_slot = self.engine.adapter_pool.acquire(
                        req.adapter
                    )
                    req._adapter_acquired = True
                except Exception as err:  # UnknownAdapter / pool races:
                    # typed retirement — the serving surfaces map the
                    # kind onto 404 (/v1) and gen_error (p2p)
                    self._fail(req, f"unknown adapter: {err}", "unknown_adapter")
                    self._refund(req)
                    continue
            if self.active == self._bsz:
                if not self._growth_headroom():
                    # HBM-ledger-gated growth (sticky widths never shrink
                    # back, so a grow under memory pressure would pin the
                    # wider bucket's footprint for good): requeue at the
                    # front — retirements free rows at the CURRENT width
                    # and the retry admits into a hole without growing
                    self._requeue_front(req)
                    self.stats.width_grow_denials += 1
                    return burst, False
                self._resize(min(self._bsz * 2, self.max_batch))
            b = next(i for i, r in enumerate(self._rows) if r is None)

            st = getattr(req, "import_state", None)
            if st is not None:
                # migrated-in generation (meshnet/migrate.py): no first-
                # token sample — cur is the already-emitted last token and
                # decode resumes from it on the next window
                try:
                    with get_tracer().span(
                        "engine.import", row=b,
                        offset=int(st.get("offset") or 0),
                        kv=st.get("kv") is not None,
                    ):
                        self._paged_import(req, b, st)
                except PoolExhausted as err:
                    # typed, immediate: the exporter's fallback ladder
                    # (re-prefill elsewhere) beats parking the import on
                    # backpressure that may never clear
                    self._fail(req, f"import failed: {err}", "pool_exhausted")
                    self._refund(req)
                    continue
                except Exception as err:
                    # this request is in neither _queue nor _rows, so the
                    # _fail_all sweep upstream can never release its slot
                    # lease — drop it here or the refcount pins the slot
                    # (and eventually the whole pool) until restart
                    self._fail(req, f"import failed: {err!r}")
                    raise
                self._rows[b] = req
                self._aids[b] = req.adapter_slot
                req.timing.t_first = time.perf_counter()
                self.stats.admitted += 1
                self._row_params_dirty = True
                self.stats.peak_active = max(self.stats.peak_active, self.active)
                # the import verdict the serving node's ACK rides on
                req.events.put({"imported": True})
                continue

            try:
                planned = self._plan_row(req, b, req.ids)
            except PoolExhausted as err:
                # backpressure, not failure: _plan_row released the row's
                # blocks before raising. With work in flight (or a burst
                # planned or placed) blocks WILL free — requeue at the
                # front and admit again after the next window. With
                # nothing in flight and nothing left to evict, this
                # request can never fit the configured pool: fail it.
                # either way this admission attempt is over: return the
                # adapter refcount (a requeued retry re-acquires)
                if self.active > 0 or placed:
                    self._requeue_front(req)
                    self.stats.paged_alloc_waits += 1
                    return burst, False
                self._fail(req, f"admission failed: {err} "
                                "(kv_pool_blocks too small for this request)")
                # TERMINAL exhaustion (nothing in flight to free blocks) is
                # an incident, unlike the backpressure requeue above — a
                # pool sized under the workload is an operator problem the
                # flight recorder should evidence
                get_recorder().incident(
                    "pool_exhausted",
                    detail=str(err),
                    extra={"prompt_tokens": len(req.ids)},
                )
                continue
            except Exception as err:
                # the popped request is in neither _queue nor _rows: fail it
                # here or its caller hangs; then let _loop's handler recover
                # (which errors the rest of this burst — they sit in _rows)
                self._fail(req, f"admission failed: {err!r}")
                raise
            # the row is the request's from here (cur gets the real token
            # after readback): the next free row is another, and a failure
            # of its group's programs finds it in _rows (_fail_all)
            self._rows[b] = req
            self._offsets[b] = len(req.ids)
            self._aids[b] = req.adapter_slot
            burst.append(planned)

    @_phase("admit")
    def _admit(self) -> int:
        """Prefill queued requests into free rows, growing the batch bucket
        up to max_batch; -> the requests of the burst it placed (0: none).
        Everyone queued NOW is planned first (_plan_burst: rows and pages, host work), then the
        burst is cut into groups (_cut_groups) and each group runs as ONE
        prefill program and ONE sample (_first_tokens), all dispatched
        asynchronously; the first tokens come back in ONE device sync (a
        burst of 8 must not pay it 8 times while active streams sit
        undecoded). Whenever the queue has nobody for a free row, the last
        settled window is delivered (_deliver_next), a row at a time: under
        the prefills already dispatched, and bringing the requests that
        follow the ended ones, which form later, smaller groups.
        A call's own seconds are booked once more by PART
        (engine.admit_seconds, annotation sched.admit.<part>): `dispatch`
        from the first popped request on, `wait` in the gather, `emit`
        from the gather to the end; what runs under another phase
        (deliveries = process, resize and compaction = compact) is theirs."""
        placed: list[tuple] = []  # (req, row, its group in firsts, its row there)
        firsts: list = []  # a group's first tokens [n], on the device
        while True:
            burst, go_on = self._plan_burst(bool(placed))
            for group in self._cut_groups(burst):
                placed += [(a.req, a.row, len(firsts), j)
                           for j, a in enumerate(group)]
                firsts.append(self._first_tokens(group))
            if not go_on:
                break
            # nobody to admit right now. A settled window's rows are
            # delivered one at a time meanwhile, the queue looked at
            # again after each: a caller in a closed loop sends its
            # next request once its done event is out, and that
            # request's prefill is then the next thing the chip gets
            if not burst and not self._deliver_next(burst=bool(placed)):
                break

        if not placed:
            return 0
        self._deliver_pending(burst=True)
        # ONE blocking gather for the whole burst (device_get on the list
        # fetches all; no eager concatenate op on device). The part `wait`:
        # the chip runs the burst's prefills and no decode window meanwhile
        self._phases.part("wait")
        toks = jax.device_get(firsts)
        self._phases.part("emit")
        _H_BURST.observe(len(placed))
        now = time.perf_counter()
        for req, b, i, j in placed:
            draft = None
            if self.engine.mtp_on:
                tok, draft = int(toks[i][0][j]), int(toks[i][1][j])
            else:
                tok = int(toks[i][j])
            req.timing.t_first = now
            t = req.timing
            _H_QUEUE_WAIT.observe((t.t_admit - t.t_submit) * 1000.0)
            _H_PREFILL.observe((now - t.t_admit) * 1000.0)
            self.stats.admitted += 1
            accepted = req.accept(tok)
            if accepted:
                # the admission-sampled first token is as useful as any
                # decode-window token — and its slot must be SCHEDULED
                # too (its FLOPs were booked with the prefill positions;
                # without the slot, a bucket-exact prompt could push
                # useful past scheduled and the 0..1 fraction past 1)
                self._meter.record_dispatch(0.0, 0.0, scheduled=1)
                self._meter.note_useful(1)
            if accepted and req.stream:
                # token events (and their cumulative re-decode) are only
                # for streaming consumers; generate() reads the done event
                req.emit([tok])
            if req.done:  # instant stop/zero-budget: free the row again
                self._vacate(b, req)
                self._retire(req)
                continue
            if req.penalized and self._counts is not None:
                # the first token was sampled AFTER the prompt bincount
                # shipped; it must count toward later penalties too
                self._counts = self._counts_bump(
                    self._counts, np.int32(b), np.int32(tok)
                )
            self._cur[b] = tok
            if draft is not None:  # made with the greedy first token
                req.mtp_draft = (len(req.ids) + len(req.out_ids), draft)
            self._row_params_dirty = True
            self.stats.peak_active = max(self.stats.peak_active, self.active)
        # disaggregated prefill→decode: a prefill-designated node offers
        # every freshly prefilled row to the migration hook; an accepted
        # row ships its prompt KV to a decode peer and never decodes here
        # (the hook owns req from the True return on). TTFT stays local —
        # the first token was sampled above — so the existing histograms
        # measure the handoff regime unchanged.
        if self.handoff_after_prefill and self.migrate_cb is not None:
            for req, b, _i, _j in placed:
                if self._rows[b] is not req or req.done or req.cancelled:
                    continue
                if req.max_new_tokens - len(req.out_ids) < 2:
                    continue  # nothing left worth shipping
                try:
                    snap = self._snapshot_row(b, req)
                    accepted = bool(
                        self.migrate_cb(req, snap, "prefill_handoff")
                    )
                except Exception:  # noqa: BLE001 — keep decoding locally
                    logger.exception("prefill handoff failed")
                    continue
                if accepted:
                    self._release_row(b)
                    self._release_adapter(req)
                    self.stats.migrated_out += 1
                    self.stats.prefill_handoffs += 1
        self._compact_and_shrink()
        return len(placed)

    def _row_sampling_arrays(self):
        if self._row_params_dirty or self._temps is None:
            temps = [r.temperature if r else 0.0 for r in self._rows]
            topks = [r.top_k if r else 0 for r in self._rows]
            topps = [r.top_p if r else 1.0 for r in self._rows]
            # host np: uploaded as jit args, never eager device arrays
            self._temps = np.asarray(temps, np.float32)
            self._topks = np.asarray(topks, np.int32)
            self._topps = np.asarray(topps, np.float32)
            self._minps = np.asarray(
                [r.min_p if r else 0.0 for r in self._rows], np.float32
            )
            self._reps = np.asarray(
                [r.repetition_penalty if r else 1.0 for r in self._rows],
                np.float32,
            )
            self._press = np.asarray(
                [r.presence_penalty if r else 0.0 for r in self._rows],
                np.float32,
            )
            self._freqs = np.asarray(
                [r.frequency_penalty if r else 0.0 for r in self._rows],
                np.float32,
            )
            self._row_params_dirty = False
        return self._temps, self._topks, self._topps

    def _lora_args(self) -> dict:
        """Adapter kwargs for the batch-wide jitted calls (decode window /
        spec verify): EMPTY when no active row holds an adapter, so the
        all-base batch runs the unchanged adapter-free trace — the same
        batch-level gate the penalized-counts split uses. Otherwise the
        pool's stacked factors + the [bsz] per-row slot ids (null slot 0
        for base rows in the mixed batch)."""
        pool = self.engine.adapter_pool
        if pool is None or not self._aids.any():
            return {}
        adapters, scales = pool.device_args()
        return {"adapters": adapters, "aids": self._aids, "ascales": scales}

    def _lora_args_row(self, req: Request) -> dict:
        """Adapter kwargs for ONE row's prefill calls."""
        if not getattr(req, "_adapter_acquired", False):
            return {}
        adapters, scales = self.engine.adapter_pool.device_args()
        return {
            "adapters": adapters,
            "aids": np.asarray([req.adapter_slot], np.int32),
            "ascales": scales,
        }

    def _budgets(self, pending: int = 0) -> list[int]:
        """Tokens each live row may still accept beyond the ``pending``
        already in flight: an upper bound on where it ends."""
        return [
            r.max_new_tokens - len(r.out_ids) - pending
            for r in self._rows if r is not None
        ]

    def _window_size(self, pending: int = 0) -> tuple[int, str]:
        """THE WINDOW POLICY: the decode steps to dispatch before the next
        host sync, and why (the `cut` label of engine.windows): (n, cut).

        The CAP is one chunk (EngineConfig.decode_chunk steps) while a row
        streams — its tokens flush at least at chunk cadence — and while
        a speculation-eligible row is live (cut `sync`: a longer window
        would decode hundreds of tokens between draft opportunities; rows
        whose content never repeats stop being eligible via the
        miss-counting adaptive disable). Otherwise it is the chunks the
        tightest row's budget needs, at most max_inflight_chunks and at
        most 2 with someone queued (queued work wants a row soon), so no
        row waits for its done event behind more than a chunk of another's
        tokens. Under the cap choose_window_steps picks the length from
        every live row's remaining budget, whether anyone is queued and the
        costs observed so far (a step, the host's turn, a burst's fixed
        part): to the longest budget with nobody queued, else to the first
        of the stops that lose the fewest tokens.

        ``pending`` is the token depth already in flight (the ring
        dispatches ahead of the readback): it comes off every budget so
        look-ahead windows never stack past a row's remaining tokens."""
        e = self.engine
        K = e.engine_cfg.decode_chunk
        budgets = self._budgets(pending)
        if self._spec_wants_sync():
            return max(1, min(K, max(budgets))), "sync"
        if any(r is not None and r.stream for r in self._rows):
            cap = K
        else:
            w = -(-min(budgets) // K)  # ceil
            if self._queue:
                w = min(w, 2)
            cap = K * max(1, min(w, e.engine_cfg.max_inflight_chunks))
        return choose_window_steps(
            budgets, bool(self._queue), cap,
            self._step_s.value, self._turn_s.value, self._bursts.fixed,
        )

    def _prepare_window_tables(self, extra: int, calls: int):
        """Paged: grow every active row's block table to cover the next
        device call's writes (positions < offset + extra — its steps for a
        decode window, K+1 for a spec verify), then build the [bsz, tw]
        device argument at the pow2-bucketed width; ``calls`` is how many
        attention calls a layer will read it (a window's decode steps, 1
        spec verify), for the page counters. A row the pool
        cannot cover even after reclaiming prefix pins fails alone
        (explicitly undersized kv_pool_blocks); returns None when no
        active rows survive."""
        for b, req in enumerate(self._rows):
            if req is None:
                continue
            try:
                self.cache.cover(b, int(self._offsets[b]) + extra)
            except PoolExhausted as err:
                # migration-based failover: a row the pool can no longer
                # grow is fully recoverable state — offer it to the
                # migration hook (a peer with headroom resumes it KV-
                # intact) before the terminal typed error
                migrated = False
                if self.migrate_cb is not None and not req.cancelled:
                    try:
                        snap = self._snapshot_row(b, req)
                        # hook failures degrade to the typed error below —
                        # never into the loop's catch-all (_fail_all)
                        migrated = bool(
                            self.migrate_cb(req, snap, "pool_exhausted")
                        )
                    except Exception:  # noqa: BLE001
                        logger.exception("pool-pressure migration failed")
                    if migrated:
                        self.stats.migrated_out += 1
                self._release_row(b)
                if not migrated:
                    self._retire_error(req, str(err))
                else:
                    self._release_adapter(req)
        live_rows = [b for b, r in enumerate(self._rows) if r is not None]
        if not live_rows:
            return None
        tables, live = self.cache.window_table(live_rows, self._bsz)
        # the two proportionality counters: what the gather reads vs what
        # is actually mapped (tests + bench assert they track each other)
        self.stats.paged_live_blocks = live
        self.stats.paged_blocks_read_last_step = tables.size
        _C_KV_PAGES_VISITED.inc(tables.size * calls)
        _C_KV_PAGES_LIVE.inc(live * calls)
        # extra / calls = the tokens a call writes: 1 a decode step, K+1
        self.cache.count_pages_written(self._bsz, extra // calls, calls)
        self.cache.count_tiles(tables, self._offsets, extra // calls, calls)
        _C_LOOP_PASSES.inc(
            calls * self.engine.model_cfg.loop_steps, kind="decode")
        return tables

    def _spec_eligible(self, b: int, req: Request) -> bool:
        """Row-level speculation gate: greedy, not penalized, some tier
        still untried (spec_tier "off" is the ladder-exhausted terminal),
        enough budget that a draft could beat the single bonus token, and
        enough cache headroom for the fixed [B, K+1] write extent. The
        headroom clause matters for _window_size too: a spec_tokens
        larger than any row's remaining capacity (or a row approaching
        the end of the cache) must stop counting as eligible, or the
        batch would pay pinned 1-chunk windows for the rest of the
        generation with zero speculation possible — and no misses ever
        accruing to fail the tier's probe, since drafting never even
        starts."""
        e = self.engine
        return (
            req.temperature <= 0.0
            and not req.penalized
            and req.spec_tier != TIER_OFF
            and not req.cancelled
            and req.max_new_tokens - len(req.out_ids) >= 2
            and int(self._offsets[b]) + e.engine_cfg.spec_tokens + 1
            <= e.max_seq_len
        )

    def _spec_possible(self) -> bool:
        """Batch-level speculation gate, shared by _spec_drafts and the
        _window_size pin so they can never disagree: no active row within
        K+1 of capacity (ineligible rows still ride the [B, K+1] forward,
        and its write extent past capacity would demand pool blocks past
        blocks_per_row). A window pinned to 1 chunk while every spec step
        is vetoed would be pure sync-cadence loss.

        A penalized row is no veto: its counts ride the verify call too
        (engine._spec_verify_fn)."""
        e = self.engine
        K = e.engine_cfg.spec_tokens
        return all(
            int(self._offsets[b]) + K + 1 <= e.max_seq_len
            for b, req in enumerate(self._rows) if req is not None
        )

    def _counts_ride(self) -> bool:
        """Does the next device call carry the penalty counts? (Some live
        row is penalised: the counts-carrying trace of its root.)"""
        return self._counts is not None and any(
            r is not None and r.penalized for r in self._rows
        )

    def _spec_wants_sync(self) -> bool:
        """Does some live row want a draft look at the NEXT readback?
        (_window_size pins the window to one chunk then, _overlap_ready
        stacks nothing ahead of it: both must agree.)"""
        return (
            self._spec is not None
            and self._spec_possible()
            and any(
                r is not None and self._spec_eligible(b, r)
                for b, r in enumerate(self._rows)
            )
        )

    def _spec_transition(self, req: Request, failed_tier: str):
        """Move a row whose CURRENT tier just failed (probe miss budget
        or a dead remote) to the next tier on the ladder — demotion to a
        cheaper tier when one remains untried, the n-gram -> model
        escalation otherwise, "off" when the ladder is exhausted. The
        failed tier never gets retried (requests are short-lived); the
        probe counters reset so the new tier gets a full budget."""
        req.spec_tiers_failed.add(failed_tier)
        self._spec.tiers[failed_tier].forget(req)
        req.spec_tier = self._spec.next_tier(
            failed_tier, req.spec_tiers_failed
        )
        req.spec_tier_drafted = 0
        req.spec_tier_accepted = 0
        req.spec_tier_misses = 0

    def _spec_tier_check(self, req: Request):
        """Per-tier probe verdict: drafted tokens plus miss-equivalents
        (a no-match step weighs like a fully-rejected K-token draft)
        against the acceptance floor — same should_disable math as ever,
        fed with the CURRENT tier's counters, so the probe budget is per
        tier and failure means transition, not death."""
        K = self.engine.engine_cfg.spec_tokens
        if req.spec_tier in (None, TIER_OFF):
            return
        if should_disable(
            req.spec_tier_drafted + K * req.spec_tier_misses,
            req.spec_tier_accepted,
            self.engine.engine_cfg.spec_probe_tokens,
            self.engine.engine_cfg.spec_min_accept,
        ):
            self._spec_transition(req, req.spec_tier)

    def _spec_degrade_dead(self, req: Request, tier: str, drafter):
        """Typed degradation off a dead remote tier: the row lands on the
        next LOCAL tier immediately — a dead draft peer must never stall
        or starve the decode loop."""
        reason = getattr(drafter, "dead_reason", None) or "peer_lost"
        _C_SPEC_DEGRADED.inc(1, reason=reason)
        if not getattr(drafter, "_degrade_logged", False):
            drafter._degrade_logged = True
            logger.warning(
                "mesh drafter dead (%s): degrading rows to the local tier",
                reason,
            )
        self._spec_transition(req, tier)

    @_phase("dispatch")
    def _spec_drafts(self):
        """Collect per-row drafts for one spec step, grouped by tier so
        each drafter sees its rows in ONE batched propose call (the model
        tier turns that into a single [B, 2]+scan device pass). Returns
        (drafts [bsz, K], lens [bsz]) or None when this step must take
        the plain/penalized window instead: no row drafted anything, or
        any active row is too close to capacity for the fixed [B, K+1]
        write extent (_spec_possible).

        Tier bookkeeping per row: a None proposal is PENDING (mesh tier,
        draft still in flight — the row just skips this step, no
        accounting); [] is a miss that feeds the tier's probe; a dead
        remote tier degrades the row to the local ladder typed, right
        here, before it could cost a step."""
        e = self.engine
        K = e.engine_cfg.spec_tokens
        if not self._spec_possible():
            return None
        by_tier: dict[str, list] = {}
        for b, req in enumerate(self._rows):
            if req is None:
                continue
            # greedy non-penalized rows speculate; sampled rows ride
            # along advancing their normal one token per forward
            if not self._spec_eligible(b, req):
                continue
            if req.spec_tier is None:
                req.spec_tier = self._spec.start_tier()
            tier = req.spec_tier
            drafter = self._spec.tiers.get(tier)
            if drafter is not None and getattr(drafter, "dead", False):
                self._spec_degrade_dead(req, tier, drafter)
                tier = req.spec_tier
                drafter = self._spec.tiers.get(tier)
            if tier == TIER_OFF or drafter is None:
                continue
            by_tier.setdefault(tier, []).append((b, req))
        drafts = np.zeros((self._bsz, K), np.int32)
        lens = np.zeros((self._bsz,), np.int32)
        self._draft_tier = {}
        any_draft = False
        for tier, rows in by_tier.items():
            proposals = self._spec.tiers[tier].propose_batch(rows)
            for b, req in rows:
                d = proposals.get(b)
                if d is None:
                    continue  # pending (mesh pipeline): not a miss
                if not d:
                    req.spec_misses += 1
                    req.spec_tier_misses += 1
                    if tier == "mtp":
                        # the row's context moved on without a verify step:
                        # its MTP rows have a hole, the tier has nothing below
                        self._spec_transition(req, tier)
                    else:
                        self._spec_tier_check(req)
                    continue
                left = req.max_new_tokens - len(req.out_ids)
                # past-budget draft positions are dead weight; a remote
                # drafter gets clipped to K defensively too
                d = list(d)[:K][:left - 1]
                if not d:
                    continue
                drafts[b, :len(d)] = d
                lens[b] = len(d)
                self._draft_tier[b] = tier
                any_draft = True
        if any_draft:
            return drafts, lens
        # the ``mtp`` tier: a step in which no row drafts (every row of the
        # tier at its last token: requests that end together) is a verify step
        # all the same, the rows riding it for their one token: such a node's
        # decode steps are ONE program a shape, and the decode window's is
        # compiled only where sampled or penalised rows decode alone
        if e.mtp_on and any(
                r is not None and r.spec_tier == "mtp" and not r.cancelled
                for r in self._rows):
            return drafts, lens
        return None

    def _spec_step(self) -> bool:
        """One speculative step: verify every drafting row's proposal in
        a single [B, K+1] forward; offsets advance by accepted+1 per row
        (rejected positions sit at/past the new offset, where the causal
        invariant hides them — see engine._spec_verify_fn). Returns False
        when the step was not taken and the caller should run a normal
        decode window."""
        proposal = self._spec_drafts()
        if proposal is None:
            return False
        drafts, lens = proposal
        e = self.engine
        # cover the whole [offset, offset+K+1) write extent — blocks
        # claimed for later-rejected slots stay owned by the row
        # (over-allocated tail) and free normally at retirement
        tables = self._prepare_window_tables(e.engine_cfg.spec_tokens + 1, 1)
        if tables is None:
            self._compact_and_shrink()
            return True  # nothing left to decode this step
        temps, topks, topps = self._row_sampling_arrays()
        minps = self._minps if self._minps.any() else None
        self._set_fill_gauges()
        # economics: the hardware runs bsz*(K+1) positions; the batch
        # SCHEDULED active*(K+1) token slots, of which only accepted
        # drafts + the bonus token will prove useful (_process_row_tokens).
        # Mean depth DURING the step includes the in-flight half-window,
        # same convention as the decode-window dispatch below
        self._meter.record_dispatch(
            self._bsz * (e.engine_cfg.spec_tokens + 1),
            self._mean_active_ctx() + (e.engine_cfg.spec_tokens + 1) / 2.0,
            scheduled=self.active * (e.engine_cfg.spec_tokens + 1),
        )
        pen_args = self._pen_args()
        t_step = time.perf_counter()
        with self._phases.phase("fetch"):
            with get_tracer().span(
                "engine.spec_verify", active=self.active, drafted=int(lens.sum())
            ):
                nxt_d, self.cache.pool, acc_d, *cnts = e._spec_verify(
                    e.params, self._cur, drafts, lens, self.cache.pool,
                    self._offsets, temps, topks, topps, minps,
                    e._next_key(), tables, **self._lora_args(), **pen_args,
                )
                if pen_args:
                    (self._counts,) = cnts
                    self.stats.counts_windows += 1
                # a spec step is always a serialized sync: the drafter needs
                # the verdict before it can propose again
                _C_HOST_SYNCS.inc()
                _C_SYNC_STALLS.inc()
                _G_OVERLAP.set(0)
                nxt, acc = jax.device_get((nxt_d, acc_d))  # meshlint: ignore[ML-J003] -- the spec verdict IS the readback window's one host sync
        _H_STEP.observe((time.perf_counter() - t_step) * 1000.0)
        self._t_fetched = None  # a verify step is no turn of the host
        self._last_dispatch_t = time.perf_counter()
        self._cur = nxt.astype(np.int32).copy()
        self._offsets = (self._offsets + acc + 1).astype(np.int32)
        self.stats.spec_steps += 1
        _C_SPEC_STEPS.inc()

        retired_any = False
        live_rows = kept = 0  # the verify's [bsz, K+1] slots (engine.decode_slots)
        for b, req in enumerate(self._rows):
            if req is None:
                continue
            live_rows += 1
            had = len(req.out_ids)
            req.chunks_decoded += 1
            a = int(acc[b])
            drafted_here = int(lens[b])
            tier = self._draft_tier.get(b, "ngram")
            self._book_spec(req, tier, drafted_here, a)
            # accepted draft prefix, then the verify's own next token
            retired = self._process_row_tokens(
                b, req, np.append(drafts[b, :a], nxt[b])
            )
            retired_any |= retired
            kept += len(req.out_ids) - had
            if drafted_here and not retired:
                # the verdict rolls the drafter's state forward (model:
                # KV frontier; mesh: pipeline the next draft_request NOW
                # so its RTT overlaps the target's next step) — AFTER
                # _process_row_tokens so the drafter sees the grown
                # context. Then the probe check, which may transition.
                drafter = self._spec.tiers.get(tier)
                if drafter is not None:
                    drafter.observe(req, a)
                self._spec_tier_check(req)
        self._meter.note_slots(self._bsz, live_rows,
                               e.engine_cfg.spec_tokens + 1, kept)
        # a spec step is serialized: nothing ran while its rows were delivered
        _C_WINDOW_DELIVERIES.inc(kind="exposed")
        if retired_any:
            self._compact_and_shrink()
        return True

    def _verify_window_size(self) -> tuple[int, str]:
        """The verify steps of the next verify window, and why (the `cut`
        label of engine.windows): _window_size's policy with a row's budget
        counted in STEPS, at the tokens a row has been observed to make a
        verify step (1 where no draft is accepted), under the cap of one
        program call (decode_chunk steps). Then cut to what the furthest
        row's context still holds: a step writes K + 1 positions at a row's
        offset whatever it accepts, so n steps may reach offset + n (K + 1)
        (cut `room`; _spec_possible has said that one step fits)."""
        e = self.engine
        K = e.engine_cfg.spec_tokens
        rate = max(1.0, self._spec_rate.value or 1.0)
        n, cut = choose_window_steps(
            np.ceil(np.asarray(self._budgets(), np.float64) / rate),
            bool(self._queue), e.engine_cfg.decode_chunk,
            self._step_s.value, self._turn_s.value, self._bursts.fixed,
        )
        room = min((e.max_seq_len - int(self._offsets[b])) // (K + 1)
                   for b, r in enumerate(self._rows) if r is not None)
        return (n, cut) if n <= room else (max(1, room), "room")

    @_phase("dispatch")
    def _dispatch_verify_window(self) -> bool:
        """A node whose model drafts for itself (engine.mtp_on): dispatch ONE
        verify window (engine._spec_window_fn: the steps _verify_window_size
        chose, each row's draft fed back on the chip) where the host-drafted
        tiers run one serialized _spec_step, and push its record onto the
        readback ring, whence it takes the decode window's road: one fetch
        (_fetch_window), one settle of a row's tokens of all its steps
        (_settle_window), one stream event a row, delivered under the next
        burst's prefills. The host's offsets advance AT THE FETCH (what a
        row accepts is the device's to say), so nothing is stacked on a
        verify window (_overlap_ready). Returns False when the step was not
        taken and the caller should run a normal decode window; True with
        nothing in flight when no row was left to decode."""
        proposal = self._spec_drafts()
        if proposal is None:
            return False
        drafts, lens = proposal
        e, c = self.engine, self.cache
        K = e.engine_cfg.spec_tokens
        n, cut = self._verify_window_size()
        # cover the furthest a row can get (every draft accepted); blocks
        # past where it got stay the row's until it ends, as a serialized
        # step's rejected slots do
        tables = self._prepare_window_tables(n * (K + 1), n)
        if tables is None:
            self._compact_and_shrink()
            return True
        _H_WINDOW_STEPS.observe(n)
        _C_WINDOWS.inc(cut=cut)
        temps, topks, topps = self._row_sampling_arrays()
        minps = self._minps if self._minps.any() else None
        self._set_fill_gauges()
        live = [(b, r) for b, r in enumerate(self._rows) if r is not None]
        # economics: the hardware runs bsz * n * (K+1) positions, the batch
        # SCHEDULED active * n * (K+1) token slots (_spec_step's, n times)
        self._meter.record_dispatch(
            self._bsz * n * (K + 1),
            self._mean_active_ctx() + n * (K + 1) / 2.0,
            scheduled=len(live) * n * (K + 1),
        )
        # which rows draft at all is fixed for the window; a step's draft
        # length is made on the device from it and the row's remaining budget
        drafting = lens > 0
        budget = np.zeros((self._bsz,), np.int32)
        for b, r in live:
            budget[b] = r.max_new_tokens - len(r.out_ids)
        pen_args = self._pen_args()
        t0 = time.perf_counter()
        cur_d, c.pool, off_d, cnts, toks, accs, draft_d, extras = e._spec_window(
            e.params, self._cur, drafts, drafting, budget,
            c.pool, self._offsets, temps, topks, topps, minps, e._next_key(),
            tables, **self._lora_args(), **pen_args, steps=np.int32(n),
        )
        if pen_args:
            self._counts = cnts
            self.stats.counts_windows += 1
        moe = []
        if extras:  # the prefills' counters since the last window, then this one's
            moe = self._moe_pending + [extras["moe_stats"]]
            self._moe_pending = []
            self._count_moe(len(live) * n * (K + 1),
                            (self._bsz - len(live)) * n * (K + 1), n, mtp=True)
        self._inflight.append({
            "verify": True, "cur": cur_d, "off": off_d, "toks": [toks],
            "acc": accs, "draft": draft_d, "n": n, "slots": n * (K + 1),
            "moe": moe, "rows": live,
            "t0": t0, "drafting": drafting, "budget": budget,
            "tiers": dict(self._draft_tier),
        })
        self._note_turn(t0)
        self.stats.spec_steps += n
        _C_SPEC_STEPS.inc(n)
        self._last_dispatch_t = time.perf_counter()
        return True

    def _verify_rows(self, rec, toks, acc, draft, off) -> list:
        """A fetched verify window on the host: the mirrors take the device's
        word (cur = the last step's token, offsets = where each row got), and
        row b's tokens of the window are its steps' kept drafts and own
        token laid end to end, by a mask over [n, K+1] -> [bsz] arrays. What
        _settle_window books a row by stays on the record: a step's verdict,
        its draft length (the device's rule: engine._spec_window_fn) and the
        tokens the row had been given before it."""
        n, K = rec["n"], toks.shape[2] - 1
        toks, acc = toks[:n], acc[:n].astype(np.int64)
        self._cur = toks[-1, :, K].astype(np.int32).copy()
        self._offsets = off.astype(np.int32).copy()
        given = np.cumsum(acc + 1, axis=0) - (acc + 1)
        lens = np.where(rec["drafting"][None, :],
                        np.clip(rec["budget"][None, :] - given - 1, 0, K), 0)
        rec.update(acc_h=acc, lens_h=lens, given_h=given, draft_h=draft)
        at = np.arange(K + 1)
        keep = (at < acc[:, :, None]) | (at == K)  # [n, bsz, K+1]
        drafting = [b for b, _ in rec["rows"] if rec["drafting"][b]]
        if drafting:
            self._spec_rate.note(
                1.0 + float(acc[:, drafting].sum()) / (n * len(drafting)))
        return [toks[:, b][keep[:, b]] for b in range(toks.shape[1])]

    def _book_verify_row(self, rec, b: int, entry: tuple):
        """The tier's books of row b for one settled verify window, from the
        sums over the steps the row LIVED (all of them, or up to the step
        whose token ended it: what the serialized steps would have run), and
        the draft it goes on with."""
        req, kept, ended = entry
        used = rec["n"]
        if ended:
            taken = len(kept) + (req.finish in ("eos", "stop"))
            if req.finish == "cancelled":
                taken = 0
            used = int(np.searchsorted(rec["given_h"][:, b], taken, side="left"))
        drafted = int(rec["lens_h"][:used, b].sum())
        a = int(rec["acc_h"][:used, b].sum())
        self._book_spec(req, rec["tiers"].get(b, "mtp"), drafted, a)
        if ended:
            return
        if drafted:
            self._spec_tier_check(req)
        # made at the window's last accepted position with its last token:
        # the draft of the token after ``cur``
        req.mtp_draft = (len(req.ids) + len(req.out_ids),
                         int(rec["draft_h"][b, 0]))

    def _book_spec(self, req: Request, tier: str, drafted: int, accepted: int):
        """One row's drafted / accepted tokens on every book of its tier: the
        request's (lifetime and the current tier's probe), the stats', the
        counters', the goodput meter's. Nothing where it drafted nothing."""
        if not drafted:
            return
        req.spec_drafted += drafted
        req.spec_accepted += accepted
        req.spec_tier_drafted += drafted
        req.spec_tier_accepted += accepted
        self.stats.spec_drafted += drafted
        self.stats.spec_accepted += accepted
        ts = self.stats.spec_tiers.setdefault(tier, {"drafted": 0, "accepted": 0})
        ts["drafted"] += drafted
        ts["accepted"] += accepted
        _C_SPEC_DRAFTED.inc(drafted, tier=tier)
        _C_SPEC_ACCEPTED.inc(accepted, tier=tier)
        self._meter.note_spec(tier, drafted, accepted)

    def _pen_args(self) -> dict:
        """The penalty operands of a verify call, where some live row is
        penalised (_counts_ride): its counts ride the call and it advances
        its normal one penalty-sampled token a step. Else empty: the
        counts-free trace."""
        if not self._counts_ride():
            return {}
        return dict(counts=self._counts, reps=self._reps,
                    press=self._press, freqs=self._freqs)

    def _note_turn(self, t0: float):
        """A window dispatched at ``t0`` into an empty ring: the chip stood
        still from the last fetch to here, for the bursts placed meanwhile
        and the host's turn (what the window policy weighs)."""
        if self._t_fetched is not None and len(self._inflight) == 1:
            self._turn_s.note(t0 - self._t_fetched - self._admit_since)
        self._admit_since = 0.0

    def _set_fill_gauges(self):
        """Batch utilization snapshot before a device step: how full the
        bucket is, the absolute active-row count, and what the live rows
        hold in the pool (RowCache.note_tokens_held)."""
        a = self.active
        _G_ACTIVE_ROWS.set(a)
        _G_BATCH_FILL.set(a / self._bsz if self._bsz else 0.0)
        if self.cache.windowed:
            self.cache.note_tokens_held(self._offsets[
                [b for b, r in enumerate(self._rows) if r is not None]])
        # pool-growth forecast (engine/introspect.py): sampled on the
        # dispatch cadence so the pool_exhaust_eta gauge the admission
        # shed reads tracks the live allocation trend
        alloc = self.cache.alloc
        self.engine.introspect.forecast.feed(alloc.used_count, alloc.free_count)

    def _mean_active_ctx(self) -> float:
        """Mean cache depth of the active rows — the attention-term input
        of the FLOPs model (introspect.GoodputMeter)."""
        depths = [
            int(self._offsets[b])
            for b, r in enumerate(self._rows) if r is not None
        ]
        return sum(depths) / len(depths) if depths else 0.0

    def _process_row_tokens(self, b: int, req: Request, tokens) -> bool:
        """THE per-row token-intake protocol, shared by the decode-window
        and spec-step paths (a retirement/streaming semantics change must
        hit both identically), in its two halves: _settle_row is what the
        SCHEDULER needs of the tokens, _deliver_row what the CALLER needs.
        Here they run back to back (a spec step is serialized); a decode
        window settles all its rows first (_settle_window) and is delivered
        once the chip has its next work (_deliver_pending).
        Returns True when the row retired."""
        with self._phases.phase("settle"):
            entry = self._settle_row(b, req, tokens)
        with self._phases.phase("process"):
            self._deliver_row(entry)
        return entry[2]

    def _settle_row(self, b: int, req: Request, tokens: np.ndarray) -> tuple:
        """The scheduler's half of the intake: which of row b's sampled
        ``tokens`` (host, [n]) its request keeps and whether it ended —
        Request.accept's rule over the whole row at once, no per-token
        Python: a stop token is not kept, a budget reached on a token keeps
        that token, what follows the cut is waste, a cancelled row keeps
        nothing. out_ids and finish are final on return and an ended row is
        free for the next admission.
        -> (req, kept tokens, ended): what _deliver_row is owed."""
        if req.cancelled and not req.done:
            req.finish = "cancelled"
        kept: list[int] = []
        if req.finish is None:
            budget = req.max_new_tokens - len(req.out_ids)
            head = tokens[:max(budget, 0)]
            stops = np.fromiter(req.stop, np.int64, len(req.stop))
            hits = np.flatnonzero((head[:, None] == stops).any(axis=1))
            if hits.size:
                cut = int(hits[0])
                req.finish = "eos" if int(head[cut]) == req.eos else "stop"
                head = head[:cut]
            elif len(head) >= budget:
                req.finish = "length"  # exhausted by head's last token
            kept = head.tolist()  # meshlint: ignore[ML-J003] -- a host array: the window's one fetch brought it
            req.out_ids.extend(kept)
        if req.done:
            self._vacate(b, req)
        return req, kept, req.done

    def _deliver_row(self, entry: tuple):
        """The caller's half: the stream event for the tokens its request
        kept, then, for an ended one, the done event."""
        req, kept, ended = entry
        # goodput accounting: only tokens ACCEPTED into an output are
        # useful — post-EOS overshoot, rejected draft positions and
        # cancelled-row tokens all stay scheduled-only
        self._meter.note_useful(len(kept))
        if kept and req.stream:
            req.emit(kept)
        if ended:
            self._retire(req)

    def _deliver_next(self, burst: bool = False) -> bool:
        """Deliver the oldest settled row still owed (False: none is).
        Oldest first, so a request's token events keep their order and
        precede its done event. ``burst``: an admission burst is in flight.
        A delivery that raises fails its own request (it is in neither
        _queue nor _rows by now) before the error reaches _loop's recovery."""
        if not self._undelivered:
            return False
        window = self._undelivered[0]
        with self._phases.phase("process"):
            if window:
                entry = window.popleft()
                try:
                    self._deliver_row(entry)
                except Exception as err:
                    if entry[2]:
                        self._fail(entry[0], f"delivery failed: {err!r}")
                    raise
            if not window:
                self._undelivered.popleft()
                _C_WINDOW_DELIVERIES.inc(
                    kind="under_device_work" if burst or self._inflight
                    else "exposed")
        return True

    def _deliver_pending(self, burst: bool = False):
        """Deliver every settled row still owed."""
        while self._deliver_next(burst):
            pass

    def _step(self):
        """One hot-loop turn (docs/PERF.md "Decode hot loop"): keep the
        readback ring full (RING_DEPTH windows), fetch the OLDEST in-flight
        window (the only host sync), refill the ring BEFORE touching its
        tokens, then SETTLE it (_settle_window: which rows ended). Its
        delivery to the callers is left pending for _loop's next turn,
        which puts the freed rows' prefills in flight first (_admit) and
        delivers under them.
        With speculation enabled, a turn where some greedy row drafted
        becomes ONE serialized [B, K+1] verify call instead (_spec_step
        — a drafter on the HOST needs each verdict before proposing again,
        so its spec steps never ride the ring). Where the model drafts for
        itself (engine.mtp_on) nothing of a step's input is made on the
        host, and the turn dispatches a verify WINDOW
        (_dispatch_verify_window) that takes the road below: fetched,
        settled and delivered once, as a decode window."""
        if not self._inflight and self._spec is not None and (
                self._dispatch_verify_window() if self.engine.mtp_on
                else self._spec_step()) and not self._inflight:
            return
        # fill the ring: the first window dispatches unconditionally;
        # look-ahead windows pass the _overlap_ready gate
        while len(self._inflight) < RING_DEPTH:
            pending = sum(r["n"] for r in self._inflight)
            chosen = self._overlap_ready(pending) if self._inflight else None
            if self._inflight and chosen is None:
                break
            if not self._dispatch_window(pending, chosen):
                break
        if not self._inflight:
            self._compact_and_shrink()
            return
        rec = self._inflight.popleft()
        toks_host = self._fetch_window(rec)
        # with rec's tokens on the host, top the ring back up before doing
        # any host-side token work (rec's tokens count toward pending —
        # they are not in out_ids yet)
        while len(self._inflight) < RING_DEPTH and not rec.get("verify"):
            pending = sum(r["n"] for r in self._inflight) + rec["n"]
            chosen = self._overlap_ready(pending)
            if chosen is None or not self._dispatch_window(pending, chosen):
                break
        if not self._inflight:
            # the device goes idle while the host processes this window —
            # the stall the ring exists to remove
            _C_SYNC_STALLS.inc()
        # settle only: which rows ended is all the next dispatch needs. The
        # tokens reach their streams once the chip has work again: under
        # the coming turn's admission burst, else before its dispatch
        retired_any = self._settle_window(rec, toks_host)
        self._release_deferred()
        if self.active == 0 and self._inflight:
            # every row retired mid-ring: the remaining windows are pure
            # overshoot nobody will read — drain them now so the batch
            # can compact and the next admission starts clean
            retired_any |= self._drain_inflight()
        if retired_any and not self._inflight:
            # compaction moves rows; in-flight records carry row indices,
            # so it must wait for an empty ring (holes cost dead-row
            # positions until then — the same price a half-empty bucket
            # already pays)
            self._compact_and_shrink()

    @_phase("dispatch")
    def _dispatch_window(self, pending: int = 0, chosen=None) -> bool:
        """Dispatch one decode window of the steps _window_size chose
        (``chosen``: its answer where _overlap_ready has asked already) —
        async, no host sync; full chunks of decode_chunk steps and a last one of
        the remainder, each a call of the ONE decode program with its step
        count as an operand — and push its record onto the readback ring.
        Chains device state off
        the ring tail (or the host mirrors when the ring is empty), so
        windows form one dependency chain on device. Host offsets advance
        AT DISPATCH — every pending-window consumer (_prepare_window_
        tables, _spec_eligible, _overlap_ready) sees the post-in-flight
        positions. Returns False when no active rows survive table prep."""
        e, c = self.engine, self.cache
        K = e.engine_cfg.decode_chunk
        n, cut = chosen or self._window_size(pending)
        tables = self._prepare_window_tables(n, n)
        if tables is None:
            return False
        _H_WINDOW_STEPS.observe(n)
        _C_WINDOWS.inc(cut=cut)
        temps, topks, topps = self._row_sampling_arrays()
        pen = self._counts_ride()
        # None selects the min_p-free trace: the relative-floor softmax
        # must cost nothing when no active row asked for it. Gate on the
        # SAME array the sampler receives — a row scan could silently
        # diverge from how _row_sampling_arrays builds _minps
        minps = self._minps if self._minps.any() else None
        self._set_fill_gauges()
        # economics: bsz*n positions run (dead rows included — the
        # hardware computes them); active*n token slots are scheduled
        self._meter.record_dispatch(
            self._bsz * n,
            self._mean_active_ctx() + n / 2.0,
            scheduled=self.active * n,
        )
        # host mirrors go in as the first call's args; chunks chain on
        # the returned DEVICE arrays; the host mirrors then advance
        # from the same readback the tokens needed anyway — the whole
        # window runs with zero eager device ops
        if self._inflight:
            tail = self._inflight[-1]
            cur_d, off_d = tail["cur"], tail["off"]
        else:
            cur_d, off_d = self._cur, self._offsets
            if self._chain_sharding is not None:
                # jax keys executables on input sharding as well as
                # shape: a raw numpy mirror lowers as an UNcommitted
                # arg while chained jit outputs carry the mesh's
                # NamedSharding, which would silently DOUBLE the decode
                # root's compile space (one executable per key per
                # source) and land the second compile mid-serve.
                # Committing the mirrors to the sharding the root's own
                # outputs carry keeps one executable per sentinel key.
                cur_d = jax.device_put(cur_d, self._chain_sharding[0])
                off_d = jax.device_put(off_d, self._chain_sharding[1])
        lora = dict(self._lora_args())
        if c.recurrent:
            steps = n * e.model_cfg.state_layers  # the layers with a mixer
            _C_SSM_STEP_ROWS.inc(self.active * steps, kind="live")
            _C_SSM_STEP_ROWS.inc((self._bsz - self.active) * steps, kind="dead")
            _C_SSM_STEP_KERNEL_CALLS.inc(steps)
        self._count_moe(self.active * n, (self._bsz - self.active) * n, n)
        if e.model_cfg.has_mla:
            # step s of a row at offset o reads its o + s + 1 cached rows
            ctx = sum(int(self._offsets[b])
                      for b, r in enumerate(self._rows) if r is not None)
            _C_LATENT_TOKENS_READ.inc(
                (ctx * n + self.active * n * (n + 1) // 2) * e.model_cfg.n_layers)
        toks_parts, moe_parts = [], []
        for done in range(0, n, K):
            lora["steps"] = np.int32(min(K, n - done))
            if c.recurrent:
                # the state chains through the windows like the pool does
                lora["state"] = c.state
            # counts left None lower to the counts-free graph
            cur_d, c.pool, off_d, cnts, toks, st = self._decode(
                e.params, cur_d, c.pool, off_d,
                temps, topks, topps, minps, e._next_key(), tables,
                counts=self._counts if pen else None,
                reps=self._reps if pen else None,
                press=self._press if pen else None,
                freqs=self._freqs if pen else None,
                **lora,
            )
            if pen:
                self._counts = cnts
            if st is not None and "moe_stats" in st:  # an expert model's counters
                st = dict(st)
                moe_parts.append(st.pop("moe_stats"))
            if c.recurrent:
                c.state = st
            toks_parts.append(toks)
        if self._chain_sharding is None:
            # metadata-only read (no sync): adopt the root's own output
            # shardings as the canonical chain-entry commitment
            self._chain_sharding = (cur_d.sharding, off_d.sharding)
        self._inflight.append({
            # toks: a buffer a device call (a partial chunk counts as a chunk)
            "cur": cur_d, "off": off_d, "toks": toks_parts, "n": n,
            # fetched with the tokens: the prefills' counters since the last
            # window, then this window's
            "moe": self._moe_pending + moe_parts,
            # each record carries its own (row, request) map: retirement
            # nulls _rows[b] between dispatch and fetch, and the fetch
            # must still route row b's tokens to the request that was
            # live when the window launched
            "rows": [
                (b, r) for b, r in enumerate(self._rows) if r is not None
            ],
            "t0": time.perf_counter(),
        })
        self._note_turn(self._inflight[0]["t0"])
        self._moe_pending = []
        self._offsets = self._offsets + np.int32(n)
        self.stats.chunks += len(toks_parts)
        if pen:
            self.stats.counts_windows += 1
        self._last_dispatch_t = time.perf_counter()
        return True

    def _overlap_ready(self, pending: int) -> tuple[int, str] | None:
        """May a look-ahead window dispatch with ``pending`` tokens
        already in flight? None, or what _window_size chose for it (for
        _dispatch_window). Look-ahead is strictly opportunistic — it must
        never be DESTRUCTIVE (evict prefix pins, migrate or retire rows)
        and never steal the sync cadence from work that wants the host
        (queued admissions, checkpoints, streaming flushes, spec drafts).
        Everything here reads post-in-flight offsets (_dispatch_window
        advances them at dispatch)."""
        if self.active == 0:
            return None
        # a verify window's rows advance by what it accepts: the host's
        # offsets come from its fetch, so nothing is stacked on it
        if self._inflight and self._inflight[-1].get("verify"):
            return None
        # queued/checkpoint work needs settled rows at the next sync;
        # streaming rows need token flushes at chunk cadence, not
        # pending*K tokens late
        if self._queue or self._checkpoints:
            return None
        if any(r is not None and r.stream for r in self._rows):
            return None
        # a spec-eligible row wants a draft look at the NEXT readback —
        # stacking plain windows ahead of it would decode past the
        # repetition the drafter feeds on
        if self._spec_wants_sync():
            return None
        e = self.engine
        # some row must still need tokens BEYOND what is already in
        # flight, or the whole window would be budget overshoot
        if min(self._budgets(pending)) <= 0:
            return None
        chosen = self._window_size(pending)
        n = chosen[0]
        growth = [
            (b, int(self._offsets[b]) + n)
            for b, r in enumerate(self._rows) if r is not None
        ]
        # hard capacity: a ring's first window may overshoot into the
        # decode_chunk margin once; stacked look-ahead may not
        if any(upto > e.max_seq_len for _, upto in growth):
            return None
        # the free list must cover the window outright: look-ahead never
        # reclaims prefix pins and never migrates/retires a row
        return chosen if self.cache.growth_fits(growth) else None

    @_phase("fetch")
    def _fetch_window(self, rec):
        """THE host sync of the decode hot loop: block on one in-flight
        window's token buffers -> its tokens, [B, n] (a verify window's: a
        row's array each, _verify_rows). Everything else the step needs came
        back with earlier fetches or never left the host."""
        _G_OVERLAP.set(len(self._inflight))
        _C_HOST_SYNCS.inc()
        verify = rec.get("verify", False)
        # a verify window's verdicts, last draft and offsets ride the fetch
        more = [rec[k] for k in ("acc", "draft", "off")] if verify else []
        with get_tracer().span(
            "engine.spec_verify" if verify else "engine.decode_window",
            active=len(rec["rows"]), chunks=len(rec["toks"]), steps=rec["n"],
            inflight=len(self._inflight),
        ):
            # (an expert model's counters ride the same fetch: rec["moe"])
            got = [np.asarray(x) for x in jax.device_get(rec["toks"] + rec.get("moe", []) + more)]  # meshlint: ignore[ML-J003] -- the one sanctioned sync per readback window (docs/PERF.md)
        k, m = len(rec["toks"]), len(got) - len(more)
        parts, moe, more = got[:k], got[k:m], got[m:]
        if moe:
            self._note_moe(moe, windows=len(rec["toks"]))
        if verify:
            toks_host = self._verify_rows(rec, parts[0], *more)
        else:
            # [B, n]: a chunk's buffer is decode_chunk wide, its steps come
            # first (a window's chunks are full but the last)
            toks_host = np.concatenate(parts, axis=1)[:, :rec["n"]]
        if not verify and not self._inflight:
            # ring drained: the host mirror of the latest sampled token
            # is this window's last column (mid-ring fetches skip this —
            # a NEWER window is already chained off the device value)
            self._cur = toks_host[:, -1].astype(np.int32).copy()
        now = time.perf_counter()
        _H_STEP.observe((now - rec["t0"]) * 1000.0)
        # a window queued behind another started when that one was fetched
        self._step_s.note(
            (now - max(rec["t0"], self._t_fetched or 0.0)) / rec["n"])
        self._t_fetched = now
        return toks_host

    def _count_moe(self, live: int, dead: int, forwards: int,
                   mtp: bool = False):
        """The host's half of the expert layer's counters for one dispatch
        of ``forwards`` forwards over ``live`` real and ``dead`` padded
        positions in all: assignments and layer calls follow from shapes
        (what the device's live mask will do: core.forward's token_live).
        ``mtp``: the program ran the model's MTP layer behind the trunk, over
        the same positions (a prefill or a verify step of the ``mtp`` tier)."""
        cfg = self.engine.model_cfg
        if not cfg.moe_dropless:
            return
        layers = cfg.n_expert_calls if mtp else cfg.n_expert_layers
        per = cfg.n_experts_per_tok * layers
        if not cfg.expert_share:  # a share's split is the device's to say
            _C_MOE_ASSIGNMENTS.inc(live * per, kind="live")
        _C_MOE_ASSIGNMENTS.inc(dead * per, kind="dead")
        _C_MOE_LAYER_CALLS.inc(forwards * layers)

    def _note_moe(self, stats: list, windows: int):
        """The device's half, fetched with a window's tokens: ``stats`` are
        the [hit, max_load, live] vectors (core.moe_stats_names) of the
        prefills since the last window, then of this window's ``windows``
        chunks (the gauge reads those alone). Under an expert share they
        speak of the experts held HERE, a fourth entry counts the live
        assignments held elsewhere, and both kinds are counted from it."""
        cfg = self.engine.model_cfg
        got = np.asarray(stats, np.int64)
        _C_MOE_EXPERTS_HIT.inc(int(got[:, 0].sum()))
        if cfg.expert_share:
            _C_MOE_ASSIGNMENTS.inc(int(got[:, 2].sum()), kind="live")
            _C_MOE_ASSIGNMENTS.inc(int(got[:, 3].sum()), kind="elsewhere")
        _, max_load, live = got[-windows:, :3].sum(axis=0)
        if live:
            _G_MOE_LOAD.set(float(max_load) * cfg.experts_held / float(live))

    @_phase("settle")
    def _settle_window(self, rec, toks_host: np.ndarray) -> bool:
        """Route one fetched window's tokens through the scheduler's half
        of the shared per-row intake (_settle_row) and queue the callers'
        half for _deliver_pending. Rows that retired or moved since
        dispatch are skipped — their overshoot tokens are scheduled-only
        work the goodput meter already books as waste."""
        window = []
        verify = rec.get("verify", False)
        for b, req in rec["rows"]:
            if self._rows[b] is not req or req.done:
                continue
            req.chunks_decoded += rec["n"] if verify else len(rec["toks"])
            window.append(self._settle_row(b, req, toks_host[b]))
            if verify:
                self._book_verify_row(rec, b, window[-1])
        if verify:  # a step's K + 1 slots a row
            rows, steps = len(toks_host), rec["slots"]
        else:
            rows, steps = toks_host.shape
        self._meter.note_slots(rows, len(rec["rows"]), steps,
                               sum(len(entry[1]) for entry in window))
        # the ended rows' callers first: what they send next fills the rows
        window.sort(key=lambda entry: not entry[2])
        self._undelivered.append(deque(window))
        return bool(window) and window[0][2]

    def _process_window(self, rec, toks_host: np.ndarray) -> bool:
        """Settle and deliver one fetched window back to back (with any
        window settled before it, in order)."""
        retired_any = self._settle_window(rec, toks_host)
        self._deliver_pending()
        return retired_any

    def _drain_inflight(self) -> bool:
        """Fetch + process every in-flight window (admission, checkpoints
        and shutdown paths need settled row state). Each drained fetch is
        a stall by definition — the device goes idle behind it."""
        retired_any = False
        while self._inflight:
            rec = self._inflight.popleft()
            _C_SYNC_STALLS.inc()
            toks_host = self._fetch_window(rec)
            retired_any |= self._process_window(rec, toks_host)
        self._release_deferred()
        return retired_any

    @_phase("process")
    def _release_deferred(self):
        """Free blocks whose rows retired while windows were in flight —
        only once the ring is empty (until then, in-flight windows still
        dead-row-scatter into them)."""
        if not self._inflight:
            self.cache.flush_deferred()

    def _vacate(self, b: int, req: Request):
        """Row b's request ended: the row, its adapter refcount and its
        drafter state (KV slot / mesh server row) are free for the next
        admission. The caller's done event is _retire's."""
        self._release_row(b)
        self._release_adapter(req)
        if self._spec is not None:
            self._spec.forget(req)

    def _retire(self, req: Request):
        req.timing.t_done = time.perf_counter()
        self.stats.retired += 1
        self.stats.history.append(
            {"new_tokens": len(req.out_ids), "chunks": req.chunks_decoded}
        )
        req.events.put({"done": True, "result": self.engine._build_result(req)})

    def _retire_error(self, req: Request, reason: str):
        """Error-terminate an ADMITTED row with full retirement accounting
        (retired/history/t_done) — `admitted - retired` must not drift for
        rows the pool failed mid-decode."""
        if self._spec is not None:
            self._spec.forget(req)
        req.timing.t_done = time.perf_counter()
        self.stats.retired += 1
        self.stats.history.append(
            {"new_tokens": len(req.out_ids), "chunks": req.chunks_decoded,
             "error": True}
        )
        self._fail(req, reason)
