"""StageRunner: executes one pipeline stage of a model on this node's mesh.

The worker-side half of cross-peer pipeline serving (BASELINE config 4).
A node loads layers [a, b) of a model (models/stages.py) and answers
part_forward requests: ids or hidden states in, hidden states or logits
out, with a per-request KV cache held between calls — the TPU-native
realization of the reference's partial-model worker (reference
node.py:236-277: HF_PART_LOAD builds a layer range, HF_PART_FORWARD feeds
text or received hidden states).

Design:
- One jit'd stage_forward per (T, cached?) shape — prefill (T=prompt
  bucket) and decode (T=1) each compile once; the cache is donated so XLA
  updates it in HBM.
- Caches are per request_id, created lazily at first forward and dropped
  on release() (or by the idle reaper when a coordinator vanishes).
- Thread-safe: gateways/mesh handlers call from executor threads; a lock
  guards the cache table only (jax dispatch is itself thread-safe).
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import get_registry
from ..models import config as model_config
from ..models import core, stages, support

STALE_CACHE_S = 600.0  # drop request caches untouched this long

# serving forward time (jit dispatch + host readback), measured INSIDE
# the concurrency gate so queue/semaphore wait never inflates it: the
# digest p50 of this series is the "stage compute" the coordinator's
# microbatch auto-depth heuristic divides by (meshnet/pipeline.py
# resolve_microbatches; health.DIGEST_HISTOGRAMS carries it).
_H_STAGE_TASK_MS = get_registry().histogram(
    "pipeline.stage_task_ms",
    "stage forward compute + readback time (excludes queue wait)",
)


class StageRunner:
    def __init__(
        self,
        model: str | model_config.ModelConfig,
        n_stages: int,
        stage: int,
        params=None,  # FULL param tree (sliced here) — or None to random-init
        checkpoint_path: str | None = None,
        max_seq_len: int = 2048,
        dtype: str = "bfloat16",
        rng_seed: int = 0,
        max_batch: int = 8,
        quantize: str = "none",  # "int8": weight-only quant of THIS stage's
        # slice — a 7B half per peer is exactly where halved weight HBM pays
        stale_cache_s: float = STALE_CACHE_S,  # reap TTL for abandoned
        # request caches (failover tests shrink it; long-idle coordinators
        # raise it)
        epoch: int = 0,  # stage epoch (pipeline failover): tasks stamped
        # with a different epoch are rejected, so late traffic routed to a
        # replaced occupant can never corrupt the rebuilt chain
        max_concurrent_forwards: int = 4,  # concurrent jit dispatches this
        # stage will run: an interleaved coordinator free-runs one chain
        # per microbatch group, and without a bound a deep group fan-out
        # (or several coordinators sharing a worker) queues unbounded
        # compute on the device while earlier dispatches still hold HBM
        # scratch. Excess callers BLOCK on their executor thread — the
        # wire-level backpressure the coordinator's sliding window rides
    ):
        # same any-checkpoint rule as the engine
        # (`serve-stage --model auto --checkpoint <dir>`)
        self.model_cfg = model_config.resolve_model_config(model, checkpoint_path)
        support.require(self.model_cfg, "pipeline_stages")
        # the mesh addresses runners by the COORDINATOR'S model string —
        # remember what the caller asked for so add_stage_runner can alias
        # it to the resolved config name
        self.requested_model = model if isinstance(model, str) else self.model_cfg.name
        self.spec = stages.StageSpec.build(self.model_cfg, n_stages, stage)
        self.dtype = jnp.dtype(dtype)
        self.max_seq_len = min(max_seq_len, self.model_cfg.max_seq_len)
        self.max_batch = max_batch
        self.stale_cache_s = float(stale_cache_s)
        self.epoch = int(epoch)
        # identity fields for matches_load (part_load idempotency): a
        # failover re-load of the SAME stage must be a no-op, not a rebuild
        self.checkpoint_path = checkpoint_path
        self.rng_seed = int(rng_seed)
        quantize = quantize or "none"  # accept ''/None like the engine does
        if quantize not in ("none", "int8"):
            raise ValueError(f"quantize={quantize!r}: only 'int8' or 'none'")
        self.quantize = quantize

        if params is None and checkpoint_path:
            from ..models.loader import load_checkpoint

            # quantizing: keep the load host-side so the dense model never
            # materializes in device memory (engine.py does the same)
            params = load_checkpoint(
                checkpoint_path, self.model_cfg, dtype=self.dtype,
                host=quantize == "int8",
            )
        if params is None:
            # deterministic random init: every stage of a pipeline derives
            # the SAME full tree from the seed, then keeps its slice — so
            # peers agree on weights without moving bytes (tests; real
            # deployments load a checkpoint or fetch pieces)
            params = core.init_params(
                self.model_cfg, jax.random.key(rng_seed), dtype=self.dtype
            )
        sliced = stages.extract_stage_params(params, self.model_cfg, self.spec)
        if quantize == "int8" or jax.default_backend() == "cpu":
            # host-side transforms, then ONE device upload (a 7B-class
            # slice making extra device round trips at part_load is real
            # time): int8 quantizes the slice; single-device CPU unstacks
            # layers into contiguous per-layer arrays (the XLA:CPU
            # packed-GEMM issue — core.forward / docs/PERF.md). TPU keeps
            # the stacked scan.
            host = jax.device_get(sliced)
            if quantize == "int8":
                from ..models.quant import quantize_params

                host = quantize_params(host)
            if jax.default_backend() == "cpu":
                host = core.unstack_layers(host)
            self.params = jax.tree.map(jnp.asarray, host)
        else:
            self.params = sliced

        def _wrapped(p, x, cache, off, mask, gather):
            out, c = stages.stage_forward(
                p, self.model_cfg, self.spec, x, cache, off, write_mask=mask
            )
            if gather is not None and self.spec.is_last:
                # per-row position pick: [B, T, V] -> [B, V]. Keeps a
                # session prefill from shipping bucket*V logits per row
                # over the wire when only one position per row matters.
                out = out[jnp.arange(out.shape[0]), jnp.asarray(gather, jnp.int32)]
            return out, c

        # retrace sentinel (engine/introspect.py, ISSUE 15): the stage
        # forward is THE pipeline worker's hot jit root — per-instance
        # sentinel (a fresh runner's compiles are its own warm-up), no
        # declared predicate (prefill widths come from the coordinator's
        # bucketing; any FIRST-seen shape is growth, repeats storm).
        from .introspect import RetraceSentinel

        self._sentinel = RetraceSentinel()
        self._fwd = self._sentinel.watch(
            "stage_forward",
            jax.jit(_wrapped, donate_argnums=(2,)),
            key_fn=lambda p, x, cache, off, mask, gather: (
                tuple(int(s) for s in x.shape),
                mask is not None, gather is not None,
            ),
        )
        self._caches: dict[str, dict] = {}  # request_id -> {"cache", "touched"}
        self._lock = threading.Lock()
        self.max_concurrent_forwards = max(1, int(max_concurrent_forwards))
        self._fwd_sem = threading.BoundedSemaphore(self.max_concurrent_forwards)

        # ---- cross-peer pipeline TRAINING (TPU-native realization of the
        # reference's layer_forward_train/layer_backward worker tasks,
        # reference node.py:99-182 — toy numpy MLP there; real stage VJP
        # + in-place SGD on the stage's own params here) ----
        # all dtype casts live INSIDE the jitted fns: an eager astype is a
        # dispatch of its own per call (cost not measured on the current
        # machine)
        out_dtype = jnp.float32 if self.spec.is_last else self.dtype

        def _fwd_train_raw(p, x):
            out, _ = stages.stage_forward(p, self.model_cfg, self.spec, x, None, 0)
            return out

        def _fwd_train(p, x):
            return _fwd_train_raw(p, x).astype(out_dtype)

        def _bwd(p, x, dy):
            if self.spec.is_first:  # x is int ids: no gradient flows to it
                out, vjp = jax.vjp(lambda p_: _fwd_train_raw(p_, x), p)
                (dp,) = vjp(dy.astype(out.dtype))
                return dp, None
            out, vjp = jax.vjp(_fwd_train_raw, p, x)
            dp, dx = vjp(dy.astype(out.dtype))
            return dp, dx.astype(self.dtype)

        def _sgd(p, dp, lr):
            return jax.tree.map(lambda w, g: w - lr * g.astype(w.dtype), p, dp)

        self._fwd_train = jax.jit(_fwd_train)
        self._bwd = jax.jit(_bwd)
        # NO donation: a concurrent inference forward may hold the old
        # param tree mid-dispatch (serve + train share the runner);
        # donating would delete buffers out from under it
        self._sgd = jax.jit(_sgd)
        self._train_acts: dict[str, dict] = {}  # request_id -> {"x", "touched"}

    # ------------------------------------------------------------------ API

    @property
    def info(self) -> dict:
        return {
            "model": self.model_cfg.name,
            "n_stages": self.spec.n_stages,
            "stage": self.spec.stage,
            "layers": [self.spec.start, self.spec.end],
            "is_first": self.spec.is_first,
            "is_last": self.spec.is_last,
            "max_seq_len": self.max_seq_len,
            # observable over the wire (part_load RESULT): a coordinator
            # can CONFIRM its stages quantized, not just request it
            "quantize": self.quantize,
            # a worker that outlived a coordinator restart reports the
            # epoch it is at; the coordinator adopts the max and re-loads
            "epoch": self.epoch,
            # stage-side concurrency cap: how many chains this worker
            # will run at once (the interleaved session's window should
            # not be sized past the fleet's smallest cap)
            "max_concurrent_forwards": self.max_concurrent_forwards,
        }

    def matches_load(self, data: dict) -> bool:
        """Does a part_load request describe THIS runner? Same model
        identity, partition, weights source, and serving shape — epoch
        excluded on purpose: an epoch bump ADOPTS the runner (no-op
        re-load, relay links re-dialed) instead of recompiling it."""
        model = data.get("model")
        try:
            dtype_match = jnp.dtype(data.get("dtype", "bfloat16")) == self.dtype
        except TypeError:
            return False
        return (
            model in (self.requested_model, self.model_cfg.name)
            and int(data.get("n_stages", -1)) == self.spec.n_stages
            and int(data.get("stage", -1)) == self.spec.stage
            and (data.get("checkpoint_path") or None) == self.checkpoint_path
            and int(data.get("rng_seed", 0)) == self.rng_seed
            and dtype_match
            and min(int(data.get("max_seq_len", 2048)),
                    self.model_cfg.max_seq_len) == self.max_seq_len
            and (data.get("quantize") or "none") == self.quantize
        )

    def forward(
        self,
        request_id: str,
        x: np.ndarray,
        offset,  # int | [B] int array — per-row write positions
        write_mask=None,  # [B] bool — rows whose cache this call updates
        gather=None,  # [B] int — last stage returns logits[b, gather[b]] only
    ) -> np.ndarray:
        """Run a chunk through this stage against the request's cache.

        x: [B, T] int ids on the first stage, [B, T, D] hidden later.
        Returns hidden [B, T, D] (f32) or logits [B, T, V] (f32, last).

        A batched pipeline session passes offset as a [B] vector (each row
        decodes at its own depth) and write_mask to admit one row's prefill
        without touching live rows (meshnet/pipeline.PipelineSession)."""
        if self.spec.is_first:
            xj = jnp.asarray(x, jnp.int32)
            B = xj.shape[0]
        else:
            xj = jnp.asarray(x, self.dtype)
            B = xj.shape[0]
        with self._lock:
            self._reap_stale()
            entry = self._caches.get(request_id)
            if entry is None:
                if len(self._caches) >= self.max_batch:
                    raise RuntimeError(
                        f"stage cache table full ({self.max_batch} requests)"
                    )
                entry = {
                    "cache": stages.init_stage_cache(
                        self.model_cfg, self.spec, B, self.max_seq_len, self.dtype
                    ),
                    "touched": time.time(),
                }
                self._caches[request_id] = entry
            cache = entry["cache"]
            if cache is None:
                # a second in-flight forward for the same request would
                # otherwise run uncached (None) and silently diverge
                raise RuntimeError(f"concurrent forward for request {request_id!r}")
            entry["cache"] = None  # donated below; never leave a stale ref
        off = jnp.asarray(np.asarray(offset, np.int32))
        mask = None if write_mask is None else jnp.asarray(np.asarray(write_mask, bool))
        gat = (
            None
            if (gather is None or not self.spec.is_last)
            else jnp.asarray(np.asarray(gather, np.int32))
        )
        try:
            with self._fwd_sem:
                t0 = time.perf_counter()
                out, cache = self._fwd(self.params, xj, cache, off, mask, gat)
        except Exception:
            # free the slot: leaving the None entry would burn a max_batch
            # row for stale_cache_s and turn retries into misleading
            # "concurrent forward" errors
            with self._lock:
                self._caches.pop(request_id, None)
            raise
        with self._lock:
            if request_id in self._caches:  # release() may have raced us
                self._caches[request_id] = {"cache": cache, "touched": time.time()}
        # logits stay f32 (sampling precision); hidden states cross the wire
        # in the compute dtype (bf16 halves inter-peer bandwidth, the
        # stages.py design point)
        if self.spec.is_last:
            host = np.asarray(jax.device_get(out), np.float32)
        else:
            host = np.asarray(jax.device_get(out.astype(self.dtype)))
        _H_STAGE_TASK_MS.observe((time.perf_counter() - t0) * 1000.0)
        return host

    # ----------------------------------------------------------- training

    def forward_train(self, request_id: str, x: np.ndarray) -> np.ndarray:
        """Uncached full forward, retaining this stage's input for the
        matching backward (one in-flight microbatch per request_id).
        Abandoned retentions are reaped with the stale caches."""
        if self.quantize != "none":
            raise RuntimeError(
                "training through a quantized stage is unsupported "
                "(gradients w.r.t. int8 payloads are meaningless)"
            )
        x_host = np.asarray(x, np.int32 if self.spec.is_first else None)
        with self._lock:
            self._reap_stale()
            self._train_acts[request_id] = {"x": x_host, "touched": time.time()}
        out = self._fwd_train(self.params, x_host)
        return np.asarray(jax.device_get(out))

    def backward(self, request_id: str, dy: np.ndarray, lr: float) -> np.ndarray | None:
        """VJP against the retained activation; SGD-update this stage's
        params; return dX for the previous stage (None on the first stage
        — ids take no gradient). Cotangent/output casts happen inside the
        jitted _bwd (dtype bookkeeping is compiled, not eager)."""
        with self._lock:
            entry = self._train_acts.pop(request_id, None)
        if entry is None:
            raise RuntimeError(f"no retained forward for request {request_id!r}")
        dp, dx = self._bwd(self.params, entry["x"], np.asarray(dy))
        self.params = self._sgd(self.params, dp, np.float32(lr))
        if dx is None:
            return None
        return np.asarray(jax.device_get(dx))

    def release(self, request_id: str) -> None:
        with self._lock:
            self._caches.pop(request_id, None)
            self._train_acts.pop(request_id, None)

    def _reap_stale(self) -> None:
        now = time.time()
        for table in (self._caches, self._train_acts):
            dead = [
                rid for rid, e in table.items()
                if now - e["touched"] > self.stale_cache_s
            ]
            for rid in dead:
                table.pop(rid, None)

    @property
    def active_requests(self) -> int:
        with self._lock:
            return len(self._caches)
