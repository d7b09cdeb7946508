"""Engine economics plane: retrace sentinel, HBM ledger, MFU/goodput meters,
and on-demand device profiling (ISSUE 15).

The mesh's observability so far (tracing spans, metrics histograms, the
health digest) describes *requests*. This module instruments the engine's
*execution economics* — the axes a TPU serving stack silently loses money
on:

- **RetraceSentinel** — a registry of the engine's jit roots (prefill /
  decode / penalized decode / spec-verify / CoW block copy, the pipeline
  StageRunner's stage forward). Each registered root counts its traces
  (exactly, via the jit callable's own cache size — persistent-compile-
  cache hits still count as the retrace they are) and its compile
  wall-time (attributed from ``jax.monitoring``'s backend-compile events
  while a watched call is on the stack), exposed as
  ``engine.compiles_total{root}`` / ``engine.compile_seconds{root}``.
  The **warm-up contract**: every root declares its legitimate compile
  space as a predicate over a small shape key (prefill bucket widths,
  pow2 batch buckets, pow2 block-table widths). The FIRST compile of
  each declared key — at boot or at late bucket growth — is warm-up and
  fires nothing, whenever it happens. A compile for an UNDECLARED key is
  a steady-state retrace and fires a typed ``engine:retrace_storm``
  flight-recorder incident naming the root immediately; repeated
  compiles of an already-seen key (weak-type flips, accidental cache
  invalidation) fire the same incident once they storm
  (``storm_repeats`` within ``storm_window_s``).
- **HbmLedger** — per-device live-memory breakdown from the engine's own
  buffer handles (weights / KV pool + scales / adapter pool), plus
  ``device.memory_stats()`` where the backend provides it (TPU does; CPU
  returns None): ``engine.hbm_bytes{component}`` gauges, an
  ``engine.hbm_headroom_frac`` gauge, and a workspace/other residual when
  the device total is known. The attached **PoolForecast** projects the
  paged pool's growth rate into an ``engine.pool_exhaust_eta_s`` gauge
  that feeds the admission controller's ``pool_exhausted`` shed *before*
  the free-fraction floor trips.
- **GoodputMeter** — an analytic per-model FLOPs model (matmul +
  attention terms, prefill vs decode) turns the scheduler's dispatches
  into ``engine.mfu`` (model FLOP/s over the platform peak — the honest
  utilization number, per "Scalable Training of LMs with pjit on TPUv4")
  and ``engine.goodput_tokens_per_s``, distinguishing *scheduled* token
  positions from *useful* tokens: rejected spec drafts, padded prefill
  tails, post-EOS window overshoot, failover re-prefills and migration
  re-decodes all count against goodput.
- **DeviceProfiler** — duration-bounded ``jax.profiler`` capture behind
  ``POST /debug/profile`` (api.py): artifacts zip under
  ``$BEE2BEE_INCIDENT_DIR/profiles`` and list/fetch like incidents;
  concurrent capture is refused typed.

Everything honors the telemetry never-throw contract: the sentinel,
ledger and meter must never take down a decode step. The module imports
no jax at import time (api.py imports it for the profile route).
"""

from __future__ import annotations

import io
import logging
import os
import threading
import time
import weakref
import zipfile
from collections import deque
from pathlib import Path
from typing import Callable

from ..health import get_recorder, register_digest_provider
from ..metrics import get_registry
from ..tracing import PhaseClock, get_tracer
from ..utils import new_id

logger = logging.getLogger("bee2bee_tpu.introspect")

_REG = get_registry()
# per-root compile accounting. The `root` label set is closed — it is
# exactly the roots the engine/stage-runner register at construction —
# so cardinality is bounded like every other labeled series here.
_C_COMPILES = _REG.counter(
    "engine.compiles", "jit traces per registered engine root"
)
_C_COMPILE_SECONDS = _REG.counter(
    "engine.compile_seconds", "XLA compile wall-time per registered root"
)
_C_RETRACE_STORMS = _REG.counter(
    "engine.retrace_storms",
    "steady-state retraces detected per root (undeclared shapes / "
    "repeat-key compile storms)",
)
_C_KEY_ERRORS = _REG.counter(
    "engine.compile_key_errors",
    "compiles of a root whose key function raised: counted un-keyed, "
    "never classified",
)
_G_MFU = _REG.gauge(
    "engine.mfu",
    "model FLOP/s over platform peak FLOP/s, trailing window (0..1)",
)
_G_GOODPUT = _REG.gauge(
    "engine.goodput_tokens_per_s",
    "USEFUL tokens per second over the trailing window (rejected drafts, "
    "re-prefills and overshoot excluded)",
)
_G_SCHEDULED_TPS = _REG.gauge(
    "engine.scheduled_tokens_per_s",
    "token positions dispatched per second over the trailing window",
)
_G_GOODPUT_FRAC = _REG.gauge(
    "engine.goodput_fraction",
    "useful / scheduled tokens over the trailing window (0..1)",
)
_G_SPEC_ACCEPT = _REG.gauge(
    "engine.spec_acceptance",
    "cumulative accepted/drafted speculative tokens per drafter tier "
    "(tier label; absent until that tier has drafted)",
)
_G_HBM_BYTES = _REG.gauge(
    "engine.hbm_bytes", "live device memory by component (bytes)"
)
_G_HBM_HEADROOM = _REG.gauge(
    "engine.hbm_headroom_frac",
    "fraction of device memory still free (1 - in_use/limit)",
)
_G_POOL_ETA = _REG.gauge(
    "engine.pool_exhaust_eta_s",
    "projected seconds until the paged KV pool runs dry at the current "
    "growth rate (absent when the pool is not growing)",
)
_C_HOST_SYNCS = _REG.counter(
    "engine.host_syncs",
    "device->host token fetches in the decode hot loop (one per readback "
    "window — the only blocking point the overlap design permits)",
)
_C_SYNC_STALLS = _REG.counter(
    "engine.host_sync_stalls",
    "host syncs that blocked with NO other decode window in flight — the "
    "device sat idle while the host processed tokens (0 when overlap "
    "keeps the ring full)",
)
_C_PHASE_SECONDS = _REG.counter(
    "engine.phase_seconds",
    "scheduler-thread wall seconds by loop phase (phase label: admit, "
    "dispatch, fetch = blocked on the device, settle, process, compact, "
    "turn = the loop's own lines between them)",
)
_C_ADMIT_SECONDS = _REG.counter(
    "engine.admit_seconds",
    "the admit phase's seconds once more, by part of an admission call "
    "(part label: dispatch = popping, planning and dispatching the burst's "
    "prefills | wait = blocked in the burst's one first-token gather, the "
    "chip running prefills and no decode window | emit = handing out the "
    "first tokens | none = a call that found nobody to admit); the parts "
    "sum to engine.phase_seconds{phase=\"admit\"}",
)
_C_DECODE_SLOTS = _REG.counter(
    "engine.decode_slots",
    "token slots of every fetched decode window (batch-bucket rows x "
    "steps) and spec-verify step (rows x K+1), by what became of them "
    "(kind label: kept = a token that entered an output | after_end = a "
    "live row's slots past its stop or budget, a rejected draft, a row "
    "that retired or moved since dispatch | dead_row = rows of the bucket "
    "with no request)",
)
_G_OVERLAP = _REG.gauge(
    "engine.overlap_inflight",
    "decode windows still in flight on-device at readback time (0 = "
    "serialized loop, >=1 = async dispatch overlap is working)",
)

# ---------------------------------------------------------------- FLOPs model


# bf16 peak FLOP/s of one chip, keyed by ``device_kind`` exactly as jax
# reports it (jax knows two spellings for the lite parts). Source: Google
# Cloud TPU documentation, "System architecture" page of each version.
TPU_PEAK_BF16_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e
    "TPU v6e": 918e12,
}


def peak_flops_per_device(platform: str, device_kind: str = "") -> float:
    """Peak dense FLOP/s for one device, for the MFU denominator.

    ``BEE2BEE_PEAK_FLOPS`` (per device) overrides everything — the only
    honest number for a part the table lacks. An accelerator that is not
    in the table is an ERROR, never a default: a utilization over a
    guessed peak is a wrong number with a real device's name on it. The
    CPU value is a NOMINAL placeholder so the gauge exists on dev boxes —
    CPU "MFU" is a proxy number, never a hardware claim
    (docs/OBSERVABILITY.md)."""
    env = os.environ.get("BEE2BEE_PEAK_FLOPS")
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            logger.warning("BEE2BEE_PEAK_FLOPS=%r is not a number", env)
    if platform == "cpu":
        return 1e11  # nominal CPU placeholder (proxy MFU only)
    if platform == "tpu" and device_kind in TPU_PEAK_BF16_FLOPS:
        return TPU_PEAK_BF16_FLOPS[device_kind]
    raise ValueError(
        f"no peak FLOP/s known for platform={platform!r} "
        f"device_kind={device_kind!r}: add it to TPU_PEAK_BF16_FLOPS with "
        "its source, or set BEE2BEE_PEAK_FLOPS (per device)"
    )


class FlopsModel:
    """Analytic forward-FLOPs model for one ModelConfig.

    ``flops(positions, ctx)`` = positions × (2·matmul_params +
    4·L·H·hd·ctx): the matmul term streams every (active) weight twice
    per position (multiply + add), the attention term is QKᵀ + AV
    against ``ctx`` cached positions across all query heads. Spec-verify
    and prefill positions use the same per-position formula — what
    differs between modes is how many positions the scheduler dispatches
    and what fraction turns out useful, which is exactly what the meter
    tracks separately."""

    def __init__(self, model_cfg):
        from ..models.core import matmul_params_per_token

        self.matmul_flops_per_pos = 2.0 * matmul_params_per_token(model_cfg)
        self.attn_flops_per_pos_per_ctx = (
            4.0 * model_cfg.cache_layers * model_cfg.n_heads * model_cfg.head_dim
        )

    def flops(self, positions: float, ctx: float) -> float:
        return positions * (
            self.matmul_flops_per_pos
            + self.attn_flops_per_pos_per_ctx * max(ctx, 0.0)
        )


# ------------------------------------------------------------ retrace sentinel

# thread-local attribution stack for jax.monitoring compile events: the
# wrapped call pushes its root before dispatching into jax, so a compile
# fired on this thread during the call books its wall-time to that root.
_TLS = threading.local()
_LISTENER_LOCK = threading.Lock()
_LISTENER_WIRED = False
# compile seconds observed OUTSIDE any watched root (model init, eager
# ops, unwatched jits) — kept so total compile time stays accountable
_OTHER_ROOT = "other"


def _wire_monitoring_listener() -> None:
    global _LISTENER_WIRED
    with _LISTENER_LOCK:
        if _LISTENER_WIRED:
            return
        try:
            import jax.monitoring

            def _on_duration(event: str, duration: float, **_kw) -> None:
                if event != "/jax/core/compile/backend_compile_duration":
                    return
                try:
                    stack = getattr(_TLS, "stack", None)
                    root = stack[-1][1].name if stack else _OTHER_ROOT
                    _C_COMPILE_SECONDS.inc(float(duration), root=root)
                except Exception:  # noqa: BLE001 — telemetry never throws
                    pass

            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _LISTENER_WIRED = True
        except Exception:  # noqa: BLE001 — a jax without monitoring only
            # loses compile-time attribution, never serving
            logger.exception("jax.monitoring listener not wired")
            _LISTENER_WIRED = True


class _Root:
    __slots__ = (
        "name", "allowed", "seen", "traces", "last_cache_size",
        "repeat_ts", "storms", "last_storm_ts", "key_errors",
    )

    def __init__(self, name: str, allowed: Callable | None):
        self.name = name
        self.allowed = allowed
        self.seen: set = set()
        self.traces = 0
        self.last_cache_size = 0
        # PER-KEY repeat timestamps: a cache-flush re-warm recompiles
        # many distinct seen keys once each — only the SAME key storming
        # is the per-step-retrace signal (bounded: keys ⊆ seen)
        self.repeat_ts: dict = {}
        self.storms = 0
        self.last_storm_ts = 0.0
        self.key_errors = 0  # compiles whose key function raised


class RetraceSentinel:
    """Watches registered jit roots for steady-state retraces.

    One sentinel per engine/StageRunner instance: a fresh engine's boot
    compiles are that instance's warm-up, not a storm in a long-lived
    sibling. The metrics are process-global (label ``root``), so multiple
    engines in one process sum — what a /metrics consumer wants."""

    def __init__(
        self,
        node: str | None = None,
        storm_window_s: float = 60.0,
        storm_repeats: int = 3,
        recorder=None,
    ):
        self.node = node
        self.storm_window_s = float(storm_window_s)
        self.storm_repeats = int(storm_repeats)
        self._recorder = recorder
        self._lock = threading.Lock()
        self._roots: dict[str, _Root] = {}
        _wire_monitoring_listener()

    # ---- registration

    def watch(self, name: str, fn, key_fn: Callable | None = None,
              allowed: Callable | None = None):
        """Wrap a jit callable as root ``name``.

        ``key_fn(*args, **kwargs)`` maps a call to a SMALL hashable shape
        key (the registrar knows the calling convention — include
        None-flags for optional operands that select different traces);
        default: no key (every trace counts, classification limited to
        repeat-storms). ``allowed(key)`` declares the legitimate compile
        space; None accepts any first-seen key (pure growth roots)."""
        with self._lock:
            root = self._roots.get(name)
            if root is None:
                root = self._roots[name] = _Root(name, allowed)

        def wrapped(*args, **kwargs):
            stack = getattr(_TLS, "stack", None)
            if stack is None:
                stack = _TLS.stack = []
            # THIS call's cache-size baseline, read before dispatch:
            # concurrent calls through one root each compare against
            # their own baseline, so two overlapping compiles both count
            # and both classify (a shared last-size would silently drop
            # the second thread's trace — and its incident)
            try:
                sizer = getattr(fn, "_cache_size", None)
                n0 = int(sizer()) if sizer is not None else None
            except Exception:  # noqa: BLE001 — telemetry never throws
                n0 = None
            stack.append((self, root))
            try:
                return fn(*args, **kwargs)
            finally:
                stack.pop()
                self._after_call(root, fn, key_fn, args, kwargs, n0)

        wrapped.__wrapped__ = fn
        wrapped.__name__ = getattr(fn, "__name__", name)
        # capability markers (e.g. the ragged attn fn's `ragged` flag)
        # must survive the wrap — callers feature-detect off attributes
        for attr in ("ragged",):
            if hasattr(fn, attr):
                setattr(wrapped, attr, getattr(fn, attr))
        return wrapped

    # ---- classification

    def _after_call(self, root: _Root, fn, key_fn, args, kwargs,
                    n0: int | None) -> None:
        """Trace detection via the jit callable's own cache size, per
        call (grew across THIS call = this call traced): exact, and
        independent of the persistent compile cache (a disk hit skips
        XLA but still paid the trace+lowering this sentinel exists to
        catch). A cache cleared mid-call (jax.clear_caches) reads as
        n <= n0 — no count; keys stay seen so the re-compiles classify
        as repeats only if they ALSO storm. Never throws."""
        try:
            sizer = getattr(fn, "_cache_size", None)
            if sizer is None or n0 is None:
                return
            n = int(sizer())
            if n <= n0:
                return
            with self._lock:
                root.last_cache_size = n
                root.traces += 1
            _C_COMPILES.inc(root=root.name)
            key = None
            if key_fn is not None:
                try:
                    key = key_fn(*args, **kwargs)
                except Exception:  # noqa: BLE001
                    # the root runs UN-KEYED from here (counted, never
                    # classified): say so, once a root, and count each
                    key = None
                    _C_KEY_ERRORS.inc(root=root.name)
                    with self._lock:
                        root.key_errors += 1
                        first = root.key_errors == 1
                    if first:
                        logger.warning(
                            "compile: the key function of root=%s raised; "
                            "its compiles are counted but not classified",
                            root.name, exc_info=True)
            # every counted compile says which shape: /trace?name=
            # engine.compile lists what compiled and when, the log line
            # puts it beside the server's other events
            with get_tracer().span("engine.compile", root=root.name, key=repr(key)):
                logger.info("compile: root=%s key=%r", root.name, key)
            self._classify(root, key)
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    def _classify(self, root: _Root, key) -> None:
        now = time.time()
        storm_detail = None
        with self._lock:
            if key is None:
                return  # un-keyed root: counted, not classified
            if key not in root.seen:
                root.seen.add(key)
                if root.allowed is None or root.allowed(key):
                    return  # declared bucket growth / warm-up: fire nothing
                storm_detail = (
                    f"root {root.name!r} compiled an UNDECLARED shape key "
                    f"{key!r} in steady state"
                )
            else:
                # a repeat compile of a seen key: storm only when THIS
                # key storms (a single weak-type flip or a cache-flush
                # re-warm touching many keys once is noise; one key
                # retracing per step is the silent 100x killer)
                ts = root.repeat_ts.setdefault(key, deque(maxlen=32))
                ts.append(now)
                recent = [t for t in ts if now - t <= self.storm_window_s]
                if len(recent) < self.storm_repeats:
                    return
                ts.clear()
                storm_detail = (
                    f"root {root.name!r} recompiled an already-seen shape "
                    f"key {key!r} {len(recent)}x within "
                    f"{self.storm_window_s:.0f}s"
                )
            root.storms += 1
            root.last_storm_ts = now
        _C_RETRACE_STORMS.inc(root=root.name)
        try:
            rec = self._recorder or get_recorder()
            rec.incident(
                "engine:retrace_storm",
                detail=storm_detail,
                node=self.node,
                extra={
                    "root": root.name,
                    "key": repr(key),
                    "traces": root.traces,
                    "storms": root.storms,
                },
            )
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass
        logger.warning("retrace storm: %s", storm_detail)

    # ---- views

    def snapshot(self) -> dict:
        """{root: {traces, storms}} for this sentinel's roots (compile
        seconds live in the process-global counter, labeled by root)."""
        with self._lock:
            return {
                name: {"traces": r.traces, "storms": r.storms}
                for name, r in self._roots.items()
            }

    def storming(self, within_s: float | None = None) -> bool:
        horizon = within_s if within_s is not None else self.storm_window_s
        now = time.time()
        with self._lock:
            return any(
                r.last_storm_ts and now - r.last_storm_ts <= horizon
                for r in self._roots.values()
            )


# ---------------------------------------------------------------- HBM ledger


def _tree_bytes_by_device(tree) -> dict:
    """{device id: live bytes} of a pytree of (possibly sharded) arrays:
    each leaf's addressable shard buffers, booked to the device that
    holds them — replicated leaves count once per local device holding
    them, which IS the HBM truth. Host (numpy) leaves book under None."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        try:
            # even the attribute READ raises on a donated/deleted array —
            # a source torn down concurrently (e.g. a closed drafter)
            # must count as 0 bytes, not break the whole snapshot
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                for sh in shards:
                    out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
                continue
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes:
                out[None] = out.get(None, 0) + int(nbytes)
        except Exception:  # noqa: BLE001 — deleted buffers count as 0
            continue
    return out


class HbmLedger:
    """Live device-memory breakdown from registered buffer sources.

    Components register a zero-arg callable returning their live pytree
    (or None when torn down); ``snapshot()`` walks the trees, reads
    ``device.memory_stats()`` where the backend provides it, refreshes
    the ``engine.hbm_*`` gauges and returns the breakdown dict that rides
    engine.info, the telemetry digest and bench stamps."""

    def __init__(self, devices=None):
        self._lock = threading.Lock()
        self._sources: dict[str, Callable] = {}
        self._devices = devices

    def register(self, component: str, source: Callable) -> None:
        with self._lock:
            self._sources[component] = source

    def unregister(self, component: str) -> None:
        with self._lock:
            self._sources.pop(component, None)
        _G_HBM_BYTES.clear(component=component)

    def close(self) -> None:
        """Drop every source closure. The kv_pool/weights lambdas close
        over the scheduler/params — a closed engine must not keep its
        donated device buffers reachable through the ledger."""
        with self._lock:
            self._sources.clear()

    def _device_stats(self) -> tuple[list[dict], int | None]:
        """(per-device memory stats, bytes_limit across them). The list
        has one entry per device of this ledger whose backend reports
        ``memory_stats()`` (TPU does; CPU returns None — empty list). An
        env ``BEE2BEE_HBM_BYTES`` budget substitutes for the limit so
        headroom still computes on stats-less backends."""
        import jax

        devices = self._devices
        if devices is None:
            devices = jax.local_devices()
        per_device = []
        for d in devices:
            st = d.memory_stats()
            if not st:
                continue
            per_device.append({
                "id": d.id,
                "bytes_in_use": int(st.get("bytes_in_use") or 0),
                "peak_bytes_in_use": int(st.get("peak_bytes_in_use") or 0),
                "bytes_limit": int(
                    st.get("bytes_limit")
                    or st.get("bytes_reservable_limit") or 0
                ),
            })
        if per_device:
            return per_device, (sum(d["bytes_limit"] for d in per_device) or None)
        env = os.environ.get("BEE2BEE_HBM_BYTES")
        if env:
            try:
                return [], int(float(env))
            except ValueError:
                pass
        return [], None

    def snapshot(self) -> dict:
        """Never-throw: a ledger read must not take down a scrape."""
        try:
            return self._snapshot()
        except Exception:  # noqa: BLE001
            logger.exception("hbm ledger snapshot failed")
            return {"components": {}, "accounted_bytes": 0}

    def _snapshot(self) -> dict:
        with self._lock:
            sources = dict(self._sources)
        components: dict[str, int] = {}
        by_device: dict = {}  # device id -> {component: bytes}
        for name, src in sources.items():
            try:
                tree = src()
            except Exception:  # noqa: BLE001 — a torn-down engine reads 0
                tree = None
            placed = _tree_bytes_by_device(tree) if tree is not None else {}
            components[name] = sum(placed.values())
            for dev_id, b in placed.items():
                if dev_id is not None:
                    by_device.setdefault(dev_id, {})[name] = b
        accounted = sum(components.values())
        per_device, limit = self._device_stats()
        in_use = sum(d["bytes_in_use"] for d in per_device) if per_device else None
        out: dict = {
            "components": components,
            "accounted_bytes": accounted,
        }
        for name, b in components.items():
            _G_HBM_BYTES.set(b, component=name)
        # the per-device view (engine.info; not gossiped): what each chip
        # holds of each component, beside the backend's own stats for it —
        # a tensor-parallel model must spread, not pile onto device 0
        stats_by_id = {d["id"]: d for d in per_device}
        if by_device or per_device:
            out["devices"] = [
                {"id": i, "components": by_device.get(i, {}),
                 **{k: v for k, v in stats_by_id.get(i, {}).items() if k != "id"}}
                for i in sorted(set(by_device) | set(stats_by_id))
            ]
        if in_use is not None:
            out["bytes_in_use"] = in_use
            # XLA workspace, fragmentation, and whatever we don't track
            workspace = max(0, in_use - accounted)
            out["components"]["workspace_other"] = workspace
            _G_HBM_BYTES.set(workspace, component="workspace_other")
        else:
            _G_HBM_BYTES.clear(component="workspace_other")
        if limit:
            used = in_use if in_use is not None else accounted
            headroom = max(0.0, min(1.0, 1.0 - used / limit))
            out["bytes_limit"] = limit
            out["headroom_frac"] = round(headroom, 4)
            _G_HBM_HEADROOM.set(headroom)
        else:
            _G_HBM_HEADROOM.clear()
        return out


class PoolForecast:
    """Linear growth forecast for the paged block pool.

    The scheduler feeds ``(used, free)`` on its dispatch path (cheap:
    one deque append, self-throttled to one gauge refresh per second).
    ``eta_s()`` projects free blocks / growth rate over the trailing
    window; the admission controller sheds ``pool_exhausted`` when the
    projection undercuts its horizon — BEFORE the free-fraction floor
    trips and requests start parking on scheduler backpressure."""

    def __init__(self, window_s: float = 30.0):
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._samples: deque = deque(maxlen=256)  # (t, used, free)
        self._last_refresh = 0.0

    def feed(self, used: int, free: int, now: float | None = None) -> None:
        try:
            now = time.time() if now is None else now
            with self._lock:
                self._samples.append((now, int(used), int(free)))
                throttled = now - self._last_refresh < 1.0
                if not throttled:
                    self._last_refresh = now
            if not throttled:
                self.refresh(now)
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    def eta_s(self, now: float | None = None) -> float | None:
        """Projected seconds to exhaustion, or None (shrinking pool /
        not enough signal). Needs >= 2 samples spanning >= 2 s so a
        single admission burst can't fabricate a trend."""
        now = time.time() if now is None else now
        with self._lock:
            samples = [
                s for s in self._samples if now - s[0] <= self.window_s
            ]
        if len(samples) < 2:
            return None
        t0, used0, _ = samples[0]
        t1, used1, free1 = samples[-1]
        dt = t1 - t0
        if dt < 2.0 or used1 <= used0:
            return None
        rate = (used1 - used0) / dt  # blocks/s, > 0
        return free1 / rate if free1 > 0 else 0.0

    def refresh(self, now: float | None = None) -> float | None:
        eta = self.eta_s(now)
        if eta is None:
            _G_POOL_ETA.clear()
        else:
            _G_POOL_ETA.set(eta)
        return eta


# (the admission controller reads the engine.pool_exhaust_eta_s gauge
# through router/admission.pool_exhaust_eta — the registry-read pattern
# keeps the front door free of engine imports)

# -------------------------------------------------------------- goodput meter


class GoodputMeter:
    """Scheduled-vs-useful token accounting + the MFU meter.

    ``record_dispatch(positions, ctx, scheduled)`` books compute at
    dispatch time (positions = batch rows × token width actually run,
    dead rows included — that's what the hardware computed); ``note_useful``
    books tokens that made it into a request's output. Cumulative
    counters snapshot into a bounded deque at most every 250 ms;
    ``refresh()`` derives trailing-window rates into the gauges."""

    SNAPSHOT_EVERY_S = 0.25

    def __init__(self, flops_model: FlopsModel | None, peak_flops: float,
                 window_s: float = 60.0):
        self.flops_model = flops_model
        self.peak_flops = max(float(peak_flops), 1.0)
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self.scheduled_total = 0
        self.useful_total = 0
        self.flops_total = 0.0
        self._snaps: deque = deque(maxlen=512)  # (t, sched, useful, flops)
        # zero baseline: the window delta subtracts the REFERENCE
        # snapshot, so without this seed the first dispatch burst would
        # vanish from the denominator (useful > scheduled for a window)
        self._snaps.append((time.time(), 0, 0, 0.0))
        self._last_snap = 0.0
        # tier -> [drafted, accepted], cumulative. The tier label set is
        # closed (spec.TIER_LADDER), so cardinality is bounded.
        self._spec_tiers: dict[str, list] = {}

    def record_dispatch(self, positions: float, ctx: float,
                        scheduled: int) -> None:
        try:
            flops = (
                self.flops_model.flops(positions, ctx)
                if self.flops_model is not None else 0.0
            )
            with self._lock:
                self.scheduled_total += int(scheduled)
                self.flops_total += flops
            self._maybe_snap()
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    def note_useful(self, n: int) -> None:
        try:
            if n <= 0:
                return
            with self._lock:
                self.useful_total += int(n)
            self._maybe_snap()
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    @staticmethod
    def note_slots(rows: int, live_rows: int, steps: int, kept: int) -> None:
        """Book one fetched decode window (or verify step) of ``rows`` x
        ``steps`` token slots, ``live_rows`` of them a request's at
        dispatch, ``kept`` tokens accepted into outputs: the cumulative
        engine.decode_slots{kind}, which a reader can take over any
        stretch (the gauges above trail 60 s and mix prefill pad in)."""
        _C_DECODE_SLOTS.inc(kept, kind="kept")
        _C_DECODE_SLOTS.inc(live_rows * steps - kept, kind="after_end")
        _C_DECODE_SLOTS.inc((rows - live_rows) * steps, kind="dead_row")

    def note_spec(self, tier: str, drafted: int, accepted: int) -> None:
        """Book one row's verify outcome against its drafter tier.

        Rejected drafts are already inside the scheduled/useful split
        (record_dispatch counts the [B,K+1] width, note_useful only the
        survivors); this adds the per-tier acceptance view on top so the
        goodput snapshot can say WHICH tier is paying for itself."""
        try:
            if drafted <= 0:
                return
            with self._lock:
                t = self._spec_tiers.setdefault(tier, [0, 0])
                t[0] += int(drafted)
                t[1] += int(accepted)
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    def _maybe_snap(self, force: bool = False) -> None:
        now = time.time()
        with self._lock:
            if not force and now - self._last_snap < self.SNAPSHOT_EVERY_S:
                return
            self._last_snap = now
            self._snaps.append(
                (now, self.scheduled_total, self.useful_total, self.flops_total)
            )

    def refresh(self) -> dict:
        """Trailing-window rates -> gauges; returns the snapshot dict.
        With no dispatch inside the window the rate gauges CLEAR (the
        empty-gauge contract) — an idle engine reports nothing rather
        than its last busy reading."""
        try:
            self._maybe_snap(force=True)
            now = time.time()
            with self._lock:
                snaps = list(self._snaps)
            # the newest snapshot AT OR BEFORE the window start anchors
            # the delta (SloTracker's rule): work recorded since the
            # anchor — including the first burst over the zero baseline —
            # is inside the window
            start = now - self.window_s
            ref = snaps[0]
            for s in snaps:
                if s[0] <= start:
                    ref = s
                else:
                    break
            out: dict = {
                "scheduled_tokens_total": self.scheduled_total,
                "useful_tokens_total": self.useful_total,
                "model_flops_total": self.flops_total,
            }
            with self._lock:
                spec_tiers = {k: tuple(v) for k, v in self._spec_tiers.items()}
            if spec_tiers:
                out["spec_tiers"] = {
                    k: {"drafted": d, "accepted": a,
                        "acceptance": round(a / d, 4) if d else 0.0}
                    for k, (d, a) in spec_tiers.items()
                }
                for k, (d, a) in spec_tiers.items():
                    if d:
                        _G_SPEC_ACCEPT.set(a / d, tier=k)
            if snaps[-1][0] - ref[0] <= 0:
                for g in (_G_MFU, _G_GOODPUT, _G_SCHEDULED_TPS,
                          _G_GOODPUT_FRAC):
                    g.clear()
                return out
            t0, s0, u0, f0 = ref
            t1, s1, u1, f1 = snaps[-1]
            if (s1, u1, f1) == (s0, u0, f0):
                # nothing dispatched inside the window: the empty-gauge
                # contract (an idle engine reports nothing, not zero —
                # and never its last busy reading)
                for g in (_G_MFU, _G_GOODPUT, _G_SCHEDULED_TPS,
                          _G_GOODPUT_FRAC):
                    g.clear()
                return out
            dt = t1 - t0
            sched_rate = (s1 - s0) / dt
            useful_rate = (u1 - u0) / dt
            mfu = (f1 - f0) / dt / self.peak_flops
            out.update(
                scheduled_tokens_per_s=round(sched_rate, 3),
                goodput_tokens_per_s=round(useful_rate, 3),
                goodput_fraction=(
                    round(useful_rate / sched_rate, 4) if sched_rate > 0 else 0.0
                ),
                mfu=round(mfu, 6),
                window_s=round(dt, 3),
            )
            _G_SCHEDULED_TPS.set(sched_rate)
            _G_GOODPUT.set(useful_rate)
            _G_MFU.set(mfu)
            if sched_rate > 0:
                _G_GOODPUT_FRAC.set(useful_rate / sched_rate)
            return out
        except Exception:  # noqa: BLE001 — telemetry never throws
            logger.exception("goodput refresh failed")
            return {}


# ------------------------------------------------------------ device profiler


class ProfileInProgress(RuntimeError):
    """A capture is already running (jax.profiler is a process singleton:
    two concurrent start_trace calls corrupt each other). Typed so the
    API surface can answer 409 profile_in_progress instead of a 500."""


class DeviceProfiler:
    """Duration-bounded on-demand jax.profiler capture.

    One capture at a time per process; the artifact (the whole profile
    dir zipped into ``prof-<id>.zip``) lands under
    ``<incident_dir>/profiles`` and is listed/fetched like incident
    bundles. Capture runs on the CALLER's thread (api.py offloads via
    asyncio.to_thread) and is wall-clock bounded by ``max_duration_s``."""

    MAX_DURATION_S = 60.0

    def __init__(self, profile_dir: str | Path | None = None):
        self._dir = Path(profile_dir) if profile_dir else None
        self._lock = threading.Lock()
        self._active: dict | None = None

    @property
    def profile_dir(self) -> Path:
        if self._dir is None:
            self._dir = get_recorder().incident_dir / "profiles"
        return self._dir

    @property
    def active(self) -> dict | None:
        with self._lock:
            return dict(self._active) if self._active else None

    def capture(self, duration_s: float = 2.0,
                workload: Callable | None = None,
                python_tracer: bool = False) -> dict:
        """Blocking capture: start jax.profiler, run ``workload()`` (or
        sleep) for ``duration_s``, stop, zip. Returns the artifact header.
        Raises ProfileInProgress when a capture is already running.

        The Python tracer is OFF unless ``python_tracer``: jax's default
        (python_tracer_level=1) makes every Python call of every thread an
        event and so stretches the host phases the capture then times
        (`sched.process` 38-48 ms a window against 11-12 untraced on PR 39's
        tree, 1.3-1.6x on PR 40's: PERF.md sections 5-6). The program's own
        TraceAnnotations and the runtime's host events (host_tracer_level
        keeps its default) stay on the one clock with the device ops."""
        import jax

        duration_s = max(0.05, min(float(duration_s), self.MAX_DURATION_S))
        prof_id = new_id("prof")
        with self._lock:
            if self._active is not None:
                raise ProfileInProgress(
                    f"capture {self._active['id']} already running"
                )
            self._active = {"id": prof_id, "started": time.time(),
                            "duration_s": duration_s}
        raw_dir = self.profile_dir / prof_id
        try:
            raw_dir.mkdir(parents=True, exist_ok=True)
            t0 = time.time()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(str(raw_dir), profiler_options=options)
            try:
                if workload is not None:
                    while time.time() - t0 < duration_s:
                        workload()
                else:
                    time.sleep(duration_s)
            finally:
                jax.profiler.stop_trace()
            captured_s = time.time() - t0
            zip_path = self.profile_dir / f"{prof_id}.zip"
            n_files = self._zip_dir(raw_dir, zip_path)
            self._rmtree(raw_dir)
            return {
                "id": prof_id,
                "ts": t0,
                "duration_s": round(captured_s, 3),
                "files": n_files,
                "bytes": zip_path.stat().st_size,
            }
        finally:
            with self._lock:
                self._active = None

    @staticmethod
    def _zip_dir(src: Path, dst: Path) -> int:
        n = 0
        with zipfile.ZipFile(dst, "w", zipfile.ZIP_DEFLATED) as zf:
            for p in sorted(src.rglob("*")):
                if p.is_file():
                    zf.write(p, p.relative_to(src))
                    n += 1
        return n

    @staticmethod
    def _rmtree(d: Path) -> None:
        import shutil

        try:
            shutil.rmtree(d)
        except OSError:
            pass

    def list_profiles(self) -> list[dict]:
        """Newest-first artifact index (id, ts, bytes) — the GET
        /debug/profile listing; mirrors FlightRecorder.list_incidents."""
        try:
            d = self.profile_dir
            if not d.is_dir():
                return []
            out = []
            for p in sorted(d.glob("prof-*.zip"),
                            key=lambda p: p.stat().st_mtime, reverse=True):
                st = p.stat()
                out.append({
                    "id": p.stem, "ts": st.st_mtime, "bytes": st.st_size,
                })
            return out
        except Exception:  # noqa: BLE001
            logger.exception("profile listing failed")
            return []

    def profile_path(self, prof_id: str) -> Path | None:
        """Artifact path by id; None when unknown. The id is URL input —
        resolved by exact stem match, never by path join (api.py streams
        the file from this path so a multi-hundred-MB TPU capture never
        materializes in memory)."""
        try:
            d = self.profile_dir
            if not d.is_dir():
                return None
            for p in d.glob("prof-*.zip"):
                if p.stem == prof_id:
                    return p
            return None
        except Exception:  # noqa: BLE001
            logger.exception("profile lookup failed")
            return None

    def load_profile(self, prof_id: str) -> bytes | None:
        """Artifact bytes by id; None when unknown (small captures /
        tests — HTTP consumers stream via profile_path)."""
        p = self.profile_path(prof_id)
        try:
            return p.read_bytes() if p is not None else None
        except OSError:
            logger.exception("profile load failed")
            return None


_PROFILER = DeviceProfiler()


def get_profiler() -> DeviceProfiler:
    """The process-global profiler (jax.profiler is a process singleton,
    so the serializing lock must be too)."""
    return _PROFILER


# --------------------------------------------------- per-engine aggregation

# live engines' introspection blocks, keyed by id: the health digest
# provider folds every live engine into one `introspect` digest entry.
# WEAK values: an engine dropped without close() (tests churn hundreds)
# must not stay pinned here — its ledger sources hold the param arrays.
_INSTANCES_LOCK = threading.Lock()
_INSTANCES: "weakref.WeakValueDictionary[int, EngineIntrospection]" = (
    weakref.WeakValueDictionary()
)
_PROVIDER_WIRED = False


def _digest_provider() -> dict | None:
    """health.build_digest's live-path hook: refresh gauges + return the
    digest block (compiles per root, MFU/goodput, HBM headroom) for every
    live engine, merged. None when no engine runs in this process."""
    with _INSTANCES_LOCK:
        instances = list(_INSTANCES.values())
    if not instances:
        return None
    merged: dict = {"compiles": {}, "storms": 0}
    mfu = goodput = None
    hbm = None
    for ins in instances:
        snap = ins.refresh()
        for root, entry in (snap.get("compiles") or {}).items():
            slot = merged["compiles"].setdefault(
                root, {"traces": 0, "storms": 0}
            )
            slot["traces"] += entry.get("traces", 0)
            slot["storms"] += entry.get("storms", 0)
            merged["storms"] += entry.get("storms", 0)
        meter = snap.get("goodput") or {}
        if meter.get("mfu") is not None:
            mfu = (mfu or 0.0) + meter["mfu"]
        if meter.get("goodput_tokens_per_s") is not None:
            goodput = (goodput or 0.0) + meter["goodput_tokens_per_s"]
        if snap.get("hbm"):
            hbm = snap["hbm"]  # one ledger per process-backend in practice
    if mfu is not None:
        merged["mfu"] = round(mfu, 6)
    if goodput is not None:
        merged["goodput_tokens_per_s"] = round(goodput, 3)
    if hbm is not None:
        merged["hbm"] = {
            k: hbm[k]
            for k in ("accounted_bytes", "bytes_in_use", "bytes_limit",
                      "headroom_frac")
            if k in hbm
        }
    merged["storming"] = any(ins.sentinel.storming() for ins in instances)
    return merged


def _wire_provider() -> None:
    global _PROVIDER_WIRED
    if not _PROVIDER_WIRED:
        _PROVIDER_WIRED = True
        register_digest_provider("introspect", _digest_provider)


class EngineIntrospection:
    """One engine's economics instruments, built by InferenceEngine:
    the retrace sentinel its jit roots register with, the HBM ledger its
    buffer owners register with, the goodput meter the scheduler feeds,
    and the pool forecast. ``refresh()`` is the scrape/digest/bench entry
    point; ``close()`` unhooks the engine from the digest provider."""

    def __init__(self, model_cfg, mesh=None, peak_flops: float | None = None):
        # the MESH's own devices, never jax.devices(): an explicit CPU
        # mesh on a TPU-default host is a CPU engine
        platform, kind, n_dev = "cpu", "", 1
        if mesh is not None:
            dev = mesh.devices.flat[0]
            platform, kind, n_dev = dev.platform, dev.device_kind, mesh.devices.size
        if peak_flops is None:
            peak_flops = peak_flops_per_device(platform, kind) * n_dev
        self.platform = platform
        self.device_kind = kind
        self.device_count = int(n_dev)
        self.sentinel = RetraceSentinel()
        self.ledger = HbmLedger(
            devices=list(mesh.devices.flat) if mesh is not None else None
        )
        self.meter = GoodputMeter(FlopsModel(model_cfg), peak_flops)
        self.forecast = PoolForecast()
        # the scheduler loop's phases; refresh() credits the open one, so
        # a scrape reads engine.phase_seconds up to that instant
        self.phases = PhaseClock(
            "sched", _C_PHASE_SECONDS, parts={"admit": _C_ADMIT_SECONDS})
        with _INSTANCES_LOCK:
            _INSTANCES[id(self)] = self
        _wire_provider()

    def close(self) -> None:
        with _INSTANCES_LOCK:
            _INSTANCES.pop(id(self), None)
        # the source closures pin the scheduler's KV pool and the param
        # tree — release them with the engine
        self.ledger.close()
        # drop the economics gauges outright — with no live engine they
        # would otherwise serve this engine's last busy reading forever
        # (the empty-gauge contract; node.py's incident gauge snapshot
        # and the admission forecast shed both read them). A surviving
        # sibling engine transiently loses its series too, but every
        # scrape/digest refreshes live engines first, so the gap never
        # reaches a consumer.
        try:
            for g in (_G_MFU, _G_GOODPUT, _G_SCHEDULED_TPS,
                      _G_GOODPUT_FRAC, _G_POOL_ETA, _G_HBM_HEADROOM,
                      _G_OVERLAP):
                g.clear()
            for labels, _v in _G_HBM_BYTES.series():
                _G_HBM_BYTES.clear(**dict(labels))
        except Exception:  # noqa: BLE001 — telemetry never throws
            pass

    def refresh(self) -> dict:
        """Refresh every gauge this plane owns; return the snapshot that
        rides engine.info / the digest / bench ``extras.introspect``."""
        self.phases.flush()
        out = {
            "compiles": self.sentinel.snapshot(),
            "goodput": self.meter.refresh(),
            "hbm": self.ledger.snapshot(),
            "platform": self.platform,
            "peak_flops": self.meter.peak_flops,
        }
        # the forecast's OWN return value, not the shared process gauge:
        # with two live engines the gauge holds the last writer's number
        eta = self.forecast.refresh()
        if eta is not None:
            out["pool_exhaust_eta_s"] = round(eta, 3)
        return out


def bench_snapshot() -> dict:
    """Cumulative introspection stamp for bench rungs: per-root compile
    counters + seconds from the process registry (they survive engine
    close), plus the live engines' MFU/goodput/HBM when any still runs.
    Cheap, never throws — a bench stamp must not fail the rung."""
    try:
        out: dict = {"compiles": {}}
        compiles = _REG.get("engine.compiles")
        seconds = _REG.get("engine.compile_seconds")
        if compiles is not None:
            for labels, v in compiles.series():
                root = dict(labels).get("root", "?")
                out["compiles"].setdefault(root, {})["count"] = int(v)
        if seconds is not None:
            for labels, v in seconds.series():
                root = dict(labels).get("root", "?")
                out["compiles"].setdefault(root, {})["seconds"] = round(v, 3)
        storms = _REG.get("engine.retrace_storms")
        if storms is not None and storms.total():
            out["retrace_storms"] = storms.total()
        live = _digest_provider()
        if live:
            for k in ("mfu", "goodput_tokens_per_s", "hbm"):
                if live.get(k) is not None:
                    out[k] = live[k]
        return out
    except Exception:  # noqa: BLE001 — the stamp must not kill a rung
        return {}
