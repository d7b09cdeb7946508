"""Tracing and observability: request spans + cross-node trace propagation
+ the per-request timeline + named loop phases.

The reference has NO tracing (SURVEY §5) — the closest artifacts are
per-request latency_ms (reference services.py:97-105) and ping RTTs
(reference p2p_runtime.py:544-557). This module is the required upgrade:

- `Tracer`: a lock-guarded ring buffer of completed `Span`s with nested
  span support (contextvar parent), percentile aggregation per span name,
  and zero dependencies. One process-global instance via `get_tracer()`.
- `Span` context manager works in sync and async code and never throws:
  tracing must not take down the serving path.
- **Trace context propagation**: every span carries a `trace_id` (opened
  fresh at the first span of a request, inherited inside it).
  `inject_trace(frame)` stamps the current (trace_id, span_id) onto a wire
  frame as the optional `trace_ctx` key; the receiving hop calls
  `extract_trace(data)` + `use_trace_ctx(ctx)` so its spans parent under
  the ORIGINATING request across nodes. `/trace?trace_id=` on any node
  returns its local fragment; `stitch_trace()` merges fragments from
  several nodes into one cross-node timeline.
- `RequestTiming`: ONE record of `time.perf_counter()` stamps per request,
  from the gateway's accept to its first written byte. The gateway opens
  it (`request_timing()`), the `contextvars.copy_context()` the serving
  paths already make carries it into the worker thread, the engine hangs
  it on its `Request`, and each layer stamps its own fields.
- `PhaseClock`: the named phases of one thread's loop, each a
  `jax.profiler.TraceAnnotation` (the host plane of a `/debug/profile`
  capture, beside the device ops) and exclusive seconds on a counter.
  Device traces themselves come from `POST /debug/profile`
  (engine/introspect.DeviceProfiler).
- `annotate` / `prog_scope`: a name on the capture's host plane for a
  turn of another thread, and ONE `jax.named_scope` around the body of a
  jit root, so the capture's device ops say which program they belong to.

Spans are cheap (monotonic clock + dict append) and bounded (ring
buffer), so they stay on in production; mesh nodes surface them at the
gateway's `/trace` route. Span NAMES are literal dotted constants —
meshlint ML-T001 rejects dynamically-built names (request-varying names
would defeat the per-name aggregation and explode cardinality).
"""

from __future__ import annotations

import contextvars
import functools
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Iterator

from .protocol import TRACE_CTX
from .utils import new_id

_current_span: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "bee2bee_current_span", default=None
)
_current_trace: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "bee2bee_current_trace", default=None
)


@dataclass
class Span:
    name: str
    span_id: str = field(default_factory=lambda: new_id("span"))
    parent_id: str | None = None
    trace_id: str | None = None
    start_ms: float = 0.0
    duration_ms: float = -1.0  # -1 while open
    attrs: dict[str, Any] = field(default_factory=dict)
    error: str | None = None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": round(self.duration_ms, 3),
            "attrs": self.attrs,
            "error": self.error,
        }


@dataclass(frozen=True)
class TraceContext:
    """The wire-portable half of a span: enough for a remote hop to parent
    its own spans under the originating request."""

    trace_id: str
    span_id: str

    def to_wire(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_wire(cls, obj: Any) -> "TraceContext | None":
        if (
            isinstance(obj, dict)
            and isinstance(obj.get("trace_id"), str)
            and isinstance(obj.get("span_id"), str)
        ):
            return cls(obj["trace_id"], obj["span_id"])
        return None


def current_trace_ctx() -> TraceContext | None:
    """The (trace_id, span_id) pair of the innermost open span, or None
    outside any span."""
    tid, sid = _current_trace.get(), _current_span.get()
    if tid is None or sid is None:
        return None
    return TraceContext(tid, sid)


def inject_trace(fields: dict) -> dict:
    """Stamp the current trace context onto a wire frame/fields dict as
    the optional `trace_ctx` key (declared in analysis/schema.py; the
    reference mesh ignores unknown keys, so frames stay wire-compatible).
    No-op outside a span — never throws."""
    try:
        ctx = current_trace_ctx()
        if ctx is not None:
            fields[TRACE_CTX] = ctx.to_wire()
    except Exception:  # noqa: BLE001 — telemetry never breaks the wire path
        pass
    return fields


def extract_trace(data: dict) -> TraceContext | None:
    """Read a `trace_ctx` key off a received frame; None when absent or
    malformed (old peers / non-instrumented senders) — never throws."""
    try:
        return TraceContext.from_wire(data.get(TRACE_CTX))
    except Exception:  # noqa: BLE001 — a bad frame must not kill a handler
        return None


@contextmanager
def use_trace_ctx(ctx: TraceContext | None):
    """Run a block under a remote trace context: spans opened inside carry
    ctx.trace_id and parent under ctx.span_id. ctx=None is a no-op, so
    handlers can call this unconditionally."""
    if ctx is None:
        yield
        return
    t_trace = _current_trace.set(ctx.trace_id)
    t_span = _current_span.set(ctx.span_id)
    try:
        yield
    finally:
        _current_span.reset(t_span)
        _current_trace.reset(t_trace)


@dataclass
class RequestTiming:
    """One request's timeline: `time.perf_counter()` seconds, 0.0 = not
    reached. Each layer stamps its own fields; the order below is the
    order a streamed request passes them (docs/OBSERVABILITY.md)."""

    t_accept: float = 0.0  # gateway handler entry, before the body is parsed
    t_admitted: float = 0.0  # admission.acquire returned
    t_submit: float = 0.0  # engine Request built, handed to the scheduler
    t_admit: float = 0.0  # popped off the queue (queue_wait endpoint)
    t_first: float = 0.0  # first token available (ttft reference point)
    t_first_text: float = 0.0  # first event with non-empty text queued
    t_first_line: float = 0.0  # first content line yielded by the service
    t_first_write: float = 0.0  # gateway's write of the first content frame returned
    t_done: float = 0.0

    def timeline_ms(self) -> dict[str, float]:
        """The stamps reached so far as ms after the earliest one, under
        the names the done line's `timing["timeline_ms"]` carries."""
        stamps = {
            "accept": self.t_accept, "admitted": self.t_admitted,
            "submit": self.t_submit, "row": self.t_admit,
            "first_token": self.t_first, "first_text": self.t_first_text,
            "first_line": self.t_first_line, "first_write": self.t_first_write,
        }
        reached = {k: v for k, v in stamps.items() if v}
        origin = min(reached.values(), default=0.0)
        return {k: round((v - origin) * 1000.0, 3) for k, v in reached.items()}


_current_timing: contextvars.ContextVar[RequestTiming | None] = contextvars.ContextVar(
    "bee2bee_request_timing", default=None
)


@contextmanager
def request_timing() -> Iterator[RequestTiming]:
    """Open the current request's timeline at a gateway handler's entry.
    The record travels by contextvar (worker threads get it through the
    `copy_context()` the serving paths already make)."""
    rec = RequestTiming(t_accept=time.perf_counter())
    token = _current_timing.set(rec)
    try:
        yield rec
    finally:
        _current_timing.reset(token)


def current_timing() -> RequestTiming | None:
    return _current_timing.get()


def annotate(name: str):
    """``name`` over a block on the host plane of a `/debug/profile`
    capture, beside the device ops: a plain `jax.profiler.TraceAnnotation`
    (no span, no counter), for a turn of a thread that an idle gap of the
    device may be waiting for. A process that has not loaded jax can have
    no capture running and gets a no-op. Names are literals (ML-T001)."""
    if "jax" not in sys.modules:
        return nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def prog_scope(name: str):
    """Decorate the function a jit root traces: its body runs under ONE
    `jax.named_scope(name)` ("prog.decode", "prog.prefill", ...), so every
    device op of the program carries `jit(f)/<name>/...` in the op_name
    metadata a capture records. Metadata only: the compiled code, its
    instruction names and the compile-cache key stay what they were."""

    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return run

    return deco


# The parts of a model's program on the device: the `jax.named_scope`s that
# `models/core.py`, `engine._verify_step` and `sampling.sample_batched` open,
# unconditionally, for EVERY model (no setting, config field or model's name
# decides one), under the `prog.*` root of the jit that runs them. A scope is
# `op_name` metadata on the HLO instruction: it costs nothing at run time and
# the compiler's fusion does not read it; a fusion is booked under the scope
# of its ROOT instruction, so the small ops that finish a product (a bias, a
# multiplier, the residual add) sit under the product's part. A WRAPPER
# (DEVICE_WRAPPERS) encloses whole parts and a capture books an op under the
# part inside it; a NESTED scope (DEVICE_NESTED) names a piece of the part that
# encloses it. Where each is opened, which models run it and which metric
# reads it: docs/OBSERVABILITY.md's table "The block's parts", a row a name.
DEVICE_PARTS = (
    "embed.tokens", "norm.block",
    "attn.qkv", "attn.rope", "attn.write", "kv.write", "attn.read", "attn.out",
    "mla.q_proj", "mla.kv_proj", "mla.write", "mla.read", "mla.out",
    "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.step", "ssm.state_write", "ssm.out_proj",
    "mlp.gate_up", "mlp.down",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
    "loop.norm", "head.logits", "mtp.proj", "mtp.head", "spec.accept", "sample.draw",
)
DEVICE_WRAPPERS = ("spec.verify", "mtp.block")
# Scopes opened INSIDE a part, for a piece of it that a reader of its own
# tells apart: an op under ``moe.experts/latent.in`` is the part's
# (``block_scopes`` books the first part in the path) and the latent
# projection's (``benchmark/readers/nemotron_scopes.py`` books the longer name)
DEVICE_NESTED = ("latent.in", "latent.out")


class PhaseClock:
    """The named phases of ONE thread's loop. `phase(name)` opens a
    `jax.profiler.TraceAnnotation("<prefix>.<name>")` — it lands on the
    host plane of the same capture as the device ops, so an idle gap of
    the device gets the phase's name — and adds the phase's EXCLUSIVE
    seconds (a nested phase pauses its parent) to `counter{phase=name}`,
    so the phases sum to the loop's busy wall time. `flush()`, from any
    thread, credits the open phase up to now: called at a scrape, it
    makes the counter's growth between two scrapes exact (a phase can
    last seconds — the scheduler blocked on the device — and would
    otherwise count only when it ends). No Tracer span: the span ring is
    for requests. Names are literals (meshlint ML-T001).

    A phase listed in ``parts`` (phase name -> counter) is told apart once
    more: `part(name)` labels the innermost open phase from there on (to
    its end, or the next `part`) with an annotation
    "<prefix>.<phase>.<name>" NESTED in the phase's own, and every second
    the clock credits to `counter{phase=...}` it credits to
    `parts[phase]{part=name}` too ("none" while no part is set) — one
    reading of one clock booked twice, so a phase's parts sum to the
    phase by construction."""

    def __init__(self, prefix: str, counter, parts: dict | None = None):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._prefix = prefix
        self._counter = counter
        self._parts = dict(parts or {})
        self._lock = threading.Lock()
        self._open: list[str] = []  # phases entered and not yet left
        # beside it, each open phase's part: [name | None, its annotation]
        self._part: list[list] = []
        self._since = 0.0  # up to when the innermost open phase is credited

    def flush(self) -> None:
        with self._lock:
            if self._open:
                now = time.perf_counter()
                name = self._open[-1]
                self._counter.inc(now - self._since, phase=name)
                if name in self._parts:
                    self._parts[name].inc(
                        now - self._since, part=self._part[-1][0] or "none")
                self._since = now

    def part(self, name: str | None) -> None:
        """From here on the innermost open phase runs as its part ``name``
        (None: as no part). Only the loop's own thread calls this."""
        if not self._open or self._part[-1][0] == name:
            return
        self.flush()  # what ran so far belongs to the part before
        if self._part[-1][1] is not None:
            self._part[-1][1].__exit__(None, None, None)
        note = None
        if name is not None:
            note = self._annotation(f"{self._prefix}.{self._open[-1]}.{name}")
            note.__enter__()
        with self._lock:
            self._part[-1] = [name, note]

    @contextmanager
    def phase(self, name: str):
        self.flush()  # the parent pauses here
        with self._lock:
            self._open.append(name)
            self._part.append([None, None])
            self._since = time.perf_counter()
        try:
            with self._annotation(f"{self._prefix}.{name}"):
                try:
                    yield
                finally:
                    self.part(None)  # a part ends with its phase, inside it
        finally:
            self.flush()
            with self._lock:
                self._open.pop()
                self._part.pop()
                self._since = time.perf_counter()


class Tracer:
    """Bounded in-memory span collector; thread-safe; never raises."""

    def __init__(self, capacity: int = 2048):
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._epoch = time.time() * 1000.0 - time.monotonic() * 1000.0
        # completion listeners (health.FlightRecorder): called with each
        # closed Span outside the lock; listener errors are swallowed —
        # an observability consumer must never fail the traced code path
        self._listeners: list = []

    def add_listener(self, fn) -> None:
        """Subscribe to span completions (idempotent per function)."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    @contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        trace_id = _current_trace.get()
        trace_token = None
        if trace_id is None:  # first span of a request: open a new trace
            trace_id = new_id("trace")
            trace_token = _current_trace.set(trace_id)
        s = Span(
            name=name,
            parent_id=_current_span.get(),
            trace_id=trace_id,
            start_ms=self._epoch + time.monotonic() * 1000.0,
            attrs=dict(attrs),
        )
        token = _current_span.set(s.span_id)
        t0 = time.monotonic()
        try:
            yield s
        except BaseException as exc:
            s.error = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            s.duration_ms = (time.monotonic() - t0) * 1000.0
            _current_span.reset(token)
            if trace_token is not None:
                _current_trace.reset(trace_token)
            with self._lock:
                self._spans.append(s)
                listeners = list(self._listeners)
            for fn in listeners:
                try:
                    fn(s)
                except Exception:  # noqa: BLE001 — tracing never throws
                    pass

    def recent(self, limit: int = 100, name: str | None = None) -> list[dict]:
        with self._lock:
            spans = list(self._spans)
        if name is not None:
            spans = [s for s in spans if s.name == name]
        return [s.to_dict() for s in spans[-limit:]]

    def for_trace(self, trace_id: str, limit: int = 1000) -> list[dict]:
        """This process's local fragment of one trace, oldest first —
        what `/trace?trace_id=` serves; stitch fragments from several
        nodes with `stitch_trace`."""
        with self._lock:
            spans = [s for s in self._spans if s.trace_id == trace_id]
        return [s.to_dict() for s in spans[-limit:]]

    def stats(self) -> dict[str, dict]:
        """Per-span-name aggregates: count, p50/p95/max duration, errors."""
        with self._lock:
            spans = list(self._spans)
        by_name: dict[str, list[Span]] = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        out: dict[str, dict] = {}
        for name, group in by_name.items():
            durs = sorted(s.duration_ms for s in group)
            out[name] = {
                "count": len(durs),
                "errors": sum(1 for s in group if s.error),
                "p50_ms": round(_pct(durs, 0.50), 3),
                "p95_ms": round(_pct(durs, 0.95), 3),
                "max_ms": round(durs[-1], 3),
            }
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


def stitch_trace(
    fragments: list[dict], expected_nodes: list[str] | None = None
) -> dict:
    """Merge per-node trace fragments into one cross-node timeline.

    `fragments` is a list of ``{"node": <peer_id>, "spans": [span dicts]}``
    (each the payload of one node's ``/trace?trace_id=`` response). Spans
    are annotated with their node, de-duplicated by span_id (fragments may
    overlap when nodes share a process, e.g. loopback tests) and ordered
    by start_ms — parent links then read as one tree across nodes.

    Degrades gracefully instead of failing the whole stitch: a fragment
    marked ``{"unreachable": True}`` (the peer never answered) or
    ``{"partial": True}`` (it answered without a usable span list), and
    any ``expected_nodes`` entry that contributed no fragment, land in
    ``missing_peers`` and flip ``incomplete`` — the merged PARTIAL
    timeline is still returned."""
    seen: dict[str, dict] = {}
    responded: set = set()
    missing: set = set()
    for frag in fragments or []:
        node = frag.get("node")
        if frag.get("unreachable") or frag.get("partial"):
            if node:
                missing.add(node)
            continue
        if node:
            responded.add(node)
        for s in frag.get("spans") or []:
            sid = s.get("span_id")
            if sid is None or sid in seen:
                continue
            seen[sid] = {**s, "node": node}
    for node in expected_nodes or []:
        if node not in responded:
            missing.add(node)
    missing -= responded  # a duplicate fragment pair: any answer counts
    spans = sorted(seen.values(), key=lambda s: s.get("start_ms") or 0.0)
    trace_ids = {s.get("trace_id") for s in spans if s.get("trace_id")}
    return {
        "trace_id": next(iter(trace_ids)) if len(trace_ids) == 1 else None,
        "nodes": sorted({s["node"] for s in spans if s.get("node")}),
        "spans": spans,
        "incomplete": bool(missing),
        "missing_peers": sorted(missing),
    }


def _pct(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    return _GLOBAL
