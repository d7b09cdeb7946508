"""The one general traffic generator: it reads a mix file (``traffic/<mix>.json``)
and drives the server's gateway from ONE thread (asyncio + aiohttp).

A mix is data: ``loop`` (closed | open), ``callers`` or ``rate_per_s`` +
``arrivals``, the length distributions, the size of the request set and the
warm-up shapes. A request is a streamed ``POST /chat`` (ndjson): one line per
content event (up to ``decode_chunk`` tokens each) and a done line that
carries the server's own token count.

Every seed gets the SAME set of (prompt, output) pairs — the distributions'
evenly spaced quantiles under one fixed pairing — in another order, with other
prompt bytes and, in an open loop, other arrival gaps of the same
distribution. So a seed changes which request meets which, never how much
work a run holds.
"""

from __future__ import annotations

import asyncio
import bisect
import json
import math
import random
import statistics
import time
from dataclasses import dataclass, field

BURST_S = 0.05  # events closer together than this left the server in one burst
DRAIN_LIMIT_S = 30.0  # a closed loop runs on at most this long past the window's end

WORDS = ("mesh", "node", "token", "cache", "block", "shard", "queue", "route",
         "draft", "batch", "page", "head", "layer", "chip", "ring", "tile")


# ------------------------------------------------------------------ lengths


def _inv_norm(p: float) -> float:
    return statistics.NormalDist().inv_cdf(p)


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """n lengths at the distribution's evenly spaced quantiles (i + 0.5) / n."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if kind == "uniform":
            x = lo + p * (hi - lo)
        elif kind == "lognormal":
            x = float(dist["median"]) * math.exp(float(dist["sigma"]) * _inv_norm(p))
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(max(lo, min(hi, int(round(x)))))
    return out


def make_prompt(rng: random.Random, n_tokens: int) -> str:
    """ASCII text of exactly n_tokens - 1 bytes: the byte tokenizer adds BOS,
    so the server counts n_tokens. One line, never a chat-role prefix."""
    n = max(1, n_tokens - 1)
    parts, size = [], 0
    while size <= n:  # the joined text is size - 1 characters
        w = rng.choice(WORDS)
        parts.append(w)
        size += len(w) + 1
    return " ".join(parts)[:n].replace("\n", " ")


@dataclass
class Spec:
    """One request to send."""
    prompt_tokens: int
    max_new: int
    prompt: str
    phase: str = "mix"  # probe | warmup | mix


PAIRING_SEED = 0xC0FFEE  # which prompt length goes with which output length: one pairing for all seeds
STRATA = 8


def request_set(mix: dict, seed: int) -> list[Spec]:
    """The mix's fixed set of (prompt, output) sizes in the order ``seed``
    gives them. The set is what a window consumes once or twice over, so every
    run does the same work, and the order is stratified by output length: each
    aligned block of 8 consecutive requests holds one request of each eighth
    of the set. Any stretch of the sequence then has nearly the set's own
    composition; an unstratified order let a 51 s window's tokens swing by
    10 % with the seed (my chip runs, PR 23)."""
    n = int(mix["set_size"])
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outs = quantile_lengths(mix["output_tokens"], n)
    random.Random(PAIRING_SEED).shuffle(prompts)
    pairs = sorted(zip(outs, prompts))
    strata = STRATA if n % STRATA == 0 else 1
    rng = random.Random(seed)
    size = n // strata
    groups = [pairs[i * size:(i + 1) * size] for i in range(strata)]
    for g in groups:
        rng.shuffle(g)
    order = []
    for r in range(size):
        block = [g[r] for g in groups]
        rng.shuffle(block)
        order += block
    return [Spec(p, o, make_prompt(rng, p)) for o, p in order]


def arrival_times(mix: dict, seed: int, horizon_s: float) -> list[float]:
    """Open loop: due times over [0, horizon) at ``rate_per_s``. ``arrivals``
    is ``poisson`` (exponential gaps) or ``gamma`` with a coefficient of
    variation ``cv`` (cv > 1: bursts)."""
    rate = float(mix["rate_per_s"])
    rng = random.Random(seed ^ 0x5EED)
    kind = mix.get("arrivals", "poisson")
    out, t = [], 0.0
    while True:
        if kind == "poisson":
            t += rng.expovariate(rate)
        elif kind == "gamma":
            cv = float(mix.get("cv", 2.0))
            shape = 1.0 / (cv * cv)
            t += rng.gammavariate(shape, 1.0 / (rate * shape))
        else:
            raise ValueError(f"unknown arrival process {kind!r}")
        if t >= horizon_s:
            return out
        out.append(t)


# ------------------------------------------------------------------ records


@dataclass
class Record:
    """What the client saw of one request. Times are time.monotonic()."""
    spec: Spec
    t_ref: float  # closed loop: when it was sent; open loop: when it was DUE
    t_send: float
    events: list[tuple[float, str]] = field(default_factory=list)  # content events
    t_end: float | None = None
    tokens: int | None = None  # the server's count, from the done line
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.tokens is not None

    def event_tokens(self) -> list[tuple[float, float]]:
        """(time, tokens) per content event. The stream carries text, not a
        count per event: an event's share of the request's tokens is its share
        of the characters (the byte tokenizer decodes one token to one
        character, bar ids 0-2 and the odd valid multi-byte run), scaled to
        the server's count where the done line arrived."""
        chars = sum(len(text) for _, text in self.events)
        if not chars:
            return []
        scale = (self.tokens / chars) if self.tokens else 1.0
        return [(t, len(text) * scale) for t, text in self.events]


async def stream_one(session, base: str, model: str, rec: Record,
                     timeout_s: float = 300.0) -> None:
    """Send ``rec``'s request streamed and record every line's arrival time.
    A request cancelled in flight keeps what had arrived and is marked ``cut``."""
    import aiohttp

    spec = rec.spec
    body = {"prompt": spec.prompt, "model": model, "max_new_tokens": spec.max_new,
            "temperature": 0.0, "stream": True}
    try:
        async with session.post(
            f"{base}/chat", json=body, timeout=aiohttp.ClientTimeout(total=timeout_s)
        ) as resp:
            if resp.status != 200:
                rec.error = f"status {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.decode("utf-8", errors="replace").strip()
                if not line:
                    continue
                obj = json.loads(line)
                if obj.get("status") == "error" or obj.get("error"):
                    rec.error = str(obj.get("message") or obj.get("error"))[:200]
                elif obj.get("done"):
                    if obj.get("tokens") is not None:
                        rec.tokens = int(obj["tokens"])
                elif obj.get("text"):
                    rec.events.append((now, obj["text"]))
            if rec.error is None and rec.tokens is None:
                rec.error = "stream ended without its done line"
    except asyncio.CancelledError:
        rec.error = "cut"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, OSError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec.t_end = time.monotonic()


# ------------------------------------------------------------------ the loops


class Load:
    """Runs one mix against the gateway. ``records`` holds every request
    sent, in the order of sending, whether it ended or was cut."""

    def __init__(self, base: str, model: str, mix: dict, seed: int):
        self.base, self.model, self.mix, self.seed = base, model, mix, seed
        self.records: list[Record] = []
        self.late_s: list[float] = []  # open loop: how late each send ran
        self.mix_done = 0  # requests of the mix that have ended
        self._set = request_set(mix, seed)
        self._next = 0
        self._stop = False

    def _take(self) -> Spec:
        spec = self._set[self._next % len(self._set)]
        self._next += 1
        return spec

    async def _send(self, session, spec: Spec, t_ref: float | None = None) -> None:
        t_send = time.monotonic()
        rec = Record(spec, t_send if t_ref is None else t_ref, t_send)
        self.records.append(rec)
        await stream_one(session, self.base, self.model, rec)
        if spec.phase == "mix":
            self.mix_done += 1

    async def run_passes(self, session, passes: list[list[Spec]], callers: int) -> None:
        """Probe and warm-up passes: each pass's requests go out from
        ``callers`` callers and the pass is drained before the next starts."""
        for specs in passes:
            pending = list(specs)

            async def drain():
                while pending:
                    await self._send(session, pending.pop(0))

            await asyncio.gather(*(drain() for _ in range(min(callers, len(specs)))))

    async def drain(self, t1: float) -> None:
        """Keep the loop running past the window's end ``t1`` until the burst
        that was being generated at ``t1`` has arrived: every stream that had
        events before ``t1`` and was still open has had one more or has ended,
        and some event has arrived after ``t1`` (a stream still in its first
        chunk), plus a moment for the rest of that burst. The stretch across
        ``t1`` is then shared out by ``overlap_share`` as the one across the
        window's start is; cutting the callers AT ``t1`` lost it, up to a
        whole chunk of every live row. Bounded by the mix's ``drain_limit_s``."""
        open_at_t1 = [r for r in self.records if r.t_end is None]
        had = {id(r): len(r.events) for r in open_at_t1}
        limit = t1 + float(self.mix.get("drain_limit_s", DRAIN_LIMIT_S))
        while open_at_t1 and time.monotonic() < limit:
            owed = [r for r in open_at_t1
                    if had[id(r)] and r.t_end is None and len(r.events) == had[id(r)]]
            seen = any(r.events and r.events[-1][0] >= t1 for r in self.records)
            if not owed and (seen or all(r.t_end is not None for r in open_at_t1)):
                await asyncio.sleep(2 * BURST_S)
                return
            await asyncio.sleep(0.005)

    async def run_closed(self, session, until) -> None:
        """N callers, each sends its next request when its last one ends.
        ``until()`` returns the window's end; the callers go on through the
        ``drain`` after it, and what is in flight then is cut."""

        async def caller():
            while not self._stop:
                await self._send(session, self._take())

        callers = [asyncio.ensure_future(caller()) for _ in range(int(self.mix["callers"]))]
        try:
            await self.drain(await until())
        finally:
            self._stop = True
            for c in callers:
                c.cancel()
            await asyncio.gather(*callers, return_exceptions=True)

    async def run_open(self, session, until, horizon_s: float) -> None:
        """Requests leave on a seeded schedule at the mix's fixed rate,
        whatever came back, each timed from when it was DUE. After
        ``until()`` nothing new is sent; what has not ended ``drain_limit_s``
        later is cut, and a cut request of an open loop counts as failed."""
        start = time.monotonic()
        tasks: set[asyncio.Task] = set()

        async def pace():
            for t in arrival_times(self.mix, self.seed, horizon_s):
                wait = start + t - time.monotonic()
                if wait > 0:
                    await asyncio.sleep(wait)
                if self._stop:
                    return
                self.late_s.append(time.monotonic() - (start + t))
                task = asyncio.ensure_future(self._send(session, self._take(), start + t))
                tasks.add(task)
                task.add_done_callback(tasks.discard)

        pacer = asyncio.ensure_future(pace())
        try:
            await until()
        finally:
            self._stop = True
            pacer.cancel()
            await asyncio.gather(pacer, return_exceptions=True)
            if tasks:
                _, late = await asyncio.wait(set(tasks), timeout=float(self.mix["drain_limit_s"]))
                for t in late:
                    t.cancel()
                await asyncio.gather(*late, return_exceptions=True)


# ------------------------------------------------------------------ reduction


def percentile(values: list[float], p: float) -> float | None:
    """Linear-interpolated percentile (p in 0..100) of all the samples."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def generation_stretches(records: list[Record]):
    """(record, event index, since, t, tokens) for every content event: its
    tokens were generated in (since, t], where ``since`` is the server's
    previous burst of events (any stream's event at least BURST_S earlier) and
    never before the request was sent. A stream's first stretch also holds
    its prefill."""
    bursts = sorted(t for r in records for t, _ in r.events)
    for r in records:
        for k, (t, n) in enumerate(r.event_tokens()):
            i = bisect.bisect_right(bursts, t - BURST_S) - 1
            since = max(bursts[i] if i >= 0 else r.t_send, r.t_send)
            if t > since:
                yield r, k, since, t, n


def overlap_share(since: float, t: float, a: float, b: float) -> float:
    """The share of the stretch (since, t] that lies inside [a, b)."""
    return max(0.0, min(t, b) - max(since, a)) / (t - since)


def summarize(records: list[Record], t0: float, t1: float, loop: str = "closed") -> dict:
    """The client's view of the window [t0, t1).

    Closed loop — attempted: requests sent inside the window that ended inside
    it; failed: those of them that ended in an error, a refusal or without
    their done line. A request still in flight at the window's end is in
    neither, but its first event is a TTFT sample if it arrived inside the
    window, and its tokens count as far as they were generated inside it. Open loop — attempted: every request DUE inside the window;
    one that had not ended at the drain limit was cut and is failed. TTFT and
    the whole request's time (``request_ms``, over the attempted requests that
    ended well) run from ``t_ref``: the send in a closed loop, the DUE time in
    an open one.

    ``tokens`` are the output tokens GENERATED inside the window. The server
    streams in bursts (every live row's event of up to ``decode_chunk`` tokens
    leaves at the same host sync), so counting a burst where it ARRIVES would
    swing a window's count by a whole burst, 9 % of a 51 s window at 4.6 s a
    chunk, with where the window's edges happen to fall. Each event's tokens
    are spread over the stretch since the previous burst and counted by the
    share of that stretch inside the window, at BOTH edges: the records must
    run past ``t1`` to the next burst (``Load.drain``), or the stretch across
    ``t1`` is missing and the count falls back to whole bursts."""
    window = [r for r in records if r.spec.phase == "mix"]
    if loop == "open":
        ended = [r for r in window if t0 <= r.t_ref < t1]
    else:
        ended = [r for r in window if t0 <= r.t_ref < t1 and r.t_end is not None
                 and r.t_end <= t1 and r.error != "cut"]
    ttft = [(r.events[0][0] - r.t_ref) * 1000.0 for r in window
            if t0 <= r.t_ref < t1 and r.events and r.events[0][0] < t1]
    gaps, tokens = [], 0.0
    for r, _, since, t, n in generation_stretches(records):
        if r.spec.phase == "mix":
            tokens += n * overlap_share(since, t, t0, t1)
    for r in window:
        times = [t for t, _ in r.events if t0 <= t < t1]
        gaps += [(b - a) * 1000.0 for a, b in zip(times, times[1:])]
    return {
        "attempted": len(ended),
        "failed": sum(not r.ok for r in ended),
        "errors": sorted({r.error for r in ended if r.error})[:5],
        "ttft_ms": ttft,
        "request_ms": [(r.t_end - r.t_ref) * 1000.0 for r in ended if r.ok],
        "gap_ms": gaps,
        "tokens": tokens,
        "window_s": t1 - t0,
    }

