"""The token intake in its two halves (ISSUE 40): a fetched window is
SETTLED first (which rows ended: what the scheduler needs before it can
give the chip its next work), and DELIVERED to its callers once that work is
in flight.

- settle decides exactly what the per-token ``Request.accept`` loop it
  replaces decided, over whole rows at once;
- in one loop turn every prefill dispatch of a burst whose requests were
  waiting precedes the delivery of the window before it, which precedes the
  gather of the burst's first tokens; where nobody waits (a closed loop), the
  ended rows' done events go out first and each follower is prefilled as
  it arrives; per request, token events precede the done event;
- an error between settle and deliver leaves no caller hanging.
"""

from __future__ import annotations

import queue
import time
from collections import deque

import jax
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine.introspect import GoodputMeter
from bee2bee_tpu.engine.scheduler import (
    _C_WINDOW_DELIVERIES,
    BatchScheduler,
    Request,
)

WK = 32  # one fetched window: W chunks of K tokens a row
EOS, STOP = 2, 7
VOCAB = 500


# ------------------------------------------------ settle == the accept loop


def _accept_loop(req: Request, tokens) -> tuple[list[int], bool]:
    """The intake settle replaces (the parent's _process_row_tokens, its
    scheduler half): one Request.accept call a token."""
    if req.cancelled and not req.done:
        req.finish = "cancelled"
    kept: list[int] = []
    for t in tokens:
        if not req.accept(int(t)):
            break
        kept.append(int(t))
        if req.done:
            break
    return kept, req.done


class _Rows:
    """What _settle_window / _settle_row touch of a scheduler."""

    _settle_row = BatchScheduler._settle_row
    _settle_window = BatchScheduler._settle_window.__wrapped__  # no phase clock

    def __init__(self, rows):
        self._rows = list(rows)
        self._undelivered: deque = deque()
        self._meter = GoodputMeter(None, 1.0)  # books the window's slots
        self.vacated: list[Request] = []

    def _vacate(self, b, req):
        self._rows[b] = None
        self.vacated.append(req)


def _request(budget_left: int, had: int, rng) -> Request:
    req = Request(
        ids=[1, 2, 3], max_new_tokens=had + budget_left, temperature=0.0,
        top_k=0, top_p=1.0, stop={EOS, STOP}, eos=EOS, tokenizer=None,
    )
    req.out_ids = [int(t) for t in rng.integers(10, VOCAB, size=had)]
    return req


def _plain(rng) -> np.ndarray:
    return rng.integers(10, VOCAB, size=WK).astype(np.int32)  # no stop token


def _case(name: str, rng) -> tuple[Request, np.ndarray]:
    """-> (a live request, its row of the window) for one named case."""
    had = int(rng.integers(0, 40))
    row = _plain(rng)
    beyond = WK + 1 + int(rng.integers(0, 50))
    middle = int(rng.integers(1, WK - 1))
    if name == "stop_first":
        row[0] = STOP
        return _request(beyond, had, rng), row
    if name == "stop_middle":
        row[middle] = STOP
        return _request(beyond, had, rng), row
    if name == "stop_last":
        row[-1] = STOP
        return _request(beyond, had, rng), row
    if name == "stop_absent":
        return _request(beyond, had, rng), row
    if name == "eos_middle":
        row[middle] = EOS
        return _request(beyond, had, rng), row
    if name == "eos_then_stop":
        row[middle], row[middle + 1:] = EOS, STOP
        return _request(beyond, had, rng), row
    if name == "stop_then_eos":
        row[middle], row[middle + 1:] = STOP, EOS
        return _request(beyond, had, rng), row
    if name == "budget_inside":
        return _request(middle, had, rng), row
    if name == "budget_on_last_token":
        return _request(WK, had, rng), row
    if name == "budget_beyond":
        return _request(beyond, had, rng), row
    if name == "budget_spent":  # a zero budget: length, nothing kept
        return _request(0, had, rng), row
    if name == "stop_right_after_budget":  # the budget's token is kept, the stop unseen
        row[middle] = EOS
        return _request(middle, had, rng), row
    if name == "stop_on_budgets_token":  # the stop wins: not kept
        row[middle] = STOP
        return _request(middle + 1, had, rng), row
    if name == "cancelled":
        req = _request(beyond, had, rng)
        req.cancelled = True
        return req, row
    if name == "no_stop_set":
        req = _request(beyond, had, rng)
        req.stop, req.eos = set(), None
        row[middle] = EOS  # an ordinary token to this request
        return req, row
    raise AssertionError(name)


CASES = [
    "stop_first", "stop_middle", "stop_last", "stop_absent", "eos_middle",
    "eos_then_stop", "stop_then_eos", "budget_inside", "budget_on_last_token",
    "budget_beyond", "budget_spent", "stop_right_after_budget",
    "stop_on_budgets_token", "cancelled", "no_stop_set",
]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", CASES)
def test_settle_decides_what_the_accept_loop_decided(name, seed):
    """A window whose rows are all of one case (positions, budgets and what
    a row already holds drawn anew a row) beside a row that ended earlier:
    the same out_ids, finish, kept tokens and retired set."""
    B = 6

    def draw():  # Request holds a queue: twins are drawn, not copied
        rng = np.random.default_rng([seed, CASES.index(name)])
        pairs = [_case(name, rng) for _ in range(B - 1)]
        done_before = _request(5, 3, rng)
        done_before.finish = "length"  # ended in an earlier window of the ring
        rows = [row for _, row in pairs] + [_plain(rng)]
        return [r for r, _ in pairs] + [done_before], np.stack(rows)

    (reqs, toks), (want, _) = draw(), draw()
    done_before = reqs[-1]
    expected = [_accept_loop(r, toks[b]) for b, r in enumerate(want[:-1])]

    sch = _Rows(reqs)
    rec = {"rows": list(enumerate(reqs)), "toks": [None] * 2}
    retired_any = sch._settle_window(rec, toks)

    [window] = sch._undelivered
    assert [r for r, _, _ in window] == reqs[:-1]  # row order; the done row skipped
    for (req, kept, ended), (kept_ref, ended_ref), ref in zip(window, expected, want):
        assert req.out_ids == ref.out_ids
        assert req.finish == ref.finish
        assert (kept, ended) == (kept_ref, ended_ref)
        assert all(type(t) is int for t in req.out_ids)
        assert req.chunks_decoded == 2
    assert sch.vacated == [r for r, ref in zip(reqs, want[:-1]) if ref.done]
    assert retired_any == bool(sch.vacated)
    assert [r is None for r in sch._rows[:-1]] == [ref.done for ref in want[:-1]]
    assert done_before.out_ids == want[-1].out_ids and done_before.chunks_decoded == 0


def test_settle_queues_the_ended_rows_first():
    """Their callers' next requests are what fills the freed rows: the done
    events go out first, the other rows' events keep their row order."""
    rng = np.random.default_rng(5)
    names = ["stop_absent", "stop_middle", "budget_beyond", "budget_inside", "stop_absent"]
    pairs = [_case(n, rng) for n in names]
    reqs = [r for r, _ in pairs]
    sch = _Rows(reqs)
    assert sch._settle_window({"rows": list(enumerate(reqs)), "toks": [None]},
                              np.stack([row for _, row in pairs]))
    [window] = sch._undelivered
    assert [(reqs.index(r), ended) for r, _, ended in window] == [
        (1, True), (3, True), (0, False), (2, False), (4, False)]


def test_settle_skips_a_row_that_moved_since_dispatch():
    rng = np.random.default_rng(3)
    a, b = _request(100, 0, rng), _request(100, 0, rng)
    sch = _Rows([b, None])  # a's row was handed to b since the window launched
    sch._settle_window({"rows": [(0, a), (1, b)], "toks": [None]}, np.stack([_plain(rng)] * 2))
    assert a.out_ids == [] and b.out_ids == [] and not sch._undelivered[0]


# ------------------------------------------------ one loop turn, in order

ROWS = 4
PROMPTS = [[1 + (i * 37 + j) % 500 for j in range(32)] for i in range(ROWS)]


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine("tiny-llama", engine_config=EngineConfig(
        max_seq_len=256, max_batch=ROWS, prefill_buckets=(32,), dtype="float32",
        cache_dtype="float32", decode_chunk=4, spec_tokens=0, rng_seed=7,
    ))
    yield eng
    eng.close()


def _submit_together(eng, budgets, stream=True):
    """Every request queued before the loop can pop the first (the
    condition is an RLock: submit() re-enters it)."""
    sch = eng.scheduler
    with sch._cond:
        return [
            sch.submit(eng._make_request(
                PROMPTS[i % ROWS], budget, 0.0, 0, 1.0, None, stream=stream))
            for i, budget in enumerate(budgets)
        ]


def _events(req) -> list[dict]:
    out = []
    while True:
        ev = req.events.get(timeout=120)
        out.append(ev)
        if ev.get("done"):
            return out


def _deliveries(sch) -> dict:
    deadline = time.monotonic() + 10.0
    while sch._undelivered and time.monotonic() < deadline:
        time.sleep(0.01)  # a window counts once its last event is out
    time.sleep(0.05)
    return {k: _C_WINDOW_DELIVERIES.value(kind=k)
            for k in ("under_device_work", "exposed")}


def test_a_burst_is_in_flight_before_the_window_is_delivered(engine, monkeypatch):
    """Six streams on four rows, budgets staggered so that rows end while
    requests wait: the turn after such a window dispatches the burst's
    prefills, THEN delivers the window, THEN gathers the firsts."""
    sch = engine.scheduler
    log: list[tuple] = []

    def recorded(name, fn, note=lambda *a, **k: ()):
        def run(*args, **kwargs):
            log.append((name, *note(*args, **kwargs)))
            return fn(*args, **kwargs)
        return run

    monkeypatch.setattr(engine, "_prefill", recorded("prefill", engine._prefill))
    monkeypatch.setattr(sch, "_settle_window", recorded("settle", sch._settle_window))
    monkeypatch.setattr(sch, "_deliver_row", recorded(
        "deliver", sch._deliver_row, lambda entry: (id(entry[0]), entry[2])))
    # the scheduler's two blocking reads, told apart by the phase then open
    monkeypatch.setattr(jax, "device_get", recorded(
        "gather", jax.device_get, lambda *a: tuple(sch._phases._open[-1:])))

    before = _deliveries(sch)
    budgets = [7, 7, 19, 19, 10, 10]
    reqs = _submit_together(engine, budgets)
    streams = [_events(r) for r in reqs]
    monkeypatch.undo()

    # per request: token events, then done, and nothing after it
    for req, evs, budget in zip(reqs, streams, budgets):
        assert [bool(e.get("done")) for e in evs] == [False] * (len(evs) - 1) + [True]
        sent = [t for e in evs[:-1] for t in e["tokens"]]
        assert sent == evs[-1]["result"].token_ids and len(sent) == budget
        assert req.events.empty()

    # every turn that placed a burst behind a settled window:
    # settle, prefill+, deliver+, gather(admit)
    hidden = 0
    for i, ev in enumerate(log):
        if ev != ("gather", "admit"):
            continue
        last = max((j for j in range(i) if log[j][0] in ("settle", "gather")), default=-1)
        names = [e[0] for e in log[last + 1:i]]
        assert "prefill" in names
        if last < 0 or log[last][0] != "settle":
            assert "deliver" not in names
            continue
        n = names.index("deliver")
        assert set(names[:n]) == {"prefill"} and set(names[n:]) == {"deliver"}, names
        hidden += 1
    assert hidden >= 1, log
    # a done event's delivery follows every token delivery of its request
    for req in reqs:
        mine = [e[2] for e in log if e[0] == "deliver" and e[1] == id(req)]
        assert mine and mine[-1] is True and not any(mine[:-1])

    after = _deliveries(sch)
    assert after["under_device_work"] - before["under_device_work"] >= hidden
    # the last windows have no burst to hide under (the queue is empty)
    assert after["exposed"] > before["exposed"]
    # as many windows delivered as settled
    assert sum(after.values()) - sum(before.values()) == sum(e[0] == "settle" for e in log)


def test_a_callers_next_request_joins_the_burst_its_done_event_opened(engine, monkeypatch):
    """A closed loop: nobody waits for a row until a done event is out. The
    ended rows are delivered first, each follower is prefilled as soon as it
    is queued, and the other rows are delivered under those prefills, all
    before the one gather."""
    sch = engine.scheduler
    log: list[str] = []
    followers: list = []
    deliver, prefill, get = sch._deliver_row, engine._prefill, jax.device_get

    def delivering(entry):
        deliver(entry)
        log.append("done" if entry[2] else "tokens")
        if entry[2] and len(followers) < 2:  # the caller sends its next request
            followers.append(sch.submit(engine._make_request(
                PROMPTS[len(followers)], 5, 0.0, 0, 1.0, None, stream=True)))

    def prefilling(*args, **kwargs):
        log.append("prefill")
        return prefill(*args, **kwargs)

    def getting(*args):
        log.append("gather:" + "".join(sch._phases._open[-1:]))
        return get(*args)

    before = _deliveries(sch)
    monkeypatch.setattr(sch, "_deliver_row", delivering)
    monkeypatch.setattr(engine, "_prefill", prefilling)
    monkeypatch.setattr(jax, "device_get", getting)
    reqs = _submit_together(engine, [7, 7, 19, 19])
    for r in reqs[:2]:
        _events(r)
    for r in followers:
        assert len(_events(r)[-1]["result"].token_ids) == 5
    for r in reqs[2:]:
        _events(r)
    monkeypatch.undo()

    i = log.index("done")
    assert log[i:i + 8] == ["done", "prefill", "done", "prefill", "tokens", "tokens",
                            "gather:admit", "gather:fetch"], log
    assert _deliveries(sch)["under_device_work"] > before["under_device_work"]


def test_who_arrives_during_the_gather_gets_a_row_before_the_next_window(engine, monkeypatch):
    """_loop admits again while a burst was placed: a request queued while
    the burst's first tokens were gathered does not wait a window."""
    sch = engine.scheduler
    log: list[str] = []
    late: list = []
    get, prefill = jax.device_get, engine._prefill

    def getting(*args):
        phase = "".join(sch._phases._open[-1:])
        log.append("gather:" + phase)
        if phase == "admit" and not late:
            late.append(sch.submit(engine._make_request(
                PROMPTS[1], 5, 0.0, 0, 1.0, None, stream=True)))
        return get(*args)

    def prefilling(*args, **kwargs):
        log.append("prefill")
        return prefill(*args, **kwargs)

    monkeypatch.setattr(jax, "device_get", getting)
    monkeypatch.setattr(engine, "_prefill", prefilling)
    [first] = _submit_together(engine, [9])
    assert len(_events(first)[-1]["result"].token_ids) == 9
    assert len(_events(late[0])[-1]["result"].token_ids) == 5
    monkeypatch.undo()
    assert log[:5] == ["prefill", "gather:admit", "prefill", "gather:admit", "gather:fetch"], log


def test_unstreamed_rows_get_their_done_event_only(engine):
    reqs = _submit_together(engine, [6, 9, 13], stream=False)
    for req, budget in zip(reqs, [6, 9, 13]):
        [done] = _events(req)
        assert len(done["result"].token_ids) == budget
        assert done["result"].finish_reason == "length"


# ------------------------------------------------ an error in between


def test_an_error_between_settle_and_deliver_leaves_no_caller_hanging(engine, monkeypatch):
    """The prefill of the burst that follows a settled window throws: the
    rows that window ended are in neither _queue nor _rows, and still get
    their tokens and done event; every other caller gets the error."""
    sch = engine.scheduler
    prefill, calls = engine._prefill, []

    def throwing(*args, **kwargs):
        calls.append(len(sch._undelivered))
        if calls[-1]:  # a settled window waits for its delivery
            raise RuntimeError("prefill lost the device")
        return prefill(*args, **kwargs)

    monkeypatch.setattr(engine, "_prefill", throwing)
    budgets = [7, 7, 19, 19, 10, 10]
    reqs = _submit_together(engine, budgets)
    streams = [_events(r) for r in reqs]  # a hang fails here (queue.Empty)
    monkeypatch.undo()

    assert calls[-1] >= 1  # the throw did fall between settle and deliver
    assert not sch._undelivered
    ended = [evs[-1] for evs in streams if evs[-1].get("result") is not None]
    failed = [evs[-1] for evs in streams if evs[-1].get("result") is None]
    # the two short rows ended in the window before the burst that threw
    assert len(ended) == 2 and len(failed) == 4
    for evs in streams:
        sent = [t for e in evs[:-1] for t in e["tokens"]]
        if evs[-1].get("result") is not None:
            assert sent == evs[-1]["result"].token_ids and len(sent) == 7
        else:
            assert "error" in evs[-1]
    # and the scheduler serves on
    out = engine.generate(PROMPTS[0], max_new_tokens=5, temperature=0.0)
    assert len(out.token_ids) == 5


def test_a_delivery_that_raises_fails_its_own_request(engine, monkeypatch):
    """_retire throwing for an ended row (it is in neither _queue nor _rows
    by then) must still answer that row's caller."""
    sch = engine.scheduler
    retire = sch._retire
    armed = [True]

    def throwing(req):
        if armed[0] and len(req.out_ids) == 6:
            armed[0] = False
            raise RuntimeError("result could not be built")
        return retire(req)

    monkeypatch.setattr(sch, "_retire", throwing)
    reqs = _submit_together(engine, [6, 14], stream=False)
    finals = [_events(r)[-1] for r in reqs]
    monkeypatch.undo()
    assert finals[0]["result"] is None and "delivery failed" in finals[0]["error"]
    assert finals[1].get("done")
    with pytest.raises(queue.Empty):
        reqs[0].events.get_nowait()
