"""The verify WINDOW through the scheduler (tests/test_exaone_engine.py holds the
program against its steps chained by hand): a node whose model drafts for itself
runs up to ``decode_chunk`` verify steps a dispatch and settles, books and
delivers a row once a window. Held against the same node at ``decode_chunk`` 1,
where a window IS one serialized step: the texts and how they end, the tier's
books, the slots, the expert layer's calls; then what a window alone has: one
stream event a row, the draft's position tag, a cancel, a row that joins between
two windows. All at ``tiny-exaone`` size on the CPU; a file of its own, as
test_exaone_engine.py is (one process's worth of jit executables)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bee2bee_tpu.engine.scheduler  # noqa: F401  (registers the metrics)
from bee2bee_tpu.metrics import get_registry
from test_exaone_engine import _echo_weights, _engine, _prompt

KINDS = ("kept", "after_end", "dead_row")
N = 4  # the window engine's decode_chunk


def _counters() -> dict:
    reg = get_registry()
    out = {f"slots.{k}": reg.get("engine.decode_slots").value(kind=k) for k in KINDS}
    out["drafted"] = reg.get("engine.spec_drafted").value(tier="mtp")
    out["accepted"] = reg.get("engine.spec_accepted").value(tier="mtp")
    out["layer_calls"] = reg.get("engine.moe_layer_calls").value()
    out["spec_steps"] = reg.get("engine.spec_steps").value()
    out["windows"], out["window_steps"] = reg.get("engine.window_steps").totals()
    return out


def _drain(req, timeout=180.0) -> list[dict]:
    out = []
    while True:
        ev = req.events.get(timeout=timeout)
        out.append(ev)
        if ev.get("done"):
            return out


def _idle(eng):
    sch = eng.scheduler
    deadline = time.monotonic() + 30.0
    while (sch._undelivered or sch._inflight or sch.active) and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)


def _spy_windows(eng) -> list:
    """(batch width, steps) of every verify window dispatched from here on."""
    calls, window = [], eng._spec_window

    def spy(params, cur, *rest, steps, **kw):
        calls.append((int(np.asarray(cur).shape[0]), int(steps)))
        return window(params, cur, *rest, steps=steps, **kw)

    eng._spec_window = spy
    return calls


WEIGHTS = _echo_weights("mixed")
# (prompt seed, prompt tokens, budget): a budget that ends inside a window, a long
# row, a row whose stop token falls inside a window (found by the first run)
ROWS = [(0, 21, 7), (1, 9, 18), (2, 30, 14), (3, 13, 11)]


def _run(decode_chunk: int, stops: dict) -> dict:
    eng = _engine(params=jax.tree.map(jnp.asarray, WEIGHTS), spec_tokens=1,
                  decode_chunk=decode_chunk)
    try:
        calls = _spy_windows(eng)
        sch, before = eng.scheduler, _counters()
        with sch._cond:  # queued before the loop can pop the first: one burst
            reqs = [sch.submit(eng._make_request(
                _prompt(seed, n), new, 0.0, 0, 1.0, stops.get(seed), stream=True))
                for seed, n, new in ROWS]
        events = [_drain(r) for r in reqs]
        _idle(eng)
        after = _counters()
        return {
            "out": [list(r.out_ids) for r in reqs], "finish": [r.finish for r in reqs],
            "events": events, "calls": calls,
            "req_books": [(r.spec_drafted, r.spec_accepted) for r in reqs],
            "grew": {k: after[k] - before[k] for k in after},
            "stats": (sch.stats.spec_steps, sch.stats.spec_drafted, sch.stats.spec_accepted,
                      dict(sch.stats.spec_tiers), sch.stats.chunks),
        }
    finally:
        eng.close()


@pytest.fixture(scope="module")
def runs():
    """The same four requests through a node of one-step windows (the serialized
    path) and a node of four-step windows; row 2 stops on a token that the first
    run shows inside its second window."""
    free = _run(1, {})
    assert len(free["out"][2]) == 14
    stop = free["out"][2][6]
    assert stop not in free["out"][2][:6]
    stops = {2: [stop]}
    return _run(1, stops), _run(N, stops), stop


def test_a_stop_token_and_a_budget_inside_a_window_end_a_row_as_the_serialized_steps_do(runs):
    serial, window, stop = runs
    assert window["out"] == serial["out"]
    assert window["finish"] == serial["finish"] == ["length", "length", "stop", "length"]
    assert [len(o) for o in window["out"]] == [7, 18, 6, 11]
    assert stop not in window["out"][2]
    # every decode step was a verify step, of windows up to the cap
    assert serial["stats"][4] == window["stats"][4] == 0
    assert {n for _, n in serial["calls"]} == {1}
    assert max(n for _, n in window["calls"]) == N and len(window["calls"]) < len(serial["calls"])


def test_the_tiers_books_are_the_serialized_steps(runs):
    serial, window, _ = runs
    assert window["req_books"] == serial["req_books"]
    for run in (serial, window):
        drafted = sum(d for d, _ in run["req_books"])
        accepted = sum(a for _, a in run["req_books"])
        assert 0 < accepted < drafted
        assert run["grew"]["drafted"] == drafted == run["stats"][1]
        assert run["grew"]["accepted"] == accepted == run["stats"][2]
        assert run["stats"][3] == {"mtp": {"drafted": drafted, "accepted": accepted}}
    # a row drafts in every step it lives but its last token's: tokens = steps + accepted
    for (drafted, accepted), out, finish in zip(window["req_books"], window["out"], window["finish"]):
        steps = len(out) - 1 - accepted + (finish == "stop")
        assert drafted in (steps - 1, steps)


def test_slots_expert_calls_and_steps_grow_by_what_the_steps_of_a_window_are(runs):
    for run in runs[:2]:
        grew, calls = run["grew"], run["calls"]
        steps = sum(n for _, n in calls)
        assert grew["spec_steps"] == steps == run["stats"][0]
        # a verify step's [bsz, K + 1] slots, n times a window
        assert sum(grew[f"slots.{k}"] for k in KINDS) == sum(b * n * 2 for b, n in calls)
        assert grew["slots.kept"] == sum(len(o) - 1 for o in run["out"])
        # five expert-layer calls a forward (four trunk layers, the MTP block's): the
        # burst's prefill programs (buckets 32, 16, 32, 16: two groups) and every step
        assert grew["layer_calls"] == (2 + steps) * 5
    serial, window, _ = runs
    assert window["grew"]["slots.kept"] == serial["grew"]["slots.kept"]
    # rows that end inside a window ride it to its end: the cost of the window
    assert window["grew"]["slots.after_end"] >= serial["grew"]["slots.after_end"]


def test_window_steps_observes_n_once_a_verify_window(runs):
    for run in runs[:2]:
        assert run["grew"]["windows"] == len(run["calls"])
        assert run["grew"]["window_steps"] == sum(n for _, n in run["calls"])


def test_one_stream_event_a_row_a_window(runs):
    serial, window, _ = runs
    for run in (serial, window):
        for events, out in zip(run["events"], run["out"]):
            tokens = [ev for ev in events if "tokens" in ev]
            assert [t for ev in tokens for t in ev["tokens"]] == out
            assert events[-1].get("done") and events[-1]["result"].token_ids == out
    # the first token's event (its admission's), then ONE a window the row lived in
    n_events = [sum("tokens" in ev for ev in events) for events in window["events"]]
    lived = [1 + -(-(len(o) - 1 - a + (f == "stop")) // N) for o, (_, a), f in
             zip(window["out"], window["req_books"], window["finish"])]
    assert n_events == lived
    assert sum(n_events) < sum(sum("tokens" in ev for ev in events)
                               for events in serial["events"])


@pytest.fixture(scope="module")
def engine():
    eng = _engine(params=jax.tree.map(jnp.asarray, WEIGHTS), spec_tokens=1, decode_chunk=N)
    yield eng
    eng.close()


def test_the_drafts_position_tag_after_a_window_is_the_rows_new_length(engine, monkeypatch):
    sch, tags = engine.scheduler, []
    settle = sch._settle_window

    def settling(rec, toks):
        ended = settle(rec, toks)
        for b, req in rec["rows"]:
            if sch._rows[b] is req:  # still live: it goes on with the window's last draft
                tags.append((req.mtp_draft[0], len(req.ids) + len(req.out_ids),
                             int(sch._offsets[b]) + 1, req.mtp_draft[1],
                             int(rec["draft_h"][b, 0])))
        return ended

    monkeypatch.setattr(sch, "_settle_window", settling)
    out = engine.generate(_prompt(5, 17), max_new_tokens=13).token_ids
    _idle(engine)
    assert len(out) == 13 and len(tags) >= 2
    for tag, length, offset, draft, last in tags:
        assert tag == length == offset and draft == last


def test_a_cancelled_row_keeps_nothing_of_the_window_and_books_nothing(engine, monkeypatch):
    sch, seen = engine.scheduler, {}
    fetch = sch._fetch_window
    req = engine._make_request(_prompt(6, 12), 40, 0.0, 0, 1.0, None, stream=True)

    def fetching(rec):
        toks = fetch(rec)
        seen["windows"] = seen.get("windows", 0) + 1
        if seen["windows"] == 2:  # fetched, not settled yet
            seen["had"] = (len(req.out_ids), req.spec_drafted, req.spec_accepted)
            req.cancelled = True
        return toks

    monkeypatch.setattr(sch, "_fetch_window", fetching)
    sch.submit(req)
    events = _drain(req)
    _idle(engine)
    assert req.finish == "cancelled" and seen["windows"] == 2
    assert (len(req.out_ids), req.spec_drafted, req.spec_accepted) == seen["had"]
    assert [t for ev in events if "tokens" in ev for t in ev["tokens"]] == req.out_ids
    assert sch.active == 0


def test_a_request_admitted_between_two_windows_joins_with_its_prefills_draft(engine, monkeypatch):
    alone = engine.generate(_prompt(8, 19), max_new_tokens=9).token_ids
    _idle(engine)
    sch = engine.scheduler
    calls = _spy_windows(engine)
    first = engine._make_request(_prompt(7, 25), 30, 0.0, 0, 1.0, None, stream=True)
    sch.submit(first)
    assert "tokens" in first.events.get(timeout=180.0)  # its admission's token
    while not calls:  # its first window is out
        time.sleep(0.005)
    late = engine._make_request(_prompt(8, 19), 9, 0.0, 0, 1.0, None, stream=True)
    sch.submit(late)
    _drain(late)
    _drain(first)
    _idle(engine)
    assert late.out_ids == alone and late.finish == "length"
    # it drafted from its first step on: the prefill's draft, no miss, still on the tier
    assert late.spec_tier == "mtp" and late.spec_misses == 0
    assert late.spec_drafted >= len(alone) - 2 - late.spec_accepted
    assert any(b == 2 for b, _ in calls) and len(first.out_ids) == 30


def test_near_the_contexts_end_a_window_is_cut_to_the_room_and_the_text_holds():
    """A step writes K + 1 positions at a row's offset whatever it accepts: where
    offset + n (K + 1) would pass max_seq_len the window is cut (`room`), down to
    the one step that always fits, and the text is the serialized steps'."""
    cuts = get_registry().get("engine.windows")

    def run(decode_chunk):
        eng = _engine(params=jax.tree.map(jnp.asarray, WEIGHTS), spec_tokens=1,
                      decode_chunk=decode_chunk, max_seq_len=64)
        try:
            calls, before = _spy_windows(eng), cuts.value(cut="room")
            out = eng.generate(_prompt(9, 23), max_new_tokens=40)
            sch = eng.scheduler
            return (out.token_ids, out.finish_reason, calls, cuts.value(cut="room") - before,
                    sch.stats.spec_drafted, sch.stats.spec_accepted, sch.stats.chunks)
        finally:
            eng.close()

    serial, window = run(1), run(N)
    assert window[:2] == serial[:2] and len(window[0]) == 40
    assert window[4:] == serial[4:] and window[6] == 0  # the books; no decode window
    assert serial[3] == 0 and window[3] >= 1
    steps = [n for _, n in window[2]]
    assert steps[0] == N and steps[-1] < N and steps == sorted(steps, reverse=True)
