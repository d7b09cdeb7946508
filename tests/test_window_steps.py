"""A decode window is as long as its rows need (ISSUE 49): the step count
is an OPERAND of the one decode program, chosen at every dispatch.

- the program: ``n`` steps of the traced-bound loop are the first ``n``
  steps of the scan it replaces (tokens, carry, pool, state, the expert
  counters), for a dense paged model, a recurrent one, a dropless-expert one
  and a looped stack, greedy and seeded, without and with the penalty
  counts; windows of a and of b steps equal one of a + b;
- the policy as a pure function of what the scheduler observes;
- the host's books after a window of ``n`` steps;
- streamed requests behind a standing queue keep more of their decode slots
  than under a fixed window, and every request gets exactly its tokens and
  its done event.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine
from bee2bee_tpu.engine import scheduler as sched_mod
from bee2bee_tpu.engine.introspect import _C_DECODE_SLOTS
from bee2bee_tpu.engine.sampling import sample_batched
from bee2bee_tpu.engine.scheduler import (
    _C_KV_PAGES_LIVE,
    _C_KV_PAGES_VISITED,
    _C_WINDOWS,
    _H_WINDOW_STEPS,
    choose_window_steps,
)
from bee2bee_tpu.health import FlightRecorder
from bee2bee_tpu.models import core

K = 8  # decode_chunk of the program tests: the cap, the token buffer's width
ROWS = 4


def _prompt(i: int, n: int) -> list[int]:
    return [1 + (i * 37 + j * 11) % 500 for j in range(n)]


def _engine(model: str = "tiny-llama", **over) -> InferenceEngine:
    kw = dict(
        max_seq_len=128, max_batch=ROWS, prefill_buckets=(32,), dtype="float32",
        cache_dtype="float32", decode_chunk=K, spec_tokens=0, rng_seed=7,
        kv_block_size=8,
    )
    kw.update(over)
    return InferenceEngine(model, engine_config=EngineConfig(**kw))


# ------------------------------------------------ the program against the scan


def _scan_reference(sch, n: int):
    """The decode chunk as a plain ``lax.scan`` over the first ``n`` of the
    chunk's keys (the program PR 49 replaced, cut to ``n`` steps), under
    the decode root's calling convention. Not donating: it runs BESIDE the
    served call on the same arguments."""
    e = sch.engine

    def fn(params, cur, cache, offsets, temps, topks, topps, minps, key,
           tables=None, adapters=None, aids=None, ascales=None, counts=None,
           reps=None, press=None, freqs=None, state=None):
        B = cur.shape[0]
        if state is not None:
            cache = dict(cache, **state)
        cache = sch._with_moe_stats(cache)

        def step(carry, key_t):
            cur, cache, off, cnt = carry
            logits, cache = core.forward(
                params, e.model_cfg, cur[:, None], cache, off,
                attn_fn=e._attn_fn(), block_tables=tables,
                adapters=adapters, adapter_ids=aids, adapter_scales=ascales,
            )
            nxt = sample_batched(logits[:, -1, :], key_t, temps, topks, topps,
                                 minps, cnt, reps, press, freqs)
            if cnt is not None:
                cnt = cnt.at[jnp.arange(B), 1, nxt].add(1)
            return (nxt, cache, off + 1, cnt), nxt

        keys = jax.random.split(key, e.engine_cfg.decode_chunk)[:n]
        (cur, cache, offsets, counts), toks = jax.lax.scan(
            step, (cur, cache, offsets, counts), keys)
        return (cur, cache, offsets, counts, jnp.moveaxis(toks, 0, 1),
                sch._chunk_extras(cache, state))

    return jax.jit(fn)


def _same(a, b) -> bool:
    """Leaf for leaf: integers equal, floats to float32 rounding (two
    programs of one body may order a reduction differently)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6)
        for x, y in zip(la, lb))


_REFS: dict = {}


class _Beside:
    """Wraps the decode root: every served call is first run as the scan
    reference on the same arguments, and the two are compared AFTER the
    served call (on the scheduler thread: what differs is kept for the test
    thread to assert on)."""

    def __init__(self, sch, served):
        self.sch, self.served = sch, served
        self.steps: list[int] = []
        self.wrong: list[str] = []

    def __call__(self, *args, **kwargs):
        kw = dict(kwargs)
        n = int(kw.pop("steps"))
        # carry, pool and extras are compared at n = K and at the 3 steps a
        # request alone is sure to run; any other n against the first n
        # tokens of the K-step scan (a compile an (engine, n) is the cost)
        whole = n in (K, 3)
        ref = _REFS.get((id(self.sch), n if whole else K))
        if ref is None:
            ref = _REFS[id(self.sch), n if whole else K] = _scan_reference(
                self.sch, n if whole else K)
        want = ref(*args, **kw)
        got = self.served(*args, **kwargs)
        self.steps.append(n)
        cur, pool, off, cnt, toks, extras = got
        names = ("cur", "pool", "offsets", "counts", "extras")
        for name, g, w in zip(names, (cur, pool, off, cnt, extras),
                              (want[0], want[1], want[2], want[3], want[5])):
            if whole and not _same(g, w):
                self.wrong.append(f"{name} after {n} steps")
        if toks.shape[1] != K:
            self.wrong.append(f"token buffer {toks.shape}")
        if not np.array_equal(np.asarray(toks)[:, :n], np.asarray(want[4])[:, :n]):
            self.wrong.append(f"tokens of {n} steps")
        return got


def _generate_all(eng, budgets, **gen):
    """The budgets as one concurrent batch, then 12 and 4 tokens each ALONE:
    such a row's window is dispatched as 8 + 3 (3) steps whatever it samples."""
    out: list = [None] * len(budgets)

    def run(i):
        out[i] = eng.generate(_prompt(i, 9 + 4 * i), max_new_tokens=budgets[i], **gen)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(budgets))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(r is not None for r in out)
    for alone in (12, 4):
        eng.generate(_prompt(7, 11), max_new_tokens=alone, **gen)
    return out


SAMPLING = {
    "greedy": dict(temperature=0.0),
    "seeded": dict(temperature=0.9, top_k=40, top_p=0.95),
}
BUDGETS = [4, 7, 12, 21]


@pytest.fixture(scope="module", params=[
    ("tiny-llama", {}), ("tiny-falcon-h1", {}), ("tiny-joyai", {}), ("tiny-ouro", {}),
], ids=["dense_paged", "recurrent", "dropless_experts", "looped_stack"])
def model_engine(request):
    model, over = request.param
    eng = _engine(model, **over)
    yield eng
    eng.close()


@pytest.mark.parametrize("sampling", list(SAMPLING))
def test_n_steps_of_the_loop_are_the_first_n_of_the_scan(model_engine, monkeypatch, sampling):
    """tokens, carry, pool and what rides the carry (the recurrent state
    after n steps, moe_stats of n steps) against the scan, at n = K and n < K."""
    sch = model_engine.scheduler
    beside = _Beside(sch, sch._decode)
    monkeypatch.setattr(sch, "_decode", beside)
    got = _generate_all(model_engine, BUDGETS, **SAMPLING[sampling])
    monkeypatch.undo()
    assert not beside.wrong, beside.wrong
    assert K in beside.steps and 3 in beside.steps, beside.steps
    # (a seeded model may emit its end token: never MORE than the budget)
    assert all(0 < len(r.token_ids) <= b for r, b in zip(got, BUDGETS))


@pytest.mark.parametrize("sampling", list(SAMPLING))
def test_the_penalty_roots_run_n_steps_token_for_token(sampling):
    """counts ride the loop carry of the one decode root."""
    eng = _engine()
    try:
        sch = eng.scheduler
        beside = _Beside(sch, sch._decode)
        sch._decode = beside
        got = _generate_all(eng, BUDGETS, repetition_penalty=1.3,
                            presence_penalty=0.2, **SAMPLING[sampling])
        assert not beside.wrong, beside.wrong
        assert K in beside.steps and 3 in beside.steps, beside.steps
        assert sch.stats.counts_windows >= len(beside.steps) // 2
        assert all(0 < len(r.token_ids) <= b for r, b in zip(got, BUDGETS))
    finally:
        eng.close()


def test_windows_of_a_and_b_steps_equal_one_of_a_plus_b(model_engine, monkeypatch):
    """Greedy rows: the chunk run as a steps then b steps (chained on the
    returned carry, pool and state) leaves what a + b steps leave."""
    sch = model_engine.scheduler
    a, b = 3, 4
    seen: list = []
    served = sch._decode
    plain = jax.jit(sch._decode_fn)  # not donating: the same arguments thrice

    def spy(*args, **kwargs):
        if not seen and int(kwargs["steps"]) == K:
            params, cur, pool, off, *rest = args
            kw = dict(kwargs)
            one = plain(*args, **dict(kw, steps=np.int32(a + b)))
            first = plain(*args, **dict(kw, steps=np.int32(a)))
            if kw.get("state") is not None:
                kw["state"] = {k: v for k, v in first[5].items() if k != "moe_stats"}
            second = plain(params, first[0], first[1], first[2], *rest,
                           **dict(kw, steps=np.int32(b)))
            seen.append((one, first, second))
        return served(*args, **kwargs)

    monkeypatch.setattr(sch, "_decode", spy)
    _generate_all(model_engine, [2 * K + 3] * ROWS, temperature=0.0)
    monkeypatch.undo()
    (one, first, second), = seen
    toks = np.concatenate([np.asarray(first[4])[:, :a], np.asarray(second[4])[:, :b]], axis=1)
    assert np.array_equal(toks, np.asarray(one[4])[:, :a + b])
    for i in (0, 1, 2):  # cur, pool, offsets
        assert _same(second[i], one[i]), i
    ex_one, ex_two = dict(one[5] or {}), dict(second[5] or {})
    if "moe_stats" in ex_one:  # the counters of a + b steps are those of a plus those of b
        assert np.array_equal(
            np.asarray(ex_one.pop("moe_stats")),
            np.asarray(first[5]["moe_stats"]) + np.asarray(ex_two.pop("moe_stats")))
    assert _same(ex_two, ex_one)


# ------------------------------------------- the sentinel still keys the decode root


@pytest.mark.parametrize("window", ["plain", "penalised"])
def test_the_sentinel_keys_a_dispatch_that_passes_steps(window, tmp_path):
    """Every dispatch passes ``steps=``: the root's key function takes it
    (a key function that raised would leave the root un-keyed: counted,
    never classified), the key does not gain a field (its last flag says
    whether the counts ride), and a batch width off the declared ladder
    still raises the incident."""
    eng = _engine()
    rec = FlightRecorder(incident_dir=tmp_path)
    sentinel = eng.introspect.sentinel
    sentinel._recorder = rec
    try:
        pen = window == "penalised"
        eng.generate(_prompt(0, 9), max_new_tokens=6,  # served windows
                     **(dict(repetition_penalty=1.3) if pen else {}))
        sch = eng.scheduler
        watched = sentinel._roots["decode"]
        assert watched.traces >= 1 and watched.storms == 0
        assert watched.seen and all(
            key[0] in eng._declared_batch_sizes and key[2:] == (False, False, pen)
            for key in watched.seen), watched.seen
        B = 3  # max_batch 4: the ladder is 1, 2, 4
        assert B not in eng._declared_batch_sizes
        zi, zf = np.zeros(B, np.int32), np.zeros(B, np.float32)
        tables = np.zeros((B, sch.cache.tables.shape[1]), np.int32)
        counts = dict(
            counts=jnp.zeros((B, 2, eng.model_cfg.vocab_size), jnp.int32),
            reps=zf + 1, press=zf, freqs=zf) if pen else {}
        out = sch._decode(eng.params, zi, sch.cache.pool, zi, zf, zi, zf + 1, None,
                          eng._next_key(), tables, steps=np.int32(2), **counts)
        sch.cache.pool = out[1]  # the call donated the pool
        assert sentinel.snapshot()["decode"]["storms"] == 1
        rec.flush()
        bundle = rec.load_incident(rec.list_incidents()[0]["id"])
        assert bundle["extra"]["root"] == "decode" and "UNDECLARED" in bundle["detail"]
        assert bundle["extra"]["key"].startswith(f"({B}, ")
    finally:
        eng.close()


# ------------------------------------------------------------------ the policy


STEP, TURN = 14.6, 9.0  # a decode step and the host's turn, ms
CHEAP, DEAR = 4.0, 660.0  # what a burst costs beyond its requests, beside such a step


@pytest.mark.parametrize("name,budgets,queued,cap,costs,want", [
    # nobody queued: the parent's length, but for a last partial window
    ("nobody_queued_beyond_cap", [40, 70, 33], False, 32, (STEP, TURN, CHEAP), (32, "full")),
    ("nobody_queued_row_ends_early", [5, 40, 70], False, 32, (STEP, TURN, CHEAP), (32, "full")),
    ("nobody_queued_all_end_sooner", [5, 11, 19], False, 32, (STEP, TURN, CHEAP), (19, "drain")),
    ("nobody_queued_unobserved", [5, 11, 19], False, 32, (None, None, None), (19, "drain")),
    # someone waits
    ("every_budget_beyond_the_cap", [33, 64, 100, 47], True, 32, (STEP, TURN, CHEAP), (32, "full")),
    ("a_row_ends_at_5_cheap_admission", [5] + [60] * 15, True, 32, (STEP, TURN, CHEAP), (5, "budget")),
    ("a_row_ends_at_5_dear_admission", [5] + [60] * 15, True, 32, (STEP, TURN, DEAR), (32, "full")),
    ("most_rows_end_at_5", [5] * 12 + [60] * 4, True, 32, (STEP, TURN, 40.0), (5, "budget")),
    ("a_row_ends_just_before_the_cap", [30] + [60] * 15, True, 32, (STEP, TURN, CHEAP), (32, "full")),
    ("the_second_end_is_the_stop", [4, 6, 6, 6] + [60] * 12, True, 32, (STEP, TURN, 12.0), (6, "budget")),
    ("unobserved_costs_run_the_parents_rule", [5] + [60] * 15, True, 32, (None, None, None), (32, "full")),
    ("unobserved_turn_runs_the_parents_rule", [5] + [60] * 15, True, 32, (STEP, None, CHEAP), (32, "full")),
    ("every_row_ends_sooner_unobserved", [3, 9, 6], True, 32, (None, None, None), (9, "budget")),
    ("one_step_left", [1, 1], True, 32, (STEP, TURN, CHEAP), (1, "budget")),
    ("a_spent_row_never_makes_zero", [0, 0], True, 32, (STEP, TURN, CHEAP), (1, "budget")),
    ("a_long_cap_is_cut_too", [40] * 4, True, 64, (STEP, TURN, CHEAP), (40, "budget")),
    # a stop that loses what it saves (27 tokens either way): the later stop
    ("ties_go_to_the_longer_window", [5, 60], True, 32, (2.0, 20.0, 7.0), (32, "full")),
])
def test_the_policy_as_a_function_of_what_it_observes(name, budgets, queued, cap, costs, want):
    assert choose_window_steps(budgets, queued, cap, *costs) == want


def _least_loss(budgets, top, stop_tokens, first=None):
    """Every plan of stops at rows' ends up to ``top`` (the last stop),
    tried one by one: the least tokens lost, over the plans whose first
    stop is ``first`` if given."""
    import itertools

    ends = sorted({min(b, top) for b in budgets} | {top})
    best = None
    for r in range(len(ends)):
        for mid in itertools.combinations(ends[:-1], r):
            stops = list(mid) + [top]
            if first is not None and stops[0] != first:
                continue
            lost, at = len(mid) * stop_tokens, 0
            for s in stops:
                lost += sum(s - b for b in budgets if at < b <= s)
                at = s
            best = lost if best is None else min(best, lost)
    return best


@pytest.mark.parametrize("seed", range(6))
def test_the_policy_stays_inside_its_bounds_and_plans_the_least_loss(seed):
    rng = np.random.default_rng(seed)
    for trial in range(120):
        few = trial % 2 == 0  # few rows: every plan of stops can be tried
        budgets = rng.integers(0, 120 if not few else 40,
                               size=int(rng.integers(1, 8 if few else 65))).tolist()
        cap = int(rng.choice([1, 4, 32, 64, 256]))
        costs = (float(rng.uniform(0.1, 50)), float(rng.uniform(0, 30)),
                 float(rng.uniform(0, 700 if not few else 60)))
        if rng.random() < 0.2:
            costs = (None, None, None)
        queued = bool(rng.random() < 0.7)
        n, cut = choose_window_steps(budgets, queued, cap, *costs)
        top = max(1, min(cap, max(budgets)))
        assert 1 <= n <= cap and n <= top
        assert cut in ("full", "budget", "drain")
        assert (cut == "full") == (n == cap)
        if not queued or None in costs:
            assert n == top
        elif few:
            live = [max(b, 1) for b in budgets]
            stop = (costs[1] + costs[2]) * len(live) / costs[0]
            assert n in {min(b, top) for b in live} | {top}
            assert _least_loss(live, top, stop, first=n) == pytest.approx(
                _least_loss(live, top, stop), abs=1e-6)


def test_what_a_burst_costs_beyond_its_requests():
    """12 ms a burst + 5 ms a request, one burst that compiled: a request
    costs what the cheapest burst cost a request (6.5), the fixed part is
    the median of the rest."""
    seen = sched_mod._ObservedBursts()
    assert seen.fixed is None
    for k in (1, 2, 8, 4, 3):
        seen.note(k, (12.0 + 5.0 * k) / 1000.0)
    seen.note(2, 3.5)  # compiled
    per = (12.0 + 5.0 * 8) / 8
    rest = sorted([(12.0 + 5.0 * k) - per * k for k in (1, 2, 8, 4, 3)] + [3500.0 - per * 2])
    assert seen.fixed * 1000.0 == pytest.approx((rest[2] + rest[3]) / 2)
    assert 0.0 <= seen.fixed * 1000.0 <= 12.0


# ------------------------------------------------------------- the host's books


def _slots() -> dict:
    return {k: _C_DECODE_SLOTS.value(kind=k) for k in ("kept", "after_end", "dead_row")}


@pytest.fixture(scope="module")
def books_engine():
    eng = _engine()
    yield eng
    eng.close()


@pytest.mark.parametrize("budget,stream,windows", [
    (6, False, [(5, 1, "drain")]),              # one partial chunk
    (9, True, [(8, 1, "full")]),                # one whole chunk
    (12, True, [(8, 1, "full"), (3, 1, "drain")]),  # a stream: a chunk a window
    (20, False, [(19, 3, "drain")]),            # no stream: chunks of 8 + 8 + 3 in ONE window
    (25, False, [(24, 3, "full")]),
], ids=["partial", "whole", "streamed_two_windows", "three_chunks_one_window", "cap_of_three"])
def test_the_books_follow_the_steps_a_window_ran(books_engine, monkeypatch, budget, stream, windows):
    """One request alone (the first token is the prefill's): offsets, the
    block table, the slot and page counters, chunks_decoded and history
    all follow n, not a multiple of decode_chunk."""
    eng, sch = books_engine, books_engine.scheduler
    prompt = _prompt(3, 13)
    seen: list = []
    pages: list = []
    dispatch = sch._dispatch_window.__wrapped__
    monkeypatch.setattr(  # (a prefill counts its [1, bucket] chunk there too)
        sch.cache, "count_pages_written",
        lambda rows, chunk, calls=1: chunk == 1 and pages.append((rows, chunk, calls)))

    def spy(pending=0, chosen=None):
        before = int(sch._offsets[0])
        ok = dispatch(sch, pending, chosen)
        rec = sch._inflight[-1]
        seen.append((rec["n"], -(-rec["n"] // K), len(rec["toks"]), int(sch._offsets[0]) - before,
                     len(sch.cache.tables[0]) * sch.cache.block_size >= before + rec["n"]))
        return ok

    monkeypatch.setattr(sch, "_dispatch_window", spy)
    slots0, steps0 = _slots(), _H_WINDOW_STEPS.totals()
    visited0, live0 = _C_KV_PAGES_VISITED.total(), _C_KV_PAGES_LIVE.total()
    cuts0 = {c: _C_WINDOWS.value(cut=c) for c in ("full", "budget", "drain", "sync")}
    chunks0 = sch.stats.chunks
    req = sch.submit(eng._make_request(prompt, budget, 0.0, 0, 1.0, None, stream=stream))
    while not req.events.get(timeout=120).get("done"):
        pass
    monkeypatch.undo()
    n_all = sum(n for n, _, _ in windows)
    assert len(req.out_ids) == budget == n_all + 1
    assert [(n, W) for n, W, *_ in seen] == [(n, W) for n, W, _ in windows]
    assert all(calls == W and grew == n and covered for n, W, calls, grew, covered in seen)
    assert pages == [(1, 1, n) for n, _, _ in windows]
    grown = {k: v - slots0[k] for k, v in _slots().items()}
    assert grown == {"kept": n_all, "after_end": 0, "dead_row": 0}
    count, total = _H_WINDOW_STEPS.totals()
    assert (count - steps0[0], total - steps0[1]) == (len(windows), n_all)
    for c, was in cuts0.items():
        assert _C_WINDOWS.value(cut=c) - was == sum(1 for *_, cut in windows if cut == c), c
    tw = _C_KV_PAGES_VISITED.total() - visited0
    assert tw % n_all == 0 or len(windows) > 1  # a table's entries x the steps that read it
    assert _C_KV_PAGES_LIVE.total() - live0 > 0
    chunks = sum(W for _, W, _ in windows)
    assert req.chunks_decoded == chunks and sch.stats.chunks - chunks0 == chunks
    assert sch.stats.history[-1] == {"new_tokens": budget, "chunks": chunks}


# ------------------------------------------------------- the streamed closed loop


def _standing_queue(eng, requests: int) -> tuple[float, list]:
    """``requests`` streamed requests queued at once (outputs 16-96), so that
    someone waits for a row at every dispatch until the last are placed, as
    behind a closed loop of more callers than rows; nothing hangs on when a
    caller's thread runs. -> (kept / all decode slots, the requests)."""
    rng = np.random.default_rng(11)
    budgets = rng.integers(16, 97, size=requests).tolist()
    sch = eng.scheduler
    before = _slots()
    with sch._cond:  # an RLock: submit() re-enters it; the loop sees them all at once
        reqs = [sch.submit(eng._make_request(
            _prompt(i, 8 + i % 17), budgets[i], 0.0, 0, 1.0, None, stream=True))
            for i in range(requests)]
    done = []
    for req, budget in zip(reqs, budgets):
        events = []
        while not events or not events[-1].get("done"):
            events.append(req.events.get(timeout=300))
        done.append((req, budget, events))
    grown = {k: v - before[k] for k, v in _slots().items()}
    return grown["kept"] / sum(grown.values()), done


class _Pinned:
    """An observed cost that reads what the chip read (PERF.md section 6,
    PR 49: phi-3's step, turn and burst), whatever this CPU takes: the loop
    is real, the policy's decisions do not hang on the sandbox's load."""

    def __init__(self, seconds: float):
        self.value = self.fixed = seconds

    def note(self, *seen) -> None:
        pass


def test_a_streamed_standing_queue_keeps_more_of_its_slots(monkeypatch):
    """48 streamed requests on 16 rows, outputs 16-96 against a 32-step
    chunk: under the parent's rule (every window the cap) a row that ends
    stands dead to the window's end; the policy cuts windows, and nobody
    loses a token."""
    eng = _engine(max_batch=16, decode_chunk=32, max_seq_len=256)
    try:
        sch = eng.scheduler
        sch._step_s, sch._turn_s, sch._bursts = _Pinned(0.0142), _Pinned(0.0063), _Pinned(0.010)
        cuts0 = _C_WINDOWS.value(cut="budget")
        use, done = _standing_queue(eng, 48)
        assert _C_WINDOWS.value(cut="budget") - cuts0 >= 5
        for req, budget, events in done:
            assert req.finish == "length" and len(req.out_ids) == budget
            streamed = [t for ev in events[:-1] for t in ev["tokens"]]
            assert streamed == req.out_ids
            assert events[-1]["done"] and events[-1]["result"].token_ids == req.out_ids
            assert sum(1 for ev in events if ev.get("done")) == 1
        monkeypatch.setattr(sched_mod, "choose_window_steps",
                            lambda budgets, queued, cap, *costs: (cap, "full"))
        fixed, done_fixed = _standing_queue(eng, 48)
        assert [len(r.out_ids) for r, *_ in done_fixed] == [len(r.out_ids) for r, *_ in done]
        assert use > fixed + 0.05, (use, fixed)
    finally:
        eng.close()
