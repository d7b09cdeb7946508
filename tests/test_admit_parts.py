"""`sched.admit` from the inside (ISSUE 41): admit's parts on the phase clock,
the burst and its prefill programs counted where they are dispatched, the
decode slots a window keeps and wastes, and ONE root scope around the body of
every jit root of the serving path."""

from __future__ import annotations

import importlib.util
import re
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee2bee_tpu.engine import EngineConfig, InferenceEngine, paged
from bee2bee_tpu.metrics import get_registry
from bee2bee_tpu.tracing import PhaseClock, prog_scope

READERS = Path(__file__).resolve().parent.parent / "benchmark" / "readers"
PARTS = ("dispatch", "wait", "emit", "none")
KINDS = ("kept", "after_end", "dead_row")
ROWS, K = 4, 4
KW = dict(max_seq_len=128, dtype="float32", cache_dtype="float32", decode_chunk=K,
          max_batch=ROWS, prefill_buckets=(16, 32, 64), rng_seed=7)


def _prompt(seed: int, n: int) -> list[int]:
    return [1] + [int(t) for t in np.random.RandomState(seed).randint(3, 259, n - 1)]


def _value(name: str, **labels) -> float:
    return get_registry().get(name).value(**labels)


def _parts() -> dict[str, float]:
    return {p: _value("engine.admit_seconds", part=p) for p in PARTS}


def _slots() -> dict[str, float]:
    return {k: _value("engine.decode_slots", kind=k) for k in KINDS}


def _grew(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _engine(model: str = "tiny-llama", **over) -> InferenceEngine:
    return InferenceEngine(model, engine_config=EngineConfig(**{**KW, **over}))


def _submit_together(eng, specs, stream=True):
    """[(prompt, budget)] queued before the loop can pop the first."""
    sch = eng.scheduler
    with sch._cond:
        return [sch.submit(eng._make_request(p, n, 0.0, 0, 1.0, None, stream=stream))
                for p, n in specs]


def _drain(req, timeout=120.0) -> list[dict]:
    out = []
    while True:
        ev = req.events.get(timeout=timeout)
        out.append(ev)
        if ev.get("done"):
            return out


def _idle(eng):
    """The loop has nothing left: every window delivered, the thread asleep."""
    sch = eng.scheduler
    deadline = time.monotonic() + 30.0
    while (sch._undelivered or sch._inflight or sch.active) and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    eng.introspect.phases.flush()


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.generate(_prompt(9, 20), max_new_tokens=6)  # the shapes below compile here
    yield eng
    eng.close()


# ---------------------------------------------------------------- the clock


def test_phase_clock_books_a_phases_seconds_once_more_by_part():
    reg = get_registry()
    phases, parts = reg.counter("test.parts_phase_seconds"), reg.counter("test.parts_seconds")
    clock = PhaseClock("test", phases, parts={"outer": parts})
    with clock.phase("outer"):
        time.sleep(0.01)  # no part set: "none"
        clock.part("a")
        time.sleep(0.02)
        with clock.phase("inner"):  # pauses the phase AND its part
            time.sleep(0.03)
            clock.part("x")  # `inner` has no parts: labelled, booked nowhere
        time.sleep(0.01)
        clock.part("b")
        time.sleep(0.02)
    got = {p: parts.value(part=p) for p in ("none", "a", "b", "x")}
    assert got["none"] >= 0.01 and got["a"] >= 0.03 and got["b"] >= 0.02 and got["x"] == 0
    assert got["a"] < 0.03 + 0.03  # the nested phase's 30 ms are not a's
    assert sum(got.values()) == pytest.approx(phases.value(phase="outer"), rel=1e-9)
    assert phases.value(phase="inner") >= 0.03
    assert clock._open == [] and clock._part == []
    clock.part("late")  # outside any phase: nothing to label
    assert parts.value(part="late") == 0


def test_a_part_is_credited_up_to_a_scrape_and_ends_with_its_phase():
    reg = get_registry()
    phases, parts = reg.counter("test.flush_phase_seconds"), reg.counter("test.flush_parts_seconds")
    clock = PhaseClock("test", phases, parts={"admit": parts})
    entered, leave = threading.Event(), threading.Event()

    def blocked():
        with clock.phase("admit"):
            clock.part("wait")
            entered.set()
            leave.wait(5.0)

    worker = threading.Thread(target=blocked)
    worker.start()
    entered.wait(5.0)
    time.sleep(0.03)
    clock.flush()  # a scrape, from another thread
    assert parts.value(part="wait") >= 0.03
    leave.set()
    worker.join()
    with clock.phase("admit"):  # the next call starts with no part
        time.sleep(0.005)
    assert parts.value(part="none") >= 0.005
    assert (parts.value(part="wait") + parts.value(part="none")
            == pytest.approx(phases.value(phase="admit"), rel=1e-9))


# ---------------------------------------------------------------- admit's parts


def test_the_parts_sum_to_the_admit_phase_and_wait_needs_a_burst(engine, monkeypatch):
    """A closed loop of bursts: six callers on four rows, three requests each.
    Over the whole stretch the parts' growth is the admit phase's growth; call
    by call, `wait` and `emit` grow only where a burst was placed and `none`
    only where nobody was admitted."""
    sch = engine.scheduler
    _idle(engine)
    calls: list[tuple[bool, dict]] = []
    admit = sch._admit

    def recorded():
        before = _parts()
        placed = admit()
        calls.append((placed, _grew(_parts(), before)))
        return placed

    monkeypatch.setattr(sch, "_admit", recorded)
    phase0, parts0 = _value("engine.phase_seconds", phase="admit"), _parts()
    bursts0 = get_registry().get("engine.admit_burst_requests").totals()

    def caller(i):
        for j in range(3):
            engine.generate(_prompt(10 * i + j, 12 + 3 * i), max_new_tokens=5 + 2 * j)

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    _idle(engine)
    monkeypatch.undo()

    grown = _grew(_parts(), parts0)
    admit_s = _value("engine.phase_seconds", phase="admit") - phase0
    assert admit_s > 0 and sum(grown.values()) == pytest.approx(admit_s, rel=0.01)
    assert all(grown[p] > 0 for p in PARTS), grown
    placed_calls = [d for placed, d in calls if placed]
    empty_calls = [d for placed, d in calls if not placed]
    assert placed_calls and empty_calls
    for d in placed_calls:
        assert d["dispatch"] > 0 and d["wait"] > 0 and d["emit"] > 0
    for d in empty_calls:  # nobody popped here: its own time is "none"
        assert d["wait"] == 0 and d["emit"] == 0 and d["dispatch"] == 0 and d["none"] > 0
    # one observation a burst, its requests summed: 18 in all
    count, total = get_registry().get("engine.admit_burst_requests").totals()
    assert count - bursts0[0] == len(placed_calls) and total - bursts0[1] == 18


def test_a_call_that_pops_a_request_but_places_none_never_waits(engine):
    """A cancelled request is popped (dispatch's work) and answered; no burst,
    so no gather: `wait` and `emit` stay where they were."""
    sch = engine.scheduler
    _idle(engine)
    req = engine._make_request(_prompt(1, 10), 4, 0.0, 0, 1.0, None, stream=True)
    req.cancelled = True
    before = _parts()
    with sch._cond:  # queued without waking the loop: this thread runs the call
        sch._queue.append(req, tenant=req.tenant, cost=4.0)
    assert not sch._admit()
    grown = _grew(_parts(), before)
    assert grown["dispatch"] > 0 and grown["wait"] == 0 and grown["emit"] == 0
    assert _drain(req)[-1]["result"].finish_reason == "cancelled"


def test_process_and_compact_time_inside_admit_is_in_no_part(monkeypatch):
    """_admit delivers the last window (phase process) and resizes the bucket
    (phase compact) itself: both pause `admit`, so neither reaches a part."""
    eng = _engine()
    try:
        eng.generate(_prompt(9, 20), max_new_tokens=6)  # compile; the bucket is 1 again below
        sch = eng.scheduler
        sch._sticky_idle_s = 0.0
        _idle(eng)
        slept = {"process": 0.0, "compact": 0.0}
        deliver, resize = sch._deliver_row, sch.cache.resize

        def slow_deliver(entry):
            time.sleep(0.02)
            slept["process"] += 0.02
            return deliver(entry)

        def slow_resize(bsz):
            time.sleep(0.03)
            slept["compact"] += 0.03
            return resize(bsz)

        calls = []
        admit = sch._admit

        def recorded():
            before, s0, t0 = _parts(), dict(slept), time.perf_counter()
            placed = admit()
            calls.append((sum(_grew(_parts(), before).values()), time.perf_counter() - t0,
                          sum(_grew(slept, s0).values())))
            return placed

        monkeypatch.setattr(sch, "_deliver_row", slow_deliver)
        monkeypatch.setattr(sch.cache, "resize", slow_resize)
        monkeypatch.setattr(sch, "_admit", recorded)
        phases0 = {p: _value("engine.phase_seconds", phase=p) for p in ("process", "compact")}
        # two waves: the second is admitted while the first's windows are
        # delivered inside _admit
        reqs = _submit_together(eng, [(_prompt(i, 14), 6 + i) for i in range(6)])
        for r in reqs:
            _drain(r)
        _idle(eng)
        monkeypatch.undo()
        assert slept["compact"] >= 0.03 and slept["process"] >= 0.1
        for parts_s, wall, asleep in calls:
            assert parts_s <= wall - asleep + 0.002, calls
        assert any(asleep > 0 for _, _, asleep in calls)
        for p in slept:
            assert _value("engine.phase_seconds", phase=p) - phases0[p] >= slept[p]
    finally:
        eng.close()


def test_every_loop_second_has_a_phase(engine):
    """phases + fetch sum to the cycle: the turn's own lines are `turn`."""
    counter = get_registry().get("engine.phase_seconds")
    names = ("admit", "dispatch", "fetch", "settle", "process", "compact", "turn")
    _idle(engine)
    before = {p: counter.value(phase=p) for p in names}
    t0 = time.perf_counter()
    out = engine.generate(_prompt(4, 20), max_new_tokens=60, temperature=0.0)
    wall = time.perf_counter() - t0
    _idle(engine)
    spent = {p: counter.value(phase=p) - before[p] for p in names}
    assert out.new_tokens == 60 and spent["turn"] > 0
    assert sum(spent.values()) == pytest.approx(wall, rel=0.10), (spent, wall)
    assert spent["turn"] < 0.05 * wall  # glue, not work


def test_the_loop_with_nothing_to_do_is_named_on_a_capture(monkeypatch):
    """The one state of the scheduler thread that is no phase: asleep until a
    request arrives. A capture names it `sched.idle`, so a device gap that
    waits for the callers' next requests is not `unattributed`."""
    from contextlib import contextmanager

    import bee2bee_tpu.engine.scheduler as sched_mod

    entered: list[str] = []

    @contextmanager
    def recording(name):
        entered.append(name)
        yield

    monkeypatch.setattr(sched_mod, "annotate", recording)
    eng = _engine()
    try:
        eng.generate(_prompt(1, 10), max_new_tokens=4)
        _idle(eng)
        assert entered and set(entered) == {"sched.idle"}
        assert eng.introspect.phases._open == []  # idle is no phase: no second is booked
    finally:
        eng.close()


# ---------------------------------------------------------------- the burst's prefills


def _prefill_counts() -> dict:
    reg = get_registry()
    calls = reg.get("engine.prefill_calls")
    return {**{f"calls{b}": calls.value(bucket=str(b)) for b in (16, 32, 64)},
            "real": _value("engine.prefill_tokens", kind="real"),
            "pad": _value("engine.prefill_tokens", kind="pad")}


def test_prefill_programs_and_their_positions_are_counted_a_chunk(engine):
    _idle(engine)
    before = _prefill_counts()
    bursts0 = get_registry().get("engine.admit_burst_requests").totals()
    # three prompts in ONE burst: 10 -> bucket 16, 20 -> 32, 40 -> 64
    reqs = _submit_together(engine, [(_prompt(1, 10), 3), (_prompt(2, 20), 3), (_prompt(3, 40), 3)])
    for r in reqs:
        _drain(r)
    _idle(engine)
    assert _grew(_prefill_counts(), before) == {
        "calls16": 1, "calls32": 1, "calls64": 1, "real": 70, "pad": 6 + 12 + 24}
    count, total = get_registry().get("engine.admit_burst_requests").totals()
    assert (count - bursts0[0], total - bursts0[1]) == (1, 3)


def test_a_chunked_walk_and_the_import_rung_count_every_program():
    a, b = _engine(prefill_chunk=16), _engine()
    try:
        before = _prefill_counts()
        a.generate(_prompt(5, 40), max_new_tokens=3)  # 40 > 16: windows at 0, 16, 32
        assert _grew(_prefill_counts(), before) == {
            "calls16": 3, "calls32": 0, "calls64": 0, "real": 40, "pad": 8}
        # the re-prefill rung: a snapshot without its blocks is prefilled
        # again on the importer: prompt + accepted tokens but the last
        # (`cur`: the next forward writes its K/V)
        seen: list[int] = []
        gen = b.generate_stream(_prompt(6, 20), max_new_tokens=24)
        for ev in gen:
            seen.extend(ev.get("tokens") or [])
            if len(seen) >= 5:
                break
        (req,) = b.scheduler.live_requests()
        snap = b.scheduler.checkpoint(req)
        snap.pop("_kv", None)
        n = len(snap["ids"]) + len(snap["out"]) - 1
        assert 20 < n <= 32
        before = _prefill_counts()
        req2 = a.import_generation(dict(snap))
        _drain(req2)
        assert a.scheduler.stats.import_reprefills == 1
        assert _grew(_prefill_counts(), before) == {
            "calls16": 2, "calls32": 0, "calls64": 0, "real": n, "pad": 32 - n}
    finally:
        a.close()
        b.close()


def test_a_recurrent_models_prefill_tokens_are_its_scanned_tokens():
    eng = _engine("tiny-falcon-h1", kv_block_size=8)
    try:
        def tokens(name):
            return {k: _value(name, kind=k) for k in ("real", "pad")}

        s0, p0 = tokens("engine.ssm_scan_tokens"), tokens("engine.prefill_tokens")
        reqs = _submit_together(eng, [(_prompt(0, 21), 5), (_prompt(1, 9), 4)])
        for r in reqs:
            _drain(r)
        assert (_grew(tokens("engine.prefill_tokens"), p0)
                == _grew(tokens("engine.ssm_scan_tokens"), s0) == {"real": 30, "pad": 11 + 7})
    finally:
        eng.close()


# ---------------------------------------------------------------- decode slots


def _windows(sch, eng, monkeypatch) -> list[int]:
    """Slots of every decode window fetched and verify step run from here on."""
    sizes: list[int] = []
    fetch = sch._fetch_window

    def fetching(rec):
        toks = fetch(rec)
        sizes.append(toks.size)
        return toks

    monkeypatch.setattr(sch, "_fetch_window", fetching)
    if sch._spec is not None:
        verify = eng._spec_verify

        def verifying(params, cur, drafts, *args, **kwargs):
            sizes.append(drafts.shape[0] * (drafts.shape[1] + 1))
            return verify(params, cur, drafts, *args, **kwargs)

        monkeypatch.setattr(eng, "_spec_verify", verifying)
    return sizes


def _decode_tokens(reqs) -> int:
    """Tokens the decode windows put into outputs: all of them but each
    request's first, which its admission sampled."""
    return sum(max(0, len(r.out_ids) - 1) for r in reqs)


def test_decode_slots_sum_to_the_windows_and_kept_is_the_outputs(engine, monkeypatch):
    """Stop token, budget, cancel, overlapped windows with rows that retire,
    move and leave dead rows behind: the kinds sum to rows x steps of every
    fetched window, and `kept` is what the outputs hold."""
    sch = engine.scheduler
    rollout = engine.generate(_prompt(7, 18), max_new_tokens=12).token_ids
    _idle(engine)
    sizes = _windows(sch, engine, monkeypatch)
    before = _slots()
    mk = engine._make_request
    stopped = mk(_prompt(7, 18), 40, 0.0, 0, 1.0, [rollout[6]], stream=True)
    cancelled = mk(_prompt(8, 25), 60, 0.0, 0, 1.0, None, stream=True)
    reqs = [stopped, cancelled,
            mk(_prompt(2, 12), 7, 0.0, 0, 1.0, None, stream=False),   # a budget inside a window
            mk(_prompt(3, 30), 33, 0.0, 0, 1.0, None, stream=False),  # outlives the others: it moves
            mk(_prompt(4, 9), 21, 0.0, 0, 1.0, None, stream=False),
            mk(_prompt(5, 16), 2, 0.0, 0, 1.0, None, stream=False)]
    with sch._cond:
        for r in reqs:
            sch.submit(r)
    while True:  # the caller of `cancelled` goes away after its first tokens
        if not cancelled.events.get(timeout=60).get("done"):
            cancelled.cancelled = True
        else:
            break
    for r in reqs:
        if r is not cancelled:
            _drain(r)
    _idle(engine)
    monkeypatch.undo()
    grown = _grew(_slots(), before)
    assert stopped.finish == "stop" and stopped.out_ids == rollout[:rollout.index(rollout[6])]
    assert cancelled.finish == "cancelled"
    assert sum(grown.values()) == sum(sizes) and len(sizes) >= 3
    assert grown["kept"] == _decode_tokens(reqs)
    assert grown["after_end"] > 0 and grown["dead_row"] > 0


def test_a_row_that_moved_since_dispatch_is_after_end_and_an_empty_one_dead():
    """Four rows x 8 steps: row 0's request keeps 8, row 1's ends on a stop
    at its third token, row 2 was handed to another request since dispatch
    (its 8 slots are waste), row 3 had no request when the window launched."""
    from collections import deque

    from bee2bee_tpu.engine.introspect import GoodputMeter
    from bee2bee_tpu.engine.scheduler import BatchScheduler, Request

    def request():
        return Request(ids=[1, 2, 3], max_new_tokens=50, temperature=0.0, top_k=0,
                       top_p=1.0, stop={7}, eos=2, tokenizer=None)

    class Rows:
        _settle_row = BatchScheduler._settle_row
        _settle_window = BatchScheduler._settle_window.__wrapped__  # no phase clock
        _meter = GoodputMeter(None, 1.0)

        def __init__(self, rows):
            self._rows, self._undelivered = list(rows), deque()

        def _vacate(self, b, req):
            self._rows[b] = None

    a, b, moved, newcomer = request(), request(), request(), request()
    toks = np.full((4, 8), 11, np.int32)
    toks[1, 2] = 7
    before = _slots()
    Rows([a, b, newcomer, None])._settle_window(
        {"rows": [(0, a), (1, b), (2, moved)], "toks": [None] * 2}, toks)
    assert _grew(_slots(), before) == {"kept": 8 + 2, "after_end": 6 + 8, "dead_row": 8}
    assert len(a.out_ids) == 8 and len(b.out_ids) == 2 and not moved.out_ids


def test_a_verify_step_books_its_width_and_keeps_what_was_accepted(monkeypatch):
    eng = _engine(spec_tokens=6)
    try:
        eng.generate([5, 6, 7, 8, 9] * 3 + [5, 6, 7], max_new_tokens=8)
        sch = eng.scheduler
        _idle(eng)
        sizes = _windows(sch, eng, monkeypatch)
        steps0, before = sch.stats.spec_steps, _slots()
        reqs = _submit_together(eng, [([5, 6, 7, 8, 9] * 3 + [5, 6, 7], 30),
                                      ([11, 12, 13] * 5, 18)], stream=False)
        for r in reqs:
            _drain(r)
        _idle(eng)
        monkeypatch.undo()
        grown = _grew(_slots(), before)
        assert sch.stats.spec_steps > steps0 and sch.stats.spec_accepted > 0
        assert sum(grown.values()) == sum(sizes)
        assert grown["kept"] == _decode_tokens(reqs)
    finally:
        eng.close()


# ---------------------------------------------------------------- program roots


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(f"bench_reader_{name}", READERS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PROG = re.compile(_reader("prog_scopes").PATTERN)


def _op_names(lowered, compiled: bool = True) -> list[str]:
    """The `op_name` metadata of the COMPILED program's instructions that
    carry a name stack (`jit(f)/scope/.../primitive`): what a capture records
    as an op's `tf_op`, and what scope_reduce matches. (The compiler composes
    it through `closed_call`s; a lowering's own locations stop at them, and
    are read only for a helper so small that it compiles to a constant.)"""
    if compiled:
        return re.findall(r'op_name="(jit\([^)"]*\)/[^"]*)"', lowered.compile().as_text())
    return re.findall(r'"(jit\([^)"]*\)/[^"]*)"', lowered.as_text(debug_info=True))


def _lowering(store: dict, name: str, jitted, call):
    """`call` as it was, its first use lowered (before the call: its
    arguments are donated) and its op names kept under ``name``."""
    def run(*args, **kwargs):
        if name not in store:
            store[name] = _op_names(jitted.lower(*args, **kwargs))
        return call(*args, **kwargs)
    return run


def _roots_of(eng, monkeypatch, prompts, **gen) -> dict[str, list[str]]:
    sch, names = eng.scheduler, {}
    monkeypatch.setattr(eng, "_prefill", _lowering(
        names, "prog.prefill", eng._prefill.__wrapped__, eng._prefill))
    monkeypatch.setattr(sch, "_decode", _lowering(
        names, "prog.decode", sch._decode.__wrapped__, sch._decode))
    monkeypatch.setattr(sch, "_sample_first", _lowering(
        names, "prog.sample", sch._sample_first, sch._sample_first))
    monkeypatch.setattr(eng, "_spec_verify", _lowering(
        names, "prog.verify", eng._spec_verify.__wrapped__, eng._spec_verify))
    for p in prompts:
        eng.generate(p, max_new_tokens=16, **gen)
    monkeypatch.undo()
    return names


def _assert_rooted(names: dict[str, list[str]], want: set[str]):
    assert set(names) == want
    for root, ops in names.items():
        assert ops
        for op in ops:
            m = PROG.search(op)
            assert m and m.group(1) == root and re.match(r"jit\([^)]*\)/prog\.", op), (root, op)


@pytest.mark.parametrize("over,gen,want", [
    ({}, {}, {"prog.prefill", "prog.decode", "prog.sample"}),
    ({"spec_tokens": 6}, {}, {"prog.prefill", "prog.decode", "prog.sample", "prog.verify"}),
    ({}, {"repetition_penalty": 1.3}, {"prog.prefill", "prog.decode", "prog.sample"}),
], ids=["plain", "spec", "penalised"])
def test_every_serving_root_lowers_under_its_program_scope(monkeypatch, over, gen, want):
    eng = _engine(**over)
    try:
        names = _roots_of(eng, monkeypatch, [[5, 6, 7, 8, 9] * 3 + [5, 6, 7]], **gen)
        _assert_rooted(names, want)
        # the sampler INSIDE a decode window stays the decode program's
        assert any("/while/" in op for op in names["prog.decode"])
    finally:
        eng.close()


@pytest.mark.parametrize("model,reader,inner", [
    ("tiny-falcon-h1", "scope_common", {"ssm.in_proj", "ssm.conv", "ssm.step", "ssm.out_proj"}),
    ("tiny-joyai", "joyai_scopes", {"moe.router", "moe.experts", "mla.q_proj", "mla.read"}),
], ids=["falcon_h1", "joyai"])
def test_the_inner_scopes_still_match_their_readers_under_a_root(monkeypatch, model, reader, inner):
    """scope_reduce books an op under the FIRST match of a reader's pattern
    in `jit(f)/prog.decode/while/body/ssm.step/...`: the root collides with
    none of them, and the roots' own pattern finds the root, not the inner."""
    pattern = re.compile(_reader(reader).PATTERN)
    eng = _engine(model, kv_block_size=8)
    try:
        names = _roots_of(eng, monkeypatch, [_prompt(0, 21)])
        _assert_rooted(names, {"prog.prefill", "prog.decode", "prog.sample"})
        found = {m.group(1) for op in names["prog.decode"] if (m := pattern.search(op))}
        assert inner <= found, found
        assert not any(pattern.search(op) for op in names["prog.sample"])
    finally:
        eng.close()


def test_the_pool_and_count_helpers_lower_under_prog_pool(engine):
    sch = engine.scheduler
    pool = {"kv": jnp.zeros((1, 4, 2, 1, 2, 2))}  # [L, NB, 2, Hkv, BS, hd]
    state = {"ssm": jnp.zeros((1, 2, 3))}
    i32 = np.int32
    counts = jnp.zeros((2, 2, sch._vocab), jnp.int32)
    lowered = {
        "copy_slot": paged._copy_slot.lower(pool, i32(0), i32(1)),
        "gather_blocks": paged._gather_blocks.lower(2, pool, np.zeros((2,), i32)),
        "scatter_blocks": paged._scatter_blocks.lower(
            pool, {n: jnp.zeros((1, 1, 2, 2, 2)) for n in "kv"}, np.zeros((2,), i32)),
        "reset_scales": paged._reset_scales.lower(
            {"kv_scale": jnp.zeros((1, 4, 2, 1))}, np.zeros((2,), i32)),
        "state_insert": paged._state_insert.lower(state, {"ssm": jnp.zeros((1, 1, 3))}, i32(1)),
        "state_shrink": paged._state_shrink.lower(state, 1),
        "counts_zeros": sch._counts_zeros.lower(2),
        "counts_grow": sch._counts_grow.lower(counts, counts[:1]),
        "counts_insert": sch._counts_insert.lower(counts, counts[:1], i32(1)),
        "counts_move": sch._counts_move.lower(counts, i32(1), i32(0)),
        "counts_bump": sch._counts_bump.lower(counts, i32(1), i32(5)),
        "counts_shrink": sch._counts_shrink.lower(counts, 1),
    }
    for name, low in lowered.items():
        ops = _op_names(low, compiled=False)
        assert ops and all(PROG.search(op).group(1) == "prog.pool" for op in ops), (name, ops)
    key = _op_names(engine._split_key.lower(jax.random.key(0)), compiled=False)
    assert key and all(PROG.search(op).group(1) == "prog.sample" for op in key)


def test_a_root_scope_changes_no_program_text():
    """Metadata only: with and without the scope the lowered program is the
    same text, and the jit root keeps its function's name."""
    def body(x, y=None):
        return jnp.tanh(x) @ x.T + (0 if y is None else y)

    scoped = prog_scope("prog.decode")(body)
    x = jnp.ones((4, 4))
    assert scoped.__name__ == "body"
    assert jax.jit(scoped).lower(x).as_text() == jax.jit(body).lower(x).as_text()
    assert jax.jit(scoped, donate_argnames=("y",)).lower(x, y=x).as_text() == \
        jax.jit(body, donate_argnames=("y",)).lower(x, y=x).as_text()
