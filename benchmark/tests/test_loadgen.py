import json
import random
from pathlib import Path

import loadgen
import pytest

TRAFFIC = Path(loadgen.__file__).resolve().parent / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_schedule_and_every_seed_the_same_sizes(mix_name):
    mix = json.loads((TRAFFIC / f"{mix_name}.json").read_text())
    a, b, c = (loadgen.request_set(mix, s) for s in (3000000001, 3000000001, 7))
    assert [(s.prompt_tokens, s.max_new, s.prompt) for s in a] == \
           [(s.prompt_tokens, s.max_new, s.prompt) for s in b]
    assert [s.prompt for s in a] != [s.prompt for s in c]
    pairs = lambda xs: sorted((s.prompt_tokens, s.max_new) for s in xs)  # noqa: E731
    assert pairs(a) == pairs(c)  # one pairing, one set of work, whatever the seed
    if len(a) % 8 == 0:  # every aligned block of 8 holds one request of each eighth
        by_out = sorted(s.max_new for s in a)
        eighth = len(a) // 8
        for k in range(0, len(a), 8):
            ranks = sorted(by_out.index(s.max_new) // eighth for s in a[k:k + 8])
            assert len(set(s.max_new for s in a)) == 1 or ranks[0] == 0 and ranks[-1] == 7
    lo, hi = ((mix["prompt_tokens"].get(k, mix["prompt_tokens"].get("value")) for k in ("min", "max")))
    assert all(lo <= s.prompt_tokens <= hi for s in a)
    # the byte tokenizer adds BOS: the server counts exactly prompt_tokens
    assert all(len(s.prompt.encode()) + 1 == s.prompt_tokens and s.prompt.isascii() for s in a)


def test_quantile_lengths_follow_the_distribution():
    xs = loadgen.quantile_lengths({"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 32, "max": 384}, 480)
    assert min(xs) == 32 and max(xs) == 384 and sorted(xs)[240] in (95, 96, 97)
    assert loadgen.quantile_lengths({"dist": "uniform", "min": 10, "max": 20}, 2) == [12, 18]
    assert loadgen.quantile_lengths({"dist": "fixed", "value": 64}, 3) == [64, 64, 64]


@pytest.mark.parametrize("kind,cv", [("poisson", 1.0), ("gamma", 2.0)])
def test_arrivals_keep_the_rate_and_the_burstiness(kind, cv):
    mix = {"rate_per_s": 50.0, "arrivals": kind, "cv": cv}
    a, b = loadgen.arrival_times(mix, 5, 400.0), loadgen.arrival_times(mix, 5, 400.0)
    assert a == b and a != loadgen.arrival_times(mix, 6, 400.0)
    gaps = [y - x for x, y in zip(a, a[1:])]
    mean = sum(gaps) / len(gaps)
    sd = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5
    assert mean == pytest.approx(1 / 50.0, rel=0.05) and sd / mean == pytest.approx(cv, rel=0.1)


def _rec(t_ref, events, t_end, tokens=None, error=None, phase="mix"):
    spec = loadgen.Spec(8, 8, "x" * 7, phase)
    return loadgen.Record(spec, t_ref, t_ref, events, t_end, tokens, error)


def test_summarize_counts_what_the_window_saw():
    recs = [
        _rec(10.5, [(11.0, "a" * 32), (12.0, "b" * 32)], 12.1, tokens=64),  # inside: counted
        _rec(9.0, [(9.5, "a" * 10), (10.5, "b" * 10)], 10.6, tokens=40),    # sent before: tokens only
        _rec(13.0, [(14.0, "a" * 32)], 20.0, error="cut"),                  # cut by the end: ttft + tokens
        _rec(11.0, [], 11.2, error="status 503: shed"),                      # failed
        _rec(11.0, [(11.5, "p")], 11.6, tokens=1, phase="probe"),            # not the mix
    ]
    s = loadgen.summarize(recs, 10.0, 15.0)
    assert (s["attempted"], s["failed"]) == (2, 1)
    assert sorted(s["ttft_ms"]) == pytest.approx([500.0, 1000.0])
    assert s["gap_ms"] == pytest.approx([1000.0])
    assert s["request_ms"] == pytest.approx([1600.0])  # sent and ended inside, and ended well
    # tokens count where they were GENERATED: since the previous burst (any stream's
    # event >= 50 ms earlier), never before the request was sent
    #   rec 1: 32 over (10.5, 11.0] + 32 over (11.5, 12.0] (burst 11.5 is the failed probe's)
    #   rec 2: 20 over (9.0, 9.5] outside; 20 over (9.5, 10.5]: half inside
    #   rec 3: 32 chars of the cut one over (13.0 sent, 14.0]
    assert s["tokens"] == pytest.approx(64 + 10 + 32)
    e = {k: loadgen.percentile(s["ttft_ms"], p) for k, p in (("p50", 50), ("p90", 90))}
    assert e["p50"] == pytest.approx(750.0) and e["p90"] == pytest.approx(950.0)
    # open loop: a request due in the window and cut at the drain limit is failed
    assert loadgen.summarize(recs, 10.0, 15.0, "open")["failed"] == 2


def _lockstep(rows, period, t_cut, per_request=2):
    """``rows`` rows in lockstep: every ``period`` seconds each live row's
    event of 32 tokens leaves in one burst; a request is ``per_request``
    chunks and its row takes the next one at once. Records as the client holds
    them when the callers are cut at ``t_cut``."""
    recs = []
    for _ in range(rows):
        k = 0
        while k * period < t_cut:
            sent = k * period
            times = [(k + j + 1) * period for j in range(per_request)]
            got = [(t, "x" * 32) for t in times if t <= t_cut]
            done = len(got) == per_request
            recs.append(_rec(sent, got, times[-1] if done else t_cut,
                             tokens=32 * per_request if done else None,
                             error=None if done else "cut"))
            k += per_request
    return recs


@pytest.mark.parametrize("t0", [100.0, 101.7, 103.3])
def test_tok_s_follows_the_true_rate_when_the_chunk_period_moves(t0):
    """The reviewer's sweep (REVIEW.md, PR 23): 16 lockstep rows, chunk period
    4.4 .. 4.7 s, a 51 s window at any phase. With the records run on to the
    burst after t1 the rate is the true one to 1 %; cut AT t1 the stretch
    across the window's end is lost and the rate sits on whole bursts."""
    t1 = t0 + 51.0
    worst_cut = 0.0
    for step in range(7):
        period = 4.4 + 0.05 * step
        true = 16 * 32 / period
        burst_after_t1 = (int(t1 / period) + 1) * period
        s = loadgen.summarize(_lockstep(16, period, burst_after_t1 + 0.1), t0, t1)
        assert s["tokens"] / s["window_s"] == pytest.approx(true, rel=0.01)
        cut = loadgen.summarize(_lockstep(16, period, t1), t0, t1)
        worst_cut = max(worst_cut, abs(cut["tokens"] / cut["window_s"] / true - 1))
    assert worst_cut > 0.03  # what cutting at t1 cost: the fault this test guards


def test_drain_waits_for_the_burst_that_was_in_the_making():
    import asyncio
    import time

    mix = json.loads((Path(__file__).parent / "fixtures" / "tiny-closed.json").read_text())

    async def scene(limit):
        load = loadgen.Load("http://x", "m", dict(mix, drain_limit_s=limit), 1)
        t1 = time.monotonic()
        streaming = _rec(t1 - 2.0, [(t1 - 1.0, "a" * 32)], None)   # owed one more event
        first_chunk = _rec(t1 - 0.5, [], None)                      # no event yet
        ended = _rec(t1 - 3.0, [(t1 - 1.0, "a" * 32)], t1 - 0.9, tokens=32)
        load.records += [streaming, first_chunk, ended]

        async def burst():
            await asyncio.sleep(0.2)
            now = time.monotonic()
            streaming.events.append((now, "b" * 32))
            first_chunk.events.append((now, "c" * 32))

        task = asyncio.ensure_future(burst())
        await load.drain(t1)
        seen = time.monotonic() - t1, len(streaming.events), len(first_chunk.events)
        await task
        return seen

    took, a, b = asyncio.run(scene(5.0))
    assert 0.2 <= took < 1.0 and (a, b) == (2, 1)  # returned once the burst was in
    took, a, b = asyncio.run(scene(0.1))
    assert took < 0.2 and a == 1                    # bounded by the mix's drain limit
