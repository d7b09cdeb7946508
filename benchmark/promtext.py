"""Prometheus text exposition -> numbers, and deltas between two scrapes.

The server's ``GET /metrics`` is the only source: counters and the
``_sum`` / ``_count`` of histograms are exact, so a window MEAN taken from
their deltas is sound. The histograms' buckets grow by a factor of two, so a
percentile read from them is good to a factor of two: nothing here reads one.
"""

from __future__ import annotations

Sample = dict[str, float]  # 'name{labels}' -> value, labels as the server printed them


def parse(text: str) -> Sample:
    """Every sample line of one scrape, keyed by name and label set."""
    out: Sample = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            continue
    return out


def total(sample: Sample, name: str) -> float | None:
    """Sum of a metric over its label sets; None when the scrape has none."""
    vals = [v for k, v in sample.items() if k == name or k.startswith(name + "{")]
    return sum(vals) if vals else None


def delta(before: Sample, after: Sample, name: str) -> float | None:
    """after - before of a counter summed over its label sets. A series that
    first appears in ``after`` counts from zero."""
    b, a = total(before, name), total(after, name)
    if a is None:
        return None
    return a - (b or 0.0)


def delta_mean(before: Sample, after: Sample, name: str) -> float | None:
    """Mean of the observations a histogram took between two scrapes:
    delta(_sum) / delta(_count). None when it took none."""
    n = delta(before, after, name + "_count")
    s = delta(before, after, name + "_sum")
    if not n or s is None:
        return None
    return s / n
