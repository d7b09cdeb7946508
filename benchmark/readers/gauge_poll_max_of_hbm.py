"""Largest value, over the traced run's 1 s polls, of one gauge of bytes as a
share (%) of the device's memory (``peaks.json``: ``hbm_bytes`` of the run's
device kind). None where the server prints no such gauge, and on the CPU
backend (a rehearsal), which has no device memory to take a share of."""

from promtext import total


def read(ctx, params):
    values = [v for v in (total(sample, params["gauge"]) for _, sample in ctx["polls"])
              if v is not None]
    if not values or max(values) <= 0 or ctx["device"].get("platform") == "cpu":
        return None
    peak = ctx["peaks"].get(ctx["device"]["kind"])
    if peak is None:
        raise KeyError(f"no peaks for device kind {ctx['device']['kind']!r} in peaks.json")
    return 100.0 * max(values) / float(peak["hbm_bytes"])
