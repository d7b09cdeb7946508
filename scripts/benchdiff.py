#!/usr/bin/env python
"""benchdiff: the perf-regression gate over BENCH_*.json trajectories.

ROADMAP bench hygiene made every bench artifact stamp its resolved
platform (top-level and per-rung) precisely so runs could be compared
honestly — this tool is the comparator:

    python scripts/benchdiff.py BENCH_r05.json BENCH_r06.json
    python scripts/benchdiff.py BENCH_r0*.json --threshold 0.10
    python scripts/benchdiff.py BENCH_spec_model_r01.json \\
        --live http://127.0.0.1:8080 --series decode_tok_s --window 600
    python scripts/benchdiff.py --self-check

- Diffs two or more artifacts **rung by rung**: every throughput-class
  numeric leaf under ``extras`` (tok/s, acceptance-weighted tok/s,
  sessions-at-capacity, the headline ``value``) becomes a trajectory row.
- **Platform-stamp aware**: a CPU-fallback run is NEVER silently compared
  against a TPU run. A top-level platform mismatch between consecutive
  artifacts refuses outright (exit 2) unless ``--allow-cross-platform``;
  a per-rung stamp mismatch skips that rung's gate and says so in the
  table.
- Exits nonzero (1) when any watched metric in the newest artifact
  regresses more than ``--threshold`` (default 15%) against the previous
  same-platform artifact — the CI gate docs/PERF.md documents.
- ``--self-check`` runs the built-in synthetic suite (regression catch +
  cross-platform refusal) — wired into scripts/lint.sh (SKIP_BENCHDIFF=1
  to skip).
- ``--live URL`` (ISSUE 20) gates a running node's retained history
  against ONE recorded artifact: the window mean of an observatory
  series from ``GET /metrics/history`` is compared to the artifact's
  headline ``value``, under the same platform-stamp refusal — live
  production telemetry as a regression gate, no bench re-run.

Artifacts may be raw bench.py output or the driver wrapper shape
(``{"parsed": {...}}``); ``schema_version`` (bench.py stamps 2+) guards
future layout changes — unknown majors refuse rather than misread.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

# numeric leaves under extras that constitute the watched perf surface —
# higher is better for every one of them. (The introspect stamps'
# trailing-window MFU/goodput are live readings, not rung measurements —
# their subtree is skipped below, so no pattern watches them.)
_WATCH_KEY_RE = re.compile(
    r"(tok_per_s|tokens_per_s|tok_s$|acceptance$|sessions_at_capacity"
    r"|^mfu$)"
)
# context keys that are measurements but not perf gates (counts, sizes)
_SKIP_SUBTREES = ("telemetry", "introspect")

KNOWN_SCHEMA_MAJOR = 2


class CrossPlatform(RuntimeError):
    pass


def load_artifact(path: str | Path) -> dict:
    obj = json.loads(Path(path).read_text())
    if isinstance(obj, dict) and isinstance(obj.get("parsed"), dict):
        obj = obj["parsed"]  # driver wrapper shape
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: not a bench artifact object")
    sv = obj.get("schema_version")
    if sv is not None and int(sv) > KNOWN_SCHEMA_MAJOR:
        raise ValueError(
            f"{path}: schema_version {sv} is newer than this benchdiff "
            f"understands ({KNOWN_SCHEMA_MAJOR}); refusing to misread it"
        )
    return obj


def artifact_platform(obj: dict) -> str:
    return str(obj.get("platform") or "unknown")


def _rung_platform(rung: dict, default: str) -> str:
    if isinstance(rung, dict) and rung.get("platform"):
        return str(rung["platform"])
    return default


def collect_metrics(obj: dict) -> dict[str, tuple[float, str]]:
    """{metric_path: (value, platform)} for every watched numeric leaf.
    The headline rides as ``value`` under the top-level platform; rungs
    carry their own stamp when bench.py recorded one."""
    top_platform = artifact_platform(obj)
    out: dict[str, tuple[float, str]] = {}
    if isinstance(obj.get("value"), (int, float)):
        # the headline metric NAME matters: bench.py renames a degraded
        # headline, so cross-name comparisons drop out naturally
        out[f"value[{obj.get('metric', 'headline')}]"] = (
            float(obj["value"]), top_platform
        )

    def walk(node, path: str, platform: str):
        if not isinstance(node, dict):
            return
        platform = _rung_platform(node, platform)
        for k, v in node.items():
            if k in _SKIP_SUBTREES:
                continue
            p = f"{path}.{k}" if path else k
            if isinstance(v, dict):
                walk(v, p, platform)
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                if _WATCH_KEY_RE.search(k):
                    out[p] = (float(v), platform)

    walk(obj.get("extras") or {}, "", top_platform)
    return out


def diff(
    paths: list[str],
    threshold: float = 0.15,
    allow_cross_platform: bool = False,
    out=print,
) -> int:
    """Trajectory table + regression gate over artifacts OLDEST FIRST.
    Returns the exit code (0 ok / 1 regression / 2 refused)."""
    arts = []
    for p in paths:
        try:
            arts.append((p, load_artifact(p)))
        except (OSError, ValueError) as e:
            out(f"benchdiff: {e}")
            return 2
    if len(arts) < 2:
        out("benchdiff: need at least two artifacts to diff")
        return 2

    # top-level platform contract between CONSECUTIVE artifacts: refuse a
    # silent cross-platform trajectory (the r03-r05 failure mode)
    for (pa, a), (pb, b) in zip(arts, arts[1:]):
        plat_a, plat_b = artifact_platform(a), artifact_platform(b)
        if plat_a != plat_b and not allow_cross_platform:
            out(
                f"benchdiff: REFUSING to compare {pa} [{plat_a}"
                f"{', fallback' if a.get('platform_fallback') else ''}] "
                f"against {pb} [{plat_b}"
                f"{', fallback' if b.get('platform_fallback') else ''}] — "
                "different platforms measure different hardware. Re-run on "
                "matching hardware or pass --allow-cross-platform to "
                "compare anyway (loudly)."
            )
            return 2

    per_file = [(p, collect_metrics(a)) for p, a in arts]
    names = sorted({m for _, ms in per_file for m in ms})
    if not names:
        out("benchdiff: no watched metrics found in any artifact")
        return 2

    headers = [Path(p).name for p, _ in per_file]
    out("metric | " + " | ".join(headers) + " | last Δ")
    regressions: list[str] = []
    for name in names:
        cells = []
        for _, ms in per_file:
            v = ms.get(name)
            cells.append("-" if v is None else f"{v[0]:g}")
        delta = ""
        prev, new = per_file[-2][1].get(name), per_file[-1][1].get(name)
        if prev is not None and new is not None:
            plat_note = ""
            if prev[1] != new[1]:
                if not allow_cross_platform:
                    out(f"{name} | " + " | ".join(cells)
                        + f" | skipped ({prev[1]} vs {new[1]})")
                    continue
                # the flag's contract: compared anyway, but LOUDLY — the
                # row must never read like a same-hardware delta
                plat_note = f"  [{prev[1]} vs {new[1]}]"
            if prev[0] > 0:
                change = (new[0] - prev[0]) / prev[0]
                delta = f"{change * 100:+.1f}%{plat_note}"
                if change < -threshold and prev[1] == new[1]:
                    delta += "  << REGRESSION"
                    regressions.append(
                        f"{name}: {prev[0]:g} -> {new[0]:g} "
                        f"({change * 100:+.1f}%, threshold "
                        f"-{threshold * 100:.0f}%)"
                    )
        out(f"{name} | " + " | ".join(cells) + f" | {delta}")

    if regressions:
        out("")
        out(f"benchdiff: {len(regressions)} regression(s) past the "
            f"{threshold * 100:.0f}% threshold:")
        for r in regressions:
            out(f"  - {r}")
        return 1
    out("")
    out("benchdiff: ok (no watched metric regressed past "
        f"{threshold * 100:.0f}%)")
    return 0


# ------------------------------------------------------------- live mode


def fetch_history(url: str, series: str, window_s: float) -> dict:
    """GET the node's raw history window (stdlib only — this script must
    run on an operator box with no repo deps installed)."""
    import urllib.parse
    import urllib.request

    q = urllib.parse.urlencode(
        {"series": series, "window": str(window_s), "format": "raw"}
    )
    req = f"{url.rstrip('/')}/metrics/history?{q}"
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read().decode("utf-8"))


def compare_live(
    baseline: dict,
    history: dict,
    series: str,
    threshold: float = 0.15,
    allow_cross_platform: bool = False,
    min_points: int = 3,
    out=print,
) -> int:
    """Gate a live /metrics/history payload against one recorded
    artifact's headline value. Returns 0 ok / 1 regression / 2 refused —
    the diff() exit contract. Pure so --self-check can exercise it
    without a server."""
    base_plat = artifact_platform(baseline)
    live_plat = str(history.get("platform") or "unknown")
    if base_plat != live_plat and not allow_cross_platform:
        out(
            f"benchdiff: REFUSING to gate live [{live_plat}] telemetry "
            f"against a [{base_plat}] artifact — different platforms "
            "measure different hardware (an 'unknown' live stamp means "
            "the node never loaded an accelerator runtime). Pass "
            "--allow-cross-platform to compare anyway (loudly)."
        )
        return 2
    value = baseline.get("value")
    if not isinstance(value, (int, float)) or value <= 0:
        out("benchdiff: baseline artifact has no positive headline value")
        return 2
    points = (history.get("series") or {}).get(series) or []
    vals = []
    for p in points:
        try:
            vals.append(float(p[1]))
        except (TypeError, ValueError, IndexError):
            continue
    if len(vals) < min_points:
        out(
            f"benchdiff: only {len(vals)} live point(s) of {series!r} "
            f"retained (need {min_points}) — is the observatory sampling?"
        )
        return 2
    mean = sum(vals) / len(vals)
    change = (mean - float(value)) / float(value)
    plat_note = (
        f"  [{base_plat} vs {live_plat}]" if base_plat != live_plat else ""
    )
    out(
        f"{baseline.get('metric', 'value')} -> live {series} | "
        f"{value:g} | {mean:g} (n={len(vals)}) | "
        f"{change * 100:+.1f}%{plat_note}"
    )
    if change < -threshold and base_plat == live_plat:
        out(
            f"benchdiff: live {series} window mean {mean:g} regressed "
            f"{change * 100:+.1f}% against {value:g} "
            f"(threshold -{threshold * 100:.0f}%)"
        )
        return 1
    out(f"benchdiff: ok (live window within {threshold * 100:.0f}%)")
    return 0


def live(
    paths: list[str],
    url: str,
    series: str,
    window_s: float,
    threshold: float,
    allow_cross_platform: bool,
    out=print,
) -> int:
    if len(paths) != 1:
        out("benchdiff: --live gates against exactly one recorded artifact")
        return 2
    try:
        baseline = load_artifact(paths[0])
    except (OSError, ValueError) as e:
        out(f"benchdiff: {e}")
        return 2
    try:
        history = fetch_history(url, series, window_s)
    except Exception as e:  # noqa: BLE001 — operator-facing refusal
        out(f"benchdiff: could not fetch {url}/metrics/history: {e}")
        return 2
    return compare_live(
        baseline, history, series,
        threshold=threshold, allow_cross_platform=allow_cross_platform,
        out=out,
    )


# ------------------------------------------------------------- self-check


def _self_check() -> int:
    """Synthetic contract suite for the lint.sh gate: the regression gate
    trips, an improvement passes, and cross-platform comparison refuses
    without the explicit flag."""
    import tempfile

    def art(value, tok, platform, fallback=False):
        return {
            "metric": "serve_tokens_per_sec_x", "value": value,
            "unit": "tok/s", "platform": platform,
            "platform_fallback": fallback, "schema_version": 2,
            "extras": {
                "rung_a": {"platform": platform, "tok_per_s": tok,
                           "nested": {"spec_acceptance": 0.9}},
            },
        }

    failures = []
    quiet = lambda *_a, **_k: None
    with tempfile.TemporaryDirectory() as d:

        def write(name, obj):
            p = Path(d) / name
            p.write_text(json.dumps(obj))
            return str(p)

        base = write("BENCH_a.json", art(100.0, 50.0, "cpu"))
        regressed = write("BENCH_b.json", art(95.0, 30.0, "cpu"))
        improved = write("BENCH_c.json", art(110.0, 60.0, "cpu"))
        tpu = write("BENCH_d.json", art(900.0, 400.0, "tpu"))
        fallback = write("BENCH_e.json", art(99.0, 49.0, "cpu", fallback=True))

        if diff([base, regressed], out=quiet) != 1:
            failures.append("regressed rung did not exit 1")
        if diff([base, improved], out=quiet) != 0:
            failures.append("improvement did not exit 0")
        if diff([base, tpu], out=quiet) != 2:
            failures.append("cross-platform comparison was not refused")
        lines: list[str] = []
        if diff([base, tpu], allow_cross_platform=True, out=lines.append) == 2:
            failures.append("--allow-cross-platform still refused")
        if not any("[cpu vs tpu]" in l for l in lines):
            # the flag compares LOUDLY: every cross-platform row carries
            # the platform pair, never a bare same-hardware-looking delta
            failures.append("cross-platform rows lost the platform marker")
        if diff([base, fallback], out=quiet) != 0:
            # fallback is the same hardware class; the flag is REPORTED,
            # never a refusal by itself
            failures.append("cpu-fallback vs cpu refused or regressed")
        if diff([base], out=quiet) != 2:
            failures.append("single artifact did not exit 2")
        newer = art(100.0, 50.0, "cpu")
        newer["schema_version"] = 99
        unread = write("BENCH_f.json", newer)
        if diff([base, unread], out=quiet) != 2:
            failures.append("unknown schema_version was not refused")

        # live mode (compare_live is pure — no server needed): the same
        # ok / regression / cross-platform-refusal contract over a
        # /metrics/history payload
        def hist(vals, platform="cpu"):
            return {
                "platform": platform, "encoding": "raw",
                "series": {"decode_tok_s": [[float(i), v]
                                            for i, v in enumerate(vals)]},
            }

        b = art(100.0, 50.0, "cpu")
        if compare_live(b, hist([99.0, 101.0, 100.0]), "decode_tok_s",
                        out=quiet) != 0:
            failures.append("healthy live window did not exit 0")
        if compare_live(b, hist([60.0, 62.0, 58.0]), "decode_tok_s",
                        out=quiet) != 1:
            failures.append("regressed live window did not exit 1")
        if compare_live(b, hist([99.0] * 3, platform="unknown"),
                        "decode_tok_s", out=quiet) != 2:
            failures.append("cross-platform live gate was not refused")
        if compare_live(b, hist([60.0] * 3, platform="unknown"),
                        "decode_tok_s", allow_cross_platform=True,
                        out=quiet) != 0:
            failures.append(
                "--allow-cross-platform live gate still refused/regressed"
            )
        if compare_live(b, hist([100.0]), "decode_tok_s", out=quiet) != 2:
            failures.append("thin live window was not refused")

    if failures:
        print("benchdiff self-check FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("benchdiff self-check ok")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifacts", nargs="*", help="BENCH_*.json, oldest first")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="regression fraction that fails the gate (0.15 = 15%%)")
    ap.add_argument("--allow-cross-platform", action="store_true",
                    help="compare artifacts from different platforms anyway "
                         "(loud per-row annotations instead of a refusal)")
    ap.add_argument("--self-check", action="store_true",
                    help="run the built-in synthetic contract suite")
    ap.add_argument("--live", metavar="URL",
                    help="gate a running node's /metrics/history window "
                         "against ONE recorded artifact instead of "
                         "diffing artifacts")
    ap.add_argument("--series", default="decode_tok_s",
                    help="observatory series to gate in --live mode "
                         "(default: decode_tok_s)")
    ap.add_argument("--window", type=float, default=600.0,
                    help="trailing live window in seconds for --live "
                         "(default: 600)")
    args = ap.parse_args(argv)
    if args.self_check:
        return _self_check()
    if args.live:
        return live(args.artifacts, args.live, args.series, args.window,
                    args.threshold, args.allow_cross_platform)
    return diff(args.artifacts, threshold=args.threshold,
                allow_cross_platform=args.allow_cross_platform)


if __name__ == "__main__":
    sys.exit(main())
