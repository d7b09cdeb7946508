"""Reduce one profiler capture (``.xplane.pb``) to what the readers use.

    JAX_PLATFORMS=cpu python3 benchmark/trace_reduce.py <file.xplane.pb> [--dump]

Runs as a child of its own held to the CPU backend (reading the file needs
``jax.profiler.ProfileData``, and the benchmark's process imports no jax).
Prints one JSON object:

- ``window_s``: the traced interval, first device-op start to last end;
- ``busy_s``: seconds in which an operation ran on the device — the UNION of
  the op intervals of a chip's op line — averaged over the chips;
- ``ops``: per op name, its SELF seconds averaged over the chips (an op that
  encloses others, such as a ``while``, keeps only what its children leave);
  an op is named ``<instruction> <opcode>[:<custom-call target>]`` from its HLO
  line, which is what the readers' patterns match;
- ``device_ops``: the ten ops with most self time, ``[name, seconds]``;
- ``idle_gaps``: the ten longest gaps between device ops on chip 0, each
  labelled with the innermost host-thread event recorded at the gap's middle,
  or ``unattributed``.

Device planes are those named ``/device:TPU:<n>``; a chip's op line is the one
named ``XLA Ops`` (the busiest line where that name is missing).
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# an op event's name is its whole HLO line: "%name = shape opcode(operands), attrs"
HLO = re.compile(r"^%?(?P<name>[^ ]+) = .*?[ )](?P<op>[a-z][a-z0-9-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
# host frames that only say "a thread is parked", never what the gap waited for
PARKED = re.compile(r"sleep|wait|select|poll|acquire|start_trace|Condition|queue\.py|threading\.py")


def short_name(text: str) -> str:
    """"name opcode[:target]" of one op event; other events keep their name."""
    m = HLO.match(text)
    if not m:
        return text[:120]
    target = TARGET.search(text)
    return f"{m['name']} {m['op']}" + (f":{target[1]}" if target else "")


def _events(line) -> list[tuple[float, float, str]]:
    """(start ns, end ns, name), by start, an enclosing event before what it encloses."""
    out = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), short_name(ev.name))
           for ev in line.events]
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union_seconds(events) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of [start, end) intervals, and the gaps between them."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e, *_ in events:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy / 1e9, gaps


def self_seconds(events) -> dict[str, float]:
    """Per op name: self seconds. Events are sorted by start, outer first."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[2] / 1e9

    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return out


def _op_line(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == OP_LINE]
    if named:
        return named[0]
    return max(lines, key=lambda ln: sum(1 for _ in ln.events), default=None)


def host_label(host_lines, t_ns: float) -> str:
    """What the host was doing at ``t_ns``: the innermost annotation the program
    wrote (``TraceAnnotation``: a name without the python tracer's ``$``) where
    one covers it, else the innermost python frame of a source file
    (``$file.py:line func``) that is not a parked thread."""
    best = (2, None, "unattributed")  # (rank, length, name)
    for events in host_lines:
        for s, e, name in events:
            if s > t_ns:
                break
            if e < t_ns or PARKED.search(name):
                continue
            if not name.startswith("$"):
                rank = 0
            elif ".py:" in name:
                rank = 1
            else:
                continue
            if (rank, e - s) < (best[0], best[1] if best[1] is not None else float("inf")):
                best = (rank, e - s, name)
    return best[2]


def reduce(path: str, dump: bool = False) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host_lines = [], []
    for plane in data.planes:
        if dump:
            print("PLANE", plane.name, file=sys.stderr)
            for ln in plane.lines:
                evs = list(ln.events)
                print("   LINE", repr(ln.name), len(evs), file=sys.stderr)
                for ev in evs[:4]:
                    print("      ", ev.name, ev.duration_ns, dict(ev.stats), file=sys.stderr)
        if DEVICE_PLANE.match(plane.name):
            line = _op_line(plane)
            if line is not None:
                chips.append((int(DEVICE_PLANE.match(plane.name).group(1)), _events(line)))
        elif plane.name.startswith("/host:"):
            host_lines += [_events(ln) for ln in plane.lines]
    chips = [(i, ev) for i, ev in sorted(chips) if ev]
    if not chips:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0, "ops": {},
                "device_ops": [], "idle_gaps": []}
    start = min(ev[0][0] for _, ev in chips)
    end = max(max(e for _, e, *_ in ev) for _, ev in chips)
    n = len(chips)
    busy = 0.0
    ops: dict[str, float] = {}
    gaps0: list[tuple[float, float]] = []
    for k, (_, ev) in enumerate(chips):
        b, gaps = union_seconds(ev)
        busy += b / n
        if k == 0:
            gaps0 = gaps
        for name, sec in self_seconds(ev).items():
            ops[name] = ops.get(name, 0.0) + sec / n
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy, "window_s": (end - start) / 1e9, "chips": n,
        "ops": dict(top),
        "device_ops": [list(item) for item in top[:10]],
        "idle_gaps": [[host_label(host_lines, (a + b) / 2.0), (b - a) / 1e9]
                      for a, b in longest],
    }


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1], dump="--dump" in sys.argv[2:])))
