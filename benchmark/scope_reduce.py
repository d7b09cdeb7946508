"""Device self time by the program's ``jax.named_scope``s, from one capture.

    JAX_PLATFORMS=cpu python3 benchmark/scope_reduce.py <file.xplane.pb> <regex> [--dump]

``trace_reduce.py`` names an op ``<instruction> <opcode>`` and so cannot tell
the mixer's fusions from the MLP's. A scope the program opened
(``ssm.in_proj`` .. ``ssm.out_proj`` in ``models/core.py``) rides each HLO
instruction's ``op_name`` metadata, which the profiler records as the ``tf_op``
stat of the op's event METADATA; ``jax.profiler.ProfileData`` shows an event's
own stats only, so this child parses the capture's ``XSpace`` message itself
(the message class comes with the installed tensorflow), takes each device op's SELF seconds
(``trace_reduce.self_seconds``: an enclosing ``while`` keeps only what its
children leave), and books them under the first group of ``regex`` found in
the event's text. A fusion is booked under its root instruction's scope.
Prints one JSON object: ``busy_s`` (as trace_reduce's), ``scopes`` {scope:
seconds, mean over the chips}, ``matched_events``, ``events``.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from trace_reduce import DEVICE_PLANE, OP_LINE, self_seconds, union_seconds  # noqa: E402


def load_space(path: str):
    """The capture as the profiler's own XSpace message. The message class ships
    with the installed tensorflow (``tensorflow.tsl``) or, where present, tsl."""
    try:
        from tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    return space


def op_names(plane) -> dict[int, str]:
    """event metadata id -> the op's ``op_name`` (the ``tf_op`` stat of an XLA
    op's metadata: ``jit(f)/while/body/ssm.step/mul``), '' where it has none."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for mid, meta in plane.event_metadata.items():
        text = ""
        for st in meta.stats:
            if stat_names.get(st.metadata_id) == "tf_op":
                text = st.str_value or stat_names.get(st.ref_value, "")
        out[mid] = text
    return out


def reduce(path: str, pattern: str, dump: bool = False) -> dict:
    rx = re.compile(pattern)
    chips = []
    for plane in load_space(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OP_LINE] or sorted(
            plane.lines, key=lambda ln: -len(ln.events))[:1]
        if not lines:
            continue
        names = op_names(plane)
        base = lines[0].timestamp_ns
        events = []
        for k, ev in enumerate(lines[0].events):
            text = names.get(ev.metadata_id, "")
            if dump and k < 40:
                print("EVENT", plane.event_metadata[ev.metadata_id].name[:80], "|", text,
                      file=sys.stderr)
            m = rx.search(text)
            label = (m.group(1) if m.groups() else m.group(0)) if m else ""
            start = base + ev.offset_ps / 1000.0
            events.append((start, start + ev.duration_ps / 1000.0, label))
        events.sort(key=lambda e: (e[0], -e[1]))
        if events:
            chips.append(events)
    if not chips:
        return {"busy_s": 0.0, "scopes": {}, "matched_events": 0, "events": 0}
    n = len(chips)
    busy, scopes, matched, total = 0.0, {}, 0, 0
    for events in chips:
        busy += union_seconds(events)[0] / n
        total += len(events)
        matched += sum(1 for e in events if e[2])
        for label, sec in self_seconds(events).items():
            if label:
                scopes[label] = scopes.get(label, 0.0) + sec / n
    return {"busy_s": busy, "scopes": scopes, "matched_events": matched, "events": total}


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1], sys.argv[2], dump="--dump" in sys.argv[3:])))
