"""From a profiler trace to numbers: device busy union, idle share, time per
jitted program, the device operations that took most time, and the longest
idle gaps labelled by what the host was doing.

What a v5e trace looks like (``jax.profiler.start_trace`` on JAX 0.9.0, read
by hand in PR 24, ``testdata/probe.xplane.pb``):

- the device is the plane ``/device:TPU:<n>``. Its line ``XLA Modules`` has
  one event per execution of a jitted program, named
  ``jit_<function>(<fingerprint>)``; its line ``XLA Ops`` has one event per
  HLO operation, named by the HLO text (``%fusion.1 = f32[] fusion(...)``).
  ``Async XLA Ops`` repeats the asynchronous copies and is not counted.
- the host is the plane ``/host:CPU``, one line per thread. The runtime's own
  spans are there (``PjitFunction(<function>)``, ``np.asarray(jax.Array)``,
  ``tpu::System::TransferFromDevice``) beside every ``TraceAnnotation``.
- all planes share one clock: nanoseconds since the trace started.

``load_xplane`` needs JAX; ``reduce`` is plain Python over the loaded form, so
the self-check runs on a recorded trace anywhere.
"""

from __future__ import annotations

import json
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_EVENT = "benchmark:window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def load_xplane(path: str) -> dict:
    """-> {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]} for the device planes and the host plane."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_name(event_name: str) -> str:
    """``jit__packed_body(1485...)`` -> ``jit__packed_body``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.1 = f32[]{:T(128)} fusion(...)`` -> ``fusion.1 fusion``: the
    result's name and the HLO opcode, without the shapes."""
    m = re.match(r"^%?([\w.\-]+) = .*? ([\w\-]+)\(", event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:80]


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _clip(events, lo: float, hi: float):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def find_window(trace: dict) -> tuple[float, float]:
    """The traced window on the trace's clock: the ``benchmark:window``
    annotation where the run wrote one, else first start to last end."""
    lo, hi = float("inf"), float("-inf")
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_EVENT:
                    return start, start + dur
                lo, hi = min(lo, start), max(hi, start + dur)
    if hi <= lo:
        raise ValueError("the trace holds no event")
    return lo, hi


def reduce(trace: dict, gaps_labelled: int = 1000) -> dict:
    """-> busy_s and window_s (averaged over the device planes), idle_share,
    programs {name: {"seconds", "count"}} summed over the planes,
    device_ops and idle_gaps as the result line's ``breakdown`` wants them."""
    lo, hi = find_window(trace)
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    programs: dict[str, dict] = {}
    ops: dict[str, float] = {}
    busy_ns = []
    gaps: list[tuple[float, float]] = []
    for plane in devices:
        intervals = []
        for line in plane["lines"]:
            if line["name"] == PROGRAM_LINE:
                for name, a, b in _clip(line["events"], lo, hi):
                    p = programs.setdefault(program_name(name), {"seconds": 0.0, "count": 0})
                    p["seconds"] += (b - a) / 1e9
                    p["count"] += 1
            elif line["name"] == OP_LINE:
                for name, a, b in _clip(line["events"], lo, hi):
                    intervals.append((a, b))
                    key = op_name(name)
                    ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        merged = _merge(intervals)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": programs,
        "device_ops": _top(ops),
        "idle_gaps": _top(_label_gaps(trace, gaps, gaps_labelled)),
    }


def _top(seconds_by_name: dict[str, float], n: int = 10) -> list[list]:
    ranked = sorted(seconds_by_name.items(), key=lambda kv: kv[1], reverse=True)
    return [[name, seconds] for name, seconds in ranked[:n]]


def _label_gaps(trace: dict, gaps, gaps_labelled: int) -> dict[str, float]:
    """Seconds of idle gap by what the host was doing: the shortest host span
    that covers the gap's middle (the most specific one), over all threads.
    The longest ``gaps_labelled`` gaps get a label; the rest go to ``(short
    gaps)`` whole."""
    starts, ends, names = [], [], []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name != WINDOW_EVENT and dur > 0:
                    starts.append(start)
                    ends.append(start + dur)
                    names.append(name)
    starts, ends = np.asarray(starts), np.asarray(ends)
    gaps = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)
    out: dict[str, float] = {}
    for a, b in gaps[:gaps_labelled]:
        mid = (a + b) / 2
        covering = np.flatnonzero((starts <= mid) & (ends > mid))
        if len(covering):
            label = names[covering[np.argmin((ends - starts)[covering])]]
        else:
            label = "(no host span)"
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    rest = sum(b - a for a, b in gaps[gaps_labelled:]) / 1e9
    if rest:
        out["(short gaps)"] = rest
    return out


def main(argv: list[str]) -> int:
    """``reduce_trace.py <file.xplane.pb | file.json> [--dump out.json]``:
    print the reduction; ``--dump`` also writes the loaded form."""
    path = argv[1]
    trace = json.load(open(path)) if path.endswith(".json") else load_xplane(path)
    if "--dump" in argv:
        with open(argv[argv.index("--dump") + 1], "w") as f:
            json.dump(trace, f)
    print(json.dumps(reduce(trace), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
