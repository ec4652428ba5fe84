"""Reduction from a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
only what the reduction needs: every event of the device planes, and the
benchmark's own host spans (names starting with ``SPAN_PREFIX``). The kept
form is plain JSON, so a small recorded trace can be committed and the
reduction tested on it.

``reduce`` computes, per device: busy time as the union of the intervals
in which an operation ran, time by XLA module (one module per jitted
program), time by operation (operations that contain others, such as a
loop, are left to their contents), and the idle gaps, each labelled with
the benchmark span the host was in when the gap began.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 200  # an operation's name is its HLO text: keep its head


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, got {paths}")
    pd = ProfileData.from_file(paths[0])
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [e.name[:NAME_CHARS], float(e.start_ns), float(e.duration_ns)]
                        for e in line.events
                    ]
            if lines:
                devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list) -> list:
    """Merge [start, end] intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(events: list) -> list:
    """The events that contain no other event of the line: a loop's own
    event (``%while``) spans its body's operations, which carry the time."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d:
            continue
        out.append([name, s, d])
    return out


def _span_at(spans: list, t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    label = "outside any span"
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or s >= best):
            label, best = name[len(SPAN_PREFIX):], s
        if s > t:
            break
    return label


def reduce(extracted: dict, window: tuple) -> dict:
    """Reduce to per-device numbers over ``window`` = (start_ns, end_ns),
    on the trace's own clock. Events outside the window are cut to it."""
    w0, w1 = window
    spans = extracted["spans"]
    devices = []
    for dev in extracted["devices"]:
        lines = dev["lines"]
        busy_line = OPS_LINE if lines.get(OPS_LINE) else MODULES_LINE
        if not lines.get(busy_line):
            continue

        def clipped(events):
            for name, s, d in events:
                a, b = max(s, w0), min(s + d, w1)
                if b > a:
                    yield name, a, b

        busy = _union([[a, b] for _, a, b in clipped(lines[busy_line])])
        modules, ops, counts = {}, {}, {}
        for name, a, b in clipped(lines.get(MODULES_LINE, [])):
            modules[name] = modules.get(name, 0.0) + (b - a) * 1e-9
            counts[name] = counts.get(name, 0) + 1
        for name, a, b in clipped(_leaves(lines.get(OPS_LINE, []))):
            ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        gaps, t = [], w0
        for a, b in busy + [[w1, w1]]:
            if a > t:
                gaps.append([_span_at(spans, t), (a - t) * 1e-9, t])
            t = max(t, b)
        devices.append({
            "name": dev["name"],
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "modules": modules,
            "module_counts": counts,
            "ops": ops,
            "gaps": gaps,
        })
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices}


def top(items: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(items.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(reduced: dict, n: int = 10) -> dict:
    """The device operations that took most time (summed over devices) and
    the longest idle gaps, by what the host was doing when each began."""
    ops = {}
    gaps = []
    for dev in reduced["devices"]:
        for k, v in dev["ops"].items():
            ops[k] = ops.get(k, 0.0) + v
        gaps.extend(dev["gaps"])
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": top(ops, n), "idle_gaps": [[g[0], g[1]] for g in gaps[:n]]}


def module_ms_per_megabatch(reduced: dict, patterns: tuple):
    """Device time of the XLA modules (jitted programs) whose name holds one
    of ``patterns``, per traced mega-batch, averaged over devices; None
    where no such module ran."""
    n = reduced["megabatches"]
    per_device = [
        sum(v for k, v in d["modules"].items() if any(p in k for p in patterns))
        for d in reduced["devices"]
    ]
    if not n or not per_device or not any(per_device):
        return None
    return 1e3 * sum(per_device) / len(per_device) / n


def op_seconds(reduced: dict, patterns: tuple) -> float:
    """Device time, summed over devices, of the operations whose name holds
    one of ``patterns``."""
    return sum(v for d in reduced["devices"] for k, v in d["ops"].items()
               if any(p in k for p in patterns))
