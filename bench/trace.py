"""From a profiler trace to numbers.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict: per device the ``XLA Ops`` events, and the
benchmark's own host spans (``bench.*`` annotations), each as
``[name, start_ns, duration_ns]`` on one clock.  ``reduce`` turns that into
the device's busy time (the union of op intervals inside the ``bench.window``
span), device time per op (a Pallas kernel is one op), each span's
host-only time, and
the breakdown: the device ops that took most time and the longest idle gaps,
each gap named by the innermost benchmark span it fell in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
WINDOW = "bench.window"
TOP = 10


def load_xplane(where: str) -> dict:
    """``where`` is an ``.xplane.pb`` file or the directory a trace was
    written to."""
    from jax.profiler import ProfileData

    paths = [where] if os.path.isfile(where) else glob.glob(
        os.path.join(where, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {where}")
    pd = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    ev = lambda e: [e.name, int(e.start_ns), int(e.duration_ns)]
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops = lines.get("XLA Ops")
            devices[plane.name] = {"ops": [ev(e) for e in ops.events]
                                   if ops is not None else []}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev(e) for e in line.events
                          if e.name.startswith("bench.")]
    return {"devices": devices, "spans": spans}


def union(intervals):
    """Merge [start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, s, e) -> int:
    """Length of [s, e) covered by a merged interval list."""
    i = max(bisect.bisect_right([m[0] for m in merged], s) - 1, 0)
    total = 0
    for ms, me in merged[i:]:
        if ms >= e:
            break
        total += max(0, min(me, e) - max(ms, s))
    return total


def reduce(trace: dict, *, n_devices: int = 1) -> dict:
    wins = [s for s in trace["spans"] if s[0] == WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    w0 = wins[0][1]
    w1 = w0 + wins[0][2]
    clip = lambda s, d: (max(s, w0), min(s + d, w1))
    devs = sorted(trace["devices"])[:n_devices]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy, op_ns, merged0 = [], defaultdict(int), None
    for name in devs:
        d = trace["devices"][name]
        iv = [clip(s, dur) for _, s, dur in d["ops"]]
        merged = union([(s, e) for s, e in iv if e > s])
        busy.append(sum(e - s for s, e in merged))
        merged0 = merged if merged0 is None else merged0
        for (op, s, dur), (cs, ce) in zip(d["ops"], iv):
            if ce > cs:
                op_ns[op] += ce - cs
    inner = sorted((s for s in trace["spans"] if s[0] != WINDOW),
                   key=lambda s: s[1])

    def label(t):
        best = None
        for name, s, dur in inner:
            if s > t:
                break
            if s <= t < s + dur and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0] if best else WINDOW

    gaps, prev = [], w0
    for s, e in merged0 + [[w1, w1]]:
        if s > prev:
            gaps.append((s - prev, prev))
        prev = max(prev, e)
    gaps.sort(reverse=True)
    span_host = defaultdict(list)
    for name, s, dur in inner:
        cs, ce = clip(s, dur)
        if ce > cs:
            span_host[name].append(((ce - cs) / 1e9,
                                    (ce - cs - covered(merged0, cs, ce)) / 1e9))
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "span_host": dict(span_host),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": [[label(s + g / 2), g / 1e9] for g, s in gaps[:TOP]],
        },
    }


def idle_share(reduced) -> float | None:
    """100 x (1 - busy / window) of a reduced trace."""
    if reduced is None or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
