"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time as the union of the intervals
in which an operation ran, idle gaps and the host span that was open in
each, device time per operation name, and the time of kernels matched
by name.

Events are plain ``(name, start_ns, duration_ns)`` tuples, so the
arithmetic is tested on hand-built traces without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

#: the device line that holds one event per operation run
OPS_LINE = "XLA Ops"
#: host spans the benchmark opens around its calls into the program
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{"devices": {plane name: [events of its ops line]},
    "spans": [host events named bench.*]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    return {"devices": devices, "spans": spans}


def clip(events, lo: int, hi: int):
    """Events cut to [lo, hi); those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(events):
    """Sorted, disjoint [start, end) intervals covering the events."""
    ivs = sorted((s, s + d) for _, s, d in events if d > 0)
    out = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, lo: int, hi: int) -> int:
    return sum(b - a for a, b in merged(clip(events, lo, hi)))


def gaps(events, lo: int, hi: int):
    """Idle [start, end) intervals of [lo, hi) between merged events."""
    out, t = [], lo
    for a, b in merged(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans, t: int) -> str:
    """The innermost (shortest) host span open at time t."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host.outside_spans"


def idle_breakdown(events, spans, lo: int, hi: int, top: int = 10):
    """The longest idle gaps, each named by the host span open at its
    middle: [[span, seconds], ...], longest first."""
    gs = sorted(gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[span_at(spans, (a + b) // 2), (b - a) * 1e-9] for a, b in gs]


def op_name(name: str) -> str:
    """An HLO op event's short name: ``%fusion.12 = f32[...] ...`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> dict:
    """Device time per op name, each event less the events nested inside
    it on the same line (a ``while`` or ``conditional`` holds the ops of
    its body)."""
    tot = defaultdict(int)
    stack = []  # [end, name, child time]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, n, child = stack.pop()
            tot[n] -= child
        if stack:
            stack[-1][2] += d
        tot[op_name(name)] += d
        stack.append([s + d, op_name(name), 0])
    for end, n, child in stack:
        tot[n] -= child
    return dict(tot)


def top_ops(events, top: int = 10):
    """The ops that took the most device self time: [[name, s], ...]."""
    tot = self_times(events)
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def matching(events, names):
    """Events whose name contains one of ``names``."""
    return [e for e in events if any(n in e[0] for n in names)]


def window(spans, name: str = "bench.window"):
    """[lo, hi) of the benchmark's window span."""
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name} span in the trace")


def reduce(path: str) -> dict:
    """Everything the metric readers take from one trace: the window's
    seconds, busy seconds averaged over the devices, each device's
    events, and the first device's events, top ops by self time and
    idle gaps."""
    data = load(path)
    if not data["devices"]:
        raise ValueError(f"no TPU device plane with an {OPS_LINE!r} line "
                         f"in {path}")
    lo, hi = window(data["spans"])
    per_dev = {}
    for dev, evs in data["devices"].items():
        inside = clip(evs, lo, hi)
        per_dev[dev] = {"busy_s": busy_ns(inside, lo, hi) * 1e-9,
                        "events": inside}
    first = sorted(per_dev)[0]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(v["busy_s"] for v in per_dev.values()) / len(per_dev),
        "events": per_dev[first]["events"],
        "device_events": [per_dev[d]["events"] for d in sorted(per_dev)],
        "device_ops": top_ops(per_dev[first]["events"]),
        "idle_gaps": idle_breakdown(per_dev[first]["events"],
                                    data["spans"], lo, hi),
    }
