"""Reduction from a profiler trace to numbers. Works on a compact form
{"devices": [{line: [[name, start_ns, dur_ns], ...]}], "host": {line: [...]}}
so a small recorded trace can check it; ``compact`` makes that form from the
``.xplane.pb`` the profiler wrote."""

import glob
import os
import re

DEVICE_LINE = "XLA Ops"


def compact(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = {"devices": [], "host": {}}
    for plane in data.planes:
        name = plane.name
        is_device = name.startswith("/device:") and "CUSTOM" not in name.upper()
        is_host = name.startswith("/host:CPU")
        if not (is_device or is_host):
            continue
        lines = {}
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if is_device and lines:
            out["devices"].append(lines)
        elif is_host:
            for k, v in lines.items():
                out["host"].setdefault(k, []).extend(v)
    return out


def _union(intervals):
    """Merged [start, end) list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def device_lines(trace: dict):
    """The operation line of every device plane that ran something."""
    return [d[DEVICE_LINE] for d in trace["devices"] if d.get(DEVICE_LINE)]


def busy_and_window(trace: dict, window_ns=None):
    """(busy_s, window_s): seconds in which an operation ran on the device,
    averaged over the devices used, and the traced window's length (first
    operation's start to last operation's end unless given)."""
    lines = device_lines(trace)
    if not lines:
        return 0.0, 0.0
    lo = min(e[1] for ev in lines for e in ev)
    hi = max(e[1] + e[2] for ev in lines for e in ev)
    busy = [sum(e - s for s, e in _union([(e[1], e[1] + e[2]) for e in ev]))
            for ev in lines]
    return sum(busy) / len(busy) / 1e9, (window_ns or (hi - lo)) / 1e9


def top_ops(trace: dict, n=10, width=64):
    totals = {}
    for ev in device_lines(trace):
        for name, _, dur in ev:
            totals[name] = totals.get(name, 0) + dur
    k = max(len(device_lines(trace)), 1)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(name, width), dur / k / 1e9] for name, dur in top]


def short_name(name: str, width=64) -> str:
    """An HLO line made into a short name: the instruction, its first result
    shape; commas, spaces and slashes out."""
    name = re.sub(r"[,/\s]+", "_", name.lstrip("$%"))
    return re.sub(r"[^A-Za-z0-9_.\-:\[\]{}=()]", "", name)[:width]


def idle_gaps(trace: dict, n=10, min_gap_ns=20_000):
    """The device's idle gaps, each named by the innermost host event that was
    open at the gap's middle, summed by name, longest first."""
    lines = device_lines(trace)
    if not lines:
        return []
    busy = _union([(e[1], e[1] + e[2]) for e in lines[0]])
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] >= min_gap_ns]
    if not gaps:
        return []
    mids = sorted(((a + b) // 2, b - a) for a, b in gaps)
    best = [None] * len(mids)  # (duration, name) of the innermost event
    for events in trace["host"].values():
        events = sorted(events, key=lambda e: (e[1], -e[2]))
        stack, at = [], 0
        for i, (mid, length) in enumerate(mids):
            while at < len(events) and events[at][1] <= mid:
                stack.append(events[at])
                at += 1
            while stack and stack[-1][1] + stack[-1][2] < mid:
                stack.pop()
            # innermost open event that covers the middle
            for ev in reversed(stack):
                if ev[1] + ev[2] >= mid:
                    cand = (ev[2], ev[0])
                    if best[i] is None or cand[0] < best[i][0]:
                        best[i] = cand
                    break
    totals = {}
    for (mid, length), b in zip(mids, best):
        name = short_name(b[1]) if b else "no_host_event"
        totals[name] = totals.get(name, 0) + length
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / 1e9] for name, dur in top]


_SHAPE = re.compile(r"= \(?\w+\[(\d+),(\d+),(\d+),(\d+)\]")


def kernel_calls(trace: dict):
    """The fused-attention kernel's events on the first device: a custom call
    whose result is [B, H, D, T] (one array forward, a tuple of three
    backward). Returns [(b, h, d, t, backward, seconds)]."""
    out = []
    lines = device_lines(trace)
    for name, _, dur in (lines[0] if lines else []):
        if "custom-call" not in name or "rng" in name.split("=")[0]:
            continue
        m = _SHAPE.search(name)
        if not m:
            continue
        b, h, d, t = (int(x) for x in m.groups())
        backward = name.split("custom-call")[0].count("[") >= 3
        out.append((b, h, d, t, backward, dur / 1e9))
    return out


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
