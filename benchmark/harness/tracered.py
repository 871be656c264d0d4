"""Reduction from a profiler trace to numbers. Works on a compact form
{"devices": [{line: [[name, start_ns, dur_ns], ...]}], "host": {line: [...]}}
so a small recorded trace can check it; ``compact`` makes that form from the
``.xplane.pb`` the profiler wrote. A device event may carry a fourth element,
{"tf_op": ..., "hlo_op": ...}: the operation's name in the program (its
``jax.named_scope`` / module path down to the primitive) and the HLO
instruction's own name, as the trace's event metadata has them. A recorded
trace without it reads as before."""

import glob
import os
import re

DEVICE_LINE = "XLA Ops"
# the stats of an event's metadata that say where in the program it comes from
OP_STATS = ("tf_op", "hlo_op")


def compact(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    data, named = ProfileData.from_serialized_xspace(raw), op_names(raw)
    out = {"devices": [], "host": {}}
    for plane in data.planes:
        name = plane.name
        is_device = name.startswith("/device:") and "CUSTOM" not in name.upper()
        is_host = name.startswith("/host:CPU")
        if not (is_device or is_host):
            continue
        ops = named.get(name, {}) if is_device else {}
        lines = {}
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      + ([ops[e.name]] if e.name in ops else [])
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if is_device and lines:
            out["devices"].append(lines)
        elif is_host:
            for k, v in lines.items():
                out["host"].setdefault(k, []).extend(v)
    return out


# -- the .xplane.pb itself ---------------------------------------------------
# ``ProfileData`` gives an event its name and times and the stats of the event
# alone; what the event's *metadata* carries (tf_op, hlo_op) it leaves out. So
# the file's wire format is read here, as far as that needs: XSpace.planes = 1;
# XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key = 1,
# value = 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
# XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a stat_metadata id
# whose name is the string). tsl/profiler/protobuf/xplane.proto.

def _fields(buf):
    """(field number, wire type, value) of one message: an int for a varint
    or a fixed-width field, the bytes of a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, kind, value


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _map_entry(buf):
    key = value = None
    for no, _, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def op_names(xspace: bytes) -> dict:
    """{plane name: {event name: {stat: string}}} for the ``OP_STATS`` that
    each event's metadata carries; an event without any is left out."""
    out = {}
    for no, _, plane in _fields(memoryview(xspace)):
        if no != 1:
            continue
        name, events, stat_names = "", [], {}
        for pno, _, v in _fields(plane):
            if pno == 2:
                name = bytes(v).decode()
            elif pno == 4:
                events.append(_map_entry(v)[1])
            elif pno == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for n, _, x in _fields(meta) if n == 2), "")
        wanted = {i for i, n in stat_names.items() if n in OP_STATS}
        named = {}
        for meta in events:
            ev_name, stats = "", {}
            for mno, _, v in _fields(meta or b""):
                if mno == 2:
                    ev_name = bytes(v).decode(errors="replace")
                elif mno == 5:
                    stat = {n: x for n, _, x in _fields(v)}
                    if stat.get(1) in wanted:
                        text = (bytes(stat[5]).decode(errors="replace")
                                if 5 in stat else stat_names.get(stat.get(7), ""))
                        stats[stat_names[stat[1]]] = text
            if stats:
                named[ev_name] = stats
        if named:
            out[name] = named
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
        for e in ev:
            totals[e[0]] = totals.get(e[0], 0) + e[2]
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


def is_fused_attention(event) -> bool:
    """Whether a device event is a call of the fused-attention kernel: by its
    name in the program where the trace kept it (``tf_op``: the module path
    down to the primitive, ``.../pallas_call``), else, on a trace recorded
    without (the fixture of PR 24), by its form: a custom call that is not the
    random generator's."""
    name = event[0]
    if len(event) > 3 and event[3].get("tf_op"):
        return "pallas_call" in event[3]["tf_op"].rsplit("/", 1)[-1]
    return "custom-call" in name and "rng" not in name.split("=")[0]


def kernel_calls(trace: dict):
    """The fused-attention kernel's events on the first device, found by
    name; the result's shape says only what was computed: [B, H, D, T], one
    array forward, a tuple of three backward. Returns
    [(b, h, d, t, backward, seconds)]."""
    out = []
    lines = device_lines(trace)
    for event in (lines[0] if lines else []):
        name, dur = event[0], event[2]
        m = _SHAPE.search(name)
        if not m or not is_fused_attention(event):
            continue
        b, h, d, t = (int(x) for x in m.groups())
        backward = name.split("custom-call")[0].count("[") >= 3
        out.append((b, h, d, t, backward, dur / 1e9))
    return out


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
