"""What the program's own spans say, for the per-layer readers that take
them: on the profiler's trace (the device's idle time put down to the spans
that were open on the host), in the window's ``train_step`` events (each
span's share of a step) and in the process's span ring (set-up phases and
first calls). The program writes a span under one name on all three; a
program without it (an earlier commit) gives None everywhere, never an error.

Works on the compact trace form of ``tracered``. Spans are found by name on
whatever host line they are: the profiler names lines after threads, and
two threads may share a name.
"""

from . import tracered

# the step loop's time on the main thread is split over these four
MAIN_THREAD_SPANS = ("train_data_wait", "train_dispatch", "train_sync",
                     "train_log")


def named_intervals(trace: dict, names) -> list:
    """Merged [start, end) of every host event called one of ``names``."""
    names = set(names)
    return tracered._union(
        (e[1], e[1] + e[2]) for events in trace["host"].values()
        for e in events if e[0] in names)


def idle_intervals(trace: dict) -> list:
    """The gaps between the first device's operations, first start to last
    end: the complement of what ``busy_and_window`` counts as busy."""
    lines = tracered.device_lines(trace)
    if not lines:
        return []
    busy = tracered._union((e[1], e[1] + e[2]) for e in lines[0])
    return [[a[1], b[0]] for a, b in zip(busy, busy[1:])]


def overlap_ns(a: list, b: list) -> int:
    """Nanoseconds that two merged, sorted interval lists share."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_pct(trace: dict, names):
    """Share of the device's idle time during which a host span called one
    of ``names`` was open, in percent. None where the trace has no device
    line, no idle time, or no such span."""
    idle = idle_intervals(trace)
    spans = named_intervals(trace, names)
    idle_ns = sum(e - s for s, e in idle)
    if not idle_ns or not spans:
        return None
    return 100.0 * overlap_ns(idle, spans) / idle_ns


def window_mean_ms(ctx: dict, *fields):
    """Mean over the window's calm ``train_step`` events of the sum of
    ``fields`` (seconds per step, as the program wrote them), in ms. None
    where an event lacks one of them."""
    events = ctx["events"]
    if not events or any(f not in e for e in events for f in fields):
        return None
    return 1e3 * sum(e[f] for e in events for f in fields) / len(events)


def run_spans() -> list:
    """The span ring's records of this process's last training run: its
    set-up phases, each batch shape's first call, the program card's build
    (one trace id a run). Empty where the program wrote none."""
    from speakingstyle_tpu.obs.trace import get_span_ring

    ring = get_span_ring().spans()
    starts = [s for s in ring if s.get("name") == "setup_model_init"]
    if not starts:
        return []
    run = starts[-1].get("trace_id")
    return [s for s in ring if s.get("trace_id") == run]


def run_span_s(name: str):
    """Seconds of the run's ring spans called ``name``, summed; None where
    there is none."""
    mine = [s["duration_s"] for s in run_spans() if s["name"] == name]
    return sum(mine) if mine else None

