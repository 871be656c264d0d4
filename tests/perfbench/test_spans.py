"""The readers of the program's own spans: the overlap of the device's idle
time with named host spans on a small hand-written trace, the event- and
ring-based readers on recorded events, and every one of them reading None,
without an error, where the program wrote no such span (an earlier commit)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, contract, spans, train_cell, trafficgen  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN = "train_ljspeech_b200"

TRACE_READERS = ["idle_in_data_wait_pct", "idle_in_loader_fetch_pct",
                 "idle_unattributed_pct"]
EVENT_READERS = ["loader_read_ms", "loader_prepare_ms", "loader_blocked_pct",
                 "step_dispatch_ms", "step_sync_ms", "loader_padding_pct"]
RING_READERS = ["setup_model_init_s", "setup_restore_s", "setup_first_calls_s"]
NEW_READERS = TRACE_READERS + EVENT_READERS + RING_READERS


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    return load("recorded_trace_spans.json")


@pytest.fixture
def empty_ring(monkeypatch):
    """A span ring of this test's own: other tests of the process train too."""
    from speakingstyle_tpu.obs import trace

    ring = trace.SpanRing()
    monkeypatch.setattr(trace, "_process_ring", ring)
    return ring


def ctx_of(fixture: dict) -> dict:
    return {"trace": fixture["trace"], "events": fixture["events"],
            "window_s": fixture["window_s"], "log_step": fixture["log_step"]}


def read_all(ctx, names=NEW_READERS):
    """Through the harness's own ``read_per_layer``, which takes every
    metric of the manifest: the new ones picked out of what it returns."""
    out = train_cell.read_per_layer(TRAIN, ctx)
    return {n: out[n]["value"] if n in out else None for n in names}


def test_manifest_lists_every_reader_and_every_reader_is_listed():
    """Whatever the list's length and order: each ``per_layer`` entry has its
    file under ``benchmark/metrics/`` and each file there its entry."""
    assert contract.reader_problems(common.manifest(), common.ROOT) == []
    names = [m["name"] for m in common.manifest()["per_layer"]]
    assert set(NEW_READERS) <= set(names)


def deck_padding_share(traffic: dict, mel_bucket=128) -> float:
    """The deck's own arithmetic: sorted by length, cut into batches, each
    batch padded to the next multiple of the loader's mel bucket."""
    spec = {**traffic["deck"], "batch_size": traffic["batch_size"]}
    frames = [int(d.sum()) for _, d in trafficgen.train_deck(spec)]
    rows = spec["batch_size"]
    padded = sum(rows * -(-max(frames[i:i + rows]) // mel_bucket) * mel_bucket
                 for i in range(0, len(frames), rows))
    return 100.0 * (1.0 - sum(frames) / padded)


def test_deck_arithmetic_gives_the_cells_padding():
    """At the cell's own size: four batches of 200 at 896/768/640/512 frames
    against the deck's 429,521 real ones (the ledger's 23.736)."""
    t = common.load_json("benchmark/traffic/train_ljspeech_lengths.json")
    assert deck_padding_share(t) == pytest.approx(100 * (1 - 429521 / 563200))


def test_idle_intervals_and_overlap_by_hand(recorded):
    tr = recorded["trace"]
    # busy 0-1000, 3000-5000 (two operations that touch), 9000-10000
    assert spans.idle_intervals(tr) == [[1000, 3000], [5000, 9000]]
    assert spans.named_intervals(tr, ["train_data_wait"]) == [
        [500, 2500], [5200, 8200]]
    # two threads' events on one line name: the union, not a stack
    assert spans.named_intervals(tr, ["loader_fetch"]) == [[1500, 7000]]
    assert spans.overlap_ns([[1000, 3000], [5000, 9000]],
                            [[500, 2500], [5200, 8200]]) == 1500 + 3000
    assert spans.overlap_ns([[0, 10]], []) == 0
    assert spans.overlap_ns([[0, 10], [20, 30]], [[5, 25]]) == 5 + 5


@pytest.mark.parametrize("name,expected", [
    # idle 6000 ns; in train_data_wait 1500 + 3000
    ("idle_in_data_wait_pct", 100 * 4500 / 6000),
    # in the union of the two loader_fetch spans: 1500 + 2000
    ("idle_in_loader_fetch_pct", 100 * 3500 / 6000),
    # under the four main-thread spans 500-2900 and 5200-8800: 1900 + 3600
    ("idle_unattributed_pct", 100 * (1 - 5500 / 6000)),
    # means over the two events
    ("loader_read_ms", 320.0),
    ("loader_prepare_ms", (380 + 50 - 300 + 420 + 70 - 340) / 2),
    # blocked 25 ms a step of a 500 ms step (4 s over 2 events x 4 steps)
    ("loader_blocked_pct", 100 * 25 / 500),
    ("step_dispatch_ms", 5.0),
    ("step_sync_ms", 305.0),
    ("loader_padding_pct", 100 * (1 - 210000 / 280000)),
])
def test_reader_on_the_recorded_spans(recorded, name, expected):
    assert read_all(ctx_of(recorded), [name])[name] == pytest.approx(expected)


def test_ring_readers_take_the_last_runs_spans(empty_ring):
    from speakingstyle_tpu.obs import Span
    from speakingstyle_tpu.obs.trace import new_context

    for run, scale in (("train-old", 100.0), ("train-new", 1.0)):
        ctx = new_context(run)
        for name, s in (("setup_model_init", 3.0), ("setup_restore", 2.0),
                        ("setup_build_steps", 0.5), ("setup_datasets", 0.25)):
            Span.record(name, 0.0, s * scale, parent=ctx, ring=empty_ring)
        for shape, s in (([4, 128, 32], 7.0), ([4, 256, 32], 5.0)):
            Span.record("train_dispatch", 0.0, s * scale, parent=ctx,
                        ring=empty_ring, shape=shape, compiles=1.0)
    got = read_all({"trace": {"devices": [], "host": {}}, "events": []},
                   RING_READERS)
    assert got == {"setup_model_init_s": 3.0, "setup_restore_s": 2.0,
                   "setup_first_calls_s": 12.0}
    assert [s["name"] for s in spans.run_spans()] == [
        "setup_model_init", "setup_restore", "setup_build_steps",
        "setup_datasets", "train_dispatch", "train_dispatch"]


def test_every_new_reader_reads_none_where_the_program_wrote_no_span(
    empty_ring, capsys
):
    """The parent commit's side of a check: the accepted benchmark's own
    recorded trace (no span of the program on it) and events with the
    fields the program had."""
    old = load("recorded_trace.json")
    events = [{"event": "train_step", "step": s, "step_time_s": 0.3,
               "data_wait_s": 0.2, "steps_per_sec": 2.0,
               "mel_frames_per_sec": 2e5} for s in (12, 16)]
    ctx = {"trace": old["trace"], "events": events, "window_s": 4.0,
           "log_step": 4}
    assert read_all(ctx) == dict.fromkeys(NEW_READERS)
    # and where there is nothing at all
    bare = {"trace": {"devices": [], "host": {}}, "events": []}
    assert read_all(bare) == dict.fromkeys(NEW_READERS)
    capsys.readouterr()


def test_traced_toy_run_gives_every_span_reader_a_value(monkeypatch, capsys):
    """The cell's command at toy size on the CPU with ``--trace 1``: the
    program's spans reach the events and the ring, so every event- and
    ring-based reader prints a finite number; the CPU's trace has no device
    plane, so the three trace readers read None, and none of them raises."""
    import math

    import speakingstyle_tpu.ops.dropout as d

    monkeypatch.setattr(d, "dropout", lambda x, *a, **k: x)
    assert train_cell.run(TRAIN, 2 ** 31 + 5, 2.0, True, toy=True) == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {n: line["metrics"].get(n, {}).get("value") for n in NEW_READERS}
    for name in EVENT_READERS + RING_READERS:
        assert got[name] is not None and math.isfinite(got[name]), name
        assert got[name] >= 0, name
    assert [got[n] for n in TRACE_READERS] == [None] * 3
    assert not [ln for ln in cap.err.splitlines()
                if ln.startswith("metric ") and any(n in ln for n in NEW_READERS)]
    # the program's counters against the deck's own arithmetic
    toy = common.sized(common.load_json(
        "benchmark/traffic/train_ljspeech_lengths.json"), True)
    assert got["loader_padding_pct"] == pytest.approx(
        deck_padding_share(toy), abs=1e-6)
    # one batch shape at toy size: its first call is the ring's only one
    names = [s["name"] for s in spans.run_spans()]
    assert names.count("train_dispatch") == 1
    assert sum(n.startswith("setup_") for n in names) == 4



@pytest.mark.parametrize(
    "name", [m["name"] for m in common.manifest()["per_layer"]])
def test_reader_reads_nothing_and_raises_nothing_without_its_key(
    name, empty_ring, capsys
):
    """A driver that owes a reader a ``ctx`` key and does not supply it, or
    supplies it empty: the metric is left out of the line, never an error
    and never a nought."""
    bare = {"trace": {"devices": [], "host": {}}, "events": [], "cycles_s": [],
            "device": {}, "peaks": None, "window_s": 0.0, "log_step": 4,
            "compiles_open": {}, "compiles_close": {}}
    for ctx in ({}, bare):
        assert name not in train_cell.read_per_layer(TRAIN, ctx)
    capsys.readouterr()
