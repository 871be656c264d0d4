"""The reader of the loader's sample cache, ``loader_cache_hit_pct``: a share
on events that carry the program's two counts, None (and no error) on the
events of a program without them, which is the parent's side of a check.

The metric's ``per_layer`` entry is ``ENTRY`` below: it lists no cell, so
every cell that reports ``train_frames_per_s`` reports it (the program's loop
writes the two counts whatever it trains). Held here to the manifest's rules
and run through the harness's own ``read_per_layer``."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, train_cell  # noqa: E402

TRAIN = "train_ljspeech_b200"
NAME = "loader_cache_hit_pct"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "train loop",
         "moves": "train_frames_per_s"}


def reader():
    path = os.path.join(ROOT, "benchmark", "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def events(*counts):
    """``train_step`` events of a program with the cache: per step of each
    window, as every window field."""
    return [{"event": "train_step", "step": 4 * (i + 3), "data_wait_s": 0.01,
             "loader_cache_hits": h, "loader_cache_misses": m}
            for i, (h, m) in enumerate(counts)]


OLD_EVENTS = [{"event": "train_step", "step": s, "step_time_s": 0.3,
               "data_wait_s": 0.2, "loader_read_s": 0.4}
              for s in (12, 16)]


@pytest.mark.parametrize("evs,expected", [
    (events((200.0, 0.0), (200.0, 0.0)), 100.0),    # the corpus is held
    (events((0.0, 200.0), (200.0, 0.0)), 50.0),     # a first epoch in the window
    (events((150.0, 50.0), (130.0, 70.0)), 70.0),   # a corpus past the budget
    (events((0.0, 200.0)), 0.0),                    # a budget of nothing
    (events((0.0, 0.0)), None),                     # nothing fetched at all
    (OLD_EVENTS, None),                             # the parent: no such field
    (events((200.0, 0.0)) + OLD_EVENTS, None),      # not on every event
    ([], None),
], ids=["held", "first_epoch", "past_budget", "budget_0", "idle_loader",
        "parent", "mixed", "no_events"])
def test_reader_gives_a_share_or_none(evs, expected):
    got = reader()({"events": evs})
    assert got == (None if expected is None else pytest.approx(expected))


def test_entry_is_the_manifests_and_lists_no_cell():
    man = common.manifest()
    assert [m for m in man["per_layer"] if m["name"] == NAME] == [ENTRY]
    assert set(ENTRY) == {"name", "unit", "better", "source", "layer", "moves"}
    assert ENTRY["moves"] in {m["name"] for m in man["end_to_end"]}


@pytest.mark.parametrize("evs,expected", [
    (events((200.0, 0.0), (200.0, 0.0)), 100.0), (OLD_EVENTS, None)],
    ids=["change", "parent"])
def test_harness_reports_it_for_the_cell(evs, expected):
    """``read_per_layer`` finds the reader by its name and reports the share;
    on the events of a program without the cache the line leaves the metric
    out and nothing is raised."""
    ctx = {"trace": {"devices": [], "host": {}}, "events": evs,
           "window_s": 4.0, "log_step": 4}
    out = train_cell.read_per_layer(TRAIN, ctx)
    if expected is None:
        assert NAME not in out
    else:
        assert out[NAME] == {"value": expected, "unit": "%"}
