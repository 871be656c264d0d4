"""The five readers of set-up's trace, lowering, missed compiles, run start and
first data wait: numbers from a run's ``ctx`` and span ring, 0.0 where the
program keeps the record and nothing happened, None where it keeps none (an
earlier program), and due in every cell."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, contract, spans, train_cell  # noqa: E402

COUNTERS = {"setup_trace_s": "trace_seconds", "setup_lower_s": "lower_seconds",
            "setup_missed_compile_s": "missed_compile_seconds"}
RING = ["setup_run_start_s", "setup_first_data_s"]
READERS = list(COUNTERS) + RING
CELLS = [c["name"] for c in common.manifest()["workloads"]]


@pytest.fixture
def ring(monkeypatch):
    """A span ring of this test's own: other tests of the process train too."""
    from speakingstyle_tpu.obs import trace

    own = trace.SpanRing()
    monkeypatch.setattr(trace, "_process_ring", own)
    return own


def record_run(ring, run_start=1.5, first_wait=0.25, compiles=((2.0, 0.5),)):
    """A run's ring as the program writes it: set-up phases, ``run_start``,
    the first data wait, a first call and its compile records."""
    from speakingstyle_tpu.obs import Span
    from speakingstyle_tpu.obs.trace import new_context

    ctx = new_context("train-under-test")
    for name in ("setup_model_init", "setup_restore", "setup_build_steps",
                 "setup_datasets"):
        Span.record(name, 0.0, 1.0, parent=ctx, ring=ring)
    if run_start is not None:
        Span.record("run_start", 0.0, run_start, parent=ctx, ring=ring)
    if first_wait is not None:
        Span.record("train_data_wait", 0.0, first_wait, parent=ctx, ring=ring)
    call = Span.record("train_dispatch", 0.0, 9.0, parent=ctx, ring=ring,
                       shape=[4, 128, 32])
    for trace_s, lower_s in compiles:
        Span.record("jax_compile", 0.0, 3.0, parent=call, ring=ring,
                    program="jit(step_fn)", outcome="hit", trace_s=trace_s,
                    lower_s=lower_s, step=0)


def read(ctx, cell=CELLS[0]):
    out = train_cell.read_per_layer(cell, {"workload": cell, **ctx})
    return {n: out[n]["value"] if n in out else None for n in READERS}


@pytest.mark.parametrize("cell", CELLS)
def test_the_five_are_due_in_every_cell(cell):
    man = common.manifest()
    assert set(READERS) <= set(contract.readers_of(man, cell))
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in READERS:
        e = entries[name]
        assert (e["layer"], e["moves"], e["unit"]) == ("entry points", "setup_s", "s")
        assert "workloads" not in e
    assert contract.reader_problems(man, ROOT) == []


@pytest.mark.parametrize("cell", CELLS)
def test_readers_take_the_counters_at_window_open_and_the_runs_ring(cell, ring):
    """The counters as ``compile_totals`` names them, the ring's spans."""
    record_run(ring)
    opened = {"compiles": 9.0, "compile_seconds": 20.0, "trace_seconds": 31.5,
              "lower_seconds": 7.25, "missed_compile_seconds": 0.0,
              "cache_misses": 0.0, "cache_hits": 9.0, "cache_requests": 9.0}
    got = read({"compiles_open": opened}, cell)
    assert got == {"setup_trace_s": 31.5, "setup_lower_s": 7.25,
                   "setup_missed_compile_s": 0.0, "setup_run_start_s": 1.5,
                   "setup_first_data_s": 0.25}


def test_nothing_happened_reads_nought_and_an_earlier_program_none(ring):
    # the program keeps the counters and the run's span: 0.0 where nothing
    # was traced, lowered or missed, and no first wait was recorded
    record_run(ring, first_wait=None)
    zeros = dict.fromkeys(COUNTERS.values(), 0.0)
    assert read({"compiles_open": zeros}) == {
        "setup_trace_s": 0.0, "setup_lower_s": 0.0,
        "setup_missed_compile_s": 0.0, "setup_run_start_s": 1.5,
        "setup_first_data_s": 0.0}
    # an earlier program: no such counter, no run_start and its first wait
    # never in the ring
    ring.clear()
    record_run(ring, run_start=None, first_wait=None)
    old = {"compiles": 9.0, "compile_seconds": 20.0, "cache_hits": 9.0,
           "cache_requests": 9.0}
    assert read({"compiles_open": old}) == dict.fromkeys(READERS)


def test_compile_records_of_a_first_call_add_up_under_it(ring):
    """What PERF.md's set-up accounts read: the child ``jax_compile`` records
    of each first ``train_dispatch``, found by their parent."""
    record_run(ring, compiles=((2.0, 0.5), (0.25, 0.125)))
    run = spans.run_spans()
    (call,) = [s for s in run if s["name"] == "train_dispatch"]
    kids = [s for s in run if s["name"] == "jax_compile"
            and s["parent_span_id"] == call["span_id"]]
    assert sum(k["fields"]["trace_s"] + k["fields"]["lower_s"] + k["duration_s"]
               for k in kids) == pytest.approx(2.0 + 0.5 + 0.25 + 0.125 + 6.0)
