"""The yardstick's own arithmetic: the trace reduction on a small recorded
trace, the peaks table, the traffic deck, the operation counts."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, flops, peaks, tracered, trafficgen  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return json.load(f)


def test_trace_reduction_busy_and_window(recorded):
    busy, window = tracered.busy_and_window(recorded["trace"])
    assert busy == pytest.approx(recorded["expected"]["busy_s"], rel=1e-9)
    assert window == pytest.approx(recorded["expected"]["window_s"], rel=1e-9)
    assert 0 < busy <= window


def test_trace_reduction_breakdown_and_kernel(recorded):
    b = tracered.breakdown(recorded["trace"])
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    for name, seconds in b["device_ops"]:
        assert seconds > 0 and len(name) <= 64
        assert not set(name) & set(" ,/")
    calls = tracered.kernel_calls(recorded["trace"])
    assert calls and all(c[4] in (True, False) and c[5] > 0 for c in calls)
    b_, h, d, t, backward, seconds = calls[0]
    ops, nbytes = flops.mha_call(b_, h, d, t, backward)
    pk = peaks.peaks("TPU v5 lite")
    share = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]) / seconds
    assert 0 < share <= 1.0


@pytest.fixture(scope="module")
def probe():
    """A traced probe of the fused kernel on a TPU v5 lite (chip run of PR 28:
    forward and backward of [8, 2, 128, 256] under ``named_scope
    ("probe_scope")``, three times), as the profiler wrote it."""
    return tracered.compact(os.path.join(HERE, "data", "probe_trace"))


def test_compact_keeps_where_in_the_program_a_device_event_comes_from(probe):
    ops = probe["devices"][0]["XLA Ops"]
    # a copy the compiler put in comes from nowhere in the program
    assert len(ops) == 21 and [e[0].split(" ")[0] for e in ops if len(e) == 3] \
        == ["%copy.1"] * 3
    assert {e[3]["tf_op"] for e in ops if len(e) == 4} == {
        "q:", "jit(step)/jvp(probe_scope)/broadcast_in_dim:",
        "jit(step)/jvp(probe_scope)/pallas_call:",
        "jit(step)/transpose(jvp())/convert_element_type:",
        "jit(step)/transpose(jvp(probe_scope))/pallas_call:",
        "jit(step)/transpose(jvp(probe_scope))/add_any:"}
    # host events carry none, and the reductions read either form
    assert all(len(e) == 3 for evs in probe["host"].values() for e in evs)
    busy, window = tracered.busy_and_window(probe)
    assert 0 < busy < window
    assert len(tracered.breakdown(probe)["device_ops"]) == 7


def test_kernel_is_found_by_its_name_and_its_shape_only_read(probe):
    calls = tracered.kernel_calls(probe)
    assert [c[:5] for c in calls] == [(8, 2, 128, 256, False),
                                      (8, 2, 128, 256, True)] * 3
    ops = probe["devices"][0]["XLA Ops"]
    # the name decides: a custom call of the same form under another
    # primitive's name is not the kernel, and without any name the form is
    # all there is (the recorded fixture of PR 24)
    other = [e[:3] + [{"tf_op": "jit(step)/jvp(conv)/other_call:"}]
             if len(e) == 4 and "pallas_call" in e[3]["tf_op"] else e
             for e in ops]
    assert tracered.kernel_calls({"devices": [{"XLA Ops": other}]}) == []
    bare = [e[:3] for e in ops]
    assert tracered.kernel_calls({"devices": [{"XLA Ops": bare}]}) == calls


def test_wire_reader_takes_strings_and_references():
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    entry = lambda key, value: field(1, key) + field(2, value)
    stat_meta = lambda i, name: field(5, entry(i, field(1, i) + field(2, name)))
    by_string = field(5, field(1, 7) + field(5, b"jit(f)/scope/dot_general:"))
    by_ref = field(5, field(1, 8) + field(7, 300))
    other = field(5, field(1, 9) + field(2 << 0, 1))  # a stat nobody asked for
    plane = (field(2, b"/device:TPU:0") + stat_meta(7, b"tf_op")
             + stat_meta(8, b"hlo_op") + stat_meta(9, b"flops")
             + stat_meta(300, b"fusion.1")
             + field(4, entry(1, field(1, 1) + field(2, b"%fusion.1 = f32[]")
                              + by_string + by_ref + other))
             + field(4, entry(2, field(1, 2) + field(2, b"%copy") + other)))
    assert tracered.op_names(field(1, plane) + field(4, b"host")) == {
        "/device:TPU:0": {"%fusion.1 = f32[]": {
            "tf_op": "jit(f)/scope/dot_general:", "hlo_op": "fusion.1"}}}


def test_idle_gaps_named_by_the_innermost_host_event():
    trace = {"devices": [{"XLA Ops": [["a", 0, 1000], ["b", 101_000, 1000]]}],
             "host": {"main": [["outer", 0, 200_000], ["$loader.py:1 read", 10_000, 80_000]]}}
    gaps = tracered.idle_gaps(trace)
    assert gaps == [["loader.py:1_read", pytest.approx(100_000 / 1e9)]]
    busy, window = tracered.busy_and_window(trace)
    assert busy == pytest.approx(2e-6) and window == pytest.approx(102e-6)


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("_source")
    with pytest.raises(KeyError):
        peaks.peaks_or_none("cpu", toy=False)
    assert peaks.peaks_or_none("cpu", toy=True) is None


def _train_spec():
    t = common.load_json("benchmark/traffic/train_ljspeech_lengths.json")
    return {**t["deck"], "batch_size": t["batch_size"],
            "pitch_range": [-2.5, 2.5], "energy_range": [-2.5, 2.5]}


def test_train_deck_is_the_files_whatever_the_seed(tmp_path):
    spec = {**_train_spec(), "utterances": 40, "batch_size": 10}
    lens = []
    for seed in (7, 2 ** 31 + 12345):
        trafficgen.write_corpus(str(tmp_path / str(seed)), spec, seed)
        with open(tmp_path / str(seed) / "train.txt") as f:
            names = [line.split("|")[0] for line in f if line.strip()]
        lens.append([int(np.load(tmp_path / str(seed) / "duration" /
                                 f"S-duration-{n}.npy").sum()) for n in names])
    assert sorted(lens[0]) == sorted(lens[1]) and lens[0] != lens[1]


def test_train_deck_has_no_tie_across_a_batch_boundary():
    spec = _train_spec()
    deck = trafficgen.train_deck(spec)
    n_ph = [n for n, _ in deck]
    assert len(deck) == 800 and n_ph == sorted(n_ph, reverse=True)
    for b in range(spec["batch_size"], len(deck), spec["batch_size"]):
        assert n_ph[b - 1] > n_ph[b]
    frames = [int(d.sum()) for _, d in deck]
    assert 86 <= min(frames) and max(frames) <= 870 and 500 < np.mean(frames) < 580


def test_operation_counts_scale_as_the_equations_do():
    m = common.load_json("benchmark/configs/ljspeech.json")["model"]
    one = flops.acoustic_forward(m, 100, 600, True)
    assert flops.train_step_flops(m, [(100, 600)]) == 3 * one
    # attention is the only quadratic term
    quad = flops.acoustic_forward(m, 100, 1200, True) - 2 * flops.acoustic_forward(m, 100, 600, True)
    assert quad > 0
    ops_f, _ = flops.mha_call(2, 2, 128, 256, False)
    ops_b, _ = flops.mha_call(2, 2, 128, 256, True)
    assert ops_b == 2 * ops_f == 8 * 2 * 2 * 256 * 256 * 128


def test_judge_fails_a_missing_or_excess_reading():
    ok, compared = common.judge({"a": 0.1, "b": 0.0}, {"a": 0.2, "b": 0.0})
    assert ok and compared["a"] == {"value": 0.1, "limit": 0.2}
    assert not common.judge({"a": 0.3}, {"a": 0.2})[0]
    assert not common.judge({}, {"a": 0.2})[0]
    assert not common.judge({"a": float("nan")}, {"a": 0.2})[0]
