#!/usr/bin/env python3
"""The builder's tool: where one run's wall time goes, set-up, window and
what comes after it (the driver stops a run at its time limit, and most of a
large state's run is outside the window). Runs the benchmark's command line
in a process of its own, keeps every line it prints under
``chiprun_out/<tag>/<name>.log`` with the seconds since the run's start in
front of it, and inside the run puts a clock around what no span of the
program and no line of the harness times: the seeded weights, each part of a
checkpoint's save and restore, the comparison's arithmetic. The run's
``events.jsonl`` is kept beside the log.

    python3 benchmark/tools/phases.py TAG NAME --workload ... --seed ...
        --seconds ... --trace 0
"""

import functools
import glob
import json
import os
import shutil
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def clock_phases(keep_events_as):
    """Each logs ``phase <name>: <seconds> s`` when it returns."""
    from benchmark.harness import common, train_cell, train_compare
    from benchmark.reference import mellum2
    from speakingstyle_tpu.training.checkpoint import CheckpointManager

    def timed(owner, attr, name):
        inner = getattr(owner, attr)

        @functools.wraps(inner)
        def outer(*a, **k):
            t = time.time()
            try:
                return inner(*a, **k)
            finally:
                common.log(f"phase {name}: {time.time() - t:.1f} s")
        setattr(owner, attr, outer)

    timed(mellum2, "init_params", "init_params")
    timed(train_cell, "save_seed_checkpoint", "save_seed_checkpoint")
    for attr in ("save", "_write", "_write_manifest", "_restore_step",
                 "_verify_restored"):
        timed(CheckpointManager, attr, "checkpoint." + attr.lstrip("_"))
    timed(train_compare, "compare_training", "compare_training")
    emit = common.emit_result

    def emit_and_keep(*a, **k):
        logs = glob.glob(os.path.join(common.ROOT, ".bench_work", "run-*", "log",
                                      "events.jsonl"))
        if logs:  # this run's: the newest
            shutil.copy(max(logs, key=os.path.getmtime), keep_events_as)
        return emit(*a, **k)
    common.emit_result = emit_and_keep


def child(argv, keep_events_as):
    sys.path.insert(0, ROOT)
    from benchmark import run

    clock_phases(keep_events_as)
    return run.main(argv)


def run_stamped(cmd, base):
    """Run ``cmd``; what it prints to ``base``.out and ``base``.err as
    printed, and every line of both to ``base``.log behind the seconds since
    its start. Returns (exit code, wall seconds)."""
    t0, lock = time.time(), threading.Lock()
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    with open(base + ".log", "w") as merged:
        def pump(stream, path, tag):
            with open(path, "wb") as raw:
                for line in stream:
                    raw.write(line)
                    text = line.decode(errors="replace").rstrip("\n")[:400]
                    with lock:
                        merged.write(f"[{time.time() - t0:7.1f} {tag}] {text}\n")
                        merged.flush()
        pumps = [threading.Thread(target=pump, args=(proc.stdout, base + ".out", "o")),
                 threading.Thread(target=pump, args=(proc.stderr, base + ".err", "e"))]
        for t in pumps:
            t.start()
        rc = proc.wait()
        for t in pumps:
            t.join()
    return rc, time.time() - t0


def main():
    if sys.argv[1] == "--child":
        sys.exit(child(json.loads(sys.argv[2]), sys.argv[3]))
    tag, name, argv = sys.argv[1], sys.argv[2], sys.argv[3:]
    out = os.path.join(os.environ.get("RUNS_OUT", ROOT), "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    base = os.path.join(out, name)
    rc, wall = run_stamped([sys.executable, os.path.abspath(__file__), "--child",
                            json.dumps(argv), base + ".events.jsonl"], base)
    print(f"== {name} rc={rc} wall={wall:.0f}s")
    with open(base + ".log", errors="replace") as f:
        for line in f:
            if "Warning" not in line and "warnings.warn" not in line:
                print("   ", line.rstrip("\n")[:300])
    return rc


if __name__ == "__main__":
    sys.exit(main())
