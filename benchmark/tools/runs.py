#!/usr/bin/env python3
"""The builder's tool: run a list of benchmark runs one after another (one
chip call), each a process of its own, and keep what each printed under
``chiprun_out/<tag>/``. A run may carry ``options`` for the driver (the
control and fault readings); the benchmark's own command takes none.

    python3 benchmark/tools/runs.py TAG '[{"workload": ..., "seed": ...,
        "seconds": ..., "trace": 0, "options": {...}, "name": "a1"}, ...]' [STOP_AFTER_S]

A run is not started once ``STOP_AFTER_S`` seconds of the call are gone.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def child(argv, options):
    sys.path.insert(0, ROOT)
    from benchmark import run

    return run.main(argv, **options)


def main():
    if sys.argv[1] == "--child":
        sys.exit(child(json.loads(sys.argv[2]), json.loads(sys.argv[3])))
    tag, runs = sys.argv[1], json.loads(sys.argv[2])
    stop_after = float(sys.argv[3]) if len(sys.argv) > 3 else float("inf")
    t_call = time.time()
    out = os.path.join(os.environ.get("RUNS_OUT", ROOT), "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    for r in runs:
        name = r.get("name") or f"{r['workload']}.{r['seed']}"
        if time.time() - t_call > stop_after:
            print(f"== {name} not started: {time.time() - t_call:.0f} s gone")
            continue
        argv = ["--workload", r["workload"], "--seed", str(r["seed"]),
                "--seconds", str(r["seconds"]), "--trace", str(r.get("trace", 0)),
                "--toy", str(r.get("toy", 0))]
        if r.get("options"):
            cmd = [sys.executable, os.path.abspath(__file__), "--child",
                   json.dumps(argv), json.dumps(r["options"])]
        else:  # the benchmark's command as the driver runs it
            cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py")] + argv
        t0 = time.time()
        with open(os.path.join(out, name + ".out"), "w") as fo, \
                open(os.path.join(out, name + ".err"), "w") as fe:
            rc = subprocess.call(cmd, cwd=ROOT, stdout=fo, stderr=fe)
        wall = time.time() - t0
        with open(os.path.join(out, name + ".err"), errors="replace") as f:
            err = [l for l in f.read().splitlines() if "Warning" not in l
                   and "warnings.warn" not in l]
        with open(os.path.join(out, name + ".out"), errors="replace") as f:
            last = (f.read().strip().splitlines() or [""])[-1]
        print(f"== {name} rc={rc} wall={wall:.0f}s")
        for l in err[-18:]:
            print("   ", l[:600])
        try:
            d = json.loads(last)
            d.pop("breakdown", None)
            print("   ", json.dumps({k: d[k] for k in ("correct", "attempted",
                                                        "failed", "metrics", "device")})[:1500])
        except (ValueError, KeyError):
            print("    last line:", last[:600])
        sys.stdout.flush()


if __name__ == "__main__":
    main()
