#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, and hands them to the driver the traffic file names (a module of
``benchmark/harness/``). Prints one JSON
line last on standard output. ``--toy 1`` lays the files' ``toy`` blocks over
them and skips the look for a chip: the CPU rehearsal, never a measurement.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import common  # noqa: E402  (starts the set-up clock)


def main(argv=None, **options):
    """``options`` go to the driver as they are: the builder's control and
    fault readings (``benchmark/tools/runs.py``), never a measured run."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--toy", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The persistent compile cache sits inside the checkout at a fixed path
    # and is not held to a size, for this process and for every child: the
    # program takes the directory the environment names (obs/jaxmon), so only
    # the first run of a cell in a checkout compiles. Set before JAX is
    # imported anywhere.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(common.ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if not os.path.isdir(os.path.join(common.ROOT, "speakingstyle_tpu")):
        common.log("the program (speakingstyle_tpu/) is not in this directory: "
                   "nothing to measure")
        return 3
    traffic = common.cell_files(args.workload)[3]
    import importlib

    driver = importlib.import_module(
        "benchmark.harness." + traffic["driver"])
    return driver.run(args.workload, args.seed, args.seconds, bool(args.trace),
                      toy=bool(args.toy), **options)


if __name__ == "__main__":
    sys.exit(main())
