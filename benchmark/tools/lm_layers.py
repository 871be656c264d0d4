#!/usr/bin/env python3
"""The builder's tool for the ``decoder_lm`` family's per-layer readings that
``BENCHMARK.json`` does not list yet (``lm_flops.LAYER_READINGS``): the
benchmark's own command, with each reading computed from the same ``ctx`` the
listed readers get and written to standard error as ``lm_layer <name>:
<value>``; the result line is the command's, unchanged. With ``LM_LAYERS_KEEP``
set to a path, what the readings were computed from (compact trace, events,
device, peaks) is kept there as gzipped JSON, and ``--from PATH`` computes the
readings again from such a file, on any machine.

    python3 benchmark/tools/lm_layers.py --workload W --seed N --seconds S --trace 1
    python3 benchmark/tools/lm_layers.py --from chiprun_out/x.ctx.json.gz [WORKLOAD]

(``WORKLOAD`` for a file kept before the cell's name was kept with it.)
"""

import gzip
import json

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import lm_flops, train_cell  # noqa: E402
from benchmark.harness.common import log  # noqa: E402


KEPT = ("workload", "trace", "events", "device", "peaks", "cycles_s", "window_s",
        "log_step")


def say(ctx):
    for name, read in lm_flops.LAYER_READINGS.items():
        log(f"lm_layer {name}: {read(ctx)}")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--from"]:
        with gzip.open(argv[1], "rt") as f:
            ctx = json.load(f)
        if len(argv) > 2:
            ctx["workload"] = argv[2]
        say(ctx)
        return 0
    listed = train_cell.read_per_layer

    def with_layers(workload, ctx):
        ctx["workload"] = workload
        say(ctx)
        keep = os.environ.get("LM_LAYERS_KEEP")
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            with gzip.open(keep, "wt") as f:
                json.dump({k: ctx.get(k) for k in KEPT}, f)
        return listed(workload, ctx)

    train_cell.read_per_layer = with_layers
    try:
        return run.main(argv)
    finally:
        train_cell.read_per_layer = listed


if __name__ == "__main__":
    sys.exit(main())
