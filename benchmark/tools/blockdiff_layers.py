#!/usr/bin/env python3
"""``lm_layers.py`` for the cell that trains by block diffusion: the
benchmark's own command (or ``--from`` a kept ``ctx``), with the readings of
``blockdiff_flops.LAYER_READINGS`` written to standard error as ``lm_layer
<name>: <value>``; the result line is the command's, unchanged.

    python3 benchmark/tools/blockdiff_layers.py --workload W --seed N --seconds S --trace 1
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import blockdiff_flops, common  # noqa: E402


def main(argv=None):
    tool = common.load_module("benchmark/tools/lm_layers.py", "bench_tool_lm_layers")

    def say(ctx):
        for name, read in blockdiff_flops.LAYER_READINGS.items():
            common.log(f"lm_layer {name}: {read(ctx)}")

    tool.say = say
    return tool.main(argv)


if __name__ == "__main__":
    sys.exit(main())
