#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: build the cell's data from the seed and the program over it, warm
every shape the traffic uses (all of that is ``setup_s``), offer the
traffic for ``--seconds``, then check what the window served against the
plain reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` records the program's spans and a profiler trace of the
window and reports its per-layer metrics. The last line of standard output
is the result; the numbers compared, each beside its limit, are the last
lines of standard error. Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import cell as cell_run  # noqa: E402
from bench import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    m = manifest.load()
    cell = manifest.cell(m, args.workload)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"no TPU found (platform {platform!r}): the benchmark runs on "
              "the chip only", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"cell {cell['name']!r} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cell_run.use_compile_cache(ROOT)
    result, checks = cell_run.run(m, cell, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  devices[: cell["chips"]])
    for line in cell_run.check_lines(checks):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
