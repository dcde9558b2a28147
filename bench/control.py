#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference put in
the program's place, answering in the precision below the one the
configuration guarantees, through the cell's own window and check.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

The configuration's ``control_precision`` names that precision (counts and
scores rounded to bfloat16). Each seed prints
the numbers the check compares beside their limits and whether the run
would count as correct; the control has to come out as not correct. It
touches no device, and the benchmark's own runs never run it.
"""

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import cell as cell_run  # noqa: E402
from bench import manifest  # noqa: E402


class ControlServer:
    """Answers each request from the reference in lower precision, one per
    step, oldest first."""

    def __init__(self, ref, precision: str):
        self.ref, self.precision = ref, precision
        self.queue = collections.deque()
        self.next = 0

    def submit(self, request) -> int:
        self.next += 1
        self.queue.append((self.next, request))
        return self.next

    def step(self):
        handle, request = self.queue.popleft()
        return [(handle, self.ref.serve(request, self.precision))]

    def pending(self) -> int:
        return len(self.queue)

    def warm(self, requests_of_template, n_templates: int, log) -> None:
        pass


def run_control(config: dict, traffic: dict, seed: int, seconds: float,
                log=print) -> dict:
    """One seed of the control: ``{name: (value, limit)}`` of the check."""
    ref_mod = manifest.reference(config["system"])
    precision = config["control_precision"]
    s = cell_run.Session(
        config, traffic, seed, log,
        server=lambda c, data: ControlServer(ref_mod.Reference(c, data),
                                             precision))
    w = s.window(traffic["arrivals"], seconds)
    checks, n, _ = cell_run.check(ref_mod.Reference(config, s.data), w,
                                  traffic, seed)
    log(f"control seed {seed}: {n} answers compared")
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    config = manifest.config(m, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    for seed in [int(x) for x in args.seeds.split(",")]:
        checks = run_control(config, traffic, seed, args.seconds)
        print(json.dumps({
            "seed": seed, "precision": config["control_precision"],
            "correct": all(v <= lim for v, lim in checks.values()),
            "check": {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
