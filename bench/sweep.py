#!/usr/bin/env python3
"""Find the highest open-loop rate a cell sustains, once, on the chip.

    python bench/sweep.py --config <config> --traffic <mix> --seed <n> \\
        --seconds <s> --rates 100,200,400

One process builds the configuration and warms the mix's shapes once,
then offers each rate for ``--seconds`` in turn and prints, per rate, the
answered count, p50 / p95 / max latency and how long the queue took to
drain after the window. A rate
is sustained while the drain stays short and p95 stays near p50's order.
The rate a cell runs at is then written into its traffic file; the
benchmark itself never searches for one.
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
from bench import manifest, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates per second")
    args = ap.parse_args(argv)

    m = manifest.load()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU found: the sweep runs on the chip only",
              file=sys.stderr)
        return 2
    cell_run.use_compile_cache(ROOT)
    s = cell_run.Session(manifest.config(m, args.config),
                         manifest.traffic(args.traffic), args.seed)
    s.warm()
    print(f"set-up {time.perf_counter() - T_START:.3f} s", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        w = s.window({"kind": "open", "rate_per_s": rate}, args.seconds)
        if len(w.answered) < w.attempted:
            print(f"rate {rate}: the queue did not drain within "
                  f"{cell_run.DRAIN_S} s of the close; stopping", flush=True)
            return 0
        lat = [1e3 * r.latency for r in w.answered]
        drain = max(r.done for r in w.answered) - args.seconds
        print(json.dumps({
            "rate_per_s": rate, "offered": w.attempted,
            "answered": len(lat), "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95), "max_ms": max(lat),
            "drain_s": drain}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
