"""Benchmark orchestrator. One section per paper table/figure, plus kernel and
roofline sections for the JAX framework layers.

Prints ``name,us_per_call,derived`` CSV rows and writes artifacts/bench.json.
Every run also writes artifacts/telemetry.json (``repro.obs`` report):
environment metadata (jax/jaxlib version, backend, host), per-section wall
times, and whatever counters/spans the instrumented layers emitted while
telemetry was on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced repeats")
    ap.add_argument("--sections", default="all",
                    help="comma list: fig2ab,fig2cd,fig2ef,tables,alg4,"
                         "dispatch,compressruns,kernels,fused,jax,robust,"
                         "store,obs,search")
    args = ap.parse_args()

    from .compile_cache import use_compile_cache
    use_compile_cache()

    from . import paper_figures as pf

    sections = args.sections.split(",") if args.sections != "all" else [
        "fig2ab", "fig2cd", "fig2ef", "tables", "alg4", "dispatch",
        "compressruns", "kernels", "fused", "jax", "robust", "store", "obs",
        "search"]
    rows = []
    section_s = {}

    # telemetry on for the whole run: counters/spans from the instrumented
    # layers land in artifacts/telemetry.json. The obs overhead A/B opens
    # its own telemetry_scope(on=False) windows, so its disabled-path
    # timings are not contaminated by this.
    try:
        import repro.obs as obs
        obs.enable()
    except ImportError:
        obs = None

    def run(name, fn):
        if name in sections:
            print(f"# --- {name} ---", file=sys.stderr, flush=True)
            t0 = time.perf_counter()
            try:
                rows.extend(fn())
            except ImportError:
                print(f"# {name} section unavailable", file=sys.stderr)
                return
            dt = time.perf_counter() - t0
            section_s[name] = round(section_s.get(name, 0.0) + dt, 3)
            print(f"# --- {name} done in {dt:.1f}s ---", file=sys.stderr,
                  flush=True)

    r = 2 if args.quick else 3
    run("fig2ab", lambda: pf.fig2ab_compression(repeats=r))
    run("fig2cd", lambda: pf.fig2cd_ops(repeats=2 if args.quick else 5))
    run("fig2cd", lambda: pf.fig2cd_streaming_crosscheck(repeats=r))
    run("fig2ef", lambda: pf.fig2ef_append_remove(n_updates=100 if args.quick else 200))
    run("tables", lambda: pf.tables_realdata(
        n_bitmaps=30 if args.quick else 60, n_pairs=15 if args.quick else 30))
    run("alg4", lambda: pf.alg4_many_way_union(repeats=r))
    run("dispatch", lambda: pf.dispatch_ab_sweep(repeats=r))
    run("compressruns", lambda: pf.run_compression())

    def _kernels():
        from . import kernel_bench
        return kernel_bench.run(quick=args.quick)

    def _fused():
        from . import kernel_bench
        return kernel_bench.fused_ab(quick=args.quick)

    def _jax():
        from . import jax_bench
        return jax_bench.run(quick=args.quick)

    def _robust():
        from . import robust_bench
        return robust_bench.run(quick=args.quick)

    def _store():
        from . import store_bench
        return store_bench.run(quick=args.quick)

    def _obs():
        from . import obs_bench
        return obs_bench.run(quick=args.quick)

    def _search():
        from . import search_bench
        return search_bench.run(quick=args.quick)

    run("kernels", _kernels)
    run("fused", _fused)
    run("jax", _jax)
    run("robust", _robust)
    run("store", _store)
    run("obs", _obs)
    run("search", _search)

    print("name,us_per_call,derived")
    for name, t, d in rows:
        print(f"{name},{t},{d}")

    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/bench.json", "w") as f:
        json.dump([{"name": n, "us_per_call": t, "derived": d}
                   for n, t, d in rows], f, indent=1)

    if obs is not None:
        from repro.obs import report as _report
        _report.write_report("artifacts/telemetry.json",
                             extra={"sections": section_s})
        obs.disable()
        print("# wrote artifacts/telemetry.json "
              f"(sections: {section_s})", file=sys.stderr)


if __name__ == "__main__":
    main()
