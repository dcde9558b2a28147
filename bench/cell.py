"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the metrics. ``bench/run.py`` calls ``run`` after it
has found the chip; the CPU tests call it directly at a tiny size."""

from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import tempfile
import time
from contextlib import nullcontext
from typing import Callable, List, NamedTuple, Optional


from bench import loadgen, manifest, peaks
from bench import trace as tr

DRAIN_S = 60.0          # an answer due in the window is waited for this long


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``. Every program is cached,
    however quick its compile, so a run after the first compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts JAX traces, backend compiles (persistent-cache loads
    included) and persistent-cache misses (programs compiled anew)."""

    def __init__(self):
        import jax

        self.traces = self.compiles = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


@dataclasses.dataclass
class Context:
    """What a metric reader reads. Times are seconds."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float                      # the measured window
    setup_s: float
    records: List[loadgen.Record]       # every request offered
    spans: List[Span]                   # the program's, drained per step
    counters: dict
    reduced: Optional[tr.Reduced]       # the device trace, when traced
    least_bytes: Optional[float]        # of the completed requests
    peak: Optional[dict]                # the chip's row of bench/peaks.py

    @property
    def completed(self) -> List[loadgen.Record]:
        return [r for r in self.records if r.answered]

    def latencies_ms(self) -> List[float]:
        return [r.latency * 1e3 for r in self.completed]

    def span_self_s(self, *names: str) -> float:
        return sum(s.self_s for s in self.spans if s.name in names)

    def idle_pct(self) -> Optional[float]:
        if self.reduced is None or self.reduced.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.reduced.busy_s / self.reduced.window_s)

    def hbm_roofline_pct(self) -> Optional[float]:
        """Least bytes over peak bandwidth, as a share of device busy time."""
        if (self.reduced is None or self.peak is None or not self.least_bytes
                or self.reduced.busy_s <= 0):
            return None
        least_s = self.least_bytes / self.peak["hbm_bytes_per_s"]
        return 100.0 * least_s / self.reduced.busy_s


class GcPauses:
    """Durations of the collector's passes while it is attached."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        return False


class Span(NamedTuple):
    """One of the program's spans: self time, attributes, and its interval
    on the ``time.monotonic`` clock."""

    name: str
    self_s: float
    attrs: dict
    t0: float
    t1: float


def _flatten(span, out: list) -> None:
    kids = sum(c.duration_s for c in span.children)
    out.append(Span(span.name, span.duration_s - kids, dict(span.attrs),
                    span.t0, span.t1))
    for c in span.children:
        _flatten(c, out)


class _Drained:
    """The server, with the program's spans drained after every step (the
    program keeps at most 4,096 root spans)."""

    def __init__(self, server, spans: list):
        self.server, self.spans = server, spans

    def submit(self, request):
        return self.server.submit(request)

    def pending(self) -> int:
        return self.server.pending()

    def step(self):
        import repro.obs as obs

        out = self.server.step()
        for root in obs.span_trees():
            _flatten(root, self.spans)
        obs.reset_traces()
        return out


class Session:
    """A cell's data, the program over it, and the requests its traffic
    draws: everything that set-up builds from the seed. ``server(config,
    data)`` replaces the program (the control puts the reference in its
    place)."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 log: Callable = print, server: Callable = None):
        self.config, self.traffic, self.seed, self.log = (config, traffic,
                                                          seed, log)
        self.ref_mod = manifest.reference(config["system"])
        t0 = time.perf_counter()
        self.data = self.ref_mod.Data(config, seed)
        t_data = time.perf_counter() - t0
        if server is None:
            self.server = manifest.system(config["system"]).Server(
                config, self.data)
            log(f"set-up: data {t_data:.3f} s, program build "
                f"{time.perf_counter() - t0 - t_data:.3f} s, resident "
                f"{self.server.resident_bytes()} bytes")
        else:
            self.server = server(config, self.data)
        # a template sees the traffic's own keys beneath its own
        common = {k: v for k, v in traffic.items()
                  if k not in ("mix", "name")}
        self.mix = [{**common, **t} for t in traffic["mix"]]
        self.weights = [float(t["weight"]) for t in self.mix]
        self.compiles = CompileCounter()

    def make(self, key, t: int):
        """The request template ``t`` describes, drawn by ``key``."""
        return self.ref_mod.request(self.config, self.data, self.mix[t],
                                    loadgen._rng(self.seed, *key))

    def warm(self) -> None:
        t0 = time.perf_counter()
        self.server.warm(
            lambda t, b: [self.make((3, t, b, j), t) for j in range(b)],
            len(self.mix), self.log)
        c = self.compiles
        self.log(f"set-up: warm-up {time.perf_counter() - t0:.3f} s, "
                 f"{c.compiles} compiles of which {c.cache_misses} missed "
                 f"the persistent cache ("
                 f"{'a cold run' if c.cache_misses else 'a warm run'}), "
                 f"{c.traces} traces")

    def window(self, arrivals: dict, seconds: float, trace: bool = False,
               is_device_op: Callable = tr.tpu_ops, on_start=None):
        """Offer ``arrivals`` for ``seconds``; returns the ``Window``.
        ``on_start()`` runs just before the first request is offered."""
        import jax

        seed = self.seed
        if arrivals["kind"] == "open":
            due, tmpl = loadgen.open_schedule(
                float(arrivals["rate_per_s"]), seconds, self.weights, seed)
            requests = [self.make((2, i), int(t))
                        for i, t in enumerate(tmpl)]

            def make(i, t):
                return requests[i]
        else:
            def make(i, t):
                return self.make((2, i), t)
        spans: list = []
        annotate = (lambda name: jax.profiler.TraceAnnotation(
            "bench." + name)) if trace else (lambda name: nullcontext())
        target = _Drained(self.server, spans) if trace else self.server
        loop = loadgen.Loop(target, make, annotate)
        log_dir = tempfile.TemporaryDirectory() if trace else nullcontext()
        with log_dir as log_path:
            if trace:
                import repro.obs as obs

                obs.reset_traces()
                obs.enable()
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0     # harness spans only
                options.host_tracer_level = 1
                jax.profiler.start_trace(log_path, profiler_options=options)
            n_compiles, n_traces = self.compiles.compiles, \
                self.compiles.traces
            # what set-up built lives for the whole run: keep the
            # collector's passes in the window from scanning it again
            gc.collect()
            gc.freeze()
            if on_start is not None:
                on_start()
            with annotate("window"), GcPauses() as pauses:
                mono_start = time.monotonic()
                if arrivals["kind"] == "open":
                    loop.open_loop(due, tmpl, seconds, DRAIN_S)
                else:
                    loop.closed_loop(int(arrivals["clients"]),
                                     loadgen.closed_templates(self.weights,
                                                              seed),
                                     seconds, DRAIN_S)
            w = Window(seconds, loop.records,
                       len(due) if arrivals["kind"] == "open"
                       else len(loop.records), spans,
                       self.compiles.compiles - n_compiles,
                       self.compiles.traces - n_traces, loop.lateness(),
                       pauses.pauses, loop.step_s)
            gc.unfreeze()
            if trace:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                self.log(f"trace: stopped in {time.perf_counter() - t0:.3f} s")
                obs.disable()
                t0 = time.perf_counter()
                trace_ = tr.load(tr.find_xplane(log_path), is_device_op)
                # the program's spans, moved from the monotonic clock onto
                # the trace's by the window's start on both
                shift = trace_.window()[0] - mono_start * 1e9
                trace_.host += [(tr.PREFIX + sp.name, sp.t0 * 1e9 + shift,
                                 sp.t1 * 1e9 + shift) for sp in spans]
                w.reduced = tr.reduce(trace_)
                self.log(f"trace: read in {time.perf_counter() - t0:.3f} s")
        self.log(w.summary())
        return w


@dataclasses.dataclass
class Window:
    seconds: float
    records: List[loadgen.Record]
    attempted: int
    spans: list
    compiles: int
    traces: int
    lateness: List[float]
    gc_pauses: List[float]
    step_s: List[float]
    reduced: Optional[tr.Reduced] = None

    @property
    def answered(self) -> List[loadgen.Record]:
        return [r for r in self.records if r.answered]

    def summary(self) -> str:
        late = self.lateness or [0.0]
        lat = sorted(r.latency for r in self.answered) or [0.0]
        steps = sorted(self.step_s) or [0.0]
        return (f"window: {self.seconds} s, {self.attempted} requests "
                f"offered, {len(self.answered)} answered, latency p50 "
                f"{1e3 * lat[len(lat) // 2]:.3f} ms max {1e3 * lat[-1]:.3f} "
                f"ms; compiles in the window {self.compiles} (traces "
                f"{self.traces}); generator lateness median "
                f"{1e3 * statistics.median(late):.3f} ms, max "
                f"{1e3 * max(late):.3f} ms; {len(steps)} steps, median "
                f"{1e3 * steps[len(steps) // 2]:.3f} ms, longest "
                f"{', '.join(f'{1e3 * x:.3f}' for x in steps[-3:])} ms; "
                f"{len(self.gc_pauses)} collector passes, longest "
                f"{1e3 * max(self.gc_pauses or [0]):.3f} ms")


def run(m: dict, cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float, devices, *, config: Optional[dict] = None,
        traffic: Optional[dict] = None, log: Callable = print,
        is_device_op: Callable = tr.tpu_ops, patch: Callable = None):
    """One run; returns ``(result, checks)``. ``config`` / ``traffic``
    replace the manifest's (the CPU tests' tiny sizes); ``patch(server)``
    may break the timed path (the tests' planted faults)."""
    import jax

    config = config or manifest.config(m, cell["config"])
    traffic = traffic or manifest.traffic(cell["traffic"])
    metrics = manifest.cell_metrics(m, cell["name"], trace)
    readers = {e["name"]: manifest.metric_reader(e["name"]) for e in metrics}
    platform, kind = devices[0].platform, devices[0].device_kind
    peak = peaks.peak(kind) if platform == "tpu" else None

    s = Session(config, traffic, seed, log)
    s.warm()
    if patch is not None:
        patch(s.server)
    if trace:
        # a trace of a whole long window takes minutes to write and read
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    setup = {}
    w = s.window(traffic["arrivals"], seconds, trace, is_device_op,
                 on_start=lambda: setup.update(
                     s=time.perf_counter() - t_start))
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    counters = s.server.counters()
    log(f"counters: {counters}")
    s.server.close()
    s.server = None

    t0 = time.perf_counter()
    ref = s.ref_mod.Reference(config, s.data)
    checks, n_checked, wrong_examples = check(ref, w, traffic, seed)
    log(f"check: reference over {n_checked} of the answers in "
        f"{time.perf_counter() - t0:.3f} s" +
        (f"; wrong, e.g. {wrong_examples}" if wrong_examples else ""))
    least = None
    if trace:
        least = float(sum(ref.least_bytes(r.request) for r in w.answered))
    ctx = Context(cell, config, traffic, seconds, setup["s"], w.records,
                  w.spans, counters, w.reduced, least, peak)
    values = {name: read(ctx) for name, read in readers.items()}
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": w.attempted,
        "failed": checks["missing"][0] + checks["errors"][0],
        "metrics": {e["name"]: {"value": values[e["name"]],
                                "unit": e["unit"]}
                    for e in metrics if values[e["name"]] is not None},
        "device": {"platform": platform, "kind": kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(memory_peak)},
    }
    if w.reduced is not None:
        result["device"].update(busy_s=w.reduced.busy_s,
                                window_s=w.reduced.window_s)
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in w.reduced.top_ops],
            "idle_gaps": [[n, t] for n, t in w.reduced.idle_gaps]}
        log(f"trace: idle by host activity {w.reduced.idle_by_activity}; "
            f"least bytes {least}")
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}
    return result, checks


def check(ref, w: Window, traffic: dict, seed: int):
    """Compare a sample of the answers, drawn from the seed, with the
    reference. Returns ``{name: (value, limit)}``, the number of answers
    compared, and a few wrong answers for the log."""
    answered = w.answered
    errors = sum(1 for r in w.records if r.error)
    n = min(len(answered), int(traffic["check_sample"]))
    rng = loadgen._rng(seed, 4)
    picked = sorted(rng.choice(len(answered), n, replace=False).tolist())
    wrong, examples = 0, []
    for i in picked:
        r = answered[i]
        if not ref.same(r.request, r.result, ref.answer(r.request)):
            wrong += 1
            if len(examples) < 3:
                examples.append((r.request, r.result))
    checks = {"wrong": (wrong, 0),
              "missing": (w.attempted - len(answered) - errors, 0),
              "errors": (errors, 0)}
    return checks, n, examples


def check_lines(checks: dict) -> List[str]:
    return [f"check {k}: {v} (limit {lim})" for k, (v, lim) in checks.items()]
