"""The telemetry plane: spans, metrics, launch accounting, cost contract.

Covers the PR 9 acceptance surface:

  * span nesting / error marking / thread-local isolation, and the
    no-allocation no-op path when tracing is disabled;
  * the metrics registry (labels, ``total`` cross-label sums, histogram
    buckets, snapshot shape);
  * measured kernel-launch counters == the analytic model
    (``index.launch_model`` / ``fused.plan_stats``) for fused N=4/16 trees;
  * fallback rungs appearing as errored child spans under injected faults,
    with the ladder counters migrated onto the registry;
  * the ``degradation_stats()`` shim: warns, mirrors the registry, and no
    ``src/`` module calls it (AST-proved);
  * ``BitmapStore`` cache stats with eager-ladder fallbacks counted
    separately from cold compiles;
  * the off-by-default overhead guard: telemetry disabled stays within 5%
    of the pre-telemetry query body (median of alternating-order trials,
    the ``api_ab`` methodology).
"""

import ast
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import repro.obs as obs
from repro import index, roaring
from repro.kernels.roaring import ops as kops
from repro.obs import metrics as obs_metrics

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.disable()
    obs.reset_metrics()
    obs.reset_traces()
    yield
    obs.disable()
    obs.reset_metrics()
    obs.reset_traces()


# =============================================================================
# tracing
# =============================================================================

def test_span_nesting_and_events():
    obs.enable()
    with obs.span("outer", who="test") as sp:
        assert obs.current_span() is sp
        sp.add_event("tick", n=1)
        with obs.span("inner") as child:
            assert obs.current_span() is child
        time.sleep(0.001)
    trees = obs.span_trees()
    assert len(trees) == 1
    root = trees[0]
    assert root.name == "outer" and root.status == "ok"
    assert root.attrs["who"] == "test"
    assert root.duration_s >= 0.001
    assert [c.name for c in root.children] == ["inner"]
    assert root.children[0].duration_s is not None
    assert [e["name"] for e in root.events] == ["tick"]
    d = root.to_dict()
    json.dumps(d)                           # exportable as-is
    assert d["children"][0]["name"] == "inner"


def test_span_error_status_propagates_exception():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("no")
    (root,) = obs.span_trees()
    assert root.status == "error"
    assert root.attrs["error"] == "ValueError"


def test_disabled_spans_are_shared_noop():
    assert not obs.enabled()
    with obs.span("invisible") as sp:
        sp.set_attr("x", 1)
        sp.add_event("y")
        inner = obs.span("nested").__enter__()
        assert inner is sp                   # the one shared null span
    assert obs.current_span() is None
    assert obs.span_trees() == []


def test_spans_are_thread_local():
    obs.enable()
    seen = {}

    def worker():
        with obs.span("thread-root"):
            seen["inner"] = obs.current_span().name

    with obs.span("main-root"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert obs.current_span().name == "main-root"
    assert seen["inner"] == "thread-root"
    names = sorted(s.name for s in obs.span_trees())
    assert names == ["main-root", "thread-root"]  # two roots, no nesting


def test_record_span_files_a_root_with_its_times():
    obs.record_span("search.queued", 1.0, 2.0, rid=3)    # disabled: nothing
    assert obs.span_trees() == []
    obs.enable()
    with obs.span("outer"):
        obs.record_span("search.queued", 10.0, 12.5, rid=7)
    queued, outer = obs.span_trees()
    assert (queued.name, queued.t0, queued.t1) == ("search.queued", 10.0,
                                                   12.5)
    assert queued.duration_s == 2.5 and queued.status == "ok"
    assert queued.attrs == {"rid": 7} and queued.children == []
    assert outer.name == "outer" and outer.children == []


def test_a_dropped_root_is_counted(monkeypatch):
    from repro.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "_MAX_ROOT_SPANS", 3)
    obs.enable()
    for i in range(5):
        with obs.span(f"s{i}"):
            pass
    assert [r.name for r in obs.span_trees()] == ["s2", "s3", "s4"]
    assert obs.registry().value("obs.spans_dropped") == 2
    obs.record_span("late", 0.0, 1.0)
    assert obs.registry().value("obs.spans_dropped") == 3


# =============================================================================
# the search service's spans
# =============================================================================

@pytest.fixture(scope="module")
def posting_index():
    from repro import search

    rng = np.random.default_rng(13)
    n_docs = 70_000                                    # C = 2
    postings = {f"t{i}": np.unique(rng.integers(0, n_docs, 30_000 // (i + 1)))
                for i in range(6)}
    return search.PostingIndex.from_postings(postings, n_docs)


def _q(a="t0", b="t1"):
    from repro import search

    return search.and_(search.term(a), search.term(b))


def _flat(spans):
    out = []
    for s in spans:
        out.append(s)
        out += _flat(s.children)
    return out


def _host_event_names(log_dir):
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).rglob("*.xplane.pb")
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    return names


def test_enabled_spans_are_profiler_annotations(posting_index, tmp_path):
    """While enabled, each span of a service step is a host annotation of
    the profile under its own name; disabled spans write none."""
    import jax

    from repro import search

    svc = search.SearchService(posting_index, max_batch=2)
    svc.search(_q(), "count")                           # compile
    for on in (False, True):
        (obs.enable if on else obs.disable)()
        log_dir = tmp_path / str(on)
        jax.profiler.start_trace(str(log_dir))
        svc.search(_q(), "count")
        jax.profiler.stop_trace()
        names = {n for n in _host_event_names(str(log_dir))
                 if n.startswith(("search.", "index."))}
        if on:
            assert names == {"search.plan", "search.admit", "search.cache",
                             "search.execute", "search.slots", "index.rung",
                             "search.sync"}
        else:
            assert names == set()


def test_rows_lifted_is_counted_per_call(posting_index):
    """Ten calls of one compiled batch program (and of the scoring) count
    ``rows_lifted`` ten times, though each program was traced once."""
    from repro import search
    from repro.search import service as S

    svc = search.SearchService(posting_index, max_batch=2)
    svc.search(_q(), "topk", k=3)                       # compile
    obs.enable()
    for _ in range(10):
        svc.search(_q(), "topk", k=3)
    spans = _flat(obs.span_trees())
    execs = [s for s in spans if s.name == "search.execute"]
    scores = [s for s in spans if s.name == "search.score"]
    C = posting_index.C
    assert len(execs) == len(scores) == 10
    assert sum(s.attrs["rows_lifted"] for s in execs) == \
        10 * S.batch_rows_lifted(1, 2, C, True) == 10 * 3 * C
    # the scan lifts the query's rows only: no posting holds a run row
    assert sum(s.attrs["rows_lifted"] for s in scores) == 10 * C
    assert all(s.attrs == {**S.score_cost(posting_index),
                           "rid": s.attrs["rid"]} for s in scores)
    # each class reads the one group of this index's 7 rows, all 16 rows
    plan = posting_index.plan
    assert scores[0].attrs["slots_scanned"] == \
        C * 16 * sum(plan.widths) > 0
    assert scores[0].attrs["slots_total"] == posting_index.n_rows * C * 4096
    assert {s.attrs["rid"] for s in scores} == {e.attrs["rids"][0]
                                                for e in execs}
    queued = [s for s in spans if s.name == "search.queued"]
    assert len(queued) == 10 and all(s.duration_s >= 0 for s in queued)
    # the launch hook fires per trace of a jitted program, not per call
    assert obs.registry().total("roaring.launches") < 10


def test_a_disabled_step_records_nothing_per_request(posting_index):
    """With telemetry off, a step of four requests leaves the registry
    exactly as a step of one does: nothing is written per request."""
    from repro import search

    svc = search.SearchService(posting_index, max_batch=4)
    for b in (1, 4):                                    # compile both
        svc.search_many([_q()] * b, "count")
    snaps = []
    for b in (1, 4):
        obs.reset_metrics()
        svc.search_many([_q()] * b, "count")
        snaps.append(obs.registry().snapshot())
    assert snaps[0] == snaps[1]
    assert not snaps[0].get("histograms")
    assert obs.span_trees() == []


# =============================================================================
# metrics registry
# =============================================================================

def test_registry_counters_gauges_labels():
    reg = obs.registry()
    reg.counter("x.events", kind="a").inc()
    reg.counter("x.events", kind="a").inc(2)
    reg.counter("x.events", kind="b").inc()
    reg.gauge("x.depth").set(7)
    assert reg.value("x.events", kind="a") == 3
    assert reg.value("x.events", kind="b") == 1
    assert reg.value("x.events", kind="zzz") == 0
    assert reg.total("x.events") == 4
    assert reg.value("x.depth") == 7
    snap = reg.snapshot()
    assert snap["counters"]["x.events{kind=a}"] == 3
    assert snap["gauges"]["x.depth"] == 7
    reg.remove("x.events")
    assert reg.total("x.events") == 0


def test_histogram_log2_buckets():
    h = obs_metrics.Histogram()
    for v in (0.5, 1, 3, 900):
        h.record(v)
    d = h.to_dict()
    assert d["count"] == 4 and d["min"] == 0.5 and d["max"] == 900
    # 0.5 -> bucket 0, 1 -> 1, 3 -> 2, 900 -> 10
    assert d["buckets"] == {"<2^0": 1, "<2^1": 1, "<2^2": 1, "<2^10": 1}


def test_record_kinds_counts_and_tracer_guard():
    import jax

    obs.enable()
    obs.record_kinds("t.kinds", np.array([0, 2, 2, 1, 3]))
    reg = obs.registry()
    assert reg.value("t.kinds", kind="empty") == 1
    assert reg.value("t.kinds", kind="array") == 1
    assert reg.value("t.kinds", kind="bitmap") == 2
    assert reg.value("t.kinds", kind="run") == 1

    # under jit tracing the kinds are Tracers: must be skipped, not crash
    @jax.jit
    def traced(k):
        obs.record_kinds("t.traced", k)
        return k

    traced(np.array([1, 2]))
    assert reg.total("t.traced") == 0


# =============================================================================
# launch hooks + measured-vs-model accounting
# =============================================================================

def _stack(n, C=2, seed=7):
    rng = np.random.default_rng(seed)
    slabs = [roaring.RoaringSlab.from_values(
        np.unique(rng.integers(0, C << 16, 3000)), C, 1 << 14)
        for _ in range(n)]
    return roaring.stack(slabs, capacity=C)


def test_launch_hook_subscription_and_events():
    events = []
    kops.add_launch_hook(events.append)
    kops.add_launch_hook(events.append)      # idempotent
    try:
        stack = _stack(2)
        index.execute(stack, index.and_(index.leaf(0), index.leaf(1)))
        assert len(events) == 1
        assert events[0] == kops.LaunchEvent("intersect_dispatch", "xla")
    finally:
        kops.remove_launch_hook(events.append)
    before = len(events)
    index.execute(_stack(2), index.and_(index.leaf(0), index.leaf(1)))
    assert len(events) == before             # unsubscribed


def test_launch_counts_match_roofline_model_fused_n4_n16():
    """Acceptance: measured per-column launch counters == the analytic
    model, fused and per-op, for N=4 and N=16 AND trees."""
    from repro.kernels.roaring import fused

    stack = _stack(16)
    for N in (4, 16):
        expr = index.and_(*[index.leaf(i) for i in range(N)])
        r = obs.launch_crosscheck(stack, expr)
        assert r["match"], r
        assert r["fused_measured"] == 1      # whole tree, ONE launch
        assert r["per_op_measured"] == (N - 1).bit_length()
        # the roofline table's logical-combine count is the plan's n_ops
        plan = fused.plan_tape(("and",) + tuple(range(N)))
        assert r["per_op_combines"] == fused.plan_stats(
            plan, 2)["launches_per_op"] == N - 1
    assert not obs.enabled()                 # crosscheck restored the state


def test_launch_model_mixed_trees():
    e = index.or_(index.and_(*[index.leaf(i) for i in range(4)]),
                  index.andnot(index.leaf(4), index.leaf(5)))
    m = index.launch_model(e)
    assert m["n_operands"] == 6
    assert m["fused_launches"] == 1
    # OR/ANDNOT combine in jnp row algebra: only the AND's tree-reduce
    # dispatches (ceil(log2 4) = 2)
    assert m["per_op_dispatches"] == 2
    assert m["per_op_combines"] == 5         # N-1 logical combines


# =============================================================================
# degradation ladder on the registry + fault span trees
# =============================================================================

def test_fallback_rungs_appear_as_errored_child_spans():
    from repro.runtime import FaultPlan, fault_scope

    index.reset_degradation()
    stack = _stack(4)
    expr = index.and_(*[index.leaf(i) for i in range(4)])
    base = index.execute(stack, expr, backend="xla").to_roaring().to_array()

    obs.reset_metrics()                      # drop the baseline's counters
    obs.enable()
    # every pallas dispatch faults: fused rung fails, per-op pallas rung
    # fails, the query completes on the per-op XLA rung
    with fault_scope(FaultPlan(every=1, backend="pallas")):
        with obs.span("query-under-fault"):
            out = index.execute(stack, expr, fused=True, backend="pallas",
                                max_retries=0)
    assert np.array_equal(out.to_roaring().to_array(), base)

    reg = obs.registry()
    assert reg.value("index.fallbacks") == 2
    assert reg.value("index.dispatch_failures") == 2
    assert reg.value("index.rung_taken", kind="per_op", backend="xla") == 1
    assert reg.total("index.rung_taken") == 1

    (root,) = obs.span_trees()
    (exe,) = root.children
    assert exe.name == "index.execute"
    rungs = [c for c in exe.children if c.name == "index.rung"]
    assert [r.attrs["kind"] for r in rungs] == ["fused", "per_op", "per_op"]
    assert [r.status for r in rungs] == ["error", "error", "ok"]
    assert rungs[0].attrs["backend"] == "pallas"
    assert rungs[2].attrs["backend"] == "xla"
    # the winning rung carries the dispatch launch events
    assert {e["name"] for e in rungs[2].events} == {"launch"}


def test_degradation_shim_warns_and_mirrors_registry():
    index.reset_degradation()
    reg = obs.registry()
    reg.counter("index.dispatch_failures").inc(3)
    reg.counter("index.retries").inc(2)
    reg.counter("index.fallbacks").inc(1)
    with pytest.warns(DeprecationWarning, match="degradation_stats"):
        s = index.degradation_stats()
    assert (s.dispatch_failures, s.retries, s.fallbacks) == (3, 2, 1)
    index.reset_degradation()
    with pytest.warns(DeprecationWarning):
        s = index.degradation_stats()
    assert (s.dispatch_failures, s.retries, s.fallbacks) == (0, 0, 0)


def test_no_src_module_calls_degradation_stats():
    """Strict-mode proof: the deprecated accessor has zero call sites in
    ``src/`` (docstrings may mention it; AST calls may not)."""
    offenders = []
    for path in sorted((ROOT / "src").rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                f = node.func
                name = f.id if isinstance(f, ast.Name) else (
                    f.attr if isinstance(f, ast.Attribute) else None)
                if name == "degradation_stats":
                    offenders.append(f"{path}:{node.lineno}")
    assert not offenders, offenders


# =============================================================================
# store cache stats + serve gauges
# =============================================================================

def _tiny_store():
    from repro.store import BitmapStore

    rng = np.random.default_rng(3)
    return BitmapStore.build({"c": rng.integers(0, 3, 400),
                              "v": rng.integers(0, 16, 400)}, bsi=("v",))


def test_store_cache_stats_hits_misses_fallbacks():
    from repro.store import predicate as P

    s = _tiny_store()
    pred = P.and_(P.eq("c", 1), P.range_("v", 2, 9))
    assert s.cache_stats() == {"hits": 0, "misses": 0, "fallbacks": 0,
                               "entries": 0,
                               "keyed_by": "(expr, fused, backend)"}
    base = s.count(pred, fused=True)
    assert s.cache_stats()["misses"] == 1
    s.count(pred, fused=True)
    st = s.cache_stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["entries"] == 1
    assert st["fallbacks"] == 0

    # poison the cached executor: the fallback must run the eager ladder,
    # count separately from cold compiles, and still answer correctly
    key = ("card", s.compile(pred), True, None)
    assert key in s._query_fns
    s._query_fns[key] = lambda stack: (_ for _ in ()).throw(
        RuntimeError("injected"))
    assert s.count(pred, fused=True) == base
    st = s.cache_stats()
    assert st["fallbacks"] == 1
    assert st["misses"] == 1                 # not conflated with a compile
    assert st["hits"] == 2                   # the poisoned lookup was a hit

    # gauges mirror the stats, labeled per store
    reg = obs.registry()
    sid = str(s._id)
    assert reg.value("store.query_cache.fallbacks", store=sid) == 1
    assert reg.value("store.query_cache.entries", store=sid) == 1


def test_store_query_span_tree_compile_execute_launch():
    """Acceptance: a traced fused ``store.query`` yields the
    compile -> execute -> launch span tree."""
    from repro.store import predicate as P

    s = _tiny_store()
    obs.enable()
    s.query(P.eq("c", 1), fused=True)
    (root,) = obs.span_trees()
    assert root.name == "store.query" and root.attrs["fused"] is True
    names = [c.name for c in root.children]
    assert names == ["store.compile", "store.execute"]
    assert root.children[1].attrs["cache"] == "miss"
    # the jitted call traces the engine exactly once: the launch event sits
    # on the execute subtree (via index.execute -> index.rung)
    def events(sp):
        out = [e["name"] for e in sp.events]
        for c in sp.children:
            out += events(c)
        return out
    assert "launch" in events(root.children[1])
    assert obs.registry().total("roaring.launches", entry="fused_tree") == 1


def test_serve_step_publishes_gauges():
    from repro.serve.engine import ServeEngine

    eng = ServeEngine.__new__(ServeEngine)   # gauge surface only, no model
    eng.queue, eng.active = [1, 2, 3], {}
    eng.slots = [None]
    eng.requeues, eng.steps_run = 1, 5
    eng.table = type("T", (), {"free": [0, 1],
                               "utilization": lambda self: 0.5})()
    obs.enable()
    eng._publish_gauges()
    reg = obs.registry()
    assert reg.value("serve.queue_depth") == 3
    assert reg.value("serve.page_pool.free_pages") == 2
    assert reg.value("serve.page_pool.utilization") == 0.5
    assert reg.value("serve.requeues") == 1
    assert reg.value("serve.steps") == 5


# =============================================================================
# report + cost contract
# =============================================================================

def test_report_collect_render_write(tmp_path):
    obs.enable()
    obs.registry().counter("roaring.launches", entry="fused_tree",
                           backend="xla").inc(4)
    with obs.span("store.query"):
        pass
    path = tmp_path / "telemetry.json"
    rep = obs.write_report(str(path), extra={"sections": {"obs": 1.5}})
    on_disk = json.loads(path.read_text())
    assert on_disk["sections"] == {"obs": 1.5}
    assert on_disk["environment"]["backend"] == rep["environment"]["backend"]
    assert on_disk["metrics"]["counters"][
        "roaring.launches{backend=xla,entry=fused_tree}"] == 4
    assert on_disk["spans"][0]["name"] == "store.query"
    text = obs.render_text(rep)
    assert "kernel launches" in text and "store.query" in text


def test_telemetry_scope_restores_state():
    assert not obs.enabled()
    with obs.telemetry_scope():
        assert obs.enabled()
        with obs.telemetry_scope(on=False):
            assert not obs.enabled()
        assert obs.enabled()
    assert not obs.enabled()


def test_overhead_guard_disabled_query_within_5pct():
    """The cost contract, asserted the ``api_ab`` way: per-trial ratios of
    the pre-telemetry query body vs the instrumented (telemetry-disabled)
    ``query()``, alternating measurement order, median compared — a single
    stalled measurement cannot fake an overhead."""
    import jax

    from repro.store import predicate as P

    s = _tiny_store()
    pred = P.and_(P.eq("c", 1), P.range_("v", 2, 9))
    s.query(pred, fused=True)                # warm: compile + jit once

    def raw():
        expr = s.compile(pred)
        return s._query_fns[(expr, True, None)](s._stack)

    def instrumented():
        return s.query(pred, fused=True)

    def timed(fn, reps=15):
        fn()
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return time.perf_counter() - t0

    us_raw, us_inst = [], []
    for trial in range(7):
        pair = [(us_raw, raw), (us_inst, instrumented)]
        if trial % 2:
            pair.reverse()
        for acc, fn in pair:
            acc.append(timed(fn))
    ratio = float(np.median(np.asarray(us_raw) / np.asarray(us_inst)))
    assert ratio >= 0.95, (ratio, us_raw, us_inst)
