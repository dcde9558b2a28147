"""Device time by stable names (``bench/scopes.py``) on hand-made events,
and the new per-layer readers on a tiny traced facet run on the CPU."""

import types

import jax
import pytest

from bench import cell as cell_run
from bench import manifest, scopes
from bench import trace as tr

from test_bench_cell import SEARCH_TOPK, SEED, cpu_ops, tiny

LIFT = "jit(index_score)/jit(main)/roaring.lift/while/body"


def _ops(*events):
    return [(name, a, b, stats) for name, a, b, stats in events]


def test_nested_ops_count_once_and_clip_to_the_window():
    sc = scopes.from_events({"/device:TPU:0": _ops(
        ("while.3", 10, 50, {"tf_op": LIFT}),
        ("cond.1", 20, 40, {"tf_op": LIFT + "/cond"}),      # inside while
        ("fusion.2", 45, 120, {"tf_op": LIFT}),             # past the end
        ("copy.1", 0, 100, {"tf_op": "jit(index_score)/jit(main)/copy"}),
    )}, window=(0, 100))
    assert scopes.scope_s(sc, "roaring.lift") == pytest.approx(90e-9)
    assert sc.scope_stat == "tf_op"


def test_a_scope_matches_whole_path_components_only():
    assert scopes.in_scope(LIFT, "roaring.lift")
    assert scopes.in_scope("roaring.lift", "roaring.lift")
    assert not scopes.in_scope("jit(f)/roaring.lifted/x", "roaring.lift")
    assert not scopes.in_scope("jit(f)/my_roaring.lift", "roaring.lift")
    assert not scopes.in_scope(None, "roaring.lift")
    sc = scopes.from_events({"d": _ops(
        ("a", 0, 10, {"tf_op": "jit(f)/roaring.lifted"}))}, window=(0, 10))
    assert scopes.scope_s(sc, "roaring.lift") is None


def test_the_scope_path_is_found_in_the_hlo_text_too():
    text = ('%fusion.3 = u32[8,16,128]{2,1,0} fusion(...), metadata='
            f'{{op_name="{LIFT}" source_file="dispatch.py"}}')
    assert scopes.scope_path("fusion.3", {"long_name": text}) == \
        (LIFT, "long_name")
    assert scopes.scope_path(text, {}) == (LIFT, "name")
    assert scopes.scope_path("fusion.3", {"hlo_op": "fusion.3"}) == \
        (None, None)


def test_device_time_is_the_mean_over_planes():
    sc = scopes.from_events({
        "/device:TPU:0": _ops(("a", 0, 10, {"tf_op": LIFT})),
        "/device:TPU:1": _ops(("a", 0, 30, {"tf_op": LIFT}),
                              ("b", 40, 50, {})),
    }, window=(0, 100))
    assert scopes.scope_s(sc, "roaring.lift") == pytest.approx(20e-9)


def test_a_program_by_its_module_stat_or_the_modules_line():
    by_stat = scopes.from_events({"d": _ops(
        ("dot.1", 0, 10, {"hlo_module": "jit_index_score"}),
        ("dot.1", 20, 25, {"hlo_module": "jit_search_batch_fused"}),
        ("add.2", 5, 15, {"hlo_module": "jit_index_score"}))},
        window=(0, 100))
    assert scopes.program_s(by_stat, "index_score") == pytest.approx(15e-9)
    assert scopes.program_s(by_stat, "search_fill") is None
    by_line = scopes.from_events(
        {"d": _ops(("while.3", 0, 10, {}))}, window=(0, 100),
        modules={"d": [("jit_index_score(12)", 0, 40),
                       ("jit_search_fill(3)", 50, 60),
                       ("jit_index_score(12)", 90, 130)]})
    assert scopes.program_s(by_line, "index_score") == pytest.approx(50e-9)
    assert scopes.module_name("jit_index_score(12)") == "jit_index_score"


# a TPU trace keeps an op's scope path in its event metadata, as a string
# or as a reference to a stat name; ProfileData shows event stats only
TPU_XPLANE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 30000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.3 = while(...)"
    stats { metadata_id: 1 str_value: "LIFT" } } }
  event_metadata { key: 2 value { id: 2 name: "%cond.1 = conditional(...)"
    stats { metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.1 = copy(...)"
    stats { metadata_id: 2 int64_value: 5 } } }
  event_metadata { key: 4 value { id: 4 name: "jit_index_score(12)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3 name: "LIFT/cond" } }
}
""".replace("LIFT", LIFT)


def test_a_tpu_trace_gives_the_scope_in_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TPU_XPLANE))
    assert scopes.metadata_paths(str(path), lambda plane: True) == {
        "/device:TPU:0": {"%while.3 = while(...)": LIFT,
                          "%cond.1 = conditional(...)": LIFT + "/cond"}}
    sc = scopes.load(str(path), tr.tpu_ops, (0, 100), lambda name: False)
    assert sc.scope_stat == "tf_op"
    assert scopes.scope_s(sc, "roaring.lift") == pytest.approx(40e-9)
    assert scopes.program_s(sc, "index_score") == pytest.approx(100e-9)


@pytest.fixture(scope="module")
def facet_run():
    """One tiny traced facet run, with the scopes of its trace read before
    the harness drops the file."""
    m = manifest.load()
    cell, config, traffic = tiny(m, "msmarco-facet", **SEARCH_TOPK)
    seen = {}
    find = tr.find_xplane

    def keep(log_dir):
        path = find(log_dir)
        window = tr.load(path, cpu_ops).window()
        seen["scopes"] = scopes.load(path, cpu_ops, window,
                                     lambda name: name.startswith("search."))
        return path

    mp = pytest.MonkeyPatch()
    mp.setattr(tr, "find_xplane", keep)
    try:
        result, _ = cell_run.run(m, cell, SEED, 0.5, True, 0.0,
                                 jax.devices(), config=config,
                                 traffic=traffic, log=lambda *a: None,
                                 is_device_op=cpu_ops)
    finally:
        mp.undo()
    return config, traffic, result, seen["scopes"]


def test_a_traced_facet_run_reads_the_program_metrics(facet_run):
    config, traffic, result, sc = facet_run
    assert result["correct"]
    got = result["metrics"]
    # rows lifted per query: every stack row and the query's in the
    # scoring, each leaf's and the result's in the batch program
    C = -(-config["n_docs"] // 65_536)
    n_rows = config["n_terms"] + 1
    leaves = len(traffic["mix"][0]["bands"])
    assert got["rows_lifted_per_query.facet"]["value"] == \
        (n_rows + 1) * C + (leaves + 1) * C
    assert got["queue_wait_ms.facet"]["value"] > 0
    assert got["device_idle_pct.facet"]["value"] > 0


def test_the_device_readers_on_the_scopes_of_a_cpu_trace(facet_run):
    _, _, result, sc = facet_run
    answered = result["attempted"] - result["failed"]
    ctx = types.SimpleNamespace(scopes=sc, completed=range(answered))
    score = manifest.metric_reader("score_ms_per_query.facet")(ctx)
    assert score > 0                  # the hlo_module stat names the program
    assert scopes.program_s(sc, "search_batch_fused") > 0
    # the CPU trace carries no scope path: the lift is left out, never 0
    assert sc.scope_stat is None
    assert manifest.metric_reader("lift_ms_per_query.facet")(ctx) is None
    # without scopes on the context (the harness as it stands) both are
    # left out
    bare = types.SimpleNamespace(completed=range(answered))
    assert manifest.metric_reader("score_ms_per_query.facet")(bare) is None
    # the program's spans are annotations of the same trace
    assert {"search.execute", "search.score",
            "search.sync"} <= {n for n, _, _ in sc.annotations}


def test_the_idle_share_agrees_with_the_union_of_all_ops(facet_run):
    """``device_idle_pct`` as the harness reads it, and the same share from
    the ops ``bench/scopes.py`` reads off the same file."""
    _, _, result, sc = facet_run
    busy = sum(sum(b - a for a, b in tr.union(
        [(op.start, op.end) for op in ops], *sc.window))
        for ops in sc.ops.values()) * 1e-9 / len(sc.ops)
    window_s = (sc.window[1] - sc.window[0]) * 1e-9
    assert result["device"]["busy_s"] == pytest.approx(busy)
    assert result["metrics"]["device_idle_pct.facet"]["value"] == \
        pytest.approx(100.0 * (1.0 - busy / window_s))


def test_the_readers_leave_out_what_the_program_does_not_record():
    """A program without the spans (the parent of this instrumentation)
    reports neither program metric, and raises nothing."""
    ctx = types.SimpleNamespace(spans=[], completed=[object()])
    for name in ("rows_lifted_per_query.facet", "queue_wait_ms.facet"):
        assert manifest.metric_reader(name)(ctx) is None


def test_the_formula_at_the_committed_shape():
    """The committed configuration's rows lifted per two-term top-k query:
    4,097 x 135 stack rows and 135 query rows in the scoring, 2 x 135 leaf
    rows and 135 result rows in the batch program."""
    from repro.search import service as S

    config = manifest.config(manifest.load(), "msmarco-passage")
    C = -(-config["n_docs"] // 65_536)
    n_rows = config["n_terms"] + 1
    assert C == 135 and n_rows == 4_097
    assert S.score_rows_lifted(n_rows, C) + \
        S.batch_rows_lifted(1, 2, C, True) == 553_635
