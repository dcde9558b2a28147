"""The reader of ``scan_share_pct``: on hand-made spans, on a program
without the attribute, and on a tiny traced facet run on the CPU."""

import types

import pytest

from bench import manifest

from test_bench_cell import SEARCH_TOPK, run, tiny

READ = manifest.metric_reader("scan_share_pct.facet")


def _span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


def test_the_share_sums_slots_over_the_scoring_spans():
    spans = [_span("search.score", rid=1, slots_scanned=30, slots_total=400),
             _span("search.score", rid=2, slots_scanned=10, slots_total=400),
             _span("search.execute", slots_scanned=999, slots_total=1)]
    assert READ(types.SimpleNamespace(spans=spans)) == pytest.approx(5.0)


@pytest.mark.parametrize("spans", [
    [], [_span("search.score", rid=1, rows_lifted=10)],
    [_span("search.execute", rows_lifted=10)]],
    ids=["no spans", "scoring without slots", "no scoring"])
def test_a_program_without_the_slots_reports_nothing(spans):
    assert READ(types.SimpleNamespace(spans=spans)) is None


def test_a_traced_facet_run_reports_the_plans_share():
    from bench.systems import search_ref

    from repro.search import PostingIndex
    from test_bench_cell import SEED

    m = manifest.load()
    result, _ = run(m, "msmarco-facet", SEARCH_TOPK, trace=True)
    assert result["correct"]
    _, config, _ = tiny(m, "msmarco-facet", **SEARCH_TOPK)
    data = search_ref.Data(config, SEED)
    plan = PostingIndex.from_postings(
        {f"t{r:05d}": p for r, p in enumerate(data.postings)},
        data.n_docs).plan
    assert result["metrics"]["scan_share_pct.facet"] == {
        "value": pytest.approx(100.0 * plan.slots_scanned / plan.slots_total),
        "unit": "%"}
    assert 0 < result["metrics"]["scan_share_pct.facet"]["value"] < 100
