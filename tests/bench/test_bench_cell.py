"""Whole runs of the harness at a tiny size on the CPU (``bench/cell.py``):
the program against the plain reference for each query shape and mode,
the per-layer readers on a CPU trace, the control, and the faults the
check has to catch."""

import copy
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import cell as cell_run
from bench import control, manifest

SEED = 2**31 + 12345


def cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLA")


# the count cell PERF.md holds out (the service's admission collapses
# after a host stall at 0.8 of its sustained rate): its traffic and
# readers stay, and these tests keep its open loop and latency path sound
COUNT_CELL = {"name": "msmarco-count", "config": "msmarco-passage",
              "traffic": "luceneutil-count", "chips": 1}
COUNT_METRICS = {
    "end_to_end": [{"name": "p50_ms", "unit": "ms"},
                   {"name": "p95_ms", "unit": "ms"}],
    "per_layer": [{"name": "frontend_ms.search", "unit": "ms"},
                  {"name": "batch_mean.search", "unit": "requests"},
                  {"name": "device_idle_pct.latency", "unit": "%"},
                  {"name": "hbm_roofline_pct.latency", "unit": "%"}]}


@pytest.fixture(scope="module")
def m():
    """The committed manifest, with the count cell added."""
    m = copy.deepcopy(manifest.load())
    m["workloads"].append(COUNT_CELL)
    for group, entries in COUNT_METRICS.items():
        m[group] += [dict(e, workloads=["msmarco-count"]) for e in entries]
    return m


def tiny(m, cell_name, **traffic):
    cell = manifest.cell(m, cell_name)
    config = manifest.config(m, cell["config"])
    config.update(n_docs=3 * 65_536 + 4_321, n_terms=48, max_batch=2)
    t = manifest.traffic(cell["traffic"])
    t.update(traffic)
    return cell, config, t


SEARCH_COUNT = dict(arrivals={"kind": "open", "rate_per_s": 40},
                    mix=[{"weight": 1, "shape": "and",
                          "bands": ["high", "med"], "mode": "count"}])
# the shapes the committed mixes leave out: a lone term, AND NOT
SEARCH_SHAPES = dict(arrivals={"kind": "open", "rate_per_s": 40},
                     mix=[{"weight": 1, "shape": "term", "bands": ["high"],
                           "mode": "count"},
                          {"weight": 1, "shape": "andnot",
                           "bands": ["high", "high", "med"],
                           "mode": "count"}])
SEARCH_TOPK = dict(arrivals={"kind": "closed", "clients": 2},
                   mix=[{"weight": 1, "shape": "or",
                         "bands": ["high", "med", "med"], "mode": "topk",
                         "k": 5}], check_sample=4)


def run(m, cell_name, traffic, trace=False, patch=None, seconds=0.5,
        log=lambda *a: None):
    cell, config, t = tiny(m, cell_name, **traffic)
    return cell_run.run(m, cell, SEED, seconds, trace, 0.0, jax.devices(),
                        config=config, traffic=t, log=log,
                        is_device_op=cpu_ops, patch=patch)


@pytest.mark.parametrize("cell_name, traffic, metrics", [
    ("msmarco-count", SEARCH_COUNT, {"setup_s", "p50_ms", "p95_ms"}),
    ("msmarco-facet", SEARCH_TOPK, {"setup_s", "qps"}),
    ("msmarco-count", SEARCH_SHAPES, {"setup_s", "p50_ms", "p95_ms"}),
])
def test_the_program_agrees_with_the_reference(m, cell_name, traffic,
                                               metrics):
    logged = []
    result, checks = run(m, cell_name, traffic, log=logged.append)
    assert result["correct"], checks
    # the warm-up compiled every program the window ran
    assert any("compiles in the window 0 " in line for line in logged)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == metrics
    assert list(result)[-1] == "check"
    assert result["check"]["wrong"] == {"value": 0, "limit": 0}
    assert result["device"]["platform"] == jax.devices()[0].platform


def test_a_traced_run_reads_its_per_layer_metrics(m):
    result, _ = run(m, "msmarco-count", SEARCH_COUNT, trace=True)
    assert result["correct"]
    got = result["metrics"]
    assert {"frontend_ms.search", "batch_mean.search",
            "device_idle_pct.latency"} <= set(got)
    # no peak table row for a CPU: the roofline share is left out, never 0
    assert "hbm_roofline_pct.latency" not in got
    assert 1 <= got["batch_mean.search"]["value"] <= 2
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["breakdown"]["device_ops"]
    assert len(result["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("cell_name, traffic", [
    ("msmarco-count", SEARCH_COUNT), ("msmarco-facet", SEARCH_TOPK),
    ("msmarco-count", SEARCH_SHAPES)])
def test_the_control_comes_out_not_correct(m, cell_name, traffic):
    cell, config, t = tiny(m, cell_name, **traffic)
    checks = control.run_control(config, t, SEED, 0.5, log=lambda *a: None)
    assert checks["wrong"][0] > 0


def _alter_answers(server):
    step = server.step

    def altered():
        out = []
        for handle, value in step():
            if isinstance(value, list):        # top-k: one score off by one
                value = [(value[0][0], value[0][1] + 1)] + value[1:]
            else:
                value = value + 1
            out.append((handle, value))
        return out
    server.step = altered


def _drop_half_the_batch(server):
    svc = server.service
    execute = svc._execute
    svc._execute = lambda batch, slot_of: execute(
        batch[: max(1, len(batch) // 2)], slot_of)


def _step_does_nothing(server):
    server.service.step = lambda: []


@pytest.mark.parametrize("cell_name, traffic, fault", [
    ("msmarco-count", SEARCH_COUNT, _alter_answers),
    ("msmarco-facet", SEARCH_TOPK, _alter_answers),
    ("msmarco-facet", SEARCH_TOPK, _drop_half_the_batch),
    ("msmarco-count", dict(SEARCH_COUNT, arrivals={"kind": "open",
                                                    "rate_per_s": 400}),
     _drop_half_the_batch),
    ("msmarco-count", SEARCH_COUNT, _step_does_nothing),
])
def test_a_broken_timed_path_comes_out_not_correct(m, monkeypatch,
                                                   cell_name, traffic,
                                                   fault):
    monkeypatch.setattr(cell_run, "DRAIN_S", 0.2)
    result, checks = run(m, cell_name, traffic, patch=fault)
    assert not result["correct"], checks


def test_the_command_refuses_without_a_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("checks the refusal without an accelerator")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "run.py"),
         "--workload", "msmarco-facet", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_term_bands_pick_the_committed_ranks(m):
    """High and Med are ranks 1-5 and 6-50 of the committed vocabulary's
    Zipf postings, and a request draws distinct terms from its bands."""
    from bench.systems import search_ref

    config = manifest.config(m, "msmarco-passage")
    config.update(n_docs=1_000)
    data = search_ref.Data(config, SEED)
    t = manifest.traffic("luceneutil-count")
    high, med = (data.band(*t["term_bands"][b]) for b in ("high", "med"))
    assert high.tolist() == list(range(5))
    assert med.tolist() == list(range(5, 50))
    template = dict(t["mix"][1], term_bands=t["term_bands"])
    for i in range(20):
        _, ranks, _, _ = search_ref.request(
            config, data, template, np.random.default_rng(i))
        assert len(set(ranks)) == 2 and set(ranks) <= set(range(5))
