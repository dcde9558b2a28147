"""The store cell (``ssb-q1``) through the harness at a tiny size on the
CPU: the program against the plain reference, the planted faults the
check has to catch, the control, and the manifest that names the cell."""

import jax
import pytest

from bench import cell as cell_run
from bench import control, manifest

SEED = 2**31 + 54321
CELL = "ssb-q1"


@pytest.fixture(scope="module")
def m():
    return manifest.load()


def tiny(m, **traffic):
    cell = manifest.cell(m, CELL)
    config = manifest.config(m, cell["config"])
    config.update(n_rows=3 * 65_536 + 4_321)
    t = manifest.traffic(cell["traffic"])
    t.update(traffic, check_sample=6)
    return cell, config, t


def run(m, patch=None, trace=False, log=lambda *a: None, **traffic):
    cell, config, t = tiny(m, **traffic)
    return cell_run.run(m, cell, SEED, 0.5, trace, 0.0, jax.devices(),
                        config=config, traffic=t, log=log,
                        is_device_op=lambda plane, line: (
                            plane == "/host:CPU" and line.startswith("tf_XLA")),
                        patch=patch)


def test_the_store_agrees_with_the_reference(m):
    logged = []
    result, checks = run(m, log=logged.append)
    assert result["correct"], checks
    assert any("compiles in the window 0 " in line for line in logged)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "qps"}
    assert result["check"]["wrong"] == {"value": 0, "limit": 0}


def test_a_traced_run_reads_the_store_metrics(m):
    result, _ = run(m, trace=True)
    assert result["correct"]
    got = result["metrics"]
    # no peak table row for a CPU: the roofline shares are left out
    assert set(got) == {"device_idle_pct.ssb", "rows_read_per_query.ssb",
                        "frontend_ms.ssb"}
    C = 4                               # chunk rows of the tiny table
    # filter leaves: a date slab, the universe and the 6 quantity slices
    # (Q1.3 two date slabs); 3 discount groups and 24 price slices each
    q11, q12, q13 = ((leaves + 3 + 24) * C for leaves in (8, 8, 9))
    assert q11 <= got["rows_read_per_query.ssb"]["value"] <= q13
    assert got["frontend_ms.ssb"]["value"] > 0


def _leave_out_a_price_slice(server):
    """Each group's sum without its highest bit slice."""
    def short(store, name, by, pred):
        top = 1 << (store.column(name).bits - 1)
        return {v: s % top for v, s in
                store.group_sum(name, by, pred).items()}
    server._group_sum = short


def _answer_half_of_each_step(server):
    step = server.step

    def half():
        done = step()
        return done[: (len(done) + 1) // 2]
    server.step = half


@pytest.mark.parametrize("fault, check", [
    (_leave_out_a_price_slice, "wrong"),
    (_answer_half_of_each_step, "missing"),
])
def test_a_broken_store_comes_out_not_correct(m, monkeypatch, fault, check):
    monkeypatch.setattr(cell_run, "DRAIN_S", 0.2)
    result, checks = run(m, patch=fault)
    assert not result["correct"]
    assert checks[check][0] > 0, checks


def test_the_bfloat16_control_comes_out_not_correct(m):
    _, config, t = tiny(m)
    checks = control.run_control(config, t, SEED, 0.5, log=lambda *a: None)
    assert checks["wrong"][0] > 0


def test_the_manifest_names_the_cell(m):
    assert manifest.validate(m) == []
    cell = manifest.cell(m, CELL)
    assert cell["chips"] == 1 and cell["config"] == "ssb-sf10"
    traced = {e["name"] for e in manifest.cell_metrics(m, CELL, True)}
    assert traced == {"device_idle_pct.ssb", "hbm_roofline_pct.ssb",
                      "rows_read_per_query.ssb", "frontend_ms.ssb"}
    config = manifest.config(m, "ssb-sf10")
    assert config["n_rows"] == 59_986_052
    assert set(config["reduced"]) == {"scale_factor", "columns"}
