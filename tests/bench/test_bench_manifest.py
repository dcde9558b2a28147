"""``BENCHMARK.json`` and the files it names (``bench/manifest.py``)."""

import copy
import json
import os

import pytest

from bench import manifest


@pytest.fixture(scope="module")
def m():
    return manifest.load()


def test_the_committed_manifest_is_sound(m):
    assert manifest.validate(m) == []
    assert m["command"] == ["python3", "bench/run.py"]


def test_every_name_and_unit_keeps_to_the_character_rules(m):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert manifest.NAME.fullmatch(entry["name"]), entry["name"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert manifest.UNIT.fullmatch(e["unit"]) and len(e["unit"]) <= 16


def test_every_file_the_manifest_implies_exists(m):
    for c in m["configs"]:
        config = manifest.config(m, c["name"])
        assert os.path.exists(os.path.join(
            manifest.BENCH, "systems", config["system"] + ".py"))
        assert os.path.exists(os.path.join(
            manifest.BENCH, "systems", config["system"] + "_ref.py"))
    for w in m["workloads"]:
        assert manifest.traffic(w["traffic"])["mix"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert callable(manifest.metric_reader(e["name"]))


def test_every_moves_target_is_reported_where_its_metric_is(m):
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for e in m["per_layer"]:
        for cell in manifest.metric_cells(e, cells):
            assert cell in manifest.metric_cells(e2e[e["moves"]], cells)


@pytest.mark.parametrize("break_it, words", [
    (lambda m: m["workloads"][0].update(name="bad name"), "not a name"),
    (lambda m: m["end_to_end"][1].update(unit="milli seconds"), "unit"),
    (lambda m: m["end_to_end"][1].update(unit="a" * 17), "unit"),
    (lambda m: m["per_layer"][0].update(moves="nope"), "moves unknown"),
    (lambda m: m["end_to_end"][1].update(workloads=[]), "does not report"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["configs"][0].update(extra=1), "extra keys"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["end_to_end"].pop(0), "setup_s"),
])
def test_validate_names_each_broken_rule(m, break_it, words):
    bad = copy.deepcopy(m)
    break_it(bad)
    problems = manifest.validate(bad)
    assert any(words in p for p in problems), problems


@pytest.mark.parametrize("file", ["answered.count.py", "answered.py"])
def test_a_metric_file_dropped_in_is_found_by_name(tmp_path, monkeypatch,
                                                   file):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / file).write_text(
        "def read(ctx):\n    return len(ctx.records)\n")
    monkeypatch.setattr(manifest, "BENCH", str(tmp_path))

    class Ctx:
        records = [1, 2, 3]
    assert manifest.metric_reader("answered.count")(Ctx()) == 3
    with pytest.raises(FileNotFoundError):
        manifest.metric_reader("absent")


def test_cell_metrics_split_by_trace(m):
    cell = m["workloads"][0]["name"]
    untraced = {e["name"] for e in manifest.cell_metrics(m, cell, False)}
    traced = {e["name"] for e in manifest.cell_metrics(m, cell, True)}
    assert "setup_s" in untraced and len(untraced) >= 2
    assert traced and not traced & untraced


def test_the_manifest_is_plain_json_under_64_kib():
    path = os.path.join(manifest.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        json.load(f)
