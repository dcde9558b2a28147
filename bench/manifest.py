"""``BENCHMARK.json``: load it, check it against the benchmark's rules, and
find each cell's configuration, traffic and metric files by name."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES_END_TO_END = {"host_clock", "device_trace"}
SOURCES = SOURCES_END_TO_END | {"program_span", "program_counter"}

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def load(path: Optional[str] = None) -> dict:
    """The parsed manifest, checked by ``validate``."""
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    problems = validate(manifest)
    if problems:
        raise ValueError("BENCHMARK.json: " + "; ".join(problems))
    return manifest


def _line(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def validate(m: dict) -> List[str]:
    """Every rule the manifest breaks, as one message each (empty: sound)."""
    bad: List[str] = []
    if set(m) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return bad
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        bad.append("command must be 1-32 one-line words")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        bad.append("paths must list 1-16 directories")
        paths = []
    for p in paths:
        if not re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) or \
                p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r} is not a plain relative path")
    for w in cmd[1:]:
        if w.endswith(".py") and not any(w.startswith(p.rstrip("/") + "/")
                                         for p in paths):
            bad.append(f"command names {w!r} outside paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        bad.append("run_seconds must be a whole number from 1 to 51")

    configs = {c.get("name"): c for c in m["configs"]}
    cells = {w.get("name"): w for w in m["workloads"]}
    e2e = {e.get("name"): e for e in m["end_to_end"]}
    for what, items, keys, lo, hi in (
            ("configs", m["configs"], CONFIG_KEYS, 1, 24),
            ("workloads", m["workloads"], CELL_KEYS, 1, 24),
            ("end_to_end", m["end_to_end"], E2E_KEYS, 1, 16),
            ("per_layer", m["per_layer"], LAYER_KEYS, 1, 128)):
        if not lo <= len(items) <= hi:
            bad.append(f"{what} must hold {lo}-{hi} entries")
        names = [i.get("name") for i in items]
        if len(set(names)) != len(names):
            bad.append(f"{what} repeats a name")
        for i in items:
            allowed = {"workloads"} if what in ("end_to_end",
                                                "per_layer") else set()
            extra = set(i) - keys - allowed
            if keys - set(i):
                bad.append(f"{what} entry {i.get('name')!r} lacks keys "
                           f"{sorted(keys - set(i))}")
            if extra:
                bad.append(f"{what} entry {i.get('name')!r} has extra keys "
                           f"{sorted(extra)}")
            if not NAME.fullmatch(str(i.get("name", ""))):
                bad.append(f"{what} name {i.get('name')!r} is not a name")
    for c in m["configs"]:
        if not _line(c.get("source")) or not _line(c.get("why")):
            bad.append(f"config {c.get('name')!r}: source and why are "
                       "one line of 1-200 characters")
        f = c.get("file", "")
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"config file {f!r} lies outside paths")
        red = c.get("reduced", [])
        if len(red) > 16 or not all(NAME.fullmatch(k) for k in red):
            bad.append(f"config {c.get('name')!r}: reduced lists at most 16 "
                       "names")
    if len({c.get("file") for c in m["configs"]}) != len(configs):
        bad.append("two configs share a file")
    pairs = set()
    for w in m["workloads"]:
        if w.get("config") not in configs:
            bad.append(f"cell {w.get('name')!r} names unknown config")
        if not NAME.fullmatch(str(w.get("traffic", ""))):
            bad.append(f"cell {w.get('name')!r}: traffic is not a name")
        if w.get("chips") not in (1, 4):
            bad.append(f"cell {w.get('name')!r}: chips must be 1 or 4")
        if not _line(w.get("why")):
            bad.append(f"cell {w.get('name')!r}: why is one line of 1-200 "
                       "characters")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            bad.append(f"config and traffic {pair} appear twice")
        pairs.add(pair)
    if sum(w.get("chips") == 4 for w in m["workloads"]) > \
            max(1, len(m["workloads"]) // 2):
        bad.append("too many four-chip cells")
    used = {w.get("config") for w in m["workloads"]}
    if set(configs) - used:
        bad.append(f"configs no cell uses: {sorted(set(configs) - used)}")
    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT.fullmatch(str(e.get("unit", ""))):
            bad.append(f"metric {e.get('name')!r}: unit is not 1-16 of "
                       "[A-Za-z0-9_/%.-]")
        if e.get("better") not in ("lower", "higher"):
            bad.append(f"metric {e.get('name')!r}: better is lower/higher")
        for cell in e.get("workloads", []):
            if cell not in cells:
                bad.append(f"metric {e.get('name')!r} lists unknown cell "
                           f"{cell!r}")
    for e in m["end_to_end"]:
        if e.get("source") not in SOURCES_END_TO_END:
            bad.append(f"end-to-end metric {e.get('name')!r}: source must "
                       "be host_clock or device_trace")
        b = e.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            bad.append(f"end-to-end metric {e.get('name')!r}: bound must "
                       "lie in [0.01, 0.25]")
    if "setup_s" not in e2e:
        bad.append("end_to_end needs setup_s")
    for e in m["per_layer"]:
        if e.get("source") not in SOURCES:
            bad.append(f"per-layer metric {e.get('name')!r}: bad source")
        if not _line(e.get("layer")):
            bad.append(f"per-layer metric {e.get('name')!r}: layer is one "
                       "line")
        target = e2e.get(e.get("moves"))
        if target is None:
            bad.append(f"per-layer metric {e.get('name')!r} moves unknown "
                       f"metric {e.get('moves')!r}")
            continue
        for cell in metric_cells(e, cells):
            if cell not in metric_cells(target, cells):
                bad.append(f"per-layer metric {e['name']!r} is read in "
                           f"{cell!r}, which does not report "
                           f"{e['moves']!r}")
    for cell in cells:
        reported = [e["name"] for e in m["end_to_end"]
                    if cell in metric_cells(e, cells)]
        if "setup_s" not in reported or len(reported) < 2:
            bad.append(f"cell {cell!r} must report setup_s and one more "
                       "end-to-end metric")
        if not any(cell in metric_cells(e, cells) for e in m["per_layer"]):
            bad.append(f"cell {cell!r} reports no per-layer metric")
    if len(json.dumps(m).encode()) > 64 * 1024:
        bad.append("manifest over 64 KiB")
    return bad


def metric_cells(metric: dict, cells) -> List[str]:
    """The cells a metric is reported in: its ``workloads``, else all."""
    return list(metric.get("workloads", cells))


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config(manifest: dict, name: str) -> dict:
    """The configuration entry ``name`` merged over its file's contents."""
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return {**json.load(f), "name": name}


def traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return {**json.load(f), "name": name}


def cell_metrics(manifest: dict, cell_name: str, trace: bool) -> List[dict]:
    """The metrics a run of the cell reports: end-to-end ones untraced,
    per-layer ones traced."""
    cells = [w["name"] for w in manifest["workloads"]]
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [e for e in group if cell_name in metric_cells(e, cells)]


def _load_file(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``. A quantity split by the
    end-to-end metric it moves (``device_idle_pct.facet``) falls back to
    the reader of the whole quantity (``device_idle_pct.py``)."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            return _load_file(
                path, "bench_metric_" + re.sub(r"\W", "_", stem)).read
    raise FileNotFoundError(f"metric {name!r} has no reader in "
                            f"{os.path.join(BENCH, 'metrics')}")


def system(name: str):
    """The adapter module ``bench/systems/<name>.py``."""
    return _load_file(os.path.join(BENCH, "systems", name + ".py"),
                      "bench_system_" + name)


def reference(name: str):
    """The plain reference ``bench/systems/<name>_ref.py``."""
    return _load_file(os.path.join(BENCH, "systems", name + "_ref.py"),
                      "bench_reference_" + name)
