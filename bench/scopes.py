"""Device time by the program's stable names, read from a window's profiler
trace.

The program names its device work in two ways that survive a change of
code, where an HLO name (``while.3``) does not:

* a named scope (``jax.named_scope("roaring.lift")``): XLA keeps the scope
  path in each op's metadata (``jit(index_score)/jit(main)/roaring.lift/
  while/...``). A trace carries it in the op's ``tf_op`` stat, or inside
  its HLO text (``long_name``, or the event name itself) as ``op_name=``.
  A TPU trace keeps ``tf_op`` among the stats of the op's *event
  metadata*, which ``ProfileData`` does not expose: ``metadata_paths``
  reads them from the file's protobuf encoding;
* a jitted program's name: ``jax.jit`` of ``index_score`` compiles the
  module ``jit_index_score``, which an op carries in its ``hlo_module``
  stat, or which the device plane's ``XLA Modules`` line spans.

Besides, while the program's telemetry is on, each of its spans is a
``jax.profiler.TraceAnnotation`` of the same name on a host line: the
program's own spans on the profiler's clock.

``load`` reads all of that from an ``.xplane.pb``; ``scope_s`` and
``program_s`` are the device seconds inside a window, the union over ops
per device plane (a ``while`` and the ops of its body count once), averaged
over the planes. Both return ``None`` where the trace carries no such name
at all, so a reader leaves its metric out rather than report 0.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

from bench import trace as tr

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)

SCOPE_STATS = ("tf_op", "long_name")        # where a scope path may sit
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"(.*?)(?:\(\d+\))?$")  # "jit_f(12)" -> "jit_f"


class Op(NamedTuple):
    """One device op: interval (ns), scope path and program, when known."""

    name: str
    start: float
    end: float
    path: Optional[str]
    module: Optional[str]


@dataclasses.dataclass
class Scopes:
    ops: Dict[str, List[Op]]                 # device plane -> its ops
    modules: Dict[str, List[Event]]          # plane -> XLA Modules events
    annotations: List[Event]                 # the program's host spans
    window: Tuple[float, float]              # ns, the traced window
    scope_stat: Optional[str] = None         # where the paths were found


def scope_path(name: str, stats: dict) -> Tuple[Optional[str], Optional[str]]:
    """An op's scope path and the stat it came from (``name`` for the event
    name); ``(None, None)`` where it carries none."""
    for key in SCOPE_STATS:
        value = stats.get(key)
        if isinstance(value, str) and value:
            if key == "tf_op":
                return value, key
            m = _OP_NAME.search(value)
            if m:
                return m.group(1), key
    m = _OP_NAME.search(name)
    return (m.group(1), "name") if m else (None, None)


def module_name(name: str) -> str:
    """A module's trace name without its program id: ``jit_f(12)`` ->
    ``jit_f``."""
    return _MODULE.match(name).group(1)


def in_scope(path: Optional[str], scope: str) -> bool:
    """Does the scope path hold ``scope`` as one whole component?"""
    return path is not None and scope in path.split("/")


def from_events(ops: Dict[str, Iterable[Tuple[str, float, float, dict]]],
                window: Tuple[float, float],
                modules: Optional[Dict[str, List[Event]]] = None,
                annotations: Optional[List[Event]] = None) -> Scopes:
    """``Scopes`` from plain ``(name, start_ns, end_ns, stats)`` device
    events per plane (the file format apart, as in ``bench/trace.py``)."""
    out: Dict[str, List[Op]] = {}
    found = None
    for plane, events in ops.items():
        out[plane] = []
        for name, a, b, stats in events:
            path, where = scope_path(name, stats)
            found = found or where
            module = stats.get("hlo_module")
            out[plane].append(Op(name, a, b, path,
                                 module_name(module) if module else None))
    return Scopes(out, dict(modules or {}), list(annotations or []),
                  window, found)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field, value)`` of the protobuf message in ``buf[lo:hi]``: an int
    for a varint, ``(start, end)`` for any other wire type."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"unknown protobuf wire type {kind}")
        yield key >> 3, value


def metadata_paths(path: str, want: Callable[[str], bool]
                   ) -> Dict[str, Dict[str, str]]:
    """``{plane: {op name: tf_op}}`` from the event metadata of the planes
    ``want`` names, read from the ``.xplane.pb`` encoding (``XSpace.planes``
    = 1; ``XPlane``: name 2, lines 3, event_metadata 4, stat_metadata 5;
    ``XEventMetadata``: name 2, stats 5; ``XStat``: metadata_id 1,
    str_value 5, ref_value 7, a reference to a ``stat_metadata`` name).
    Event lines are skipped unread."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")

    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = text(v)
            elif f in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[entry.get(1, meta.get(1, 0))] = \
                        text(meta[2]) if 2 in meta else ""
        if name is None or not want(name):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        paths = out[name] = {}
        for ev in events:
            op, found = None, None
            for f, v in _fields(buf, *ev):
                if f == 2:
                    op = text(v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in tf_op:
                        found = (text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7)))
            if op is not None and found:
                paths[op] = found
    return out


def load(path: str, is_device_op: Callable[[str, str], bool],
         window: Tuple[float, float],
         is_annotation: Callable[[str], bool]) -> Scopes:
    """Read the ops (with their stats), the ``XLA Modules`` line of each
    device plane and the program's annotations (host events whose name
    ``is_annotation``) from an ``.xplane.pb``. An op whose own stats carry
    no ``tf_op`` takes its event metadata's."""
    from jax.profiler import ProfileData

    ops: Dict[str, list] = collections.defaultdict(list)
    modules: Dict[str, List[Event]] = collections.defaultdict(list)
    annotations: List[Event] = []
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    device_planes = {plane.name for plane in planes
                     if any(is_device_op(plane.name, ln.name)
                            for ln in plane.lines)}
    paths = metadata_paths(path, device_planes.__contains__)
    for plane in planes:
        lines = list(plane.lines)
        device = plane.name in device_planes
        by_name = paths.get(plane.name, {})
        for line in lines:
            op_line = is_device_op(plane.name, line.name)
            module_line = device and line.name == "XLA Modules"
            for ev in line.events:
                a = float(ev.start_ns)
                b = a + float(ev.duration_ns)
                if op_line:
                    stats = dict(ev.stats)
                    if "tf_op" not in stats and ev.name in by_name:
                        stats["tf_op"] = by_name[ev.name]
                    ops[plane.name].append((ev.name, a, b, stats))
                elif module_line:
                    modules[plane.name].append((ev.name, a, b))
                elif is_annotation(ev.name):
                    annotations.append((ev.name, a, b))
    return from_events(ops, window, modules, annotations)


def _mean_union(groups: Iterable[List[Tuple[float, float]]],
                window: Tuple[float, float], n: int) -> float:
    lo, hi = window
    total = sum(sum(b - a for a, b in tr.union(iv, lo, hi))
                for iv in groups)
    return total * 1e-9 / n


def scope_s(sc: Scopes, scope: str) -> Optional[float]:
    """Device seconds inside the window under named scope ``scope``;
    ``None`` where no op of the trace carries it."""
    if not any(in_scope(op.path, scope)
               for ops in sc.ops.values() for op in ops):
        return None
    return _mean_union(([(op.start, op.end) for op in ops
                         if in_scope(op.path, scope)]
                        for ops in sc.ops.values()),
                       sc.window, max(1, len(sc.ops)))


def program_s(sc: Scopes, program: str) -> Optional[float]:
    """Device seconds inside the window of the jitted program ``program``
    (module ``jit_<program>``): its ops by their ``hlo_module`` stat, or,
    where no op carries one, the ``XLA Modules`` line; ``None`` where the
    trace names no such module."""
    want = "jit_" + program
    if any(op.module for ops in sc.ops.values() for op in ops):
        groups = {p: [(op.start, op.end) for op in ops if op.module == want]
                  for p, ops in sc.ops.items()}
    else:
        groups = {p: [(a, b) for name, a, b in evs
                      if module_name(name) == want]
                  for p, evs in sc.modules.items()}
    if not any(groups.values()):
        return None
    return _mean_union(groups.values(), sc.window, max(1, len(sc.ops)))


def per_query_ms(seconds: Optional[float], answered: int) -> Optional[float]:
    """Device milliseconds per answered query; ``None`` without either."""
    if seconds is None or not answered:
        return None
    return 1e3 * seconds / answered
