"""From a profiler trace to device busy time, idle gaps and top operations.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device operations are the events of each device plane's
``XLA Ops`` line; the harness's own host spans are the
``jax.profiler.TraceAnnotation`` events named ``bench.<what>`` on the host
plane, and the harness adds the program's spans under the same prefix. Everything below works on plain ``(name, start_ns, end_ns)`` tuples,
so the arithmetic is tested apart from the file format.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)

PREFIX = "bench."


def tpu_ops(plane: str, line: str) -> bool:
    """Device operations on a TPU: the ``XLA Ops`` line of each chip."""
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Event]]           # plane name -> op events
    host: List[Event]                        # bench.* host spans

    def window(self) -> Tuple[float, float]:
        """The ``bench.window`` span: the traced window."""
        spans = [(a, b) for n, a, b in self.host if n == PREFIX + "window"]
        if len(spans) != 1:
            raise ValueError(f"expected one {PREFIX}window span, found "
                             f"{len(spans)}")
        return spans[0]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def load(path: str, is_device_op: Callable[[str, str], bool] = tpu_ops
         ) -> Trace:
    from jax.profiler import ProfileData

    device: Dict[str, List[Event]] = collections.defaultdict(list)
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            device_line = is_device_op(plane.name, line.name)
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if device_line:
                    device[plane.name].append((ev.name, start, start + dur))
                elif ev.name.startswith(PREFIX):
                    host.append((ev.name, start, start + dur))
    return Trace(dict(device), host)


def union(intervals: Sequence[Tuple[float, float]],
          lo: float = float("-inf"), hi: float = float("inf")
          ) -> List[Tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in a gap: the innermost (shortest) host span
    open at the gap's midpoint, the window span itself left out; ``idle``
    where none is."""
    mid = 0.5 * (gap[0] + gap[1])
    best, shortest = "idle", float("inf")
    for name, a, b in host:
        if name != PREFIX + "window" and a <= mid <= b and b - a < shortest:
            best, shortest = name[len(PREFIX):], b - a
    return best


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over the device planes
    top_ops: List[Tuple[str, float]]   # [(name, seconds)] most time first
    idle_gaps: List[Tuple[str, float]]  # [(host activity, seconds)]
    idle_by_activity: Dict[str, float]


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Reduced:
    """Busy union, idle gaps and top operations inside ``window`` (ns); by
    default the span of the ``bench.window`` host annotation."""
    lo, hi = window or trace.window()
    if not trace.device:
        raise ValueError("the trace holds no device operations")
    busy_total = 0.0
    per_op: Dict[str, float] = collections.defaultdict(float)
    all_gaps: List[Tuple[str, float]] = []
    by_activity: Dict[str, float] = collections.defaultdict(float)
    for events in trace.device.values():
        merged = union([(a, b) for _, a, b in events], lo, hi)
        busy_total += sum(b - a for a, b in merged)
        for name, a, b in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                per_op[name] += d * 1e-9
        for g in gaps(merged, lo, hi):
            what = label(g, trace.host)
            all_gaps.append((what, (g[1] - g[0]) * 1e-9))
            by_activity[what] += (g[1] - g[0]) * 1e-9
    n = len(trace.device)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    ops = [(_short(name), secs) for name, secs in ops]
    longest = sorted(all_gaps, key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   top_ops=ops, idle_gaps=longest,
                   idle_by_activity=dict(by_activity))


def _short(op: str, limit: int = 160) -> str:
    """An XLA op's trace name is its whole HLO instruction; keep its head."""
    return op if len(op) <= limit else op[: limit - 3] + "..."
