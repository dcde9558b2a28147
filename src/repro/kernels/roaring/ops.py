"""Jit'd wrappers for the roaring container kernels.

``use_pallas=None`` auto-selects the backend from the device: on a TPU every
entry launches its Pallas path (``kernel.py`` / ``fused.py``: kind lifts in
XLA, word ops and popcount in the Pallas kernel), elsewhere the XLA
reference (``ref.py``, same bytes out). ``use_pallas=True`` off the TPU runs
the Pallas kernel in interpret mode, which is how the CPU tests hold the two
paths to each other; ``tests/test_tpu_compile.py`` compiles the Pallas
paths for a TPU v5e.

Robustness hooks (PR 6): the public entry points are thin Python wrappers
over the jitted implementations, so two per-launch controls exist without
retracing —

* ``backend_scope("pallas" | "xla" | "auto")`` overrides the backend for
  every dispatch launched inside the ``with`` block whose caller did not
  pass an explicit ``use_pallas``/``interpret``; ``repro.index.execute``
  uses it to run its Pallas→XLA-ref degradation ladder without threading a
  flag through the whole row-state algebra;
* ``set_fault_hook(fn)`` installs a callable invoked with the resolved
  backend name (``"pallas"``/``"xla"``) before every kernel launch — the
  injectable-failure seam ``runtime.fault_tolerance.FaultPlan`` plugs into
  (raising there simulates a device/runtime failure at dispatch
  granularity). The hook fires at Python call time; inside an outer ``jit``
  trace that means once per trace, matching where a real lowering failure
  would surface;
* ``add_launch_hook(fn)`` / ``remove_launch_hook(fn)`` (PR 9) subscribe
  observers to every dispatch as a ``LaunchEvent(entry, backend)`` — the
  seam the ``repro.obs`` telemetry plane counts kernel launches through.
  Launch hooks fire *before* the fault hook, so a launch that the fault
  plan then fails is still accounted (matching real hardware, where the
  dispatch happened and then faulted). Launch hooks must not raise; any
  exception from one is swallowed.

Explicit ``use_pallas``/``interpret`` arguments always win over the scope
override, so tests pinning a backend stay pinned.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import dispatch as _d
from . import fused as _f
from . import kernel as _k
from . import ref as _ref
from . import scan as _s


# -- per-launch controls ------------------------------------------------------
_BACKEND_OVERRIDE: Optional[str] = None       # None == "auto"
_FAULT_HOOK: Optional[Callable[[str], None]] = None
_LAUNCH_HOOKS: Tuple[Callable[["LaunchEvent"], None], ...] = ()


@dataclasses.dataclass(frozen=True)
class LaunchEvent:
    """One kernel dispatch: which public entry point and which backend the
    launch resolved to (``"pallas"`` / ``"xla"``)."""

    entry: str
    backend: str


def add_launch_hook(hook: Callable[[LaunchEvent], None]) -> None:
    """Subscribe an observer to every kernel dispatch. Idempotent."""
    global _LAUNCH_HOOKS
    if hook not in _LAUNCH_HOOKS:
        _LAUNCH_HOOKS = _LAUNCH_HOOKS + (hook,)


def remove_launch_hook(hook: Callable[[LaunchEvent], None]) -> None:
    """Unsubscribe a launch observer (no-op if absent)."""
    global _LAUNCH_HOOKS
    _LAUNCH_HOOKS = tuple(h for h in _LAUNCH_HOOKS if h != hook)


@contextlib.contextmanager
def backend_scope(backend: Optional[str]):
    """Scoped backend override for auto-selecting dispatch launches.

    ``"pallas"`` forces the Pallas body (interpret mode off-TPU), ``"xla"``
    forces the XLA reference, ``"auto"``/``None`` restores hardware
    auto-selection. Nests; restores the previous override on exit.
    """
    global _BACKEND_OVERRIDE
    if backend not in (None, "auto", "pallas", "xla"):
        raise ValueError(f"unknown roaring backend {backend!r} "
                         "(want 'pallas', 'xla', or 'auto')")
    prev = _BACKEND_OVERRIDE
    _BACKEND_OVERRIDE = None if backend == "auto" else backend
    try:
        yield
    finally:
        _BACKEND_OVERRIDE = prev


def current_backend() -> str:
    """The backend an auto-selecting launch would use right now."""
    if _BACKEND_OVERRIDE is not None:
        return _BACKEND_OVERRIDE
    return "pallas" if _d.on_tpu() else "xla"


def set_fault_hook(hook: Optional[Callable[[str], None]]):
    """Install (or clear, with ``None``) the per-launch fault hook; returns
    the previous hook so callers can restore it."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    return prev


def _resolve(use_pallas: Optional[bool], interpret: bool,
             entry: str = "dispatch") -> tuple:
    """Resolve (use_pallas, interpret) to concrete booleans: explicit args
    win, then the scope override, then hardware auto-selection — then fire
    the launch hooks (accounting) and the fault hook (injection) with the
    resolved backend name, in that order so faulted launches still count."""
    if use_pallas is None and not interpret:
        use_pallas = current_backend() == "pallas"
    elif use_pallas is None:
        use_pallas = _d.on_tpu()
    backend = "pallas" if (use_pallas or interpret) else "xla"
    if _LAUNCH_HOOKS:
        ev = LaunchEvent(entry, backend)
        for hook in _LAUNCH_HOOKS:
            try:
                hook(ev)
            except Exception:
                pass
    if _FAULT_HOOK is not None:
        _FAULT_HOOK(backend)
    return use_pallas, interpret


@functools.partial(jax.jit, static_argnames=("op", "use_pallas", "interpret"))
def _container_op(a_bits, b_bits, kinds, op, use_pallas, interpret):
    if use_pallas or interpret:
        return _k.container_op_pallas(a_bits, b_bits, kinds, op,
                                      interpret=not _d.on_tpu())
    return _ref.container_op_ref(a_bits, b_bits, kinds, op)


def container_op(a_bits, b_bits, kinds, op: str = "or",
                 use_pallas: bool | None = None, interpret: bool = False):
    """Batched fused container op + popcount over key-aligned rows."""
    use_pallas, interpret = _resolve(use_pallas, interpret, "container_op")
    return _container_op(a_bits, b_bits, kinds, op, use_pallas, interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _array_intersect(a_arr, b_arr, cards, use_pallas, interpret):
    if use_pallas or interpret:
        return _k.array_intersect_pallas(a_arr, b_arr, cards,
                                         interpret=not _d.on_tpu())
    return _ref.array_intersect_ref(a_arr, b_arr, cards)


def array_intersect(a_arr, b_arr, cards,
                    use_pallas: bool | None = None, interpret: bool = False):
    """Batched array-container intersection (vectorized galloping)."""
    use_pallas, interpret = _resolve(use_pallas, interpret, "array_intersect")
    return _array_intersect(a_arr, b_arr, cards, use_pallas, interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _intersect_dispatch(a_data, b_data, meta, use_pallas, interpret):
    if use_pallas or interpret:
        return _k.intersect_dispatch_pallas(a_data, b_data, meta,
                                            interpret=not _d.on_tpu())
    return _ref.intersect_dispatch_ref(a_data, b_data, meta)


def intersect_dispatch(a_data, b_data, meta,
                       use_pallas: bool | None = None,
                       interpret: bool = False):
    """Kind-dispatch container intersection over key-aligned rows, routed
    by the declarative registry (``dispatch.AND_TABLE`` — the 4x4 grid
    including run containers).

    meta: i32[6C] interleaved (kind_a, kind_b, card_a, card_b, nruns_a,
    nruns_b). Returns (hits u16[C, 4096], card i32[C]) — the slab layer
    compacts / lazily canonicalizes best-of-three on top of this. Pallas
    path on TPU, XLA reference elsewhere.
    """
    use_pallas, interpret = _resolve(use_pallas, interpret,
                                     "intersect_dispatch")
    return _intersect_dispatch(a_data, b_data, meta, use_pallas, interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _intersect_dispatch_stacked(a_data, b_data, meta, use_pallas, interpret):
    if use_pallas or interpret:
        return _k.intersect_dispatch_stacked_pallas(a_data, b_data, meta,
                                                    interpret=not _d.on_tpu())
    N, C = a_data.shape[0], a_data.shape[1]
    b_data = jnp.broadcast_to(b_data, a_data.shape)
    hits, card = _ref.intersect_dispatch_ref(
        a_data.reshape(N * C, a_data.shape[2]),
        b_data.reshape(N * C, b_data.shape[2]), meta.reshape(-1))
    return hits.reshape(N, C, a_data.shape[2]), card.reshape(N, C)


@functools.partial(jax.jit, static_argnames=("plan", "use_pallas",
                                             "interpret"))
def _fused_tree(ops_data, meta, plan, use_pallas, interpret):
    if use_pallas or interpret:
        return _f.fused_eval_pallas(ops_data, meta, plan=plan,
                                    interpret=not _d.on_tpu())
    return _f.fused_eval_ref(ops_data, meta, plan=plan)


def fused_tree(ops_data, meta, plan,
               use_pallas: bool | None = None, interpret: bool = False):
    """Evaluate a whole compiled Boolean expression tree in ONE launch.

    ops_data: u16[N, C, 4096] raw container rows (one per distinct leaf, key
    aligned); meta: the ``fused.pack_lift_meta`` block (i32[3*N*C]); plan:
    a ``fused.FusedPlan`` (static — hash-consed per expression shape, so
    same-shape queries never retrace). Returns
    (bits u16[C, 4096] bitmap-domain root rows, card i32[C]); the caller
    runs the single best-of-three canonicalization. Pallas tape kernel on
    TPU, tape-mirroring XLA evaluator elsewhere.
    """
    use_pallas, interpret = _resolve(use_pallas, interpret, "fused_tree")
    return _fused_tree(ops_data, meta, plan, use_pallas, interpret)


def intersect_dispatch_stacked(a_data, b_data, meta,
                               use_pallas: bool | None = None,
                               interpret: bool = False):
    """Stacked (batched-meta) kind-dispatch intersection: N key-aligned
    slabs of C rows each in one launch — the ``repro.index`` wide-query
    engine's inner kernel.

    a_data: u16[N, C, 4096] raw container rows; b_data: the same shape, or
    u16[C, 4096] — one slab shared by all N (a query scored against a
    stack); meta: i32[N, 6C] per-slab interleaved (kind, card, n_runs) x2.
    Returns (hits u16[N, C, 4096], card i32[N, C]) with the same
    per-pair-class semantics as ``intersect_dispatch``.
    """
    use_pallas, interpret = _resolve(use_pallas, interpret,
                                     "intersect_dispatch_stacked")
    return _intersect_dispatch_stacked(a_data, b_data, meta, use_pallas,
                                       interpret)


@functools.partial(jax.jit, static_argnames=("width", "bitmaps", "use_pallas",
                                             "interpret"))
def _card_scan(payload, groups, places, codes, q_words, width, bitmaps,
               use_pallas, interpret):
    if use_pallas or interpret:
        return _s.card_scan_pallas(payload, groups, places, codes, q_words,
                                   width=width, bitmaps=bitmaps,
                                   interpret=not _d.on_tpu())
    return _s.card_scan_ref(payload, groups, places, codes, q_words,
                            width=width, bitmaps=bitmaps)


def card_scan(payload, groups, places, codes, q_words, *, width: int,
              bitmaps: bool, use_pallas: bool | None = None,
              interpret: bool = False):
    """Scores of one width class of a scan plan: i32[S, GROUP]
    ``|term ∩ query|`` for the terms of the stack's row groups ``groups``
    whose scan codes ``codes`` leaves unmasked, each read over its first
    ``width`` slots (``scan.card_scan_pallas`` for the arguments). Pallas
    kernel ``roaring_card_scan`` on TPU, the probing XLA mirror
    elsewhere."""
    use_pallas, interpret = _resolve(use_pallas, interpret, "card_scan")
    return _card_scan(payload, groups, places, codes, q_words, width,
                      bitmaps, use_pallas, interpret)


@functools.partial(jax.jit, static_argnames=("plan", "use_pallas",
                                             "interpret"))
def _group_card(leaves, groups, slices, plan, use_pallas, interpret):
    if use_pallas or interpret:
        return _f.group_card_pallas(leaves, groups, slices, plan,
                                    interpret=not _d.on_tpu())
    return _f.group_card_ref(leaves, groups, slices, plan)


def group_card(leaves, groups, slices, plan, use_pallas: bool | None = None,
               interpret: bool = False):
    """i32[max(G, 1), B] ``|filter ∩ group_g ∩ slice_b|`` over bitmap-domain
    words (``fused.group_card_pallas`` for the arguments; ``plan`` is
    static). Pallas kernel ``roaring_group_card`` on TPU, the tape-mirroring
    XLA evaluator elsewhere."""
    use_pallas, interpret = _resolve(use_pallas, interpret, "group_card")
    return _group_card(leaves, groups, slices, plan, use_pallas, interpret)
