"""Batched sharded query engine for wide Boolean queries over Roaring slabs.

Since the ``repro.roaring`` object API, the stacked-slab *type* is
``repro.roaring.RoaringSlab`` with a leading batch axis (built by
``roaring.stack``); this package keeps the expression layer:

  * ``engine`` — Boolean expression trees (AND/OR/ANDNOT over stack members
    or directly-attached ``leaf(slab)`` operands) evaluated as log-depth
    kind-dispatching tree reductions with a single deferred canonicalization,
    cardinality-only and top-k-by-cardinality scoring through the
    batched-meta dispatch kernel, ``shard_map`` sharding of the slab
    axis across a device mesh, and the planned scoring of an immutable
    stack (``plan_scan`` / ``topk_by_scan``: each row read in place over
    the slots its containers fill).

``SlabStack`` / ``stack_from_slabs`` / ``union_many_batched`` remain as
deprecated shims over the ``repro.roaring`` equivalents.

Consumers: ``serve.kv_cache`` pool rebuilds, ``sparsity.masks`` pattern
unions, and ``grad_comp`` leaf-overlap scans.
"""

from repro.index.engine import (And, AndNot, DegradationStats, Expr, Leaf, Or,
                                ScanPlan, SlabLeaf, and_, andnot,
                                batched_and_card, batched_and_card_sharded,
                                degradation_stats, execute, execute_card,
                                launch_model, leaf, or_, plan_scan,
                                reset_degradation, topk_by_card,
                                topk_by_card_sharded,
                                topk_by_scan, union_many_batched,
                                wide_intersect, wide_union)
from repro.index.stack import SlabStack, stack_from_slabs

__all__ = [
    "SlabStack", "stack_from_slabs",
    "Expr", "Leaf", "SlabLeaf", "And", "Or", "AndNot",
    "leaf", "and_", "or_", "andnot",
    "execute", "execute_card", "wide_union", "wide_intersect",
    "batched_and_card", "batched_and_card_sharded",
    "topk_by_card", "topk_by_card_sharded",
    "ScanPlan", "plan_scan", "topk_by_scan",
    "union_many_batched", "launch_model",
    "DegradationStats", "degradation_stats", "reset_degradation",
]
