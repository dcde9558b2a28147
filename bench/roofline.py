"""The least bytes a query must move, counted from its operands.

An operand is a bitmap over a universe cut into 2^16-value chunks. Each
non-empty chunk is one Roaring container, and no implementation of the
format reads it in fewer bytes than its smallest encoding: an array of
``2 * card`` bytes, a bitmap of 8,192 bytes, or ``4 * runs`` bytes of run
pairs. The query's least bytes are the sum over its distinct operands'
containers: each read once. Empty chunks cost nothing, and a result the
query does not return (a count, a sum, a score) is not written.

Counting the smallest encoding, and never a layout's row size, keeps
``least bytes / peak bandwidth`` under the time any layout takes, so the
roofline share cannot pass 100% by a change of layout.
"""

from __future__ import annotations

import numpy as np

CHUNK = 1 << 16
BITMAP_BYTES = 8192


def container_bytes(card: np.ndarray, runs: np.ndarray) -> np.ndarray:
    """Smallest Roaring encoding of each container (0 where empty)."""
    card = np.asarray(card, np.int64)
    runs = np.asarray(runs, np.int64)
    best = np.minimum(np.minimum(2 * card, BITMAP_BYTES), 4 * runs)
    return np.where(card > 0, best, 0)


def sorted_ids_bytes(ids: np.ndarray) -> int:
    """Least bytes of a bitmap given as sorted unique values."""
    ids = np.asarray(ids, np.int64)
    if ids.size == 0:
        return 0
    chunk = ids >> 16
    keys, card = np.unique(chunk, return_counts=True)
    # a run starts where a value does not follow its predecessor, or where
    # a new chunk begins
    start = np.ones(ids.size, bool)
    start[1:] = (np.diff(ids) != 1) | (np.diff(chunk) != 0)
    runs = np.bincount(np.searchsorted(keys, chunk[start]),
                       minlength=keys.size)
    return int(container_bytes(card, runs).sum())


def mask_bytes(mask: np.ndarray) -> int:
    """Least bytes of a bitmap given as a boolean row mask."""
    mask = np.asarray(mask, bool)
    pad = (-mask.size) % CHUNK
    m = np.concatenate([mask, np.zeros(pad, bool)]).reshape(-1, CHUNK)
    card = m.sum(axis=1)
    runs = m[:, :1].sum(axis=1) + (m[:, 1:] & ~m[:, :-1]).sum(axis=1)
    return int(container_bytes(card, runs).sum())
