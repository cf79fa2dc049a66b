"""Hashed prefix cache for chunked prefill: batch-1 cache snapshots.

The dominant serving pattern since the protein-design workloads landed
is many requests sharing one scaffold — the same template prefix,
batch-score prompt, or infill frame, differing only in the tail and the
sampling knobs. Each one re-runs the shared prefix through the model at
admission. PagedAttention's cache-reuse argument (PAPERS.md) is that a
prefix computed once should be computed once: the batch-1 decode cache
after feeding ``tokens[0:d]`` is a pure function of those ``d`` tokens
and the weights — sampling parameters, PRNG key, and request identity
play no part until the first decode step — so a snapshot taken at depth
``d`` can seed ANY later request whose first ``d`` tokens match,
bit-identically.

This is an LRU over such snapshots, keyed on ``(depth, sha1 of the
token bytes)``. ``advance_prefill`` inserts at every chunk boundary;
``begin_prefill`` looks up the DEEPEST stored prefix of a new request's
feed region and resumes there. A stored snapshot is the cache's OWN
tree: the engine's programs overwrite the batch-1 cache they are handed
(it is donated to them), so the admission that inserted a tree goes on
with a copy of it and an admission that hits one is seeded with a copy
— ``insert`` and ``lookup`` themselves hand references about and copy
nothing (the engine does, ``engine._copy_cache``, and counts it). A byte
budget bounds device memory:
snapshots are whole batch-1 cache trees (summed leaf ``nbytes``), and
inserting past the budget evicts least-recently-used entries first.

Weight dependence is the one invalidation hazard: a hot reload swaps
the params a snapshot was computed under, so ``ServeEngine
.commit_params`` calls ``clear()``. Counters survive a clear — the
fleet console should see the invalidation as a bytes dip, not a
history reset.

Telemetry: one ``{"ev": "prefix_cache", "op": "hit"|"miss"|"evict"}``
record per event. The record grammar is owned HERE (PGL006 lints it to
stay here); hit/miss/bytes/evictions also ride the serving metrics
registry as gauges, published by the scheduler.
"""

from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from typing import Any, Optional, Tuple

import jax
import numpy as np

from progen_tpu.telemetry.spans import get_telemetry


def _tree_bytes(cache) -> int:
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(cache)))


def _digest(row: np.ndarray, depth: int) -> bytes:
    return hashlib.sha1(
        np.ascontiguousarray(row[:depth], np.int32).tobytes()
    ).digest()


class PrefixCache:
    """LRU of (token-prefix -> batch-1 cache snapshot) under a byte
    budget. Single-threaded like the scheduler that feeds it."""

    def __init__(self, max_bytes: int):
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_bytes = int(max_bytes)
        # (depth, digest) -> (cache, nbytes); insertion/refresh order IS
        # the LRU order (oldest first)
        self._entries: "OrderedDict[Tuple[int, bytes], Tuple[Any, int]]" \
            = OrderedDict()
        # depths present, maintained so lookup probes only real
        # candidates (a handful of chunk boundaries, not every int)
        self._depth_counts: dict = {}
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.inserts = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _emit(self, op: str, depth: int) -> None:
        get_telemetry().emit({
            "ev": "prefix_cache", "op": op, "ts": time.time(),
            "depth": int(depth), "bytes": int(self.bytes),
            "entries": len(self._entries),
        })

    def lookup(self, row: np.ndarray, feed_len: int
               ) -> Tuple[int, Optional[Any]]:
        """(depth, snapshot) for the DEEPEST stored prefix of
        ``row[:feed_len]``, or ``(0, None)``. A hit refreshes the
        entry's LRU position. ``feed_len`` caps the usable depth: a
        snapshot deeper than the feed region would include positions
        this request wants to prime differently."""
        row = np.asarray(row, np.int32).reshape(-1)
        for depth in sorted(self._depth_counts, reverse=True):
            if depth > feed_len:
                continue
            key = (depth, _digest(row, depth))
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                self._emit("hit", depth)
                return depth, entry[0]
        self.misses += 1
        self._emit("miss", 0)
        return 0, None

    def insert(self, row: np.ndarray, depth: int, cache) -> bool:
        """Store a snapshot of the cache after feeding ``row[:depth]``.
        Refreshes (without re-storing) a prefix already present; skips
        snapshots that alone exceed the whole budget; evicts LRU
        entries until the new one fits. Returns True when stored."""
        if depth < 1:
            return False
        row = np.asarray(row, np.int32).reshape(-1)
        key = (depth, _digest(row, depth))
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        nbytes = _tree_bytes(cache)
        if nbytes > self.max_bytes:
            return False
        while self._entries and self.bytes + nbytes > self.max_bytes:
            self._evict_lru()
        self._entries[key] = (cache, nbytes)
        self._depth_counts[depth] = self._depth_counts.get(depth, 0) + 1
        self.bytes += nbytes
        self.inserts += 1
        return True

    def _evict_lru(self) -> None:
        (depth, _), (_, nbytes) = self._entries.popitem(last=False)
        self.bytes -= nbytes
        self._depth_counts[depth] -= 1
        if self._depth_counts[depth] == 0:
            del self._depth_counts[depth]
        self.evictions += 1
        self._emit("evict", depth)

    def clear(self) -> None:
        """Drop every snapshot (hot reload: they were computed under
        the old weights). Counters are NOT reset."""
        self._entries.clear()
        self._depth_counts.clear()
        self.bytes = 0

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "inserts": self.inserts,
            "bytes": self.bytes,
            "entries": len(self._entries),
        }
