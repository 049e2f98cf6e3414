"""Distributed paged KV pool: pages over ``data``, heads over ``model``.

The logical pool is still ONE array pair ``k/v`` with the PagedKVPool
layout, but its page-row axis is laid out as ``D`` contiguous
per-shard blocks of ``pages_per_shard + 1`` rows — the last row of
every block is that shard's **trash page** (the write sink for padded
batch rows and non-owner tail writes of the sharded fused step).  A
``NamedSharding`` places block ``d`` on the mesh's data-row ``d`` and
splits the KV-head axis over ``model`` (``launch.sharding.
paged_pool_spec`` — the same head axis the TP param rules shard), so
under ``shard_map`` each device sees exactly its ``(pages_d,
heads_m)`` slab and plans index it with shard-local page rows.

Allocation goes through one :class:`ShardedPageAllocator` facade over
``D`` per-shard :class:`~repro.serving.kv_cache.PageAllocator`\\ s — the
single-device invariants (refcounts, free-list partition, ``check()``,
watermarks) hold *per shard*.  Placement is deterministic: a node's
pages stay on one shard until its ``seq_split_pages`` quota is
reached, then continue on the next-freest shard — a long shared prefix
therefore lands as contiguous page runs on several shards, which is
exactly the sequence split the plan partitioner turns into a
cross-device POR merge.

Host-side prefill keeps using the global array (gathers/scatters over
shard boundaries lower to collectives under GSPMD); ``canonicalize()``
re-pins the arrays to the pool sharding at plan-epoch boundaries so
the donated fused step always starts from the canonical layout.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..launch.sharding import paged_pool_spec
from ..serving.kv_cache import PageAllocator, PagedKVPool


class ShardedPageAllocator:
    """Facade over per-shard allocators with a placement policy.

    Page ids are global *rows* into the pool array: row ``g`` lives on
    shard ``g // stride`` as local row ``g % stride`` where ``stride =
    pages_per_shard + 1`` (local row ``pages_per_shard`` is the shard's
    trash page and is never allocated).
    """

    def __init__(self, num_shards: int, pages_per_shard: int,
                 seq_split_pages: int = 0):
        self.num_shards = num_shards
        self.pages_per_shard = pages_per_shard
        self.stride = pages_per_shard + 1
        # quota of consecutive pages one affinity key keeps on a shard
        # before placement moves on (0 = only move when the shard fills)
        self.seq_split_pages = int(seq_split_pages)
        self.shards = [PageAllocator(pages_per_shard)
                       for _ in range(num_shards)]
        # hint (node id) -> [shard, pages placed there since last move,
        # live refcount over the hint's rows].  Insertion order doubles
        # as LRU order: entries are re-appended on every use, and the
        # size bound only evicts entries whose live count is zero — a
        # FIFO pop could drop a LIVE node's entry, resetting its
        # seq_split_pages quota and scattering later growth.
        self._affinity: Dict[int, List[int]] = {}
        self._row_hint: Dict[int, int] = {}     # global row -> hint

    # -- id mapping ---------------------------------------------------- #
    def shard_of(self, row: int) -> int:
        return row // self.stride

    def local_of(self, row: int) -> int:
        return row % self.stride

    # -- aggregate accounting (engine-facing API) ---------------------- #
    @property
    def num_pages(self) -> int:
        return self.num_shards * self.pages_per_shard

    @property
    def num_free(self) -> int:
        return sum(s.num_free for s in self.shards)

    @property
    def num_used(self) -> int:
        return sum(s.num_used for s in self.shards)

    @property
    def peak_used(self) -> int:
        return sum(s.peak_used for s in self.shards)

    @property
    def total_allocs(self) -> int:
        return sum(s.total_allocs for s in self.shards)

    def occupancy(self) -> float:
        return self.num_used / max(self.num_pages, 1)

    def shard_occupancy(self) -> List[float]:
        return [s.occupancy() for s in self.shards]

    # -- alloc / release ------------------------------------------------ #
    def _touch(self, hint: int) -> None:
        """LRU-touch: re-append the entry so the size bound sees it last."""
        self._affinity[hint] = self._affinity.pop(hint)

    def _pick(self, hint: Optional[int]) -> int:
        if hint is not None:
            st = self._affinity.get(hint)
            if (st is not None and self.shards[st[0]].num_free > 0
                    and (self.seq_split_pages <= 0
                         or st[1] < self.seq_split_pages)):
                self._touch(hint)
                return st[0]
        # next-freest shard, deterministic ties (lowest index); when an
        # affinity key moves on, exclude its current shard so a reached
        # quota really splits the run even if that shard is the freest
        prev = self._affinity.get(hint, [None, 0])[0] if hint is not None \
            else None
        best, best_free = -1, -1
        for i, s in enumerate(self.shards):
            if i == prev and any(j != prev and x.num_free > 0
                                 for j, x in enumerate(self.shards)):
                continue
            if s.num_free > best_free:
                best, best_free = i, s.num_free
        if best_free <= 0:
            raise MemoryError(
                f"KV pool exhausted: need 1, have {self.num_free}")
        if hint is not None:
            st = self._affinity.get(hint)
            if st is None:
                self._affinity[hint] = [best, 0, 0]
            else:
                st[0], st[1] = best, 0
                self._touch(hint)
            self._trim()
        return best

    def _trim(self) -> None:
        # bound on stale node ids: evict oldest entry with NO live pages
        # (live entries must keep their quota state — see _affinity)
        while len(self._affinity) > 8192:
            dead = next((k for k, v in self._affinity.items() if v[2] == 0),
                        None)
            if dead is None:
                return
            del self._affinity[dead]

    def alloc(self, n: int, hint: Optional[int] = None) -> List[int]:
        if n > self.num_free:
            raise MemoryError(
                f"KV pool exhausted: need {n}, have {self.num_free}")
        rows = []
        for _ in range(n):
            sh = self._pick(hint)
            local = self.shards[sh].alloc(1)[0]
            row = sh * self.stride + local
            if hint is not None:
                st = self._affinity[hint]
                st[1] += 1
                st[2] += 1
                self._row_hint[row] = hint
            rows.append(row)
        return rows

    def alloc_replicas(self, n: int,
                       hint: Optional[int] = None) -> Dict[int, List[int]]:
        """Allocate ``n`` pages on EVERY shard (replication placement).

        Returns ``{shard: [global rows]}`` with one ``n``-page run per
        shard.  All-or-nothing: raises ``MemoryError`` without touching
        any shard if one of them cannot fit ``n`` pages.  The affinity
        entry is pinned to the freest shard (the replica the scheduler
        treats as primary) and its live count covers ALL replica rows,
        so the entry survives the size bound while any replica lives.
        """
        if any(s.num_free < n for s in self.shards):
            raise MemoryError(
                f"KV pool exhausted for replication: need {n} pages on "
                f"each of {self.num_shards} shards, free per shard = "
                f"{[s.num_free for s in self.shards]}")
        primary = max(range(self.num_shards),
                      key=lambda i: (self.shards[i].num_free, -i))
        out: Dict[int, List[int]] = {}
        for sh in range(self.num_shards):
            locals_ = self.shards[sh].alloc(n)
            out[sh] = [sh * self.stride + lo for lo in locals_]
        if hint is not None:
            st = self._affinity.get(hint)
            if st is None:
                st = self._affinity[hint] = [primary, 0, 0]
            else:
                st[0] = primary
                self._touch(hint)
            for rows in out.values():
                for g in rows:
                    self._row_hint[g] = hint
                    st[2] += 1
            self._trim()
        return out

    def _by_shard(self, rows: List[int]) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for g in rows:
            sh, local = self.shard_of(g), self.local_of(g)
            if sh >= self.num_shards or local >= self.pages_per_shard:
                raise ValueError(f"page row {g} outside the pool")
            out.setdefault(sh, []).append(local)
        return out

    def retain(self, rows: List[int]) -> None:
        for sh, locals_ in self._by_shard(rows).items():
            self.shards[sh].retain(locals_)
        for g in rows:
            h = self._row_hint.get(g)
            if h is not None and h in self._affinity:
                self._affinity[h][2] += 1

    def release(self, rows: List[int]) -> None:
        for sh, locals_ in self._by_shard(rows).items():
            self.shards[sh].release(locals_)
        for g in rows:
            h = self._row_hint.get(g)
            if h is None:
                continue
            if self.local_of(g) not in self.shards[self.shard_of(g)]._refs:
                del self._row_hint[g]     # row fully freed
            st = self._affinity.get(h)
            if st is not None:
                st[2] = max(0, st[2] - 1)

    def used_page_ids(self) -> Dict[int, int]:
        """Allocated GLOBAL rows -> refcount across every shard (the
        engine's self-check compares this with forest page ownership)."""
        out: Dict[int, int] = {}
        for sh, s in enumerate(self.shards):
            for local, refs in s.used_page_ids().items():
                out[sh * self.stride + local] = refs
        return out

    def check(self) -> None:
        """Per-shard structural invariants (tests call after workloads)."""
        for s in self.shards:
            s.check()


class ShardedKVPool(PagedKVPool):
    """Mesh-sharded paged pool; same engine-facing API as PagedKVPool."""

    def __init__(self, n_layers: int, num_pages: int, page_size: int,
                 n_kv: int, head_dim: int, *, mesh,
                 seq_split_pages: int = 0, dtype=jnp.float32):
        D = mesh.shape["data"]
        per_shard = num_pages // D
        if per_shard < 1 or num_pages % D:
            raise ValueError(
                f"num_pages={num_pages} must be a positive multiple of "
                f"the data axis ({D}): silent truncation would change "
                f"eviction behaviour in capacity-tuned runs")
        self.mesh = mesh
        self.n_layers = n_layers
        self.num_pages = D * per_shard          # allocatable pages
        self.page_size = page_size
        self.allocator = ShardedPageAllocator(D, per_shard, seq_split_pages)
        rows = D * self.allocator.stride
        self.sharding = jax.sharding.NamedSharding(
            mesh, paged_pool_spec(mesh, n_kv))
        # allocated sharded: a pool sized for the whole mesh need not
        # fit on its first device
        shape = (n_layers, rows, page_size, n_kv, head_dim)
        self.k = jnp.zeros(shape, dtype, device=self.sharding)
        self.v = jnp.zeros(shape, dtype, device=self.sharding)

    @property
    def num_shards(self) -> int:
        return self.allocator.num_shards

    @property
    def page_stride(self) -> int:
        return self.allocator.stride

    @property
    def local_trash(self) -> int:
        """Shard-local row id of every shard's trash page."""
        return self.allocator.pages_per_shard

    @property
    def trash_page(self) -> int:
        """Global row of shard 0's trash page (single-device API compat;
        the sharded step always uses per-shard local trash rows)."""
        return self.local_trash

    def shard_of(self, row: int) -> int:
        return self.allocator.shard_of(row)

    def local_of(self, row: int) -> int:
        return self.allocator.local_of(row)

    def shard_occupancy(self) -> List[float]:
        return self.allocator.shard_occupancy()

    def canonicalize(self) -> None:
        """Re-pin k/v to the pool sharding (host-side prefill scatters
        may have let GSPMD drift the layout); called at plan epochs so
        the donated SPMD step starts canonical."""
        self.k = jax.device_put(self.k, self.sharding)
        self.v = jax.device_put(self.v, self.sharding)
