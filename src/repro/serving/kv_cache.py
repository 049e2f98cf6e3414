"""Paged KV-cache pool + page allocator (PagedAttention layout, paper §4.1/§6).

The pool holds all attention layers' KV pages:
``k/v: (n_attn_layers, num_pages, page_size, n_kv, head_dim)``.
Pages are allocated from a free list with reference counts so prefix nodes
shared by multiple requests are freed only when the last request releases
them.  The forest nodes record their ``page_ids``; the plan compiler reads
them directly — the CoDec kernel follows this exact layout.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class PageAllocator:
    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        # accounting (watermarks / eviction diagnostics)
        self.peak_used = 0
        self.total_allocs = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return len(self._refs)

    def occupancy(self) -> float:
        return self.num_used / max(self.num_pages, 1)

    def alloc(self, n: int, hint: Optional[int] = None) -> List[int]:
        """``hint`` (a forest node id) is a placement affinity key; the
        single-shard allocator ignores it (the sharded pool's allocator
        uses it to keep a node's pages together / sequence-split them)."""
        if n > len(self._free):
            raise MemoryError(
                f"KV pool exhausted: need {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.total_allocs += n
        self.peak_used = max(self.peak_used, self.num_used)
        return pages

    def retain(self, pages: List[int]) -> None:
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"retain of unallocated page id {p}")
            self._refs[p] += 1

    def release(self, pages: List[int]) -> None:
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"release of unallocated page id {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)

    def used_page_ids(self) -> Dict[int, int]:
        """Snapshot of allocated pages -> refcount (``DecodeEngine.check``
        cross-references this against forest node ownership)."""
        return dict(self._refs)

    def check(self) -> None:
        """Structural invariants (tests call this after workloads)."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate page ids on the free list")
        if free & set(self._refs):
            raise AssertionError("page both free and referenced")
        if len(free) + len(self._refs) != self.num_pages:
            raise AssertionError(
                f"page partition broken: {len(free)} free + "
                f"{len(self._refs)} used != {self.num_pages}")
        if any(r <= 0 for r in self._refs.values()):
            raise AssertionError("non-positive refcount")


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_tokens(k, v, li, pg, of, k_new, v_new):
    """In-place scatter into the donated pools: an eager ``.at[].set``
    would hold a second copy of the whole pool while it runs."""
    return (k.at[li, pg, of].set(k_new.astype(k.dtype)),
            v.at[li, pg, of].set(v_new.astype(v.dtype)))


class PagedKVPool:
    """Device-resident paged pool for all attention layers."""

    def __init__(self, n_layers: int, num_pages: int, page_size: int,
                 n_kv: int, head_dim: int, dtype=jnp.float32):
        self.n_layers = n_layers
        self.num_pages = num_pages
        self.page_size = page_size
        # one extra physical page past the allocator's range: a write
        # sink for padded batch rows of the fused decode step (their
        # scattered tail KV must land somewhere that no plan ever reads)
        self.k = jnp.zeros((n_layers, num_pages + 1, page_size, n_kv,
                            head_dim), dtype)
        self.v = jnp.zeros_like(self.k)
        self.allocator = PageAllocator(num_pages)

    @property
    def trash_page(self) -> int:
        """Physical page id of the write sink (never allocated)."""
        return self.num_pages

    @property
    def num_free(self) -> int:
        return self.allocator.num_free

    @property
    def page_bytes(self) -> int:
        """K+V bytes of one page across all attention layers."""
        per = self.k.shape[0] * self.k.shape[2] * self.k.shape[3] \
            * self.k.shape[4]
        return 2 * per * self.k.dtype.itemsize

    def occupancy(self) -> float:
        return self.allocator.occupancy()

    def layer_pools(self, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.k[layer], self.v[layer]

    def write_tokens(self, layer: int, pages: np.ndarray, offsets: np.ndarray,
                     k_new: jnp.ndarray, v_new: jnp.ndarray) -> None:
        """Scatter n tokens into (page, offset) slots of one layer.

        pages/offsets: (n,); k_new/v_new: (n, n_kv, head_dim).
        """
        li = jnp.full(pages.shape, layer, jnp.int32)
        pg = jnp.asarray(pages, jnp.int32)
        of = jnp.asarray(offsets, jnp.int32)
        self.k, self.v = _scatter_tokens(self.k, self.v, li, pg, of,
                                         k_new, v_new)

    def copy_slots(self, src_pages: np.ndarray, src_offs: np.ndarray,
                   dst_pages: np.ndarray, dst_offs: np.ndarray) -> None:
        """Copy token slots across pages, all layers at once.

        The speculative engine's commit path: an accepted draft token's
        KV was computed into its draft node's page during verification;
        committing moves it to the request's leaf tail slot so the draft
        page can be released and the committed layout stays identical to
        what non-speculative decode would have produced.
        """
        sp = jnp.asarray(src_pages, jnp.int32)
        so = jnp.asarray(src_offs, jnp.int32)
        dp = jnp.asarray(dst_pages, jnp.int32)
        do = jnp.asarray(dst_offs, jnp.int32)
        self.k = self.k.at[:, dp, do].set(self.k[:, sp, so])
        self.v = self.v.at[:, dp, do].set(self.v[:, sp, so])

    def gather_context(self, layer: int, pages: List[int], length: int,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Dense (length, n_kv, hd) view of a page run (prefill reuse)."""
        idx = jnp.asarray(pages, jnp.int32)
        ps = self.page_size
        k = self.k[layer, idx].reshape(len(pages) * ps, *self.k.shape[3:])
        v = self.v[layer, idx].reshape(len(pages) * ps, *self.v.shape[3:])
        return k[:length], v[:length]

    def bytes_used(self) -> int:
        return int(self.k.size + self.v.size) * self.k.dtype.itemsize
