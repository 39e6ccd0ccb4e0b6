"""Paged KV-cache: a block pool of fixed-size pages with free-list reuse.

The pool owns the device arrays the decode/prefill programs donate and
rebind each step (``k_pool``/``v_pool``, shape ``(L, P, ps, H*D)``:
page-major, a token's heads side by side on the lanes — the layout the
paged-attention kernel's page block reads, so the programs write a row
(decode) or whole pages (prefill) into it and hand it to the kernel with
no slice, reshape or copy between; :func:`pool_shapes`), a
host-side free list of page ids, and a *reservation* ledger used for
admission control: the scheduler reserves a sequence's worst-case page
count (prompt + max_new_tokens) before prefill so a sequence admitted
into the batch can never stall mid-decode waiting for a page.

Page 0 is reserved as the **null page**: padding rows of a batch
bucket and the unused tail of every page table point at it, so the
programs' scatter/gather of padding lanes touch real (never-read)
storage instead of needing per-lane predication.

Observability rides the PR 14 rails: when telemetry is on, occupancy
gauges (``pt_serve_kv_pages{state=used|free|reserved}``) are updated on
every alloc/free, and the pool registers a live-buffer attribution
provider so the memory census names the pools ``kv::k_pages`` /
``kv::v_pages``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

__all__ = ["PagePool", "KVPoolExhausted", "NULL_PAGE", "kv_page_budget",
           "pool_shapes"]

NULL_PAGE = 0


def pool_shapes(layers: int, pages: int, page_size: int, heads: int,
                head_dim: int):
    """``(value_shape, scale_shape)`` of the K/V pools: values
    ``(L, P, ps, H*D)``, the int8 pools' per-(token, head) f32 scales
    ``(L, P, ps, H)``.  Position ``t`` of a sequence lives at
    ``[layer, page_table[t // ps], t % ps]`` in both — the one
    addressing every precision, program and kernel uses."""
    return ((layers, pages, page_size, heads * head_dim),
            (layers, pages, page_size, heads))


def kv_page_budget(pages: int, precision: str, head_dim: int) -> int:
    """Scale an fp32-denominated page budget to a precision's real cost.

    ``PT_SERVE_KV_PAGES`` is a BYTE budget expressed in fp32 pages (so
    deployments compare precisions at identical HBM spend).  Per
    (token, head) an fp32 page row costs ``4*D`` bytes; bf16 halves it;
    int8 costs ``D`` for the values plus 4 for the f32 scale riding in
    the scale pages.  The null page scales with everything else, so the
    *usable* count is what gets the ratio — int8 at D=16 yields 3.2x
    the admission headroom at the same byte spend.
    """
    if precision in ("fp32", "float32"):
        return pages
    fp32_cost = 4.0 * head_dim
    if precision in ("bf16", "bfloat16"):
        cost = 2.0 * head_dim
    elif precision == "int8":
        cost = head_dim + 4.0
    else:
        raise ValueError(f"unknown serve precision {precision!r}")
    return 1 + int((pages - 1) * fp32_cost / cost)


class KVPoolExhausted(RuntimeError):
    """Raised when an alloc/reserve exceeds pool headroom."""


class PagePool:
    """Block-pool allocator over the serve KV arrays (``k_pool`` /
    ``v_pool`` ``(L, P, ps, H*D)``, and ``k_scale`` / ``v_scale``
    ``(L, P, ps, H)`` beside an int8 pool).

    Thread-safety: all bookkeeping is lock-guarded; the device arrays
    themselves are only rebound from the engine's step loop.
    """

    def __init__(self, *, layers: int, pages: int, page_size: int,
                 heads: int, head_dim: int, dtype=jnp.float32,
                 scale_pages: bool = False):
        if pages < 2:
            raise ValueError("pages must be >= 2 (page 0 is the null page)")
        self.layers = layers
        self.pages = pages
        self.page_size = page_size
        self.heads = heads
        self.head_dim = head_dim
        self.dtype = dtype
        # quantized pools carry per-(token, head) f32 scales in shadow
        # "scale pages" addressed by the same page table (the scale
        # travels with the tensor — the TPU022 contract)
        self.scale_pages = bool(scale_pages)
        shape, sshape = pool_shapes(layers, pages, page_size, heads,
                                    head_dim)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype)
        self.k_scale = jnp.zeros(sshape, jnp.float32) \
            if self.scale_pages else None
        self.v_scale = jnp.zeros(sshape, jnp.float32) \
            if self.scale_pages else None
        self._lock = threading.Lock()
        # LIFO free list: hot pages get reused while still cache/HBM warm
        self._free: List[int] = list(range(pages - 1, 0, -1))
        self._reserved = 0
        self.stats = {
            "allocs": 0, "frees": 0, "alloc_failures": 0,
            "reserve_refusals": 0, "high_watermark": 0,
        }
        self._register_memory_provider()

    # -- capacity ----------------------------------------------------------

    @property
    def usable_pages(self) -> int:
        return self.pages - 1  # minus the null page

    def pages_needed(self, tokens: int) -> int:
        return max(1, -(-int(tokens) // self.page_size))

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return self.usable_pages - len(self._free)

    @property
    def reserved_pages(self) -> int:
        with self._lock:
            return self._reserved

    def headroom(self) -> int:
        """Pages available to NEW admissions (free minus already promised)."""
        with self._lock:
            return len(self._free) - self._reserved

    # -- admission-control reservations ------------------------------------

    def can_admit(self, n_pages: int) -> bool:
        return self.headroom() >= n_pages

    def reserve(self, n_pages: int) -> None:
        """Promise ``n_pages`` to a sequence about to be admitted."""
        with self._lock:
            if len(self._free) - self._reserved < n_pages:
                self.stats["reserve_refusals"] += 1
                raise KVPoolExhausted(
                    f"reserve({n_pages}): only "
                    f"{len(self._free) - self._reserved} unreserved pages")
            self._reserved += n_pages
        self._gauges()

    def release_reservation(self, n_pages: int) -> None:
        """Return unused promised pages (sequence finished early)."""
        with self._lock:
            self._reserved = max(0, self._reserved - n_pages)
        self._gauges()

    # -- alloc / free -------------------------------------------------------

    def alloc(self, n_pages: int = 1, *, reserved: bool = False) -> List[int]:
        """Pop ``n_pages`` page ids off the free list.

        ``reserved=True`` draws down a prior :meth:`reserve` promise
        (the scheduler's path); an unreserved alloc can fail even when
        pages are free if they are all promised elsewhere.
        """
        with self._lock:
            avail = len(self._free) if reserved \
                else len(self._free) - self._reserved
            if avail < n_pages:
                self.stats["alloc_failures"] += 1
                raise KVPoolExhausted(
                    f"alloc({n_pages}): {avail} pages available")
            ids = [self._free.pop() for _ in range(n_pages)]
            if reserved:
                self._reserved = max(0, self._reserved - n_pages)
            self.stats["allocs"] += n_pages
            used = self.usable_pages - len(self._free)
            self.stats["high_watermark"] = max(
                self.stats["high_watermark"], used)
        self._gauges()
        return ids

    def free(self, page_ids: Sequence[int]) -> None:
        """Return a retired sequence's pages to the free list."""
        with self._lock:
            for pid in page_ids:
                if pid == NULL_PAGE:
                    raise ValueError("cannot free the null page")
                if not (0 < pid < self.pages):
                    raise ValueError(f"page id {pid} out of range")
                if pid in self._free:
                    raise ValueError(f"double free of page {pid}")
                self._free.append(pid)
            self.stats["frees"] += len(page_ids)
        self._gauges()

    def check_consistency(self, expect_all_free: bool = False) -> None:
        """Invariant check used by tests and the serve chaos drills:
        no duplicate/lost pages.  ``expect_all_free=True`` additionally
        proves a clean slate — every usable page back on the free list
        and zero outstanding reservations (the post-drain / post-storm
        zero-leak assertion)."""
        with self._lock:
            assert len(set(self._free)) == len(self._free), "dup free ids"
            assert all(0 < p < self.pages for p in self._free)
            assert 0 <= self._reserved <= len(self._free), \
                f"reserved {self._reserved} > free {len(self._free)}"
            if expect_all_free:
                assert len(self._free) == self.usable_pages, \
                    (f"page leak: {self.usable_pages - len(self._free)} "
                     f"of {self.usable_pages} pages unaccounted for")
                assert self._reserved == 0, \
                    f"{self._reserved} pages still reserved"

    # -- device state -------------------------------------------------------

    def swap(self, k_pool, v_pool, k_scale=None, v_scale=None) -> None:
        """Rebind the pools to a program's donated outputs (scale pools
        included when this is a quantized pool)."""
        self.k_pool = k_pool
        self.v_pool = v_pool
        if self.scale_pages:
            if k_scale is None or v_scale is None:
                raise ValueError(
                    "quantized pool swap requires k_scale and v_scale")
            self.k_scale = k_scale
            self.v_scale = v_scale

    def utilization(self) -> float:
        with self._lock:
            return (self.usable_pages - len(self._free)) / \
                max(1, self.usable_pages)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            free = len(self._free)
            return {
                "pages": self.pages,
                "dtype": np.dtype(self.dtype).name,
                "scale_pages": self.scale_pages,
                "usable_pages": self.usable_pages,
                "free_pages": free,
                "used_pages": self.usable_pages - free,
                "reserved_pages": self._reserved,
                "utilization": (self.usable_pages - free) /
                max(1, self.usable_pages),
                **self.stats,
            }

    # -- observability ------------------------------------------------------

    def _gauges(self) -> None:
        """Occupancy gauges; inert while telemetry is off (registry must
        stay empty then — the record_dispatch contract)."""
        try:
            from ..observability.metrics import get_registry
            from ..observability.telemetry import get_telemetry
            if not get_telemetry().enabled:
                return
            with self._lock:
                free = len(self._free)
                reserved = self._reserved
            g = get_registry().gauge(
                "pt_serve_kv_pages",
                "Serve KV page-pool occupancy by state",
                labelnames=("state",))
            g.set(self.usable_pages - free, state="used")
            g.set(free, state="free")
            g.set(reserved, state="reserved")
            get_registry().gauge(
                "pt_serve_kv_utilization",
                "Fraction of usable KV pages in use").set(
                (self.usable_pages - free) / max(1, self.usable_pages))
        except Exception:
            pass

    def _register_memory_provider(self) -> None:
        try:
            from ..observability import memory as _memory
            mon = _memory.get_memory_monitor()
            if mon.enabled:
                mon.register_provider(self._memory_named)
        except Exception:
            pass

    def _memory_named(self):
        """Live-buffer attribution for the PR 14 census: the pools
        (and, for quantized pools, their scale shadows) under ``kv::``
        paths."""
        named = {"kv::k_pages": self.k_pool, "kv::v_pages": self.v_pool}
        if self.scale_pages:
            named["kv::k_scales"] = self.k_scale
            named["kv::v_scales"] = self.v_scale
        return named

    def null_padded_table(self, page_ids: Sequence[int],
                          max_pages: int) -> np.ndarray:
        """Host-side page table row: ids then null-page padding."""
        if len(page_ids) > max_pages:
            raise ValueError(
                f"{len(page_ids)} pages exceed table width {max_pages}")
        row = np.full((max_pages,), NULL_PAGE, np.int32)
        row[:len(page_ids)] = np.asarray(page_ids, np.int32)
        return row
